"""What the dispatch lap of run_round hands the runtime: one upload a round
that staged proposals (the (2, G) staged array) and none in a round that
staged nothing (the boot-time zeros), the tick one of two device scalars
made at boot, gather_rows the six state fields it reads: ten buffers a
call. On a mesh all of it lies, before either call, on the sharding the
compiled programs take it in, so no call re-shards what it was handed. The
records and answers are what they were (tests/test_engine_compact.py pins
them against the full readback; here: with a mesh against without)."""
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from etcd_tpu.ops import kernel  # noqa: E402
from etcd_tpu.parallel.mesh import make_mesh  # noqa: E402
from etcd_tpu.server import obs  # noqa: E402
from etcd_tpu.server.engine import (EngineConfig, MultiEngine,  # noqa: E402
                                    gather_program)

from tests.test_engine_compact import (E, G, P, W, _assert_same_records,  # noqa: E402,E501
                                       _drive, _led, _put, _wal_records)


@pytest.fixture(scope="module", params=[None, 4], ids=["one", "mesh4"])
def mesh(request):
    """One device, and the 4x1 groups mesh the sharded tests use."""
    if request.param is None:
        return None
    if len(jax.devices()) < request.param:
        pytest.skip("needs the 8-device CPU mesh")
    return make_mesh(jax.devices()[:request.param])


def _uploads(eng: MultiEngine) -> tuple:
    """(uploads, bytes) one run_round makes, by the engine's counters."""
    n0, b0 = obs.h2d_syncs.value, obs.h2d_bytes.value
    eng.run_round()
    return obs.h2d_syncs.value - n0, obs.h2d_bytes.value - b0


@pytest.mark.parametrize("lag", [False, True], ids=["plain", "lag"])
def test_a_round_uploads_its_staged_array_or_nothing(tmp_path, mesh, lag):
    """K > 0 writes staged: exactly one upload, of 2 * 4 * G bytes; nothing
    staged: none. A LagSchedule's hold map is one more, every round."""
    kw = dict(lag_share=0.25, lag_hold_rounds=8, lag_seed=1) if lag else {}
    eng = _led(str(tmp_path / "u"), mesh=mesh, **kw)
    more, more_b = (1, G * P) if lag else (0, 0)
    for _ in range(3):                      # (settle: nothing pending)
        eng.run_round()
    assert _uploads(eng) == (more, more_b)
    for k, rid in ((1, 1), (5, 10)):
        for g in range(k):
            _put(eng, g, rid + g)
        assert _uploads(eng) == (1 + more, 8 * G + more_b)
        assert len(eng._staged) == k
        assert _uploads(eng) == (more, more_b)
    eng.stop()


def _spied(eng: MultiEngine):
    """Every (staged array, tick) a step and (staged array) the gather was
    handed, and the number of array buffers of each gather call."""
    seen = {"prop": [], "tick": [], "gather_prop": [], "gather_buffers": []}
    step_c, gather = eng._step_fn_c, eng._gather_rows

    def spy_step(st, inbox, prop, tick, *rest):
        seen["prop"].append(prop)
        seen["tick"].append(tick)
        return step_c(st, inbox, prop, tick, *rest)

    def spy_gather(*a):
        seen["gather_prop"].append(a[4])
        seen["gather_buffers"].append(len(jax.tree.leaves(a[:5])))
        return gather(*a)

    eng._step_fn_c, eng._gather_rows = spy_step, spy_gather
    return seen


def test_both_programs_are_handed_one_placed_array(tmp_path, mesh):
    """The step and the gather take the SAME staged array (the round's
    upload, or the boot-time zeros) and the tick is one of the two boot-time
    scalars; each lies on the sharding the compiled programs take it in,
    and the two calls run under a transfer guard that refuses any implicit
    move between devices."""
    eng = _led(str(tmp_path / "p"), mesh=mesh)
    seen = _spied(eng)
    eng.run_round()                                    # nothing staged
    _put(eng, 3, 1)
    _put(eng, 7, 2)
    with jax.transfer_guard_device_to_device("disallow"):
        eng.run_round()                                # two groups staged
    zero, staged = seen["prop"]
    assert zero is eng._prop_zero and staged is not eng._prop_zero
    assert seen["gather_prop"][0] is zero and seen["gather_prop"][1] is staged
    assert all(t is eng._ticks[1] for t in seen["tick"])
    assert seen["gather_buffers"] == [10, 10]
    got = np.asarray(staged)
    assert got.shape == (2, G) and got.dtype == np.int32
    assert got[0].tolist() == [int(g in (3, 7)) for g in range(G)]
    assert got[1, 3] == eng.leader_slot(3) and got[1, 7] == eng.leader_slot(7)
    assert not np.asarray(zero).any()

    # The shardings the two executables were compiled to take them in.
    placed = [zero, staged, *eng._ticks]
    if mesh is None:
        assert all(len(x.sharding.device_set) == 1 for x in placed)
    else:
        assert all(x.sharding.is_fully_replicated
                   and len(x.sharding.device_set) == 4 for x in placed)
    fields = eng._gather_fields(eng.st)
    flags = jax.numpy.zeros((G, P), jax.numpy.uint8)
    head = (jax.numpy.zeros((), bool),
            jax.numpy.zeros((2, eng.cfg.hops), jax.numpy.int32))
    rep = None
    if mesh is not None:
        from etcd_tpu.parallel.mesh import flag_sharding, replicated_sharding
        rep = replicated_sharding(mesh)
        flags = jax.device_put(flags, flag_sharding(mesh))
        head = jax.device_put(head, rep)
    compiled = gather_program(rep).lower(fields, flags, *head, staged,
                                         256).compile()
    want = compiled.input_shardings[0][4]
    for x in placed[:2]:
        assert x.sharding.is_equivalent_to(want, 2), (x.sharding, want)
    eng.stop()


def test_gather_rows_is_lowered_with_ten_array_arguments():
    """The six fields (kernel.GATHER_FIELDS), the flag map, the
    attestation, the hops' counts, the staged array: ten, where the state
    alone is 17 leaves."""
    from etcd_tpu.ops.state import KernelConfig, init_state
    st = jax.eval_shape(lambda: init_state(
        KernelConfig(groups=G, peers=P, window=W)))
    assert len(jax.tree.leaves(st)) == 17
    shape = jax.ShapeDtypeStruct
    lowered = gather_program().lower(
        tuple(getattr(st, f) for f in kernel.GATHER_FIELDS),
        shape((G, P), np.uint8), shape((), np.bool_),
        shape((2, 3), np.int32), shape((2, G), np.int32), 256)
    assert len(jax.tree.leaves(lowered.in_avals)) == 10
    assert lowered.out_info.shape == (257, kernel.ROW_RING + W)


@pytest.mark.parametrize("ticks_per_round", [1, 3])
def test_tick_follows_the_round_number(tmp_path, ticks_per_round):
    """tick = (round_no % ticks_per_round == 0), picked from the two
    boot-time scalars: no upload of its own."""
    eng = MultiEngine(EngineConfig(
        groups=G, peers=P, data_dir=str(tmp_path / "t"), window=W,
        max_ents=E, fsync=False, sync_interval=0.0,
        ticks_per_round=ticks_per_round, checkpoint_rounds=1 << 30,
        pipeline_applies=False))
    assert [bool(t) for t in eng._ticks] == [False, True]
    seen = _spied(eng)
    n0 = obs.h2d_syncs.value
    want = []
    for _ in range(7):
        want.append(eng.round_no % ticks_per_round == 0)
        eng.run_round()
    assert [t is eng._ticks[1] for t in seen["tick"]] == want
    assert obs.h2d_syncs.value == n0
    eng.stop()


@pytest.mark.parametrize("reads", [False, True], ids=["writes", "reads"])
def test_seeded_traffic_is_the_same_with_and_without_a_mesh(tmp_path, reads):
    """test_engine_compact.py's script (elections, a partition window,
    with `reads` parked quorum reads and a snapshot install) over the 4x1
    mesh against one device: the same rounds, answers, mirrors and
    records."""
    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-device CPU mesh")
    one = _drive(str(tmp_path / "one"), compact=True, reads=reads)
    four = _drive(str(tmp_path / "four"), compact=True, reads=reads,
                  mesh=make_mesh(jax.devices()[:4]))
    assert one.rounds == four.rounds
    assert one.answers == four.answers
    assert one.acked_requests == four.acked_requests
    for name in ("h_term", "h_vote", "h_commit", "h_state", "h_last",
                 "h_ring", "h_mask", "applied"):
        assert np.array_equal(getattr(one, name), getattr(four, name)), name
    one.stop()
    four.stop()
    _assert_same_records(_wal_records(str(tmp_path / "one")),
                         _wal_records(str(tmp_path / "four")))
