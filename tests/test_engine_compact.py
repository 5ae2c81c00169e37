"""Compact-readback equivalence: the on-device-diff round tail
(kernel.step_routed_compact + MultiEngine._compact_record_admit) must be
observationally IDENTICAL to the full-readback tail — same durable WAL
records (field-for-field), same host mirrors, same acks — including
through elections, a leader-partition churn window, and the tiny-cap
fallback, and in rounds that carry parked quorum reads (the read step
returns the same diff). The compact path exists purely to cut readback
bytes (O(changed rows) instead of O(G*P*W) per round — the ring alone is
32 MB at G=100k) and blocking reads (one a round: the device picks and
packs the rows, kernel.gather_rows); any behavioral difference is a bug."""
import os
import queue
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from etcd_tpu import errors  # noqa: E402
from etcd_tpu.ops import kernel  # noqa: E402
from etcd_tpu.server import obs  # noqa: E402
from etcd_tpu.server.engine import (EngineConfig, MultiEngine,  # noqa: E402
                                    _bucket)
from etcd_tpu.server.enginewal import EngineWAL  # noqa: E402
from etcd_tpu.server.request import Request  # noqa: E402

G, P, W, E = 24, 3, 8, 2
ROUNDS = 70
CHURN_AT, HEAL_AT = 25, 40
GROW = 7           # the group the script with reads grows past the window
ROUNDS_READS = 100  # ... and long enough to re-elect it and install after


def _kinds() -> dict:
    return {lab["kind"]: v for _, lab, v in obs.readback_rounds.samples()}


def _answer(q):
    """What a registered waiter was answered, in a form that compares:
    an event's fields, an error's, a conf change's slot list; None while
    the request is still parked or pending."""
    try:
        res = q.get_nowait()
    except queue.Empty:
        return None
    if hasattr(res, "resolve"):            # store/event.py LazyWriteEvent
        res = res.resolve()
    node = getattr(res, "node", None)
    if node is not None:
        return (res.action, node.key, node.value, node.modified_index,
                res.etcd_index)
    if isinstance(res, errors.EtcdError):  # a read of no such key
        return ("error", res.code, res.cause, res.index)
    return res


def _drive(data_dir: str, compact: bool, cap: int = 0,
           reads: bool = False, mesh=None) -> MultiEngine:
    """Deterministic traffic: seeded enqueues, a leader-partition window
    (exercises elections, demotions, ring overwrites — the CHG_STATE and
    CHG_RING corners), no wall-clock dependence (sync_interval=0).

    `reads` interleaves seeded quorum reads (parked as the front parks
    them) with the writes, at least one in every round from the heal to
    past the install, and grows
    group GROW past the ring window while one of its followers is cut
    off, so the heal ends in a snapshot install. The engine then carries `.rounds`,
    one (carried reads, staged proposals, serviced need-host, followed a
    surgery, readback kind) per round, and `.answers` by request id.
    `mesh`: the same script with the state sharded over it
    (tests/test_round_dispatch.py)."""
    eng = MultiEngine(EngineConfig(
        groups=G, peers=P, data_dir=data_dir, window=W, max_ents=E,
        fsync=False, stagger=True, sync_interval=0.0,
        compact_readback=compact, compact_cap=cap, mesh=mesh,
        checkpoint_rounds=1 << 30, pipeline_applies=False))

    class _Seq:  # idutil embeds wall time; payload bytes must be equal
        def __init__(self):
            self.i = 0

        def next(self):
            self.i += 1
            return self.i

    eng.reqid = _Seq()
    rng = random.Random(7)
    read_rng = random.Random(13)
    waits, serviced = {}, []
    service = eng._service_need_host

    def spy_service(nh):
        serviced.append(eng.round_no)
        service(nh)

    eng._service_need_host = spy_service
    eng.rounds = []

    def put(g, path, val):
        rid = eng.reqid.next()
        rq = Request(method="PUT", path=path, val=val, id=rid)
        waits[rid] = eng.wait.register(rid)
        with eng._lock:
            eng._pending[g].append((rid, bytes([0]) + rq.encode(), rq))
            eng._dirty.add(g)

    import jax.numpy as jnp
    for r in range(ROUNDS_READS if reads else ROUNDS):
        for _ in range(rng.randrange(0, 10)):
            put(rng.randrange(G), f"/k{rng.randrange(4)}", f"v{r}")
        if reads:
            if CHURN_AT <= r < HEAL_AT:
                put(GROW, "/grow", f"r{r}")
            n_reads = read_rng.choice((0, 0, 1, 2, 3))
            if HEAL_AT - 2 <= r < ROUNDS_READS - 10:
                n_reads = max(n_reads, 1)
            for _ in range(n_reads):
                g = read_rng.randrange(G)
                rid = eng.reqid.next()
                waits[rid] = eng.wait.register(rid)
                with eng._lock:
                    eng._park_read(g, Request(
                        method="GET", path=f"/k{read_rng.randrange(4)}",
                        quorum=True, id=rid))
        if r == CHURN_AT:
            # Partition the current leader of the first 6 groups (both
            # directions) — forces re-election among the rest.
            mask = np.ones((G, P, P, 1), np.int32)
            lead = (np.where(eng.h_mask, eng.h_state, 0) == 2)
            for g in range(6):
                if lead[g].any():
                    s = int(lead[g].argmax())
                    mask[g, s, :, 0] = 0
                    mask[g, :, s, 0] = 0
            if reads:
                s = (int(lead[GROW].argmax()) + 1) % P
                mask[GROW, s, :, 0] = 0
                mask[GROW, :, s, 0] = 0
            eng.drop_mask = jnp.asarray(mask)
        elif r == HEAL_AT:
            eng.drop_mask = None
        carried, forced = eng._reads_waiting > 0, eng._force_full
        before = _kinds()
        eng.run_round()
        after = _kinds()
        (kind,) = [k for k in after if after[k] != before[k]]
        eng.rounds.append((carried, bool(eng._staged),
                           r in serviced, forced, kind))
    eng.answers = {rid: _answer(q) for rid, q in waits.items()}
    return eng


def _wal_records(data_dir: str):
    wal = EngineWAL(data_dir, fsync=False)
    recs = list(wal.replay(after_round=-1))
    wal.close()
    return recs


def _assert_same_records(recs_a, recs_b):
    assert len(recs_a) == len(recs_b)
    arr_fields = ("hs_g", "hs_p", "hs_term", "hs_vote", "hs_commit",
                  "last_g", "last_p", "last_v",
                  "ring_g", "ring_p", "ring_i", "ring_t")
    for ra, rb in zip(recs_a, recs_b):
        assert ra.round_no == rb.round_no
        for f in arr_fields:
            va, vb = getattr(ra, f), getattr(rb, f)
            assert np.array_equal(np.asarray(va), np.asarray(vb)), \
                (ra.round_no, f, va, vb)
        assert ra.entries == rb.entries, ra.round_no
        assert ra.confs == rb.confs, ra.round_no


def _assert_reaches_every_read_round(full, comp, cap):
    """The script with reads reaches each kind of read round, and each
    round's record came from the readback it should have."""
    assert {k for *_, k in full.rounds} == {"full"}
    # (carried, staged, need-host, followed a surgery) are the script's
    # and the consensus trajectory's, not the readback's.
    assert [r[:4] for r in full.rounds] == [r[:4] for r in comp.rounds]
    for carried, staged, need_host, forced, kind in comp.rounds:
        if need_host or forced:
            assert kind == "full"
        else:
            assert kind in (("compact", "over_cap") if cap else ("compact",))
    reads = [r[1:] for r in comp.rounds if r[0]]
    assert len(reads) > ROUNDS_READS // 2
    assert len(reads) < len(comp.rounds)                 # and write rounds
    under = "over_cap" if cap else "compact"
    assert (False, False, False, under) in reads     # a pure read round
    assert (True, False, False, under) in reads      # one with proposals
    assert any(need_host for _, need_host, _, _ in reads)
    assert any(forced for _, _, forced, _ in reads)
    got = [a for a in comp.answers.values() if a and a[0] == "get"]
    assert len(got) > ROUNDS // 2 and any(a[2] for a in got)


@pytest.mark.parametrize("reads", [False, True], ids=["writes", "reads"])
@pytest.mark.parametrize("cap", [0, 1])
def test_compact_equals_full(tmp_path, cap, reads):
    """cap=0: the real compact path (auto cap). cap=1: every round
    overflows the cap and falls back to full readback inside compact
    mode — the fallback must be just as identical. With `reads` the
    script parks quorum reads in most rounds: a pure read round, read
    rounds that carry proposals, go over the cap, raise need-host and
    follow a snapshot install (_drive)."""
    full = _drive(str(tmp_path / "full"), compact=False, reads=reads)
    comp = _drive(str(tmp_path / "comp"), compact=True, cap=cap,
                  reads=reads)

    for name in ("h_term", "h_vote", "h_commit", "h_state", "h_last",
                 "h_ring", "h_mask", "applied"):
        assert np.array_equal(getattr(full, name), getattr(comp, name)), \
            name
    assert full.acked_requests == comp.acked_requests
    assert full.round_no == comp.round_no
    assert full.answers == comp.answers
    if reads:
        _assert_reaches_every_read_round(full, comp, cap)

    _assert_same_records(_wal_records(str(tmp_path / "full")),
                         _wal_records(str(tmp_path / "comp")))

    # Both keyspaces answer identically.
    for g in list(full._stores):
        assert g in comp._stores
        assert full._stores[g].save() == comp._stores[g].save()
    full.stop()
    comp.stop()


def test_compact_restart_replays_identically(tmp_path):
    """The compact WAL must be COMPLETE: a fresh engine replaying it
    reconstructs the same mirrors and keyspace (the r5 motivation — a
    diff the device missed would silently vanish from durability)."""
    comp = _drive(str(tmp_path / "c"), compact=True)
    mirrors = {n: getattr(comp, n).copy()
               for n in ("h_term", "h_vote", "h_commit", "h_last",
                         "h_ring")}
    stores = {g: s.save() for g, s in comp._stores.items()}
    comp.stop()

    re = MultiEngine(EngineConfig(
        groups=G, peers=P, data_dir=str(tmp_path / "c"), window=W,
        max_ents=E, fsync=False, stagger=True, sync_interval=0.0,
        checkpoint_rounds=1 << 30, pipeline_applies=False))
    for n, v in mirrors.items():
        assert np.array_equal(getattr(re, n), v), n
    for g, blob in stores.items():
        assert re._stores[g].save() == blob, g
    re.stop()


def _led(data_dir: str, groups: int = G, **kw) -> MultiEngine:
    """An engine driven by hand to a leader in every group."""
    eng = MultiEngine(EngineConfig(
        groups=groups, peers=P, data_dir=data_dir, window=W, max_ents=E,
        fsync=False, sync_interval=0.0, mask_check_rounds=0,
        checkpoint_rounds=1 << 30, pipeline_applies=False, **kw))
    for _ in range(400):
        eng.run_round()
        if all(eng.leader_slot(g) >= 0 for g in range(groups)):
            return eng
    raise AssertionError("no leaders")


def _put(eng: MultiEngine, g: int, rid: int, val: str = "v"):
    q = eng.wait.register(rid)
    with eng._lock:
        eng._pending[g].append((rid, bytes([0]) + Request(
            method="PUT", path="/k", val=val, id=rid).encode(), None))
        eng._dirty.add(g)
    return q


def _buf_bytes(kp: int) -> int:
    """gather_rows' packed buffer: a header row and kp rows of int32."""
    return (1 + kp) * (kernel.ROW_RING + W) * 4


def _mirrors(eng: MultiEngine) -> list:
    return [getattr(eng, n).copy() for n in (
        "h_term", "h_vote", "h_commit", "h_state", "h_last", "h_ring")]


def test_quiet_read_round_reads_back_flags_and_confirmation_only(tmp_path):
    """A read round that changes no mirrored row moves gather_rows'
    buffer at the smallest bucket (the attestation and no row) and the
    read plane's two (G,) arrays to the host, in three blocking reads,
    and nothing of the state (the d2h counters are exact); its record
    comes from the compact path."""
    eng = _led(str(tmp_path / "q"))
    q = _put(eng, 3, 1)
    for _ in range(20):         # commit indexes converge on every peer
        eng.run_round()
    q.get_nowait()
    mirrors = _mirrors(eng)

    q = eng.wait.register(2)
    with eng._lock:
        eng._park_read(3, Request(method="GET", path="/k", quorum=True,
                                  id=2))
    syncs, nbytes, kinds = obs.d2h_syncs.value, obs.d2h_bytes.value, _kinds()
    eng.run_round()
    assert q.get_nowait().node.value == "v"
    assert obs.d2h_syncs.value - syncs == 3
    # the packed buffer + (G,) bool + (G,) int32
    assert obs.d2h_bytes.value - nbytes == _buf_bytes(256) + G + 4 * G
    after = _kinds()
    assert {k: after[k] - kinds[k] for k in after} == {
        "compact": 1, "full": 0, "over_cap": 0}
    for name, was in zip(("h_term", "h_vote", "h_commit", "h_state",
                          "h_last", "h_ring"), mirrors):
        assert np.array_equal(getattr(eng, name), was), name
    eng.stop()


def test_write_round_makes_one_blocking_read(tmp_path):
    """A round that admits writes reads the device once: gather_rows'
    header and kp rows, nothing else; the rows it changed are in the
    mirrors and the write is answered."""
    eng = _led(str(tmp_path / "w"))
    for _ in range(20):
        eng.run_round()
    qs = [_put(eng, g, 1 + g) for g in (2, 5, 11)]
    syncs, nbytes, kinds = obs.d2h_syncs.value, obs.d2h_bytes.value, _kinds()
    rebuckets = obs.gather_rebuckets.value
    last = eng.h_last.copy()
    eng.run_round()
    assert obs.d2h_syncs.value - syncs == 1
    assert obs.d2h_bytes.value - nbytes == _buf_bytes(256)
    assert obs.gather_rebuckets.value == rebuckets
    after = _kinds()
    assert {k: after[k] - kinds[k] for k in after} == {
        "compact": 1, "full": 0, "over_cap": 0}
    # every peer of the three groups took the entry (hops=3), no other row
    assert sorted(set(np.nonzero(eng.h_last != last)[0])) == [2, 5, 11]
    assert eng._gather_ks[-1] == 3 * P
    for q in qs:
        assert q.get_nowait().action == "set"
    eng.stop()


def test_bucket_miss_reads_twice_and_builds_the_same_record(tmp_path):
    """A round that picks more rows than the bucket gather_rows was
    called with (K = 270 just over kp = 256: the rule is held to the
    smallest bucket for that one round) makes the call and the read
    again at the bucket that holds them, counts it once, and journals the
    record the full readback journals."""
    big = 100
    full = _led(str(tmp_path / "f"), groups=big, compact_readback=False)
    comp = _led(str(tmp_path / "c"), groups=big)
    for eng in (full, comp):
        for _ in range(20):
            eng.run_round()
    assert full.round_no == comp.round_no
    waits = [[_put(eng, g, 1 + g, f"v{g}") for g in range(90)]
             for eng in (full, comp)]
    comp._gather_bucket = lambda n_staged: 256
    syncs, nbytes = obs.d2h_syncs.value, obs.d2h_bytes.value
    rebuckets, kinds = obs.gather_rebuckets.value, _kinds()
    comp.run_round()
    assert comp._gather_ks[-1] == 90 * P
    assert obs.d2h_syncs.value - syncs == 2
    assert obs.d2h_bytes.value - nbytes == _buf_bytes(256) + _buf_bytes(512)
    assert obs.gather_rebuckets.value - rebuckets == 1
    after = _kinds()
    assert {k: after[k] - kinds[k] for k in after} == {
        "compact": 1, "full": 0, "over_cap": 0}
    del comp._gather_bucket
    full.run_round()
    for a, b in zip(_mirrors(full), _mirrors(comp)):
        assert np.array_equal(a, b)
    for qf, qc in zip(*waits):
        got = _answer(qc)
        assert got[0] == "set" and got == _answer(qf)
    full.stop()
    comp.stop()
    _assert_same_records(_wal_records(str(tmp_path / "f")),
                         _wal_records(str(tmp_path / "c")))


def test_bucket_rule_follows_the_staged_groups_and_the_last_pick(tmp_path):
    """The bucket is chosen before dispatch from the groups staged now
    and in the last round and the most rows a compact round picked since
    a heartbeat ago; a power of two >= 256, never past the cap's bucket."""
    eng = _led(str(tmp_path / "b"), groups=2000)
    for _ in range(20):
        eng.run_round()
    assert max(eng._gather_ks) == 0 and eng._staged_prev == 0
    assert eng._gather_bucket(0) == 256
    assert eng._gather_bucket(85) == 256            # 85 groups x 3 rows
    assert eng._gather_bucket(86) == 512
    eng._staged_prev = 86                           # their followers' rows
    assert eng._gather_bucket(0) == 512
    assert eng._gather_bucket(86) == 1024
    eng._staged_prev = 0
    eng._gather_ks.append(1500)                     # a catch-up, an election
    assert eng._gather_bucket(0) == 2048
    for _ in range(eng.cfg.heartbeat_tick):         # ... is remembered until
        eng._gather_ks.append(0)                    # a heartbeat has passed
        assert eng._gather_bucket(0) == 2048
    eng._gather_ks.append(0)
    assert eng._gather_bucket(0) == 256
    eng._gather_ks.append(6000)                     # the last round overran
    assert eng._compact_cap == 2048 and eng._gather_bucket(0) == 2048
    assert eng._gather_buckets() == [256, 512, 1024, 2048]
    eng.stop()


def test_every_bucket_is_built_before_the_first_round(tmp_path):
    """_warm_gather (the engine thread's first act) builds gather_rows at
    every bucket on arguments placed as a round's are: rounds that then
    meet each bucket add no program to the jit's cache."""
    eng = MultiEngine(EngineConfig(
        groups=200, peers=P, data_dir=str(tmp_path / "warm"), window=W,
        max_ents=E, fsync=False, stagger=True, sync_interval=0.0,
        checkpoint_rounds=1 << 30, pipeline_applies=False))
    assert eng._gather_buckets() == [256, 512, 1024]
    eng._warm_gather()
    built = eng._gather_rows._cache_size()
    assert built >= 3
    for _ in range(80):
        eng.run_round()
    picked, rid = set(), 0
    for every in (1, 2, 5):
        for g in range(0, 200, every):
            rid += 1
            _put(eng, g, rid)
        for _ in range(2):
            eng.run_round()
            picked.add(_bucket(eng._gather_ks[-1]))
    assert picked == {256, 512, 1024}
    assert eng._gather_rows._cache_size() == built
    eng.stop()


def _seeded_round(seed: int, groups: int, density: float):
    """A state, a flag map and staged proposals drawn from `seed`."""
    import jax.numpy as jnp
    from etcd_tpu.ops.state import KernelConfig, init_state
    rng = np.random.default_rng(seed)
    st = init_state(KernelConfig(groups=groups, peers=P, window=W))
    draw = lambda *shape: jnp.asarray(                      # noqa: E731
        rng.integers(0, 1 << 20, shape), jnp.int32)
    st = st._replace(term=draw(groups, P), vote=draw(groups, P),
                     commit=draw(groups, P), state=draw(groups, P),
                     last_index=draw(groups, P),
                     log_term=draw(groups, P, W))
    flags = ((rng.random((groups, P)) < density)
             * rng.integers(1, 16, (groups, P))).astype(np.uint8)
    count = ((rng.random(groups) < density)
             * rng.integers(1, E + 1, groups)).astype(np.int32)
    slot = rng.integers(0, P, groups).astype(np.int32)
    return st, flags, count, slot


@pytest.mark.parametrize("seed,groups,density", [
    (1, G, 0.0), (2, G, 0.2), (3, G, 1.0), (4, 400, 0.02), (5, 400, 0.15),
    (6, 400, 0.6), (7, 1000, 0.08)])
def test_device_pick_is_the_hosts_union(seed, groups, density):
    """gather_rows picks what the host used to: np.nonzero(flags) and the
    staged leader rows, sorted, with their flags and values; the header
    holds the attestation and the union's true size; rows past it (the
    bucket's padding, or a bucket that is too small) say so."""
    import jax.numpy as jnp
    st, flags, count, slot = _seeded_round(seed, groups, density)
    staged = np.flatnonzero(count)
    lin = np.unique(np.concatenate(
        [np.flatnonzero(flags.reshape(-1)), staged * P + slot[staged]]))
    for kp, nh in ((256, False), (1024, True)):
        buf = np.asarray(kernel.gather_rows(
            st, jnp.asarray(flags), jnp.asarray(nh),
            jnp.asarray([3, 0, kp], jnp.int32), jnp.asarray(count),
            jnp.asarray(slot), kp))
        assert buf.dtype == np.int32
        assert buf.shape == (1 + kp, kernel.ROW_RING + W)
        assert kernel.HEAD_STATS == 2   # the hops' counts ride along
        assert buf[0].tolist() == [int(nh), len(lin), 3, 0, kp] + [0] * (
            buf.shape[1] - 5)
        n = min(kp, len(lin))
        rows, pad = buf[1:1 + n], buf[1 + n:]
        assert np.array_equal(rows[:, kernel.ROW_LIN], lin[:n])
        assert (pad[:, kernel.ROW_LIN] == groups * P).all()
        g, p = np.divmod(lin[:n], P)
        assert np.array_equal(rows[:, kernel.ROW_FLAGS], flags[g, p])
        for col, name in ((kernel.ROW_TERM, "term"),
                          (kernel.ROW_VOTE, "vote"),
                          (kernel.ROW_COMMIT, "commit"),
                          (kernel.ROW_STATE, "state"),
                          (kernel.ROW_LAST, "last_index")):
            assert np.array_equal(rows[:, col],
                                  np.asarray(getattr(st, name))[g, p]), name
        assert np.array_equal(rows[:, kernel.ROW_RING:],
                              np.asarray(st.log_term)[g, p])
