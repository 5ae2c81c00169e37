"""Compact-readback equivalence: the on-device-diff round tail
(kernel.step_routed_compact + MultiEngine._compact_record_admit) must be
observationally IDENTICAL to the full-readback tail — same durable WAL
records (field-for-field), same host mirrors, same acks — including
through elections, a leader-partition churn window, and the tiny-cap
fallback, and in rounds that carry parked quorum reads (the read step
returns the same diff). The compact path exists purely to cut readback
bytes (O(changed rows) instead of O(G*P*W) per round — the ring alone is
32 MB at G=100k); any behavioral difference is a bug."""
import os
import queue
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from etcd_tpu import errors  # noqa: E402
from etcd_tpu.server import obs  # noqa: E402
from etcd_tpu.server.engine import EngineConfig, MultiEngine  # noqa: E402
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
           reads: bool = False) -> MultiEngine:
    """Deterministic traffic: seeded enqueues, a leader-partition window
    (exercises elections, demotions, ring overwrites — the CHG_STATE and
    CHG_RING corners), no wall-clock dependence (sync_interval=0).

    `reads` interleaves seeded quorum reads (parked as the front parks
    them) with the writes, at least one in every round from the heal to
    past the install, and grows
    group GROW past the ring window while one of its followers is cut
    off, so the heal ends in a snapshot install. The engine then carries `.rounds`,
    one (carried reads, staged proposals, serviced need-host, followed a
    surgery, readback kind) per round, and `.answers` by request id."""
    eng = MultiEngine(EngineConfig(
        groups=G, peers=P, data_dir=data_dir, window=W, max_ents=E,
        fsync=False, stagger=True, sync_interval=0.0,
        compact_readback=compact, compact_cap=cap,
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


def test_quiet_read_round_reads_back_flags_and_confirmation_only(tmp_path):
    """A read round that changes no mirrored row moves the attestation,
    the flag map and the read plane's two (G,) arrays to the host, in
    four blocking reads, and nothing of the state (the d2h counters are
    exact); its record comes from the compact path."""
    eng = MultiEngine(EngineConfig(
        groups=G, peers=P, data_dir=str(tmp_path / "q"), window=W,
        max_ents=E, fsync=False, sync_interval=0.0, mask_check_rounds=0,
        checkpoint_rounds=1 << 30, pipeline_applies=False))
    for _ in range(400):
        eng.run_round()
        if all(eng.leader_slot(g) >= 0 for g in range(G)):
            break
    q = eng.wait.register(1)
    with eng._lock:
        eng._pending[3].append((1, bytes([0]) + Request(
            method="PUT", path="/k", val="v", id=1).encode(), None))
        eng._dirty.add(3)
    for _ in range(20):         # commit indexes converge on every peer
        eng.run_round()
    q.get_nowait()
    mirrors = [getattr(eng, n).copy() for n in (
        "h_term", "h_vote", "h_commit", "h_state", "h_last", "h_ring")]

    q = eng.wait.register(2)
    with eng._lock:
        eng._park_read(3, Request(method="GET", path="/k", quorum=True,
                                  id=2))
    syncs, nbytes, kinds = obs.d2h_syncs.value, obs.d2h_bytes.value, _kinds()
    eng.run_round()
    assert q.get_nowait().node.value == "v"
    assert obs.d2h_syncs.value - syncs == 4
    # attestation + (G, P) uint8 flags + (G,) bool + (G,) int32
    assert obs.d2h_bytes.value - nbytes == 1 + G * P + G + 4 * G
    after = _kinds()
    assert {k: after[k] - kinds[k] for k in after} == {
        "compact": 1, "full": 0, "over_cap": 0}
    for name, was in zip(("h_term", "h_vote", "h_commit", "h_state",
                          "h_last", "h_ring"), mirrors):
        assert np.array_equal(getattr(eng, name), was), name
    eng.stop()
