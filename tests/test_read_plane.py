"""The batched-ReadIndex read plane (round 9): zero-append linearizable
quorum reads.

Covers the read plane's safety contract end to end against the serving
engine: a quorum GET must never append to the log or the WAL (reference
raft read_only.go — ReadIndex piggybacks on the heartbeat quorum), must
serve exactly what the propose-path QGET would have served at the same
index, must FAIL (or re-confirm) — never serve stale — when leadership is
lost while the read is parked, and must keep the leader-lease fast path
off unless explicitly configured. Every case runs with the read round's
record built from the on-device diff (compact readback, the default) and
from the full state.
"""
import os
import threading
import time

import numpy as np
import pytest

from etcd_tpu import errors
from etcd_tpu.server.engine import EngineConfig, MultiEngine
from etcd_tpu.server.request import Request


@pytest.fixture(autouse=True, params=[True, False], ids=["compact", "full"])
def compact_readback(request, monkeypatch):
    """The readback that make_cfg's engines build their records from."""
    monkeypatch.setitem(DEFAULTS, "compact_readback", request.param)
    return request.param


DEFAULTS = {}


def make_cfg(tmp, **kw):
    for k, v in DEFAULTS.items():
        kw.setdefault(k, v)
    kw.setdefault("groups", 4)
    kw.setdefault("peers", 5)
    kw.setdefault("window", 16)
    kw.setdefault("max_ents", 4)
    kw.setdefault("heartbeat_tick", 3)
    kw.setdefault("request_timeout", 30.0)
    kw.setdefault("fsync", False)  # tmpdirs; durability logic unchanged
    return EngineConfig(data_dir=str(tmp), **kw)


def run_until(eng, pred, max_rounds=400, msg="condition"):
    for _ in range(max_rounds):
        if pred():
            return
        eng.run_round()
    raise AssertionError(f"{msg} not reached in {max_rounds} rounds")


def do_async(eng, g, r, timeout=None):
    """Issue a blocking do() from a side thread so the test thread keeps
    driving rounds deterministically."""
    out = {}

    def work():
        try:
            out["res"] = eng.do(g, r, timeout=timeout)
        except Exception as e:  # surfaced by settle()
            out["err"] = e

    t = threading.Thread(target=work, daemon=True)
    t.start()
    return t, out


def settle(eng, t, out, max_rounds=500):
    for _ in range(max_rounds):
        if not t.is_alive():
            break
        eng.run_round()
        t.join(timeout=0.001)
    t.join(timeout=1.0)
    if "err" in out:
        raise out["err"]
    assert "res" in out, "request did not complete"
    return out["res"]


def put(eng, g, key, val):
    t, out = do_async(eng, g, Request(method="PUT", path=key, val=val))
    return settle(eng, t, out)


def qread(eng, g, key, timeout=None, max_rounds=500):
    t, out = do_async(eng, g,
                      Request(method="GET", path=key, quorum=True),
                      timeout=timeout)
    return settle(eng, t, out, max_rounds=max_rounds)


def wal_bytes(data_dir):
    n = 0
    for root, _dirs, files in os.walk(data_dir):
        for f in files:
            try:
                n += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return n


def log_lengths(eng):
    return np.where(eng.h_mask, eng.h_last, 0).max(axis=1).copy()


def quiesce_wal(eng, data_dir, stable_rounds=20, max_rounds=400):
    """Run rounds until the WAL byte count stops moving: commit-index
    convergence keeps appending hardstate diffs for a few rounds after
    the last ack, and the zero-append assertion needs a settled
    baseline."""
    stable, wb = 0, wal_bytes(data_dir)
    for _ in range(max_rounds):
        eng.run_round()
        nb = wal_bytes(data_dir)
        stable = stable + 1 if nb == wb else 0
        wb = nb
        if stable >= stable_rounds:
            return wb
    raise AssertionError("WAL never quiesced")


def test_quorum_read_appends_nothing(tmp_path):
    """The acceptance headline: a read-only quorum-read phase moves
    neither the WAL byte count nor any group's log length."""
    d = tmp_path / "za"
    eng = MultiEngine(make_cfg(d))
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(4)),
              msg="leaders")
    for g in range(4):
        put(eng, g, "/k", f"v{g}")
    wb0 = quiesce_wal(eng, str(d))
    ll0 = log_lengths(eng)

    for rep in range(3):
        for g in range(4):
            ev = qread(eng, g, "/k")
            assert ev.node.value == f"v{g}"
    # A few extra rounds so any (wrong) read-plane append would reach
    # the WAL writer before the assert samples it.
    for _ in range(30):
        eng.run_round()

    assert wal_bytes(str(d)) == wb0, "quorum reads appended WAL bytes"
    assert (log_lengths(eng) == ll0).all(), "quorum reads grew the log"
    # And the reads were metered as reads, not proposals: nothing new in
    # the proposal families.
    eng.stop()


def test_quorum_read_differential_vs_qget(tmp_path):
    """The read plane serves exactly what the propose-path QGET serves:
    same value, same store index — for every group, before and after
    interleaved writes."""
    eng = MultiEngine(make_cfg(tmp_path / "dq"))
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(4)),
              msg="leaders")
    for step in range(3):
        for g in range(4):
            put(eng, g, "/d", f"v{step}.{g}")
        for g in range(4):
            t, out = do_async(eng, g, Request(method="QGET", path="/d"))
            via_log = settle(eng, t, out)
            via_read = qread(eng, g, "/d")
            assert via_read.node.value == via_log.node.value \
                == f"v{step}.{g}"
            assert via_read.node.modified_index \
                == via_log.node.modified_index
            assert via_read.etcd_index == via_log.etcd_index
    eng.stop()


def test_quorum_read_sees_own_write(tmp_path):
    """Read-your-writes across the ack boundary: a quorum read issued
    after a write's ack must observe that write (the read index is
    captured at >= the acked commit index)."""
    eng = MultiEngine(make_cfg(tmp_path / "ryw"))
    run_until(eng, lambda: eng.leader_slot(0) >= 0, msg="leader")
    for i in range(8):
        put(eng, 0, "/w", f"v{i}")
        ev = qread(eng, 0, "/w")
        assert ev.node.value == f"v{i}"
    eng.stop()


def test_parked_read_fails_on_leadership_loss(tmp_path):
    """A read parked under a partitioned leader is never served stale:
    the deposed leader's confirmation never arrives and the read times
    out with a raft error (re-confirmation under the next leader is the
    other legal outcome — what it must never do is return data)."""
    import jax.numpy as jnp

    eng = MultiEngine(make_cfg(tmp_path / "ll", request_timeout=6.0))
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(4)),
              msg="leaders")
    put(eng, 0, "/p", "committed")
    s = eng.leader_slot(0)

    # Fully partition group 0's leader: its forced read heartbeats can
    # reach no one, so no quorum confirmation can form. Two followers go
    # with it, each alone, so the two peers left cannot elect a leader
    # that could re-confirm the read inside its timeout either (they
    # could once the step variants are compiled: ~12 rounds).
    G, P = eng.cfg.groups, eng.cfg.peers
    m_to = np.ones((G, P, 1, 1), np.int32)
    m_from = np.ones((G, 1, P, 1), np.int32)
    for cut in (s, (s + 1) % P, (s + 2) % P):
        m_to[0, cut] = 0
        m_from[0, 0, cut] = 0
    eng.drop_mask = jnp.asarray(m_to * m_from)

    t, out = do_async(eng, 0,
                      Request(method="GET", path="/p", quorum=True),
                      timeout=2.5)
    deadline = time.time() + 20.0
    while t.is_alive() and time.time() < deadline:
        eng.run_round()
        t.join(timeout=0.001)
    t.join(timeout=1.0)
    assert not t.is_alive(), "parked read neither served nor failed"
    # Either outcome must be an error — never a stale Event. (With the
    # partition still up no side holds a quorum, re-confirmation is
    # impossible, so the only legal result here is the timeout/raft
    # error.)
    assert "err" in out, f"read served under a partitioned leader: {out}"
    assert isinstance(out["err"], errors.EtcdError)
    assert out["err"].code == errors.ECODE_RAFT_INTERNAL

    # Heal; the read plane recovers and serves fresh reads again.
    eng.drop_mask = None
    run_until(eng, lambda: eng.leader_slot(0) >= 0, max_rounds=800,
              msg="re-elect")
    ev = qread(eng, 0, "/p", max_rounds=800)
    assert ev.node.value == "committed"
    eng.stop()


def test_read_lease_off_by_default(tmp_path):
    """EngineConfig.read_lease_ms defaults to 0 and the lease fast path
    stays untaken: every quorum read pays a confirmation round."""
    eng = MultiEngine(make_cfg(tmp_path / "ld"))
    assert eng.cfg.read_lease_ms == 0
    run_until(eng, lambda: eng.leader_slot(0) >= 0, msg="leader")
    put(eng, 0, "/l", "v")
    from etcd_tpu.server import obs as obs_mod
    lease0 = obs_mod.read_index_lease.value
    for _ in range(4):
        assert qread(eng, 0, "/l").node.value == "v"
    assert obs_mod.read_index_lease.value == lease0
    assert float(eng._lease_until.max()) == 0.0
    eng.stop()


def test_read_lease_fast_path_still_fresh(tmp_path):
    """With read_lease_ms set, back-to-back reads take the lease path —
    and still observe the latest acked write (the lease read parks at
    the CURRENT commit mirror, not the confirmation-time index)."""
    eng = MultiEngine(make_cfg(tmp_path / "lf", read_lease_ms=60_000))
    run_until(eng, lambda: eng.leader_slot(0) >= 0, msg="leader")
    from etcd_tpu.server import obs as obs_mod
    put(eng, 0, "/f", "v0")
    assert qread(eng, 0, "/f").node.value == "v0"  # grants the lease
    lease0 = obs_mod.read_index_lease.value
    for i in range(3):
        put(eng, 0, "/f", f"v{i + 1}")
        assert qread(eng, 0, "/f").node.value == f"v{i + 1}"
    assert obs_mod.read_index_lease.value > lease0, \
        "lease fast path never engaged"
    eng.stop()


def test_engine_stop_fails_parked_reads(tmp_path):
    """stop() drains the parked-read queues with an error instead of
    leaving serving threads to ride out the request timeout."""
    eng = MultiEngine(make_cfg(tmp_path / "st"))
    run_until(eng, lambda: eng.leader_slot(0) >= 0, msg="leader")
    put(eng, 0, "/s", "v")
    # Park a read and stop the engine WITHOUT driving another round.
    t, out = do_async(eng, 0,
                      Request(method="GET", path="/s", quorum=True),
                      timeout=10.0)
    for _ in range(200):
        with eng._lock:
            if eng._reads_waiting:
                break
        time.sleep(0.005)
    eng.stop()
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert "err" in out and isinstance(out["err"], errors.EtcdError)


def test_read_parked_by_submit_pairs_after_the_snapshot_waits_its_own_round(
        tmp_path):
    """The event-loop front parks quorum reads through submit_pairs, under
    the lock the round takes its read_take snapshot under: a read parked
    after a round's snapshot (its step already dispatched) is not moved
    by that round's confirmation; it is confirmed by a later round."""
    from etcd_tpu.utils.wait import Sink

    eng = MultiEngine(make_cfg(tmp_path / "late"))
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(4)),
              msg="leaders")
    put(eng, 0, "/k", "v")
    parked0 = eng.obs.g_read_parked.value
    sink = Sink(lambda: None)
    read = Request(method="GET", path="/k", quorum=True)
    early = eng.submit_pairs([(0, read)], sink)[0]
    late, takes = [], []
    real = eng._confirm_reads

    def confirm(read_take, conf, rc):
        # this round's step was dispatched with `read_take` pinned
        if not late:
            late.append(eng.submit_pairs([(0, read)], sink)[0])
            takes.append(dict(read_take))
        return real(read_take, conf, rc)

    eng._confirm_reads = confirm
    run_until(eng, lambda: late, msg="a read round")
    assert takes == [{0: 1}]            # the snapshot holds the early read
    assert late[0].rid in [rid for rid, _r in eng._reads[0]]
    order = []
    for _ in range(400):
        order += [rid for rid, _v in sink.drain()]
        if len(order) == 2:
            break
        eng.run_round()
    assert order == [early.rid, late[0].rid]
    assert eng._reads_waiting == 0 and eng._ripe_waiting == 0
    # settle closes the read's accounts as a woken _quorum_read does
    eng.settle([early, late[0]], [None, None])
    assert eng.obs.g_read_parked.value == parked0
    eng.stop()
