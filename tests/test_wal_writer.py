"""WAL-writer compartment (walwriter.WALWriter): group commit, parallel
per-range segment streams, and the crash-ordering invariant.

Pins the contract the compartmentalization must keep: acks strictly
follow their round's fsync (gating on the durability watermark — the
doc.go:31-39 contract, now proven across a real SIGKILL); a crash
mid-group-commit or mid-parallel-fsync truncates replay at the last
durable round boundary PER STREAM and never loses an acked write;
wal_shards=1 and wal_shards=4 are replay-equivalent (store state, event
history, watch replay); a dead writer shard fails the engine at the next
seam, never hangs; and the root layout stays byte-compatible at S=1.
"""
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from etcd_tpu import errors
from etcd_tpu.server.engine import EngineConfig, MultiEngine
from etcd_tpu.server import obs as obs_mod
from etcd_tpu.server.enginewal import EngineWAL, RoundRecord
from etcd_tpu.server.request import Request
from etcd_tpu.server.walwriter import WALWriter, shard_dir, split_record

G, P = 8, 3  # one kernel shape for the module => one XLA compile


# -- pure writer-layer tests (no engine, no kernel) --------------------------


def mkrec(round_no, groups=G, tag="p"):
    """A round record touching EVERY group: hs/last/ring columns across
    the full range plus one entry per group (so every shard range gets a
    non-empty sub-record)."""
    rec = RoundRecord(round_no=round_no)
    g = np.arange(groups, dtype=np.uint32)
    rec.hs_g = g
    rec.hs_p = np.zeros(groups, np.uint16)
    rec.hs_term = np.full(groups, round_no + 1, np.uint32)
    rec.hs_vote = np.zeros(groups, np.uint16)
    rec.hs_commit = np.full(groups, round_no, np.uint32)
    rec.entries = [(int(gg), round_no + 1, 1,
                    f"{tag}-{gg}-{round_no}".encode()) for gg in g]
    return rec


def test_split_record_partitions_and_reassembles():
    rec = mkrec(5)
    rec.last_g = np.array([0, 3, 7], np.uint32)
    rec.last_p = np.zeros(3, np.uint16)
    rec.last_v = np.array([10, 11, 12], np.uint32)
    rec.confs = [(2, 1, 0), (6, 2, 1)]
    ranges = [(0, 2), (2, 4), (4, 6), (6, 8)]
    subs = split_record(rec, ranges)
    assert len(subs) == 4 and all(s is not None for s in subs)
    # Disjoint union: every column row / entry / conf lands in exactly
    # the range owning its group, with content intact.
    assert sorted(g for s in subs for g in s.hs_g) == list(range(8))
    assert sorted(g for s in subs for g in s.last_g) == [0, 3, 7]
    assert sorted(e for s in subs for e in s.entries) == sorted(rec.entries)
    assert sorted(c for s in subs for c in s.confs) == sorted(rec.confs)
    for (lo, hi), s in zip(ranges, subs):
        assert all(lo <= g < hi for g in s.hs_g)
        assert all(lo <= e[0] < hi for e in s.entries)
        assert s.round_no == 5
    # A range with no deltas maps to None.
    narrow = RoundRecord(round_no=1)
    narrow.entries = [(0, 1, 1, b"x")]
    subs = split_record(narrow, ranges)
    assert subs[0] is not None and subs[1:] == [None, None, None]


def test_group_commit_one_fsync_covers_queued_rounds(tmp_path):
    """While one fsync is in flight the queue refills; the next sync
    covers everything queued — k rounds, one fsync."""
    w = WALWriter(str(tmp_path), groups=G, shards=1, fsync=False,
                  queue_rounds=64)
    gate = threading.Event()
    orig_sync = w.shards[0].wal.sync

    def gated_sync():
        gate.wait(10)
        orig_sync()

    w.shards[0].wal.sync = gated_sync
    t0 = w.submit(mkrec(0))          # writer picks this up, parks in sync
    time.sleep(0.1)
    for r in range(1, 10):
        w.submit(mkrec(r))           # queue up behind the parked fsync
    gate.set()
    w.flush()
    sh = w.shards[0]
    assert w._submitted == 10
    assert max(sh.batch_sizes) >= 5, list(sh.batch_sizes)
    assert sh.fsyncs < 10, sh.fsyncs
    assert t0 == 1 and w.ticket == 10   # tickets: monotonic submission seq
    w.shards[0].wal.sync = orig_sync
    w.close()
    rounds = [r.round_no for r in
              WALWriter(str(tmp_path), groups=G, shards=1).replay(-1)]
    assert rounds == list(range(10))


def test_append_sync_is_durable_on_return_and_phase_in_writer(tmp_path):
    """append_sync keeps the old inline EngineWAL.append contract, and
    the fsync is clocked by the WRITER thread into its shard's own
    histogram, etcd_wal_writer_fsync_seconds (the round loop only ever
    pays for the hand-off): one observation a shard for one record."""
    for S in (1, 4):
        ob = obs_mod.EngineObs(wal_shards=S, applier_shards=1)
        before = [(h.count, h.sum) for h in ob.h_wal_fsync]
        w = WALWriter(str(tmp_path / f"s{S}"), groups=G, shards=S,
                      fsync=False, obs=ob)
        w.append_sync(mkrec(0))
        assert w._durable == w.ticket == 1
        after = [(h.count, h.sum) for h in ob.h_wal_fsync]
        assert len(after) == S
        for (c0, s0), (c1, s1) in zip(before, after):
            assert c1 == c0 + 1 and s1 > s0
        w.close()


def test_writer_failure_is_fail_stop(tmp_path):
    """A failed shard stays failed: the error re-raises at every later
    seam (wait_durable / submit / flush) and the thread is never
    respawned — a retry would re-append around a hole."""
    w = WALWriter(str(tmp_path), groups=G, shards=1, fsync=False)

    def boom():
        raise RuntimeError("disk on fire")

    w.shards[0].wal.sync = boom
    t = w.submit(mkrec(0))
    with pytest.raises(RuntimeError, match="disk on fire"):
        w.wait_durable(t)
    with pytest.raises(RuntimeError, match="disk on fire"):
        w.submit(mkrec(1))
    w.shards[0].thread.join(timeout=5)
    assert not w.shards[0].thread.is_alive()
    w._ensure_threads()
    assert not w.shards[0].thread.is_alive(), "failed shard respawned"
    w.close()


def test_mid_parallel_fsync_boundary_cut(tmp_path):
    """Deterministic image of a crash BETWEEN the parallel per-stream
    fsyncs: streams stopped at unequal tails. Replay must settle on the
    min-over-streams boundary, yield nothing beyond it, and physically
    cut the streams that ran ahead (their extra rounds were never acked
    — the watermark is the min — but left on disk they would alias
    reused round numbers after restart)."""
    tails = [9, 7, 9, 8]
    for k, tail in enumerate(tails):
        wal = EngineWAL(shard_dir(str(tmp_path), k), fsync=False)
        for r in range(tail + 1):
            rec = RoundRecord(round_no=r)
            rec.entries = [(2 * k, r + 1, 1, f"s{k}-{r}".encode())]
            wal.append(rec)
        wal.close()
    w = WALWriter(str(tmp_path), groups=G, shards=4, fsync=False)
    recs = list(w.replay(-1))
    assert max(r.round_no for r in recs) == 7
    # Every stream contributed its full surviving prefix.
    per_round = {}
    for r in recs:
        for g, *_ in r.entries:
            per_round.setdefault(r.round_no, set()).add(g)
    assert all(per_round[r] == {0, 2, 4, 6} for r in range(8))
    w.close()
    # The cut is physical: a raw re-read of each stream ends at 7.
    for k in range(4):
        e = EngineWAL(shard_dir(str(tmp_path), k))
        got = [r.round_no for r in e.replay(-1)]
        assert got == list(range(8)), (k, got)
        assert e.last_round == 7
        e.close()


def test_torn_tails_truncate_per_stream(tmp_path):
    """Crash mid-group-commit: every stream may carry a torn frame (and
    trailing garbage) past its last whole record. Replay truncates each
    stream independently and the writer appends cleanly afterwards."""
    w = WALWriter(str(tmp_path), groups=G, shards=4, fsync=False)
    for r in range(6):
        w.append_sync(mkrec(r))
    w.close()
    for k in range(4):
        segs = sorted(n for n in os.listdir(shard_dir(str(tmp_path), k))
                      if n.endswith(".wal"))
        with open(os.path.join(shard_dir(str(tmp_path), k), segs[-1]),
                  "ab") as f:
            f.write(b"\x02\x00\x00\x00GARBAGE-TORN-FRAME"[:10 + k])
    w2 = WALWriter(str(tmp_path), groups=G, shards=4, fsync=False)
    rounds = sorted({r.round_no for r in w2.replay(-1)})
    assert rounds == list(range(6))
    w2.append_sync(mkrec(6))     # appender positioned past the tear
    w2.close()
    w3 = WALWriter(str(tmp_path), groups=G, shards=4, fsync=False)
    assert sorted({r.round_no for r in w3.replay(-1)}) == list(range(7))
    w3.close()


_CRASH_CHILD = r"""
import sys
from etcd_tpu.server.enginewal import RoundRecord
from etcd_tpu.server.walwriter import WALWriter
import numpy as np

d, S, G, ackpath = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
w = WALWriter(d, groups=G, shards=S, fsync=True, queue_rounds=8)
ack = open(ackpath, "a")
pending = []
r = 0
print("READY", flush=True)
while True:
    rec = RoundRecord(round_no=r)
    rec.entries = [(g, r + 1, 1, ("c-%d-%d" % (g, r)).encode())
                   for g in range(G)]
    pending.append((r, w.submit(rec)))
    r += 1
    if len(pending) >= 6:            # pipeline depth: real group commits
        rr, tt = pending.pop(0)
        w.wait_durable(tt)           # ack gates on the watermark
        ack.write("%d\n" % rr)
        ack.flush()
"""


@pytest.mark.parametrize("S", [1, 4])
def test_sigkill_mid_commit_loses_no_acked_write(tmp_path, S):
    """The invariant, proven against a real crash: SIGKILL the writer
    process while group commits (S=1) / parallel per-stream fsyncs (S=4)
    are in flight; every round the child ACKED (observed durable via
    wait_durable) must replay in full from what survived on disk."""
    d = tmp_path / f"crash{S}"
    ackpath = tmp_path / f"acked{S}.log"
    proc = subprocess.Popen(
        [sys.executable, "-c", _CRASH_CHILD, str(d), str(S), str(G),
         str(ackpath)],
        stdout=subprocess.PIPE, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    try:
        assert proc.stdout.readline().strip() == b"READY"
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if len(ackpath.read_text().splitlines()) >= 25:
                    break
            except OSError:
                pass
            time.sleep(0.005)
        proc.send_signal(signal.SIGKILL)   # mid-batch, mid-fsync
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    acked = [int(x) for x in ackpath.read_text().splitlines() if x]
    assert len(acked) >= 25, "child never got going"

    w = WALWriter(str(d), groups=G, shards=S)
    per_round = {}
    for rec in w.replay(-1):
        for g, _, _, payload in rec.entries:
            per_round.setdefault(rec.round_no, {})[g] = payload
    w.close()
    for r in acked:
        assert per_round.get(r) == {
            g: ("c-%d-%d" % (g, r)).encode() for g in range(G)
        }, f"acked round {r} lost or partial after crash"
    # Replay is a consistent prefix: no gaps below the boundary.
    assert sorted(per_round) == list(range(len(per_round)))


# -- engine-level tests ------------------------------------------------------


def make_engine(tmp, wal_shards, **kw):
    kw.setdefault("groups", G)
    kw.setdefault("peers", P)
    kw.setdefault("window", 16)
    kw.setdefault("max_ents", 4)
    kw.setdefault("heartbeat_tick", 3)
    kw.setdefault("request_timeout", 30.0)
    kw.setdefault("fsync", False)
    kw.setdefault("sync_interval", 0.0)
    kw.setdefault("checkpoint_rounds", 1 << 30)
    kw.setdefault("applier_shards", 2)
    return MultiEngine(EngineConfig(data_dir=str(tmp),
                                    wal_shards=wal_shards, **kw))


def ev_sig(e):
    def nd(x):
        if x is None:
            return None
        return (x.key, x.value, x.dir, x.created_index, x.modified_index,
                x.expiration)
    return (e.action, nd(e.node), nd(e.prev_node), e.etcd_index)


def history_replay(st):
    hist = st.watcher_hub.event_history
    out = []
    i = hist.start_index
    while i <= hist.last_index:
        e = hist.scan("/", True, i)
        if e is None:
            break
        out.append(ev_sig(e))
        i = e.etcd_index + 1
    return out


def watch_replay(st, since):
    w = st.watch("/", recursive=True, stream=True, since_index=since)
    out = []
    while True:
        e = w.next_event(timeout=0.05)
        if e is None:
            return out
        out.append(ev_sig(e))


def run_workload(tmp, wal_shards):
    """Seeded per-group workload covering the event-producing apply
    shapes (PUT chains, CAS, POST, conditional create, DELETE, a failing
    CAS), then a full RESTART: what comes back is pure WAL replay, which
    is exactly what the sharded log must reproduce."""
    eng = make_engine(tmp, wal_shards)
    eng.start()
    try:
        assert eng.wait_leaders(60), "no leaders"
        results = {}

        def client(g):
            out = []

            def do(r):
                try:
                    return ev_sig(eng.do(g, r, timeout=30))
                except errors.EtcdError as e:
                    return ("err", e.code, e.cause)

            for i in range(6):
                out.append(do(Request(method="PUT", path=f"/k{i % 2}",
                                      val=f"v{g}_{i}")))
            out.append(do(Request(method="PUT", path="/k0",
                                  val="swapped", prev_value=f"v{g}_4")))
            out.append(do(Request(method="POST", path="/q", val="job")))
            out.append(do(Request(method="PUT", path="/new", val="n",
                                  prev_exist=False)))
            out.append(do(Request(method="DELETE", path="/k1")))
            out.append(do(Request(method="PUT", path="/k0", val="nope",
                                  prev_value="wrong")))   # fails: 101
            results[g] = out

        ths = [threading.Thread(target=client, args=(g,))
               for g in range(G)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert all(not t.is_alive() for t in ths), "client writes hung"
    finally:
        eng.stop()

    eng2 = make_engine(tmp, wal_shards)   # restart: state = replay only
    try:
        state = {}
        for g in range(G):
            st = eng2.store(g)
            dump = st.get("/", recursive=True, want_sorted=True)
            state[g] = {"dump": ev_sig(dump),
                        "index": st.current_index,
                        "history": history_replay(st),
                        "watch": watch_replay(st, 1)}
        return results, state
    finally:
        eng2.stop()


def test_differential_wal_shards_1_vs_4(tmp_path):
    """The sharded log's pin (mirrors the applier pool's K-differential):
    wal_shards=4 must be observably identical to the single stream after
    replay — waiter results, store state, event history, watch replay."""
    r1, s1 = run_workload(tmp_path / "ws1", wal_shards=1)
    r4, s4 = run_workload(tmp_path / "ws4", wal_shards=4)
    assert r1 == r4, "waiter-visible results diverged"
    for g in range(G):
        assert s1[g]["index"] == s4[g]["index"], g
        assert s1[g]["dump"] == s4[g]["dump"], g
        assert s1[g]["history"] == s4[g]["history"], g
        assert s1[g]["watch"] == s4[g]["watch"], g


def test_engine_restart_sharded_wal_with_torn_tails(tmp_path):
    """Engine-level crash-recovery: acked writes + torn bytes on EVERY
    shard stream; restart replays all acked data and keeps serving."""
    d = tmp_path / "torn"
    eng = make_engine(d, wal_shards=4)
    eng.start()
    try:
        assert eng.wait_leaders(60)
        for g in range(G):
            eng.do(g, Request(method="PUT", path="/persist", val=f"g{g}"),
                   timeout=30)
    finally:
        eng.stop()
    for k in range(4):
        sd = shard_dir(str(d), k)
        segs = sorted(n for n in os.listdir(sd) if n.endswith(".wal"))
        with open(os.path.join(sd, segs[-1]), "ab") as f:
            f.write(b"\x02\x00\x00\x00torn-mid-append")
    eng2 = make_engine(d, wal_shards=4)
    try:
        for g in range(G):
            ev = eng2.do(g, Request(method="GET", path="/persist"))
            assert ev.node.value == f"g{g}", f"group {g} lost data"
        eng2.start()
        assert eng2.wait_leaders(60)
        eng2.do(0, Request(method="PUT", path="/after", val="restart"),
                timeout=30)
        assert eng2.do(0, Request(method="GET", path="/after")
                       ).node.value == "restart"
    finally:
        eng2.stop()


def test_geometry_pins_wal_shards(tmp_path):
    """wal_shards may go 1 -> S once (root freezes as legacy history);
    any other change is refused — shrinking would leave frozen shard
    streams dragging the min-over-streams boundary forever."""
    d = tmp_path / "geo"
    eng = make_engine(d, wal_shards=1)
    eng.stop()
    eng = make_engine(d, wal_shards=4)     # 1 -> 4: allowed, pins S=4
    eng.stop()
    with pytest.raises(ValueError, match="wal_shards"):
        make_engine(d, wal_shards=2)       # 4 -> 2: refused
    eng = make_engine(d, wal_shards=4)     # same S: fine
    eng.stop()
