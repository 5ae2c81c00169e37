"""MultiEngine: etcd served from the batched consensus kernel.

Covers VERDICT round-1 item 1 (the batched-kernel host engine): clients
PUT/GET against kernel-served groups, restart-from-WAL, checkpoints,
device-side membership changes, and snapshot-install of lagging followers
(reference seams: raft/multinode.go:166-322, etcdserver/raft.go:112-172,
raft/doc.go:31-39 ordering contract).
"""
import threading
import time

import numpy as np
import pytest

from etcd_tpu import errors
from etcd_tpu.server.engine import EngineConfig, MultiEngine
from etcd_tpu.server.request import Request


# One shared kernel shape across tests => one XLA compile for the module.
def make_cfg(tmp, **kw):
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


def drive_conf(eng, g, op, slot, max_rounds=600, timeout=30.0):
    """Propose a conf change from a side thread while driving rounds;
    returns the new slot list (asserts the change settled)."""
    res = {}

    def work():
        try:
            res["res"] = eng.conf_change(g, op, slot, timeout=timeout)
        except Exception as e:  # pragma: no cover - surfaced by caller
            res["err"] = e

    th = threading.Thread(target=work, daemon=True)
    th.start()
    for _ in range(max_rounds):
        if not th.is_alive():
            break
        eng.run_round()
        th.join(timeout=0.001)
    th.join(1.0)
    assert "err" not in res, res.get("err")
    assert "res" in res, f"conf {op} slot {slot} never settled"
    return res["res"]


def partition_mask(G, P, rng, prob=0.4):
    """Random drop mask: fully partition one random slot in ~prob of the
    groups; returns the (G, P, P, 1)-broadcastable multiplier for
    eng.drop_mask (or None when nothing got partitioned)."""
    import jax.numpy as jnp

    m_to = np.ones((G, P, 1, 1), np.int32)
    m_from = np.ones((G, 1, P, 1), np.int32)
    any_cut = False
    for g in range(G):
        if rng.rand() < prob:
            s = rng.randint(P)
            m_to[g, s] = 0
            m_from[g, 0, s] = 0
            any_cut = True
    return jnp.asarray(m_to * m_from) if any_cut else None


def put_async(eng, g, key, val):
    """Issue a blocking do() from a side thread so the test thread can keep
    driving rounds deterministically."""
    out = {}

    def work():
        try:
            out["res"] = eng.do(g, Request(method="PUT", path=key, val=val))
        except Exception as e:  # pragma: no cover - surfaced by caller
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


def test_engine_serves_puts_and_gets(tmp_path):
    eng = MultiEngine(make_cfg(tmp_path / "e1"))
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(4)),
              msg="leaders")
    # Tenant isolation: same key, different groups, different values.
    for g in range(4):
        t, out = put_async(eng, g, "/k", f"v{g}")
        ev = settle(eng, t, out)
        assert ev.action == "set"
    for g in range(4):
        ev = eng.do(g, Request(method="GET", path="/k"))
        assert ev.node.value == f"v{g}"
    # Unknown key errors like etcd.
    with pytest.raises(errors.EtcdError):
        eng.do(0, Request(method="GET", path="/nope"))
    eng.stop()


def test_engine_mask_watchdog_repairs_corrupt_device_mask(tmp_path):
    """The peer_mask liveness watchdog: membership truth lives in h_mask
    (it flows host -> device only), so a corrupted DEVICE mask — the
    observed donated-buffer failure mode: one active slot per group,
    silencing all replication and suppressing campaigns — must be
    detected and restored within mask_check_rounds, after which
    replication resumes without outside help."""
    import jax.numpy as jnp

    eng = MultiEngine(make_cfg(tmp_path / "wd", mask_check_rounds=16))
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(4)),
              msg="leaders")
    t, out = put_async(eng, 0, "/a", "1")
    settle(eng, t, out)
    G, P = eng.cfg.groups, eng.cfg.peers
    diag = np.zeros((G, P), bool)
    diag[np.arange(G), np.arange(G) % P] = True
    eng.st = eng.st._replace(peer_mask=jnp.asarray(diag))
    for _ in range(eng.cfg.mask_check_rounds + 1):
        eng.run_round()
    assert eng.mask_repairs >= 1
    assert np.array_equal(np.asarray(eng.st.peer_mask), eng.h_mask)
    t, out = put_async(eng, 0, "/b", "2")
    ev = settle(eng, t, out)
    assert ev.node.value == "2"
    eng.stop()


def test_engine_background_thread_serving(tmp_path):
    eng = MultiEngine(make_cfg(tmp_path / "e2"))
    eng.start()
    try:
        assert eng.wait_leaders(60.0)
        ev = eng.do(1, Request(method="PUT", path="/a/b", val="x"))
        assert ev.node.value == "x"
        ev = eng.do(1, Request(method="GET", path="/a/b", quorum=True))
        assert ev.node.value == "x"
    finally:
        eng.stop()


def test_engine_restart_from_wal(tmp_path):
    d = tmp_path / "e3"
    eng = MultiEngine(make_cfg(d))
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(4)),
              msg="leaders")
    for g in range(4):
        t, out = put_async(eng, g, "/persist", f"g{g}")
        settle(eng, t, out)
    eng.stop()

    eng2 = MultiEngine(make_cfg(d))
    # Data is there BEFORE any round runs: restore replays WAL into stores.
    for g in range(4):
        ev = eng2.do(g, Request(method="GET", path="/persist"))
        assert ev.node.value == f"g{g}", f"group {g} lost data"
    # The restarted cluster still makes progress.
    run_until(eng2, lambda: all(eng2.leader_slot(g) >= 0 for g in range(4)),
              msg="re-election")
    t, out = put_async(eng2, 0, "/after", "restart")
    settle(eng2, t, out)
    assert eng2.do(0, Request(method="GET", path="/after")).node.value == \
        "restart"
    eng2.stop()


def test_engine_checkpoint_and_segment_purge(tmp_path):
    d = tmp_path / "e4"
    eng = MultiEngine(make_cfg(d, checkpoint_rounds=64))
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(4)),
              msg="leaders")
    t, out = put_async(eng, 2, "/pre-ckpt", "1")
    settle(eng, t, out)
    for _ in range(130):   # cross >= 2 checkpoint boundaries
        eng.run_round()
    t, out = put_async(eng, 2, "/post-ckpt", "2")
    settle(eng, t, out)
    eng.stop()

    import os
    names = os.listdir(d)
    assert any(n.startswith("checkpoint-") for n in names), names

    eng2 = MultiEngine(make_cfg(d, checkpoint_rounds=64))
    assert eng2.do(2, Request(method="GET", path="/pre-ckpt")).node.value == "1"
    assert eng2.do(2, Request(method="GET", path="/post-ckpt")).node.value == "2"
    eng2.stop()


def test_engine_conf_change_grow_and_shrink(tmp_path):
    eng = MultiEngine(make_cfg(tmp_path / "e5", initial_peers=3))
    run_until(eng, lambda: eng.leader_slot(0) >= 0, msg="leader")
    assert sorted(eng.status(0)["active_slots"]) == [0, 1, 2]

    # Grow 3 -> 4 -> 5 through the group's own consensus.
    for new_slot in (3, 4):
        t, out = put_async(eng, 0, f"/before{new_slot}", "x")
        settle(eng, t, out)
        res = {}

        def conf():
            try:
                res["slots"] = eng.conf_change(0, "add", new_slot,
                                               timeout=30.0)
            except Exception as e:
                res["err"] = e

        th = threading.Thread(target=conf, daemon=True)
        th.start()
        for _ in range(400):
            if not th.is_alive():
                break
            eng.run_round()
            th.join(timeout=0.001)
        th.join(1.0)
        assert "err" not in res, res.get("err")
        assert new_slot in res["slots"]
        # The joiner catches up and acks: group commit keeps advancing.
        t, out = put_async(eng, 0, f"/after{new_slot}", "y")
        settle(eng, t, out)
        run_until(
            eng,
            lambda: eng.h_commit[0, new_slot] >= eng.applied[0] - 1
            and eng.h_commit[0, new_slot] > 0,
            msg=f"slot {new_slot} catch-up")

    # Shrink: remove the current leader; the rest re-elect and serve.
    victim = eng.leader_slot(0)
    res = {}

    def conf_rm():
        try:
            res["slots"] = eng.conf_change(0, "remove", victim, timeout=30.0)
        except Exception as e:
            res["err"] = e

    th = threading.Thread(target=conf_rm, daemon=True)
    th.start()
    for _ in range(600):
        if not th.is_alive():
            break
        eng.run_round()
        th.join(timeout=0.001)
    th.join(1.0)
    assert "err" not in res, res.get("err")
    assert victim not in res["slots"] and len(res["slots"]) == 4
    run_until(eng, lambda: eng.leader_slot(0) >= 0, max_rounds=800,
              msg="re-election after leader removal")
    assert eng.leader_slot(0) != victim
    t, out = put_async(eng, 0, "/post-shrink", "z")
    settle(eng, t, out, max_rounds=800)
    assert eng.do(0, Request(method="GET", path="/post-shrink")).node.value \
        == "z"
    eng.stop()


def test_engine_snapshot_install_catches_up_partitioned_follower(tmp_path):
    import jax.numpy as jnp

    eng = MultiEngine(make_cfg(tmp_path / "e6", initial_peers=3))
    run_until(eng, lambda: eng.leader_slot(0) >= 0, msg="leader")
    s = eng.leader_slot(0)
    f = (s + 1) % 3  # victim follower

    # Full partition of (group 0, slot f): no traffic to or from it.
    G, P = eng.cfg.groups, eng.cfg.peers
    m_to = np.ones((G, P, 1, 1), np.int32)
    m_from = np.ones((G, 1, P, 1), np.int32)
    m_to[0, f] = 0
    m_from[0, 0, f] = 0
    eng.drop_mask = jnp.asarray(m_to * m_from)

    # Push the leader's log far beyond the ring window.
    for i in range(eng.cfg.window + 8):
        t, out = put_async(eng, 0, f"/k{i}", str(i))
        settle(eng, t, out)
    assert eng.h_last[0, s] - eng.h_commit[0, f] > eng.cfg.window

    # Heal. The follower either rejoins via appends (impossible here: its
    # entries fell off the ring) or the engine snapshot-installs it.
    eng.drop_mask = None
    run_until(
        eng,
        lambda: (eng.leader_slot(0) >= 0
                 and eng.h_commit[0, f] >= eng.h_commit[0].max() - 1
                 and eng.h_commit[0, f] > eng.cfg.window),
        max_rounds=1500, msg="lagging follower catch-up")
    # And the group still serves writes afterwards.
    t, out = put_async(eng, 0, "/healed", "ok")
    settle(eng, t, out, max_rounds=800)
    assert eng.do(0, Request(method="GET", path="/healed")).node.value == "ok"
    eng.stop()


def test_engine_restart_after_slot_readd_keeps_writes(tmp_path):
    """Soak-found durability bug: remove slot 0, re-add it, write, then
    restart. Restore picks the committed-span slot by argmax(commit) —
    a tie lands on slot 0, whose ring was zeroed below its re-join point,
    so pre-fix the replay resolved those committed entries to term 0 and
    silently dropped them as leader no-ops (ACKED WRITES VANISHED)."""
    d = tmp_path / "readd"

    def mk():
        # Module-standard shape; only group 0 is exercised.
        return MultiEngine(make_cfg(d, initial_peers=3))

    eng = mk()
    run_until(eng, lambda: eng.leader_slot(0) >= 0, msg="leader")
    keys = []
    for i in range(3):
        t, out = put_async(eng, 0, f"/pre{i}", "v")
        settle(eng, t, out)
        keys.append(f"/pre{i}")
    assert 0 not in drive_conf(eng, 0, "remove", 0)
    run_until(eng, lambda: eng.leader_slot(0) >= 0, max_rounds=800,
              msg="re-election")
    for i in range(3):
        t, out = put_async(eng, 0, f"/mid{i}", "v")
        settle(eng, t, out, max_rounds=800)
        keys.append(f"/mid{i}")
    assert 0 in drive_conf(eng, 0, "add", 0)
    for i in range(3):
        t, out = put_async(eng, 0, f"/post{i}", "v")
        settle(eng, t, out, max_rounds=800)
        keys.append(f"/post{i}")
    # The re-added slot must fully catch up: restore picks the span slot
    # by argmax(commit), and the tie lands on slot 0 — the poisoned-ring
    # slot — only once its commit matches the max (the soak's heal
    # window did this implicitly; without it the test passes on broken
    # code).
    run_until(eng,
              lambda: (eng.h_commit[0, 0] == eng.h_commit[0].max()
                       and eng.h_commit[0, 0] > 0),
              max_rounds=800, msg="re-added slot catch-up")
    eng.stop()

    eng2 = mk()
    lost = [k for k in keys
            if eng2.do(0, Request(method="GET", path=k)).node.value != "v"]
    assert not lost, f"acked writes lost after slot re-add restart: {lost}"
    eng2.stop()


def test_engine_watch_fires_on_apply(tmp_path):
    eng = MultiEngine(make_cfg(tmp_path / "e7"))
    run_until(eng, lambda: eng.leader_slot(3) >= 0, msg="leader")
    w = eng.do(3, Request(method="GET", path="/watched", wait=True))
    t, out = put_async(eng, 3, "/watched", "event")
    settle(eng, t, out)
    ev = w.next_event(timeout=5.0)
    assert ev is not None and ev.node.value == "event"
    eng.stop()


def test_engine_http_surface(tmp_path):
    """A real HTTP client PUT/GETs against kernel-served tenant groups
    (the multi-tenant etcd-as-a-service surface, BASELINE.json north star)."""
    import json
    import urllib.error
    import urllib.request

    from etcd_tpu.etcdhttp.tenants import EngineHttp

    def req(method, url, body=None):
        r = urllib.request.Request(url, data=body, method=method)
        if body is not None:
            r.add_header("Content-Type", "application/x-www-form-urlencoded")
        try:
            resp = urllib.request.urlopen(r, timeout=15.0)
            return resp.status, json.loads(resp.read() or b"null")
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"null")

    eng = MultiEngine(make_cfg(tmp_path / "e8"))
    front = EngineHttp(eng)
    front.start()
    eng.start()
    base = front.url
    try:
        assert eng.wait_leaders(60.0)
        st, body = req("PUT", f"{base}/tenants/0/v2/keys/foo", b"value=bar")
        assert st == 201 and body["node"]["value"] == "bar"
        st, body = req("PUT", f"{base}/tenants/1/v2/keys/foo", b"value=other")
        assert st == 201
        st, body = req("GET", f"{base}/tenants/0/v2/keys/foo")
        assert st == 200 and body["node"]["value"] == "bar"
        st, body = req("GET", f"{base}/tenants/1/v2/keys/foo")
        assert body["node"]["value"] == "other"          # tenant isolation
        st, body = req("GET", f"{base}/tenants/2/v2/keys/foo")
        assert st == 404 and body["errorCode"] == 100    # empty tenant
        st, body = req("GET", f"{base}/tenants/99/v2/keys/foo")
        assert st == 404                                  # no such tenant
        st, body = req("GET", f"{base}/tenants/0/status")
        assert st == 200 and body["lead"] >= 0
        st, body = req("GET", f"{base}/engine/status")
        assert st == 200 and body["groups_with_leader"] == eng.cfg.groups
        # CAS through HTTP.
        st, body = req("PUT", f"{base}/tenants/0/v2/keys/foo?prevValue=bar",
                       b"value=baz")
        assert st == 200 and body["action"] == "compareAndSwap"
        st, body = req("PUT", f"{base}/tenants/0/v2/keys/foo?prevValue=bar",
                       b"value=nope")
        assert st == 412 and body["errorCode"] == 101
        # Membership change over HTTP.
        st, body = req("POST", f"{base}/tenants/3/conf",
                       json.dumps({"op": "remove", "slot": 4}).encode())
        assert st == 200 and body["active_slots"] == [0, 1, 2, 3]
    finally:
        front.stop()
        eng.stop()


def test_engine_rounds_under_the_profiler(tmp_path):
    """SURVEY §5 A1: engine rounds driven under jax.profiler.trace (what
    the benchmark's member does from outside; MultiEngine.profile() went
    in PR 41) leave a TensorBoard-loadable trace directory."""
    import os
    import jax
    eng = MultiEngine(make_cfg(tmp_path / "e9"))
    try:
        run_until(eng, lambda: eng.leader_slot(0) >= 0, msg="leader")
        out = str(tmp_path / "profiles")
        with jax.profiler.trace(out):
            for _ in range(3):
                eng.run_round()
        assert os.path.isdir(out)
        found = []
        for root, _, files in os.walk(out):
            found.extend(files)
        assert found, "profiler produced no trace files"
        assert eng.round_ms_ewma > 0
    finally:
        eng.stop()


def test_engine_chaos_soak_acked_writes_survive(tmp_path):
    """Chaos soak (functional-tester analogue on the kernel path): random
    slot partitions flip every epoch while writers hammer all groups;
    the engine is crash-restarted twice mid-run. Every ACKED write must be
    readable afterwards — the durability contract (ack only after the WAL
    fsync of the committing round)."""
    import jax.numpy as jnp

    d = tmp_path / "soak"
    rng = np.random.RandomState(42)
    acked = {}          # key -> group
    epoch = {"n": 0}

    def make_engine():
        return MultiEngine(make_cfg(d, groups=4, peers=5, window=16,
                                    request_timeout=60.0))

    eng = make_engine()
    try:
        run_until(eng, lambda: all(eng.leader_slot(g) >= 0
                                   for g in range(4)), msg="leaders")
        for restart in range(3):
            for ep in range(4):
                epoch["n"] += 1
                # Random partition: one random slot in ~half the groups
                # (never enough to kill quorum everywhere for long).
                eng.drop_mask = partition_mask(eng.cfg.groups,
                                               eng.cfg.peers, rng, prob=0.5)

                outs = []
                for w in range(6):
                    g = rng.randint(4)
                    key = f"/soak/{epoch['n']}_{w}"
                    t, out = put_async(eng, g, key, "v")
                    outs.append((t, out, key, g))
                for t, out, key, g in outs:
                    try:
                        settle(eng, t, out, max_rounds=800)
                    except (AssertionError, errors.EtcdError):
                        continue  # timed out / no leader: not acked
                    acked[key] = g
                eng.drop_mask = None
                for _ in range(10):   # heal window
                    eng.run_round()
            # Crash-restart (except after the final loop).
            eng.stop()
            if restart < 2:
                eng = make_engine()
                run_until(eng, lambda: all(eng.leader_slot(g) >= 0
                                           for g in range(4)),
                          max_rounds=800, msg="post-restart leaders")

        eng2 = make_engine()
        try:
            assert len(acked) >= 30, f"too few acked writes: {len(acked)}"
            lost = [k for k, g in acked.items() if not _has_key(eng2, g, k)]
            assert not lost, f"ACKED writes lost after restart: {lost[:5]}"
        finally:
            eng2.stop()
    finally:
        try:
            eng.stop()
        except Exception:
            pass


def _has_key(eng, g, key):
    try:
        return eng.do(g, Request(method="GET", path=key)).node.value == "v"
    except errors.EtcdError:
        return False


def test_engine_chaos_soak_membership_churn(tmp_path):
    """Chaos soak variant with MEMBERSHIP churn: random add/remove through
    consensus interleaved with partitions, writes and crash-restarts; all
    acked writes must survive (this schedule class found the slot-re-add
    restore bug the dedicated regression test pins)."""
    d = tmp_path / "confsoak"
    rng = np.random.RandomState(17)
    acked = {}

    def mk():
        # Module-standard shape (one shared XLA compile; see make_cfg).
        return MultiEngine(make_cfg(d, request_timeout=60.0,
                                    initial_peers=3))

    eng = mk()
    try:
        NG = eng.cfg.groups
        run_until(eng, lambda: all(eng.leader_slot(g) >= 0
                                   for g in range(NG)), msg="leaders")
        for restart in range(2):
            for ep in range(3):
                g = rng.randint(NG)
                active = list(np.nonzero(eng.h_mask[g])[0])
                grow = (len(active) <= 2
                        or (len(active) < 5 and rng.rand() < 0.5))
                if grow:
                    free = [s for s in range(5) if s not in active]
                    drive_conf(eng, g, "add", int(rng.choice(free)))
                else:
                    drive_conf(eng, g, "remove", int(rng.choice(active)))

                eng.drop_mask = partition_mask(NG, eng.cfg.peers, rng)
                outs = []
                for w in range(4):
                    gg = rng.randint(NG)
                    key = f"/churn/{restart}_{ep}_{w}"
                    t, out = put_async(eng, gg, key, "v")
                    outs.append((t, out, key, gg))
                for t, out, key, gg in outs:
                    try:
                        settle(eng, t, out, max_rounds=800)
                    except (AssertionError, errors.EtcdError):
                        continue
                    acked[key] = gg
                eng.drop_mask = None
                for _ in range(10):
                    eng.run_round()
            eng.stop()
            if restart < 1:
                eng = mk()
                run_until(eng, lambda: all(eng.leader_slot(g) >= 0
                                           for g in range(NG)),
                          max_rounds=900, msg="post-restart leaders")

        eng2 = mk()
        try:
            assert len(acked) >= 12, f"too few acked writes: {len(acked)}"
            lost = [k for k, gg in acked.items()
                    if not _has_key(eng2, gg, k)]
            assert not lost, f"acked writes lost: {lost[:5]}"
        finally:
            eng2.stop()
    finally:
        try:
            eng.stop()
        except Exception:
            pass


def test_engine_violation_dumps_and_fails(tmp_path):
    # VERDICT r2 item 8: the conflict-below-commit flag is a protocol
    # violation detector — the engine must dump diagnostics and fail
    # loudly, not zero the flag and keep serving.
    import glob
    import os

    from etcd_tpu.server.engine import EngineViolation

    cfg = make_cfg(tmp_path)
    eng = MultiEngine(cfg)
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0
                               for g in range(cfg.groups)),
              msg="leaders")
    # Artificially corrupt: raise the violation bit on one instance (the
    # kernel ORs need_host forward, so the next round's readback sees it).
    from etcd_tpu.ops.state import NH_VIOLATION
    eng.st = eng.st._replace(
        need_host=eng.st.need_host.at[1, 2].set(NH_VIOLATION))
    with pytest.raises(EngineViolation):
        run_until(eng, lambda: False, max_rounds=3, msg="violation")
    dumps = glob.glob(os.path.join(str(tmp_path), "diagnostics",
                                   "violation-*.json"))
    assert dumps, "no violation dump written"
    import json

    with open(dumps[0]) as f:
        d = json.load(f)
    assert "1" in d["flagged"]
    assert d["flagged"]["1"]["slots"] == [2]
    assert "term" in d["flagged"]["1"] and "log_term" in d["flagged"]["1"]


def test_engine_batches_hot_group_writes(tmp_path):
    # Group commit for hot tenants (the Zipf answer): many queued writes
    # coalesce into few log entries, every request still acked with its own
    # result, and the batch survives restart replay.
    cfg = make_cfg(tmp_path)
    eng = MultiEngine(cfg)
    run_until(eng, lambda: eng.leader_slot(0) >= 0, msg="leader")
    s = eng.leader_slot(0)
    last0 = int(eng.h_last[0, s])

    n = 100
    results = {}

    def put(i):
        def work():
            try:
                results[i] = eng.do(0, Request(method="PUT",
                                               path=f"/k{i}", val=str(i)))
            except Exception as e:  # pragma: no cover
                results[i] = e
        return work

    threads = [threading.Thread(target=put(i), daemon=True)
               for i in range(n)]
    for th in threads:
        th.start()
    time.sleep(0.3)   # let every do() enqueue before the next round
    for _ in range(300):
        if len(results) == n:
            break
        eng.run_round()
        time.sleep(0.001)
    for th in threads:
        th.join(5)
    assert len(results) == n
    assert not any(isinstance(r, Exception) for r in results.values()), \
        [r for r in results.values() if isinstance(r, Exception)][:3]
    # All n writes applied...
    got = eng.store(0).get("/k7", False, False)
    assert got.node.value == "7"
    # ...but the log grew by far fewer entries than writes (coalescing).
    s = eng.leader_slot(0)
    ents_used = int(eng.h_last[0, s]) - last0
    assert ents_used < n // 2, (ents_used, n)

    # Restart: batched entries replay from the WAL byte-identically.
    eng.wal.close()
    eng2 = MultiEngine(cfg)
    for i in (0, 42, 99):
        assert eng2.store(0).get(f"/k{i}", False, False).node.value == str(i)
    eng2.wal.close()


def test_engine_ttl_expiry_watch_and_restart(tmp_path):
    # VERDICT r2 item 5: TTL keys in engine tenants must expire via a
    # replicated leader SYNC (reference SyncTicker server.go:667-681): the
    # watch fires an "expire" event, and the deletion — riding the log —
    # survives restart replay.
    from etcd_tpu import errors as _err

    cfg = make_cfg(tmp_path, sync_interval=0.02)
    eng = MultiEngine(cfg)
    run_until(eng, lambda: eng.leader_slot(1) >= 0, msg="leader")

    exp = time.time() + 0.4
    out = {}

    def work():
        out["res"] = eng.do(1, Request(method="PUT", path="/ttl",
                                       val="v", expiration=exp))

    t = threading.Thread(target=work, daemon=True)
    t.start()
    settle(eng, t, out)
    w = eng.do(1, Request(method="GET", path="/ttl", wait=True))

    deadline = time.time() + 10
    expired = False
    while time.time() < deadline:
        eng.run_round()
        time.sleep(0.01)
        try:
            eng.store(1).get("/ttl", False, False)
        except _err.EtcdError:
            expired = True
            break
    assert expired, "TTL key never expired in engine mode"
    ev = w.next_event(timeout=5.0)
    assert ev is not None and ev.action == "expire", ev

    # Restart: the SYNC replays from the WAL; the key must stay gone.
    eng.stop()
    eng2 = MultiEngine(cfg)
    with pytest.raises(_err.EtcdError):
        eng2.store(1).get("/ttl", False, False)
    eng2.wal.close()


def test_stage_syncs_due_tenants_once_and_no_tuple_per_tenant(tmp_path):
    """The twice-a-second TTL scan stages one SYNC for each tenant whose
    store holds a DUE expiration, none for the others, none again while
    that SYNC is in flight; it tolerates a store that goes away under the
    scan, and it allocates no container per tenant (G tuples a scan were
    G objects for the collector to count and promote at G=50,000)."""
    import gc

    from etcd_tpu.server.engine import METHOD_SYNC

    class FakeStore:
        def __init__(self, exp):
            self.exp = exp

        def next_expiration(self):
            return self.exp

    eng = MultiEngine(make_cfg(tmp_path, groups=8, sync_interval=0.0))
    try:
        now = 1000.0

        class Stores(dict):
            def get(self, g, default=None):      # store 5 is removed
                return None if g == 5 else dict.get(self, g, default)

        eng._stores = Stores({0: FakeStore(None), 1: FakeStore(now - 1),
                              2: FakeStore(now + 60), 3: FakeStore(now),
                              5: FakeStore(now - 1)})
        eng._stage_syncs(now)
        staged = {g: [t[2].method for t in eng._pending[g]]
                  for g in range(8) if eng._pending[g]}
        assert staged == {1: [METHOD_SYNC], 3: [METHOD_SYNC]}
        assert eng._dirty >= {1, 3}
        eng._stage_syncs(now + 0.5)              # still in flight
        assert [len(eng._pending[g]) for g in (1, 3)] == [1, 1]

        eng._stores = {g: FakeStore(None) for g in range(8)}
        eng._stores.update({g: FakeStore(None) for g in range(8, 20000)})
        gc.collect()
        was = gc.get_threshold()
        gc.set_threshold(1_000_000, 10, 10)      # count, do not collect
        try:
            before = gc.get_count()[0]
            eng._stage_syncs(now)
            grown = gc.get_count()[0] - before
        finally:
            gc.set_threshold(*was)
        assert grown < 100, grown
    finally:
        eng.wal.close()


def admin_async(eng, fn, *args):
    """Run a blocking tenant admin op from a side thread while the test
    thread drives rounds."""
    out = {}

    def work():
        try:
            out["res"] = fn(*args)
        except Exception as e:
            out["err"] = e

    t = threading.Thread(target=work, daemon=True)
    t.start()
    return t, out


def test_engine_tenant_lifecycle(tmp_path):
    # VERDICT r2 item 4: runtime CreateGroup/RemoveGroup (reference
    # multinode.go:181-218) over a fixed pre-compiled pool — create,
    # serve, remove, re-create, restart; geometry guard allows pool growth.
    from etcd_tpu import errors as _err

    cfg = make_cfg(tmp_path, groups=6, initial_tenants=2)
    eng = MultiEngine(cfg)
    assert eng.tenants() == [0, 1]
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in (0, 1)),
              msg="boot leaders")
    # Unprovisioned pool slots never elect.
    assert eng.leader_slot(3) < 0

    t, out = put_async(eng, 0, "/a", "x")
    settle(eng, t, out)

    # Create at the lowest free slot -> 2; serve against it.
    t, out = admin_async(eng, eng.create_tenant)
    g = settle(eng, t, out)
    assert g == 2
    assert eng.tenants() == [0, 1, 2]
    run_until(eng, lambda: eng.leader_slot(2) >= 0, msg="new tenant leader")
    t, out = put_async(eng, 2, "/b", "y")
    settle(eng, t, out)

    # Remove tenant 1; its slot becomes reusable and its state is gone.
    t, out = admin_async(eng, eng.remove_tenant, 1)
    settle(eng, t, out)
    assert eng.tenants() == [0, 2]
    t, out = admin_async(eng, eng.create_tenant, 1)
    assert settle(eng, t, out) == 1
    run_until(eng, lambda: eng.leader_slot(1) >= 0, msg="recreated leader")
    t, out = put_async(eng, 1, "/fresh", "z")
    settle(eng, t, out)
    with pytest.raises(_err.EtcdError):
        eng.store(1).get("/a", False, False)   # no leakage from tenant 0

    # Restart: lifecycle replays from the WAL.
    eng.stop()
    eng2 = MultiEngine(cfg)
    assert eng2.tenants() == [0, 1, 2]
    assert eng2.store(0).get("/a", False, False).node.value == "x"
    assert eng2.store(2).get("/b", False, False).node.value == "y"
    assert eng2.store(1).get("/fresh", False, False).node.value == "z"
    eng2.wal.close()

    # Pool growth: reopen with a larger pool; tenants survive, new slots
    # are unprovisioned and creatable.
    cfg3 = make_cfg(tmp_path, groups=9, initial_tenants=2)
    eng3 = MultiEngine(cfg3)
    assert eng3.tenants() == [0, 1, 2]
    assert eng3.store(2).get("/b", False, False).node.value == "y"
    run_until(eng3, lambda: all(eng3.leader_slot(g) >= 0
                                for g in (0, 1, 2)), msg="regrown leaders")
    t, out = admin_async(eng3, eng3.create_tenant, 7)
    assert settle(eng3, t, out) == 7
    eng3.stop()

    # Shrinking the pool still refuses.
    with pytest.raises(ValueError):
        MultiEngine(make_cfg(tmp_path, groups=4, initial_tenants=2))


def test_engine_tenant_lifecycle_soak(tmp_path):
    # Seeded randomized create/write/remove churn with a restart check:
    # every surviving tenant's store must match the model, removed slots
    # must be inactive.
    from etcd_tpu import errors as _err

    cfg = make_cfg(tmp_path, groups=8, initial_tenants=2)
    eng = MultiEngine(cfg)
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in (0, 1)),
              msg="boot leaders")
    rng = __import__("random").Random(0xC0FFEE)
    model = {0: {}, 1: {}}

    for i in range(60):
        ops = ["write", "write", "write"]
        if len(model) < cfg.groups:
            ops.append("create")
        if len(model) > 1:
            ops.append("remove")
        op = rng.choice(ops)
        if op == "create":
            t, out = admin_async(eng, eng.create_tenant)
            g = settle(eng, t, out)
            assert g not in model
            model[g] = {}
            run_until(eng, lambda: eng.leader_slot(g) >= 0,
                      msg=f"leader for created {g}")
        elif op == "remove":
            g = rng.choice(sorted(model))
            t, out = admin_async(eng, eng.remove_tenant, g)
            settle(eng, t, out)
            del model[g]
        else:
            g = rng.choice(sorted(model))
            k, v = f"/k{rng.randrange(6)}", f"v{i}"
            t, out = put_async(eng, g, k, v)
            settle(eng, t, out)
            model[g][k] = v

    eng.stop()
    eng2 = MultiEngine(cfg)
    assert eng2.tenants() == sorted(model)
    for g, kv in model.items():
        for k, v in kv.items():
            assert eng2.store(g).get(k, False, False).node.value == v, \
                (g, k)
    for g in set(range(8)) - set(model):
        assert not eng2.tenant_active(g)
    eng2.wal.close()


def test_engine_tenant_remove_recreate_same_record(tmp_path):
    # Regression (review-found, reproduced): remove+re-create of the same
    # pool slot batched into ONE round's record must reset host state
    # BETWEEN the flips on replay — otherwise the re-created tenant's
    # fresh indices fall below the checkpoint's stale apply cursor: acked
    # writes vanish and removed data resurfaces after restart.
    from etcd_tpu import errors as _err

    cfg = make_cfg(tmp_path, groups=4, initial_tenants=2)
    eng = MultiEngine(cfg)
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in (0, 1)),
              msg="leaders")
    for i in range(3):
        t, out = put_async(eng, 1, f"/old{i}", "o")
        settle(eng, t, out)
    eng._checkpoint()   # capture tenant 1 with applied > 0

    t1, o1 = admin_async(eng, eng.remove_tenant, 1)
    time.sleep(0.05)    # both ops queued before the next round boundary
    t2, o2 = admin_async(eng, eng.create_tenant, 1)
    settle(eng, t1, o1)
    settle(eng, t2, o2)
    run_until(eng, lambda: eng.leader_slot(1) >= 0, msg="recreated leader")
    t, out = put_async(eng, 1, "/fresh", "f")
    settle(eng, t, out)

    eng.stop()
    eng2 = MultiEngine(cfg)
    assert eng2.store(1).get("/fresh", False, False).node.value == "f"
    with pytest.raises(_err.EtcdError):
        eng2.store(1).get("/old0", False, False)
    eng2.wal.close()


def test_engine_batched_fast_path_mixed_entry(tmp_path):
    """The C batched apply (store.set_applied_many) must be semantically
    invisible: one coalesced P_MULTI entry mixing waiterless plain PUTs,
    a waiter-held PUT, a CAS, and a TTL write applies in exact log order
    with correct results, store state, stats, watch events, and replay."""
    from etcd_tpu.store import HAVE_NATIVE_STORE
    if not HAVE_NATIVE_STORE:
        pytest.skip("native store core not built")
    eng = MultiEngine(make_cfg(tmp_path / "fp", groups=4))
    run_until(eng, lambda: eng.leader_slot(0) >= 0, msg="leader")
    # Seed a key the CAS will hit, and a watcher that must see every write.
    t, out = put_async(eng, 0, "/seed", "s0")
    settle(eng, t, out)
    w = eng.store(0).watch("/", recursive=True, stream=True,
                           since_index=eng.store(0).current_index + 1)

    # ONE round's staging coalesces everything queued for group 0 into a
    # single P_MULTI entry: 3 waiterless PUTs + a CAS + a conditioned PUT
    # + 2 more waiterless PUTs. Queue directly (no waiters registered for
    # the plain ones — ids never enter Wait).
    plain = []
    with eng._lock:
        for i in range(3):
            r = Request(method="PUT", path=f"/fast{i}", val=f"f{i}",
                        id=eng.reqid.next())
            plain.append(r)
            eng._pending[0].append((r.id, bytes([0]) + r.encode(), r))
        eng._dirty.add(0)
    t1, out1 = put_async(eng, 0, "/seed", "s1")   # waiter-held plain PUT
    time.sleep(0.05)
    cas = Request(method="PUT", path="/seed", prev_value="s1", val="s2",
                  id=eng.reqid.next())
    t2, out2 = (None, None)
    with eng._lock:
        q = eng.wait.register(cas.id)
        eng._pending[0].append((cas.id, bytes([0]) + cas.encode(), cas))
        for i in range(3, 5):
            r = Request(method="PUT", path=f"/fast{i}", val=f"f{i}",
                        id=eng.reqid.next())
            plain.append(r)
            eng._pending[0].append((r.id, bytes([0]) + r.encode(), r))
        eng._dirty.add(0)
    settle(eng, t1, out1)
    assert out1["res"].node.value == "s1"
    for _ in range(200):
        if not q.empty():
            break
        eng.run_round()
    cas_ev = q.get(timeout=5)
    assert not isinstance(cas_ev, Exception), cas_ev
    assert cas_ev.node.value == "s2"
    eng._drain_applies()

    # State: every fast-path PUT landed, in order, with distinct indices.
    idxs = []
    for i in range(5):
        ev = eng.store(0).get(f"/fast{i}", False, False)
        assert ev.node.value == f"f{i}"
        idxs.append(ev.node.modified_index)
    assert eng.store(0).get("/seed", False, False).node.value == "s2"

    # The stream watcher saw every event (fast-path ones included).
    seen = []
    for _ in range(20):
        e = w.next_event(timeout=2)
        if e is None:
            break
        seen.append((e.action, e.node.key))
        if len([1 for a, k in seen if k.startswith("/fast")]) == 5 \
                and ("compareAndSwap", "/seed") in seen:
            break
    fast_seen = [k for a, k in seen if k.startswith("/fast")]
    assert fast_seen == [f"/fast{i}" for i in range(5)], seen
    assert ("compareAndSwap", "/seed") in seen, seen

    # Replay parity: a fresh engine on the same WAL reconstructs the
    # exact same store (the fast path also runs under trigger=False).
    eng.stop()
    eng2 = MultiEngine(make_cfg(tmp_path / "fp", groups=4))
    for i in range(5):
        assert eng2.store(0).get(f"/fast{i}", False, False).node.value \
            == f"f{i}"
        assert eng2.store(0).get(f"/fast{i}", False,
                                 False).node.modified_index == idxs[i]
    assert eng2.store(0).get("/seed", False, False).node.value == "s2"
    eng2.wal.close()


# -- the event-loop front's submit (submit_pairs / settle / expire) ----------


def test_sink_submitted_writes_are_released_only_behind_wait_durable(
        tmp_path):
    """submit_pairs stages writes of several tenants under one lock and
    parks no thread; their results reach the sink from the applier's
    release behind wal.wait_durable, never earlier: with the durability
    gate held the writes are applied and nothing is delivered."""
    from etcd_tpu.utils import metrics
    from etcd_tpu.utils.wait import Sink

    eng = MultiEngine(make_cfg(tmp_path / "sink"))
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(4)),
              msg="leaders")
    woke = []
    sink = Sink(lambda: woke.append(1))
    gate, entered = threading.Event(), threading.Event()
    real = eng.wal.wait_durable

    def held(ticket):
        entered.set()
        gate.wait(30)
        return real(ticket)

    eng.wal.wait_durable = held
    pending0 = metrics.propose_pending.value
    toks = eng.submit_pairs(
        [(0, Request(method="PUT", path="/a", val="1")),
         (1, Request(method="PUT", path="/b", val="2")),
         (0, Request(method="PUT", path="/c", val="3"))], sink)
    assert [t.g for t in toks] == [0, 1, 0]
    assert metrics.propose_pending.value == pending0 + 3
    stop = threading.Event()

    def drive():            # blocks on the applier's queue cap meanwhile
        while not stop.is_set():
            eng.run_round()

    driver = threading.Thread(target=drive, daemon=True)
    driver.start()
    try:
        assert entered.wait(30), "no ack batch reached the gate"

        def applied():
            try:
                return [eng.store(g).get(k, False, False).node.value
                        for g, k in ((0, "/a"), (1, "/b"), (0, "/c"))]
            except errors.EtcdError:
                return None

        deadline = time.time() + 30
        while applied() is None and time.time() < deadline:
            time.sleep(0.01)
        # applied (the stores run ahead of the WAL pipeline) ...
        assert applied() == ["1", "2", "3"]
        time.sleep(0.3)
        # ... and not released: no signal, nothing in the sink
        assert not woke and sink.drain() == []
        gate.set()
        deadline = time.time() + 30
        while len(sink._items) < 3 and time.time() < deadline:
            time.sleep(0.01)
    finally:
        gate.set()
        stop.set()
        driver.join(30)
    # however many ack batches the three came in, the owner that has not
    # drained yet was signalled once
    assert woke == [1]
    got = dict(sink.drain())
    assert set(got) == {t.rid for t in toks}
    vals = [e.node.value
            for e in eng.settle(toks, [got[t.rid] for t in toks])]
    assert vals == ["1", "2", "3"]
    assert metrics.propose_pending.value == pending0
    eng.stop()


def test_front_sweep_times_a_request_out_like_do(tmp_path):
    """With nobody running rounds a request can only time out: the
    front's sweep answers what a timed-out do() / _quorum_read raises,
    cancels the waiter and closes the request's accounts."""
    import http.client
    import json

    from etcd_tpu.etcdhttp.tenants import EngineHttp
    from etcd_tpu.utils import metrics

    eng = MultiEngine(make_cfg(tmp_path / "sweep", request_timeout=0.4))
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(4)),
              msg="leaders")
    http_front = EngineHttp(eng)
    http_front.start()
    try:
        pending0 = metrics.propose_pending.value
        failed0 = metrics.propose_failed.value
        parked0 = eng.obs.g_read_parked.value
        rfailed0 = eng.obs.c_reads_failed.value
        c = http.client.HTTPConnection("127.0.0.1", http_front.http.port,
                                       timeout=10)
        t0 = time.time()
        c.request("PUT", "/tenants/2/v2/keys/k", body="value=v", headers={
            "Content-Type": "application/x-www-form-urlencoded"})
        resp = c.getresponse()
        err = json.loads(resp.read())
        assert 0.4 <= time.time() - t0 < 5.0
        assert resp.status == 500
        assert err["errorCode"] == errors.ECODE_RAFT_INTERNAL
        assert err["cause"] == "request timed out"
        assert metrics.propose_pending.value == pending0
        assert metrics.propose_failed.value == failed0 + 1
        c.request("GET", "/tenants/2/v2/keys/k?quorum=true")
        resp = c.getresponse()
        err = json.loads(resp.read())
        assert resp.status == 500
        assert err["cause"] == "quorum read timed out"
        assert eng.obs.g_read_parked.value == parked0
        assert eng.obs.c_reads_failed.value == rfailed0 + 1
        assert not eng.wait._waiters    # both rids were cancelled
    finally:
        http_front.stop()
        eng.stop()


def test_submit_pairs_refuses_a_bad_pair_alone(tmp_path):
    """A pair submit_pairs cannot stage (a local read or a watch, a bad
    method, an id already waited for) has an EtcdError in its token's
    place and leaves no waiter; the other tenants' pairs of the same call
    are staged and acknowledged as if it had not been there."""
    from etcd_tpu.utils import metrics
    from etcd_tpu.utils.wait import Sink

    eng = MultiEngine(make_cfg(tmp_path / "refuse"))
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(4)),
              msg="leaders")
    sink = Sink(lambda: None)
    pending0 = metrics.propose_pending.value
    taken = eng.reqid.next()
    eng.wait.register(taken)
    toks = eng.submit_pairs(
        [(0, Request(method="PUT", path="/a", val="1")),
         (1, Request(method="GET", path="/a")),                 # local read
         (1, Request(method="GET", path="/a", quorum=True, wait=True)),
         (2, Request(method="PATCH", path="/a")),
         (2, Request(method="PUT", path="/a", val="2", id=taken)),
         (3, Request(method="PUT", path="/a", val="3"))], sink)
    bad = toks[1:5]
    assert all(isinstance(t, errors.EtcdError) for t in bad)
    assert [t.code for t in bad] == [
        errors.ECODE_INVALID_FORM, errors.ECODE_INVALID_FORM,
        errors.ECODE_INVALID_FORM, errors.ECODE_RAFT_INTERNAL]
    assert "duplicate" in bad[3].cause
    good = [toks[0], toks[5]]
    assert [t.g for t in good] == [0, 3]
    # two waiters of this call and the one the test took: nothing is left
    # behind for a refused pair, and only the staged ones are pending
    assert set(eng.wait._waiters) == {taken, good[0].rid, good[1].rid}
    assert metrics.propose_pending.value == pending0 + 2
    got = {}
    run_until(eng, lambda: got.update(sink.drain()) or len(got) == 2,
              msg="acks of the staged pairs")
    assert [e.node.value for e in eng.settle(
        good, [got[t.rid] for t in good])] == ["1", "3"]
    assert metrics.propose_pending.value == pending0
    with pytest.raises(errors.EtcdError):
        eng.store(2).get("/a", False, False)    # tenant 2 got neither
    eng.stop()


@pytest.mark.parametrize("compact", [None, False])
def test_idle_engine_thread_waits_for_work_and_wakes_on_a_submit(
        tmp_path, compact):
    """The engine thread does not spin rounds that have nothing to do:
    once every group has a leader and the mirrors are settled it waits
    for whoever queues work (or for the idle tick, IDLE_TICK_S), a write
    wakes it at once, and the rounds that carry the write to its commit
    follow each other without a wait; whichever readback builds the
    rounds' records."""
    from etcd_tpu.server import engine as engine_mod
    eng = MultiEngine(make_cfg(tmp_path / "idle", compact_readback=compact))
    eng.start()
    try:
        assert eng.wait_leaders(120)
        deadline = time.time() + 30
        while not eng._idle() and time.time() < deadline:
            time.sleep(0.01)
        assert eng._idle()
        r0, t0 = eng.round_no, time.monotonic()
        time.sleep(1.0)
        idle_rate = (eng.round_no - r0) / (time.monotonic() - t0)
        # one round an idle tick, not one a millisecond
        assert 2 <= idle_rate <= 2.0 / engine_mod.IDLE_TICK_S, idle_rate
        took = []
        for i in range(5):
            time.sleep(0.02)        # inside an idle wait
            t = time.monotonic()
            ev = eng.do(0, Request(method="PUT", path="/k", val=f"v{i}"))
            took.append(time.monotonic() - t)
            assert ev.node.value == f"v{i}"
        # woken by the submit, not by the 30 ms that were left of the tick
        assert min(took) < 0.4 * engine_mod.IDLE_TICK_S, took
    finally:
        eng.stop()


def test_a_write_waiting_on_a_lost_quorum_is_not_idled_on(tmp_path):
    """An admitted entry that is not committed keeps the rounds coming at
    round speed: the engine is idle only once every leader's log is
    committed to its end, so a write held up by dropped traffic meets its
    retransmits, step-downs and elections without a 50 ms wait a tick."""
    import jax.numpy as jnp
    from etcd_tpu.server import engine as engine_mod

    eng = MultiEngine(make_cfg(tmp_path / "lostq"))
    run_until(eng, lambda: eng._idle(), msg="a settled, idle engine")
    G, P = eng.cfg.groups, eng.cfg.peers
    cut = np.ones((G, P, P, 1), np.int32)
    cut[0] = 0                      # group 0: nobody hears anybody
    eng.drop_mask = jnp.asarray(cut)
    t, out = put_async(eng, 0, "/held", "1")
    s = eng.leader_slot(0)
    run_until(eng, lambda: eng.h_last[0, s] > eng.h_commit[0, s],
              msg="the write admitted at its leader")
    # the next round changes nothing and stages nothing, and is no reason
    # to wait: the entry is still on its way
    quiet = 0
    for _ in range(3):
        eng.run_round()
        quiet += eng._quiet
        assert not eng._idle()
    assert quiet, "no round under the partition journalled nothing"
    assert t.is_alive()

    # the same under the engine's own thread: rounds at round speed for
    # as long as the quorum is lost (the held entry may be lost to the
    # election that follows, as in the reference: its client times out);
    # healed, the group serves again and the engine comes to rest
    eng.start()
    try:
        r0, t0 = eng.round_no, time.monotonic()
        time.sleep(0.5)
        rate = (eng.round_no - r0) / (time.monotonic() - t0)
        assert rate > 2.0 / engine_mod.IDLE_TICK_S, rate
        eng.drop_mask = None
        deadline = time.time() + 30
        while not eng._idle() and time.time() < deadline:
            time.sleep(0.01)
        assert eng._idle()
        ev = eng.do(0, Request(method="PUT", path="/healed", val="2"))
        assert ev.node.value == "2"
    finally:
        eng.stop()


def test_a_checkpoint_survives_a_tenants_first_touch(tmp_path):
    """The front creates a tenant's store at the tenant's first request, on
    its own thread; a checkpoint walking the stores meanwhile must not die
    of it (it took the engine thread along: every later request timed out;
    seen on the chip in a mix that preloads nothing, PERF.md §6 PR 38)."""
    G = 300
    eng = MultiEngine(make_cfg(tmp_path / "ck", groups=G, peers=3,
                               checkpoint_rounds=4))
    eng.start()
    try:
        assert eng.wait_leaders(180), eng.failed
        for g in range(100):        # stores worth a checkpoint's while
            eng.do(g, Request(method="PUT", path="/k", val="v" * 256))
        for g in range(100, G):
            eng.store(g)
            time.sleep(0.002)
        time.sleep(0.3)
        assert eng.failed is None
        r0 = eng.round_no
        eng.do(0, Request(method="PUT", path="/k", val="again"))
        assert eng.round_no > r0
    finally:
        eng.stop()
