"""MultiEngine over a multi-device mesh: the multi-chip SERVING path.

The kernel alone proving sharded execution (test_kernel/dryrun) is not the
story — this drives the full engine round (proposals -> sharded step ->
readback -> WAL -> apply -> ack) with the state sharded over a real
("groups", "peers") device mesh, message routing crossing devices as an
all_to_all on the peers axis (conftest forces 8 virtual CPU devices).

Reference seam: raft.MultiNode's one-process-many-groups loop
(raft/multinode.go:166-322) scaled over chips instead of goroutines.
"""
import json
import random
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.server.engine import P_CONF, EngineConfig, MultiEngine
from etcd_tpu.server.request import Request
from etcd_tpu.parallel.mesh import (flag_sharding, group_sharding,
                                    make_mesh, replicated_sharding)

from tests.test_engine import put_async, run_until, settle
from tests.test_engine_compact import (_answer, _assert_same_records,
                                       _kinds, _wal_records)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")


def make_cfg(tmp, mesh, **kw):
    kw.setdefault("groups", 8)
    kw.setdefault("peers", 4)
    kw.setdefault("window", 16)
    kw.setdefault("max_ents", 4)
    kw.setdefault("heartbeat_tick", 3)
    kw.setdefault("request_timeout", 30.0)
    kw.setdefault("fsync", False)
    return EngineConfig(data_dir=str(tmp), mesh=mesh, **kw)


@pytest.fixture(scope="module", params=[1, 2], ids=["groups8", "g4xp2"])
def mesh(request):
    return make_mesh(jax.devices()[:8], peers_axis=request.param)


@pytest.fixture(params=[True, False], ids=["compact", "full"])
def compact(request):
    """The mesh engine's two readbacks: flag map + gathered rows (the
    default), and the full state every round."""
    return request.param


def test_sharded_engine_serves_and_keeps_shardings(tmp_path, mesh, compact):
    eng = MultiEngine(make_cfg(tmp_path / "s1", mesh,
                               compact_readback=compact))
    assert eng._compact is compact
    G = eng.cfg.groups
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(G)),
              msg="leaders")

    # The state really lives on the mesh (not single-device fallback).
    sh = eng.st.term.sharding
    assert set(getattr(sh, "mesh", None).axis_names) == {"groups", "peers"}
    assert not eng.st.term.sharding.is_fully_replicated
    assert len(eng.st.term.devices()) == 8

    for g in range(G):
        t, out = put_async(eng, g, "/k", f"v{g}")
        assert settle(eng, t, out).action == "set"
    for g in range(G):
        assert eng.do(g, Request(method="GET", path="/k")).node.value == \
            f"v{g}"

    # After serving rounds the inbox is still on its canonical sharding —
    # no silent per-round resharding (which would recompile or transfer).
    assert eng.inbox.sharding.is_equivalent_to(eng._mb_sh, eng.inbox.ndim)
    eng.stop()


def test_sharded_engine_restart_from_wal(tmp_path, mesh, compact):
    d = tmp_path / "s2"
    eng = MultiEngine(make_cfg(d, mesh, compact_readback=compact))
    G = eng.cfg.groups
    run_until(eng, lambda: all(eng.leader_slot(g) >= 0 for g in range(G)),
              msg="leaders")
    for g in range(G):
        t, out = put_async(eng, g, "/persist", f"g{g}")
        settle(eng, t, out)
    eng.stop()

    eng2 = MultiEngine(make_cfg(d, mesh, compact_readback=compact))
    for g in range(G):
        assert eng2.do(g, Request(method="GET", path="/persist")).node.value \
            == f"g{g}"
    run_until(eng2, lambda: all(eng2.leader_slot(g) >= 0 for g in range(G)),
              msg="re-election")
    t, out = put_async(eng2, 0, "/after", "restart")
    settle(eng2, t, out)
    eng2.stop()


def test_sharded_engine_conf_change_and_host_surgery_keep_sharding(
        tmp_path, mesh, compact):
    """Membership surgery (host writebacks) must put fields back on their
    canonical shardings — the regression this guards: a jnp.asarray
    writeback would strand a field on one device and force resharding."""
    eng = MultiEngine(make_cfg(tmp_path / "s3", mesh, initial_peers=3,
                               compact_readback=compact))
    run_until(eng, lambda: eng.leader_slot(0) >= 0, msg="leader")

    res = {}

    def conf():
        try:
            res["slots"] = eng.conf_change(0, "add", 3, timeout=30.0)
        except Exception as e:  # pragma: no cover
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
    assert 3 in res["slots"]

    sh = eng._st_sh
    for name in ("term", "log_term", "next", "peer_mask", "state"):
        arr = getattr(eng.st, name)
        want = getattr(sh, name)
        assert arr.sharding.is_equivalent_to(want, arr.ndim), name

    # Still serves after surgery.
    t, out = put_async(eng, 0, "/post-conf", "ok")
    settle(eng, t, out)
    assert eng.do(0, Request(method="GET", path="/post-conf")).node.value \
        == "ok"
    eng.stop()


# ---------------------------------------------------------------------------
# Compact readback across devices == full readback == one device
# ---------------------------------------------------------------------------
# The same seeded script of proposals, quorum reads parked in most rounds,
# two conf changes and one snapshot-install surgery through three engines. The single-device engine
# with full readback is the independent implementation of the semantics;
# the mesh engine must journal the same RoundRecord stream, keep the same
# mirrors and give the same answers, with full and with compact readback.

ROUNDS = 130
ADD_AT, CUT_AT, HEAL_AT, REMOVE_AT = 20, 30, 58, 100
CAP = 6            # rows: busy rounds go over it, quiet ones stay under


class _Seq:        # idutil embeds wall time; payload bytes must be equal
    def __init__(self):
        self.i = 0

    def next(self):
        self.i += 1
        return self.i


def _enqueue(eng, g, rid, payload, rq, conf=False):
    q = eng.wait.register(rid)
    with eng._lock:
        eng._pending[g].append((rid, payload, rq))
        eng._dirty.add(g)
        if conf:
            eng._confs_outstanding += 1
    return q


def _drive(data_dir, mesh, compact, cap=0):
    """Runs the script; returns (engine, kind of every round, rounds that
    ended in a snapshot-install surgery, {rid: answer}, what the compact
    step, the read step and the gather returned on which shardings)."""
    eng = MultiEngine(make_cfg(
        data_dir, mesh, initial_peers=3, stagger=True, sync_interval=0.0,
        compact_readback=compact, compact_cap=cap,
        checkpoint_rounds=1 << 30, pipeline_applies=False))
    eng.reqid = _Seq()
    G, P = eng.cfg.groups, eng.cfg.peers
    shardings = {"flags": set(), "attest": set(), "rows": set(),
                 "read": set(), "read_flags": set(), "read_attest": set(),
                 "handed": set(), "gather_buffers": set()}
    if mesh is not None and compact:
        step_c, step_r, gather = (eng._step_fn_c, eng._step_fn_r,
                                  eng._gather_rows)

        def spy_step(*a):
            # (st, inbox, the staged array, the tick, hold, down)
            shardings["handed"].update(x.sharding for x in a[2:4])
            out = step_c(*a)
            shardings["flags"].add(out[2].sharding)
            shardings["attest"].add(out[3].sharding)
            return out

        def spy_read(*a):
            shardings["handed"].update(x.sharding for x in a[2:4])
            out = step_r(*a)
            shardings["read"].update(x.sharding for x in out[2:4])
            shardings["read_flags"].add(out[4].sharding)
            shardings["read_attest"].add(out[5].sharding)
            return out

        def spy_gather(*a):
            # (the six fields, flags, attestation, hops' counts, the
            # staged array, the bucket)
            shardings["handed"].add(a[4].sharding)
            shardings["gather_buffers"].add(len(jax.tree.leaves(a[:5])))
            out = gather(*a)
            shardings["rows"].add(out.sharding)
            return out

        eng._step_fn_c, eng._step_fn_r, eng._gather_rows = (
            spy_step, spy_read, spy_gather)
    surgeries = []
    service = eng._service_need_host

    def spy_service(nh):
        service(nh)
        if eng._force_full:
            surgeries.append(eng.round_no)

    eng._service_need_host = spy_service

    rng = random.Random(11)
    read_rng = random.Random(17)
    waits, kinds, victim = {}, [], None
    for r in range(ROUNDS):
        # A read of any group in most rounds (parked as the front parks
        # it), one in every round from the heal past the install.
        for _ in range(max(read_rng.choice((0, 1, 1, 2)),
                           HEAL_AT <= r < HEAL_AT + 20)
                       if r < ROUNDS - 10 else 0):
            rid = eng.reqid.next()
            waits[rid] = eng.wait.register(rid)
            with eng._lock:
                eng._park_read(read_rng.randrange(G), Request(
                    method="GET", path=f"/k{read_rng.randrange(4)}",
                    quorum=True, id=rid))
        for _ in range(rng.randrange(0, 7)):
            g = rng.randrange(G)
            rid = eng.reqid.next()
            rq = Request(method="PUT", path=f"/k{rng.randrange(4)}",
                         val=f"v{r}", id=rid)
            waits[rid] = _enqueue(eng, g, rid, bytes([0]) + rq.encode(), rq)
        if CUT_AT <= r < HEAL_AT:
            # Group 0 outgrows the ring window while one follower is cut.
            rid = eng.reqid.next()
            rq = Request(method="PUT", path="/grow", val=f"r{r}", id=rid)
            waits[rid] = _enqueue(eng, 0, rid, bytes([0]) + rq.encode(), rq)
        for at, g, op, slot in ((ADD_AT, 1, "add", 3),
                                (REMOVE_AT, 2, "remove", 1)):
            if r == at:
                rid = eng.reqid.next()
                payload = bytes([P_CONF]) + json.dumps(
                    {"id": rid, "op": op, "slot": slot}).encode()
                waits[rid] = _enqueue(eng, g, rid, payload, None, conf=True)
        if r == CUT_AT:
            victim = (eng.leader_slot(0) + 1) % 3
            m_to = np.ones((G, P, 1, 1), np.int32)
            m_from = np.ones((G, 1, P, 1), np.int32)
            m_to[0, victim] = 0
            m_from[0, 0, victim] = 0
            eng.drop_mask = jnp.asarray(m_to * m_from)
        elif r == HEAL_AT:
            eng.drop_mask = None
        before = _kinds()
        eng.run_round()
        after = _kinds()
        (kind,) = [k for k in after if after[k] != before[k]]
        kinds.append(kind)
    answers = {rid: _answer(q) for rid, q in waits.items()}
    return eng, kinds, surgeries, answers, shardings


def _mirrors(eng):
    return {n: getattr(eng, n).copy() for n in (
        "h_term", "h_vote", "h_commit", "h_state", "h_last", "h_ring",
        "h_mask", "applied")}


def _stores(eng, replayed=False):
    """Every tenant's saved store; `replayed` leaves out the counts of
    gets, which a replay of the WAL does not make again."""
    out = {g: st.save() for g, st in eng._stores.items()}
    if replayed:
        for g, blob in out.items():
            doc = json.loads(blob)
            doc["stats"].update(getsSuccess=0, getsFail=0)
            out[g] = doc
    return out


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """(i) one device, full readback."""
    d = tmp_path_factory.mktemp("ref")
    eng, kinds, surgeries, answers, _ = _drive(d, None, compact=False)
    out = {"records": _wal_records(str(d)), "mirrors": _mirrors(eng),
           "stores": _stores(eng), "replayed": _stores(eng, True),
           "answers": answers,
           "acked": eng.acked_requests, "surgeries": surgeries,
           "mask": eng.h_mask.copy()}
    eng.stop()
    assert set(kinds) == {"full"}
    assert surgeries, "the script must reach a snapshot install"
    assert out["mask"][1, 3] and not out["mask"][2, 1]
    assert len(answers) > 200 and all(
        isinstance(a, (tuple, list)) for a in answers.values()), answers
    return out


def _assert_same_run(ref, eng, answers, data_dir):
    for name, want in ref["mirrors"].items():
        assert np.array_equal(want, getattr(eng, name)), name
    assert eng.acked_requests == ref["acked"]
    assert answers == ref["answers"]
    assert _stores(eng) == ref["stores"]
    eng.stop()
    _assert_same_records(ref["records"], _wal_records(str(data_dir)))


def test_mesh_full_readback_equals_one_device(tmp_path, mesh, reference):
    """(ii): sharding alone changes nothing."""
    eng, kinds, surgeries, answers, _ = _drive(tmp_path / "mf", mesh,
                                               compact=False)
    assert set(kinds) == {"full"}
    assert surgeries == reference["surgeries"]
    _assert_same_run(reference, eng, answers, tmp_path / "mf")


@pytest.mark.parametrize("cap", [0, CAP], ids=["autocap", f"cap{CAP}"])
def test_mesh_compact_readback_equals_one_device(tmp_path, mesh, reference,
                                                 cap):
    """(iii): flag map + gathered rows across devices journal the records
    the full path would have, round by round; need-host, post-surgery and
    over-cap rounds take the full path and say so; every field stays on
    its sharding; a restart from this WAL serves the same keys."""
    d = tmp_path / "mc"
    eng, kinds, surgeries, answers, seen = _drive(d, mesh, compact=True,
                                                  cap=cap)
    assert surgeries == reference["surgeries"]
    for r in surgeries:
        # the need-host round itself, and the round after the surgery
        # (_force_full: the install is journaled by a full diff)
        assert kinds[r] == "full" and kinds[r + 1] == "full", (r, kinds)
    want = {"compact", "full", "over_cap"} if cap else {"compact", "full"}
    assert set(kinds) == want, kinds
    assert kinds.count("compact") > (ROUNDS // 8 if cap else ROUNDS // 2)
    assert kinds.count("over_cap") > (ROUNDS // 8 if cap else -1)

    assert seen["flags"] and all(
        sh.is_equivalent_to(flag_sharding(mesh), 2) for sh in seen["flags"])
    rep = replicated_sharding(mesh)
    assert all(sh.is_equivalent_to(rep, 0) for sh in seen["attest"])
    assert seen["rows"] and all(sh.is_fully_replicated
                                for sh in seen["rows"])
    # what a round hands both programs (the staged array, uploaded or the
    # boot-time zeros, and the tick) lies replicated before the call, and
    # the gather is handed ten buffers
    assert seen["handed"] and all(sh.is_equivalent_to(rep, 2)
                                  for sh in seen["handed"])
    assert seen["gather_buffers"] == {10}
    # the read step's four: (G,) confirmed and read index on groups, its
    # flag map and attestation where the compact step's are
    assert seen["read"] and all(
        sh.is_equivalent_to(group_sharding(mesh), 1) for sh in seen["read"])
    assert seen["read_flags"] and all(
        sh.is_equivalent_to(flag_sharding(mesh), 2)
        for sh in seen["read_flags"])
    assert all(sh.is_equivalent_to(rep, 0) for sh in seen["read_attest"])
    for name, want_sh in zip(eng.st._fields, eng._st_sh):
        arr = getattr(eng.st, name)
        assert arr.sharding.is_equivalent_to(want_sh, arr.ndim), name
    assert eng.inbox.sharding.is_equivalent_to(eng._mb_sh, eng.inbox.ndim)

    _assert_same_run(reference, eng, answers, d)

    eng2 = MultiEngine(make_cfg(
        d, mesh, initial_peers=3, sync_interval=0.0, compact_readback=True,
        compact_cap=cap, checkpoint_rounds=1 << 30, pipeline_applies=False))
    assert _stores(eng2, True) == reference["replayed"]
    assert np.array_equal(eng2.h_mask, reference["mask"])
    eng2.stop()
