"""Leader-election churn on the served path (EngineConfig.churn_down_rounds,
`--engine-churn-down-rounds`; BASELINE.json configs[4]: "leader-election
churn + snapshot install mixed in").

(a) the schedule (server/lag.py ChurnSchedule): the share of groups in a cut
    at every round, one slot a group and always the working leader at the
    cut's start, a quorum never at risk, a pure function of (seed, g, round),
    and the same slots after a restart from the WAL;
(b) the kernel's `down` map: the trajectory of the equivalent drop_mask, the
    scalar Raft (etcd_tpu/raft/core.py) stepping the same messages round for
    round at P = 7, and tests/raft_fixtures.Network with the same slot
    isolated for the same span; without it the step programs are the
    parent's, with it they take one more (G, P) i1;
(c) in the engine, with a leader in a LOWER slot than its successor cut off:
    staging picks the leader of the highest term, writes sent before, during
    and after the election are all acknowledged, each applied exactly once
    (in-order POSTs), acknowledged values are quorum-read back, from crash
    images taken mid-cut and in the round of the returning leader's install
    too; a quorum read during the cut is never stale and not answered by the
    cut-off leader; an idle member sleeps through a cut;
(d) `python -m etcd_tpu` with the three flags elects, exports the five
    series and survives a real SIGKILL; the flags are refused where they
    could risk a quorum.
"""
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from etcd_tpu.ops import kernel
from etcd_tpu.ops.state import KernelConfig, LEADER, NH_SNAP, init_state
from etcd_tpu.server.lag import ChurnSchedule

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
STEPS = ("step_routed_auto", "step_routed_compact", "step_routed_read_auto")


# ---------------------------------------------------------------------------
# (a) the schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("G,down,period", [
    (12_500, 128, 512), (12_500, 128, 256), (8, 40, 120), (200, 7, 50),
    (3, 1, 2)])
def test_schedule_cuts_the_share_at_every_round(G, down, period):
    sch = ChurnSchedule(G, 7, down, period, seed=38)
    rounds = list(range(0, 2 * period + 3)) + [10**6 + 7]
    want = G * down / period
    for r in rounds:
        t = (r + sch.phase) % period
        assert np.array_equal(np.sort(sch.cutting(r)),
                              np.nonzero(t < down)[0]), r
        assert np.array_equal(np.sort(sch.starting(r)),
                              np.nonzero(t == 0)[0]), r
        assert np.array_equal(np.sort(sch.ending(r)),
                              np.nonzero(t == down)[0]), r
        assert abs(len(sch.cutting(r)) - want) <= 1, r
        # cuts begin a few groups a round, never all at once
        assert len(sch.starting(r)) <= G // period + 1
    again = ChurnSchedule(G, 7, down, period, seed=38)
    assert np.array_equal(again.phase, sch.phase)
    if G > 8:
        other = ChurnSchedule(G, 7, down, period, seed=39)
        assert not np.array_equal(other.phase, sch.phase)


def test_schedule_refuses_what_could_risk_a_quorum():
    for peers, down, period in ((2, 4, 8), (7, 0, 8), (7, 8, 8), (7, 9, 8)):
        with pytest.raises(ValueError):
            ChurnSchedule(4, peers, down, period, seed=0)


def test_recover_names_the_cut_off_slot_from_terms_and_votes():
    """After a restart roles are gone: the slot cut off is the one that kept
    the lowest term with its self-vote, or, before its successor's
    election, the one a quorum voted for in the common term."""
    G, P = 6, 7
    sch = ChurnSchedule(G, P, 5, 6, seed=1)         # nearly always cutting
    r = next(r for r in range(12) if len(sch.cutting(r)) == G - 1)
    out = next(g for g in range(G) if g not in sch.cutting(r))
    mask = np.ones((G, P), bool)
    term = np.full((G, P), 5, np.int32)
    vote = np.zeros((G, P), np.int32)
    gs = [g for g in range(G) if g != out]
    # group A: successor elected at term 6, slot 2 stayed behind at 5
    a, b, c, d, e = gs
    term[a] = 6
    term[a, 2] = 5
    vote[a] = [4, 4, 3, 4, 4, 0, 4]
    # group B: no successor yet, slot 4 has the term's quorum of votes
    vote[b] = [5, 5, 0, 5, 5, 0, 1]
    # group C: nobody has a quorum of votes: nobody is named
    vote[c] = [1, 1, 3, 3, 5, 5, 0]
    # group D: two active slots only
    mask[d, 2:] = False
    vote[d, :2] = [1, 1]
    # group E: two slots behind, both self-voted: ambiguous, nobody
    term[e] = 7
    term[e, 1] = term[e, 3] = 5
    vote[e] = [1, 2, 1, 4, 1, 1, 1]
    # the group outside any cut: never named, whatever it looks like
    term[out] = 6
    term[out, 0] = 5
    vote[out, 0] = 1
    down = sch.recover(r, mask, term, vote)
    assert down[a].tolist() == [0, 0, 1, 0, 0, 0, 0]
    assert down[b].tolist() == [0, 0, 0, 0, 1, 0, 0]
    assert not down[[c, d, e, out]].any()


# ---------------------------------------------------------------------------
# (b) the kernel's down map
# ---------------------------------------------------------------------------

def _small(G=8, P=5):
    cfg = KernelConfig(groups=G, peers=P, window=16, max_ents=4,
                       election_tick=10, heartbeat_tick=3)
    inbox = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    return cfg, init_state(cfg, stagger=True), inbox


def _n_args(text):
    head = text[text.index("func.func public @main("):]
    return head[:head.index("->")].count("%arg")


@pytest.mark.parametrize("name", STEPS)
def test_the_down_map_is_one_more_argument_of_the_step_program(name):
    """(that without it the programs are the recorded ones, text and
    arguments, is tests/test_lagging_followers.py's, against
    step_programs_parent.json: its program hashes recorded again in PR 40,
    whose steps fold the quiescence predicate group by group, make a busy
    hop's passes by rank and return the hops' counts, its `args` and
    `trajectory_sha256` left as they were)"""
    cfg, st, inbox = _small()
    z = jnp.zeros(cfg.groups, jnp.int32)
    args = (cfg, st, inbox, z, z, jnp.asarray(True), None, 3)
    plain = getattr(kernel, name).lower(*args).as_text()
    down = getattr(kernel, name).lower(
        *args, None, jnp.zeros((8, 5), bool)).as_text()
    assert _n_args(down) == _n_args(plain) + 1
    head = down[:down.index("->")]
    assert (head.count("tensor<8x5xi1>")
            == plain[:plain.index("->")].count("tensor<8x5xi1>") + 1)
    with open(os.path.join(HERE, "step_programs_parent.json")) as f:
        parent = json.load(f)
    if parent["jax"] == jax.__version__:
        assert _n_args(plain) == parent["programs"][name]["args"]


def _elect(cfg, st, inbox, rounds=60, hops=1):
    z = jnp.zeros(cfg.groups, jnp.int32)
    for _ in range(rounds):
        st, inbox, _ = kernel.step_routed_auto(cfg, st, inbox, z, z,
                                               jnp.asarray(True), None, hops)
    state = np.asarray(st.state)
    assert (state == LEADER).sum(axis=1).tolist() == [1] * cfg.groups
    return st, inbox, (state == LEADER).argmax(axis=1)


def _leaders_by_term(st):
    lt = np.where(np.asarray(st.state) == LEADER, np.asarray(st.term), 0)
    return lt.argmax(axis=1), lt.max(axis=1)


@pytest.mark.parametrize("hops", [1, 3])
def test_the_down_map_is_the_equivalent_drop_mask(hops):
    """The same seeded script (proposals at the leader of the highest term,
    the leaders of half the groups cut off for 60 rounds, then back) under
    `down` and under the (G, P, P, 1) drop_mask built from it: every state
    field and the routed inbox equal after every round, but need_host,
    where the down map raises no snapshot to or by a slot that is cut off."""
    cfg, st, inbox = _small(G=6, P=7)
    G, P = cfg.groups, cfg.peers
    st, inbox, lead = _elect(cfg, st, inbox, hops=hops)
    down = np.zeros((G, P), bool)
    down[np.arange(0, G, 2), lead[::2]] = True
    a = b = st
    ia = ib = inbox
    rng = np.random.default_rng(38)
    nh_b = 0
    for r in range(150):
        d = down if 10 <= r < 70 else np.zeros_like(down)
        slots, terms = _leaders_by_term(a)
        pc = jnp.asarray((rng.integers(0, cfg.max_ents + 1, size=G)
                          * (terms > 0)).astype(np.int32))
        ps = jnp.asarray(slots.astype(np.int32))
        dd = jnp.asarray(d)
        a, ia, _ = kernel.step_routed_auto(
            cfg, a, ia, pc, ps, jnp.asarray(True), None, hops, None, dd)
        b, ib, _ = kernel.step_routed_auto(
            cfg, b, ib, pc, ps, jnp.asarray(True),
            kernel.down_drop_mask(dd), hops)
        for k, v in a._asdict().items():
            if k != "need_host":
                assert np.array_equal(np.asarray(v),
                                      np.asarray(getattr(b, k))), (r, k)
        assert np.array_equal(np.asarray(ia), np.asarray(ib)), r
        # nothing reaches or leaves a slot that is down
        got = np.asarray(ia)
        assert not got[d].any() and not got.swapaxes(1, 2)[d].any()
        nh_a = np.asarray(a.need_host)
        nh_b += int(((np.asarray(b.need_host) & NH_SNAP) != 0).sum())
        # the host clears what it serviced: here, what the down map also
        # raises (a returned leader beyond its successor's ring)
        b = b._replace(need_host=a.need_host)
        if d.any():
            assert not nh_a[d].any()
    state = np.asarray(a.state)
    term = np.asarray(a.term)
    assert (state == LEADER).sum(axis=1).tolist() == [1] * G
    assert (term[::2].max(axis=1) > term[1::2].max(axis=1)).all()
    # (no host here: a returned leader beyond its successor's ring waits
    # for an install, need_host raised, under either map)
    assert np.asarray(a.commit).max(axis=1).min() > 40
    assert nh_b > 0


def test_the_kernel_under_the_down_map_is_the_scalar_raft_at_seven_peers():
    """etcd_tpu/raft/core.py (it imports nothing of the kernel) steps the
    messages the kernel routed, which the test's own isolation of the same
    slot for the same span leaves as they are, and agrees on term, vote, state, lead, commit,
    last_index and the ring's terms after every round: the election the cut
    forces, the deposed leader's uncommitted tail, its step-down and the
    truncation of its log when it comes back."""
    from test_equivalence import Mirror
    # (a ring that holds what a successor takes during a cut: no host here
    # to install a returned leader that fell out of it)
    cfg = KernelConfig(groups=3, peers=7, window=128, max_ents=3)
    G, P = cfg.groups, cfg.peers
    st = init_state(cfg)
    mirror = Mirror(cfg)
    inbox = np.zeros((G, P, P, cfg.fields), np.int32)
    rng = np.random.RandomState(38)
    down = np.zeros((G, P), bool)
    cuts = []
    lost_tail = 0
    for r in range(260):
        if r in (60, 150):
            slots, terms = _leaders_by_term(st)
            assert (terms > 0).all()
            down[:] = False
            down[np.arange(G), slots] = True
            cuts.append(slots.copy())
        if r in (110, 200):
            # what the deposed leaders admitted alone is still there
            last = np.asarray(st.last_index)[np.arange(G), cuts[-1]]
            commit = np.asarray(st.commit)[np.arange(G), cuts[-1]]
            lost_tail += int((last - commit).sum())
            down[:] = False
        slots, terms = _leaders_by_term(st)
        # writes go where a host that sees no fault map sends them: to the
        # leader of the highest term; to the cut-off one until it has a
        # successor
        pc = np.where((terms > 0) & (rng.rand(G) < 0.5),
                      rng.randint(1, cfg.max_ents + 1, G), 0).astype(np.int32)
        ps = slots.astype(np.int32)
        st, nxt, _ = kernel.step_routed_auto(
            cfg, st, jnp.asarray(inbox), jnp.asarray(pc), jnp.asarray(ps),
            jnp.asarray(True), None, 1, None, jnp.asarray(down))
        mirror.run_round(inbox, pc, ps)
        assert not np.asarray(st.need_host).any(), r
        mirror.assert_equal(st, r)
        # what this round sent, after the test's own isolation of the slot
        # (nothing TO it, nothing FROM it): the kernel cut the same
        inbox = np.array(nxt)
        inbox[down] = 0
        inbox.swapaxes(1, 2)[down] = 0
        assert np.array_equal(inbox, np.asarray(nxt)), r
    state = np.asarray(st.state)
    assert (state == LEADER).sum(axis=1).tolist() == [1] * G
    assert lost_tail > 0, "no deposed leader had anything to truncate"
    for slots in cuts:
        assert (state[np.arange(G), slots] != LEADER).all()
    assert np.asarray(st.commit).min() > 60


def test_a_cut_and_a_return_end_where_the_scalar_network_ends():
    """tests/raft_fixtures.Network with the same slot isolated for the same
    span: the leader takes 3 proposals, is isolated and takes 4 more alone,
    its successor is elected and takes 5, the old leader comes back. The
    kernel under the down map, given the same script, leaves the old
    leader and its successor with the same term, state, commit, last_index
    and entry terms."""
    from raft_fixtures import Network, msg
    from etcd_tpu.raftpb import Entry, MessageType
    cfg = KernelConfig(groups=1, peers=7, window=32, max_ents=4,
                       election_tick=10, heartbeat_tick=3)
    P = cfg.peers
    st = init_state(cfg, stagger=True)
    inbox = jnp.zeros((1, P, P, cfg.fields), jnp.int32)
    st, inbox, lead = _elect(cfg, st, inbox)
    old = int(lead[0])
    t_old = int(np.asarray(st.term)[0, old])

    def step(n, slot, down):
        nonlocal st, inbox
        st, inbox, _ = kernel.step_routed_auto(
            cfg, st, inbox, jnp.asarray([n], jnp.int32),
            jnp.asarray([slot], jnp.int32), jnp.asarray(True), None, 1, None,
            jnp.asarray(down))

    up = np.zeros((1, P), bool)
    cut = up.copy()
    cut[0, old] = True
    step(3, old, up)
    for _ in range(8):
        step(0, old, up)
    assert int(np.asarray(st.commit)[0, old]) == 1 + 3
    step(4, old, cut)                    # admitted alone, never committed
    for _ in range(60):
        step(0, old, cut)
    slots, terms = _leaders_by_term(st)
    new, t_new = int(slots[0]), int(terms[0])
    assert new != old and t_new > t_old
    assert int(np.asarray(st.state)[0, old]) == LEADER     # no check-quorum
    step(4, new, cut)
    step(1, new, cut)
    for _ in range(8):
        step(0, new, cut)
    for _ in range(20):
        step(0, new, up)                 # back: steps down, is truncated

    nt = Network(*([None] * P))
    for r in nt.peers.values():
        r.become_follower(t_old - 1, 0)
    nt.send(msg(MessageType.HUP, frm=old + 1, to=old + 1))

    def propose(at, n):
        for _ in range(n):
            nt.send(msg(MessageType.PROP, frm=at, to=at,
                        entries=(Entry(data=b"x"),)))

    propose(old + 1, 3)
    nt.isolate(old + 1)
    propose(old + 1, 4)
    for pid, r in nt.peers.items():
        if pid != old + 1:
            r.become_follower(t_new - 1, 0)
    nt.send(msg(MessageType.HUP, frm=new + 1, to=new + 1))
    propose(new + 1, 5)
    nt.recover()
    for _ in range(3):
        nt.send(msg(MessageType.BEAT, frm=new + 1, to=new + 1))
    for slot in (old, new):
        r = nt.peers[slot + 1]
        last = r.raft_log.last_index()
        got = {k: int(np.asarray(getattr(st, k))[0, slot])
               for k in ("term", "state", "commit", "last_index")}
        assert got == {"term": r.term, "state": int(r.state),
                       "commit": r.raft_log.committed,
                       "last_index": last}, slot
        assert got["term"] == t_new and last == 1 + 3 + 1 + 5
        ring = np.asarray(st.log_term)[0, slot]
        assert ([int(ring[i % cfg.window]) for i in range(1, last + 1)]
                == [r.raft_log.term(i) for i in range(1, last + 1)])
    assert int(np.asarray(st.vote)[0, old]) in (0, new + 1)


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------

def make_cfg(tmp, **kw):
    from etcd_tpu.server.engine import EngineConfig
    kw.setdefault("groups", 4)
    kw.setdefault("peers", 7)
    kw.setdefault("window", 16)
    kw.setdefault("max_ents", 4)
    kw.setdefault("heartbeat_tick", 3)
    kw.setdefault("request_timeout", 60.0)
    kw.setdefault("fsync", False)
    kw.setdefault("sync_interval", 0.0)
    return EngineConfig(data_dir=str(tmp), **kw)


def run_until(eng, pred, max_rounds=800, msg="condition"):
    for _ in range(max_rounds):
        if pred():
            return
        eng.run_round()
    raise AssertionError(f"{msg} not reached in {max_rounds} rounds")


def all_led(eng):
    return all(eng.leader_slot(g) >= 0 for g in range(eng.cfg.groups))


def do_async(eng, g, req):
    out = {}

    def work():
        try:
            out["res"] = eng.do(g, req)
        except Exception as e:  # noqa: BLE001 - handed to the caller
            out["err"] = e

    t = threading.Thread(target=work, daemon=True)
    t.start()
    return t, out


def settle_all(eng, pending, max_rounds=800):
    """Run rounds until every (thread, out) answered; no request may fail."""
    for _ in range(max_rounds):
        if not any(t.is_alive() for t, _ in pending):
            break
        eng.run_round()
        for t, _ in pending:
            t.join(timeout=0.0005)
    res = []
    for t, out in pending:
        t.join(timeout=1.0)
        assert "err" not in out, out.get("err")
        assert "res" in out, "request did not complete"
        res.append(out["res"])
    return res


def post(g, val):
    from etcd_tpu.server.request import Request
    return g, Request(method="POST", path="/q", val=val)


def qread(eng, g, key="/q"):
    from etcd_tpu.server.request import Request
    return settle_all(eng, [do_async(eng, g, Request(
        method="GET", path=key, quorum=True, recursive=True,
        sorted=True))])[0]


def queue_values(eng, g):
    return [n.value for n in qread(eng, g).node.nodes]


def _n_leader_rows(eng, g):
    return int((eng.h_mask[g] & (eng.h_state[g] == LEADER)).sum())


def test_engine_cuts_the_scheduled_leaders_and_the_same_after_a_restart(
        tmp_path):
    from etcd_tpu.server.engine import MultiEngine
    kw = dict(groups=8, churn_down_rounds=40, churn_period_rounds=120,
              churn_seed=5)
    sch = ChurnSchedule(8, 7, 40, 120, seed=5)

    def drive(eng, upto):
        while eng.round_no < upto:
            r = eng.round_no
            lead_before = [eng.leader_slot(g) for g in range(8)]
            was = eng._down.copy()
            eng.run_round()
            down = eng._down
            assert down.sum(axis=1).max() <= 1
            # quorum 4 of 7 is never at risk: six stay in the protocol
            assert ((eng.h_mask & ~down).sum(axis=1) >= 6).all()
            for g in sch.starting(r).tolist():
                # always the working leader at the cut's start
                want = np.zeros(7, bool)
                if lead_before[g] >= 0:
                    want[lead_before[g]] = True
                assert np.array_equal(down[g], want), (r, g)
            rest = np.setdiff1d(np.arange(8), sch.starting(r))
            cutting = np.isin(rest, sch.cutting(r))
            assert np.array_equal(down[rest[cutting]], was[rest[cutting]])
            assert not down[rest[~cutting]].any()

    eng = MultiEngine(make_cfg(tmp_path / "a", **kw))
    try:
        run_until(eng, lambda: all_led(eng), msg="leaders")
        drive(eng, 400)
        assert eng.churn_cuts >= 8 * 2
        # every cut ended in an election: terms moved in every group
        assert (eng.h_term.max(axis=1) >= 3).all()
        # stop inside some cuts, after their successors were elected
        run_until(eng, lambda: eng._down.sum() >= 2 and all(
            _n_leader_rows(eng, g) == 2
            for g in np.nonzero(eng._down.any(axis=1))[0]),
            msg="cuts with successors")
        settle_all(eng, [do_async(eng, *post(0, "journalled late"))])
        down_then, stopped_at = eng._down.copy(), eng.round_no
    finally:
        eng.stop()
    eng = MultiEngine(make_cfg(tmp_path / "a", **kw))
    try:
        assert stopped_at - 3 <= eng.round_no <= stopped_at
        # told from the journalled terms and votes: the same slots
        assert np.array_equal(eng._down, down_then)
        was = eng._down.copy()
        eng.run_round()
        keep = np.setdiff1d(sch.cutting(eng.round_no - 1),
                            sch.starting(eng.round_no - 1))
        assert np.array_equal(eng._down[keep], was[keep])
        run_until(eng, lambda: all_led(eng), msg="leaders after the restart")
        assert queue_values(eng, 0) == ["journalled late"]
    finally:
        eng.stop()


def _cut_a_low_leader(eng, g=0):
    """Run until group g is in a cut whose victim sits in a LOWER slot than
    its successor will (slot 0 leads; any successor is higher)."""
    def low_cut_begins():
        return eng._down[g, 0] and _n_leader_rows(eng, g) == 1
    run_until(eng, low_cut_begins, max_rounds=6000,
              msg="a cut of a leader in slot 0")


def test_writes_across_a_cut_are_acked_once_and_read_back(tmp_path):
    """Group 0's leader sits in slot 0 when it is cut off. Writes sent
    before the cut, in the rounds before its successor exists (admitted by
    the cut-off leader, journalled, never committed), after the election and
    after the return are all acknowledged; the queue directory holds each
    value exactly once, in an order that keeps every client's own; crash
    images taken mid-cut and in the round of the returning leader's install
    serve all that was acknowledged by then."""
    from etcd_tpu.server.engine import MultiEngine
    from etcd_tpu.server.request import Request
    kw = dict(groups=2, window=8, churn_down_rounds=60,
              churn_period_rounds=150, churn_seed=2)
    eng = MultiEngine(make_cfg(tmp_path / "live", **kw))
    acked = []
    images = []
    try:
        run_until(eng, lambda: all_led(eng), msg="leaders")
        # move group 0's leadership to slot 0: wait for cuts to land it
        # there (each cut elects a new leader; slot 0 comes up in time)
        _cut_a_low_leader(eng)
        t_old = int(eng.h_term[0, 0])
        n = 0

        def burst(k, tag):
            nonlocal n
            out = [do_async(eng, *post(0, f"{tag}{n + j}")) for j in range(k)]
            n += k
            return out

        # sent in the cut's first rounds: the host still routes to slot 0
        during = burst(6, "during")
        for _ in range(3):
            eng.run_round()
        assert eng.leader_slot(0) == 0 and eng._down[0, 0]
        lost = [k for k in eng.payload_reqs if k[0] == 0 and k[2] == t_old]
        assert lost, "the cut-off leader admitted nothing"
        assert int(eng.h_commit[0, 0]) < min(k[1] for k in lost)
        # a quorum read in the cut: parked until a leader that can confirm
        # it exists, never answered by the cut-off one
        rd = do_async(eng, 0, Request(method="GET", path="/q", quorum=True,
                                      recursive=True, sorted=True))
        for _ in range(4):
            eng.run_round()
        assert rd[0].is_alive(), "a cut-off leader confirmed a quorum read"
        run_until(eng, lambda: _n_leader_rows(eng, 0) == 2,
                  msg="the successor")
        new = eng.leader_slot(0)
        assert new > 0 and int(eng.h_term[0, new]) > t_old
        assert int(eng.h_state[0, 0]) == LEADER     # still thinks it leads
        after = burst(6, "after")
        vals = [r.node.value for r in settle_all(eng, during + after)]
        acked += vals
        assert eng.reproposed >= len(lost)
        # the read parked in the cut is answered by the successor, with
        # nothing the cut-off leader alone had admitted left out or doubled
        got = [x.value for x in settle_all(eng, [rd])[0].node.nodes]
        assert len(set(got)) == len(got) and set(got) <= set(acked)
        assert eng._down[0, 0]
        _crash_image(eng, tmp_path / "mid_cut")
        images.append(("mid_cut", list(acked)))
        # writes go on through the rest of the cut: at its return the old
        # leader is beyond its successor's ring (W = 8) and is installed
        installs = eng.snap_installs
        pend = []
        for _ in range(3000):
            if not any(t.is_alive() for t, _ in pend):
                acked += [r.node.value for r in settle_all(eng, pend)]
                pend = burst(4, "w")
            eng.run_round()
            for t, _ in pend:
                t.join(timeout=0.0005)
            if eng.snap_installs > installs:
                break
        assert eng.snap_installs > installs and not eng._down[0, 0]
        assert eng._force_full          # on the device only, not journalled
        _crash_image(eng, tmp_path / "install_round")
        images.append(("install_round", list(acked)))
        eng.run_round()                 # its full readback journals it
        assert not eng._force_full
        _crash_image(eng, tmp_path / "round_after")
        images.append(("round_after", list(acked)))
        acked += [r.node.value for r in settle_all(eng, pend)]
        run_until(eng, lambda: int(eng.h_state[0, 0]) != LEADER
                  and int(eng.h_last[0, 0]) == int(eng.h_last[0, new]),
                  msg="the old leader caught up")
        assert (eng.h_term[0] == eng.h_term[0, new]).all()
        final = queue_values(eng, 0)
    finally:
        eng.stop()
    # exactly once, and nothing that was not sent
    assert sorted(final) == sorted(acked) and len(set(final)) == len(final)
    assert len(acked) == n
    for name, acked_then in images:
        eng2 = MultiEngine(make_cfg(tmp_path / name, **kw))
        try:
            run_until(eng2, lambda: all_led(eng2), max_rounds=1200,
                      msg=f"{name}: leaders after the crash")
            vals = queue_values(eng2, 0)
            assert len(set(vals)) == len(vals), name
            assert set(acked_then) <= set(vals), (name, set(acked_then)
                                                  - set(vals))
        finally:
            eng2.stop()


def _crash_image(eng, dst):
    """The data dir as a SIGKILL at this instant would leave it."""
    eng.wal.wait_durable(eng.wal.ticket)
    shutil.copytree(eng.cfg.data_dir, dst)


def test_a_quorum_read_in_a_cut_is_never_stale(tmp_path):
    """While the old leader still believes it leads, a write acknowledged
    through its successor is what every later quorum read returns."""
    from etcd_tpu.server.engine import MultiEngine
    from etcd_tpu.server.request import Request
    eng = MultiEngine(make_cfg(tmp_path / "r", groups=2, churn_down_rounds=80,
                               churn_period_rounds=200, churn_seed=2))
    try:
        run_until(eng, lambda: all_led(eng), msg="leaders")
        _cut_a_low_leader(eng)
        run_until(eng, lambda: _n_leader_rows(eng, 0) == 2,
                  msg="the successor")
        assert eng._down[0, 0] and int(eng.h_state[0, 0]) == LEADER
        for i in range(12):
            settle_all(eng, [do_async(eng, 0, Request(
                method="PUT", path="/k", val=f"v{i}"))])
            got = settle_all(eng, [do_async(eng, 0, Request(
                method="GET", path="/k", quorum=True))])[0]
            assert got.node.value == f"v{i}"
            assert eng._down[0, 0], "the cut ended before the test did"
        # the successor's commit, not the cut-off leader's, is what served
        assert int(eng.h_commit[0, 0]) < int(eng.h_commit[0].max())
    finally:
        eng.stop()


def test_an_idle_member_sleeps_through_a_cut(tmp_path):
    """A cut-off leader's uncommitted tail is lost, not pending: once its
    successor has committed everything, _settled holds and the engine
    thread waits for work instead of spinning for the rest of the cut."""
    from etcd_tpu.server.engine import MultiEngine
    eng = MultiEngine(make_cfg(tmp_path / "i", groups=2, churn_down_rounds=80,
                               churn_period_rounds=200, churn_seed=2))
    try:
        run_until(eng, lambda: all_led(eng), msg="leaders")
        _cut_a_low_leader(eng)
        pend = [do_async(eng, *post(0, f"x{j}")) for j in range(4)]
        for _ in range(3):
            eng.run_round()
        assert int(eng.h_commit[0, 0]) < int(eng.h_last[0, 0])
        settle_all(eng, pend)
        run_until(eng, lambda: eng._idle(), max_rounds=60, msg="idle")
        assert eng._down[0, 0]
        assert int(eng.h_commit[0, 0]) < int(eng.h_last[0, 0])
    finally:
        eng.stop()


def test_the_five_series_move_with_what_they_name(tmp_path):
    from etcd_tpu.server import obs
    from etcd_tpu.server.engine import MultiEngine
    eng = MultiEngine(make_cfg(tmp_path / "o", groups=2, churn_down_rounds=60,
                               churn_period_rounds=150, churn_seed=2))
    if not eng.obs.enabled:
        eng.stop()
        pytest.skip("ETCD_TPU_OBS=off")
    before = (obs.leader_changes.value, obs.churn_cuts.value,
              obs.reproposed_requests.value, obs.leaderless_wait.count,
              obs.leaderless_wait.sum)
    try:
        run_until(eng, lambda: all_led(eng), msg="leaders")
        assert obs.leader_changes.value - before[0] == 2    # the boot's
        _cut_a_low_leader(eng)
        assert obs.churn_down_slots.value == eng._down.sum() >= 1
        pend = [do_async(eng, *post(0, f"x{j}")) for j in range(5)]
        settle_all(eng, pend)
    finally:
        eng.stop()
    assert obs.churn_cuts.value - before[1] == eng.churn_cuts >= 1
    assert obs.leader_changes.value - before[0] >= 2 + eng.churn_cuts - 2
    assert obs.reproposed_requests.value - before[2] == eng.reproposed >= 1
    # each re-proposed request waited for its new leader, and is counted
    assert obs.leaderless_wait.count - before[3] >= eng.reproposed
    assert obs.leaderless_wait.sum > before[4]


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device CPU mesh")
def test_on_a_mesh_the_down_map_is_sharded_like_the_state(tmp_path):
    from etcd_tpu.parallel.mesh import flag_sharding, make_mesh
    from etcd_tpu.server.engine import MultiEngine
    kw = dict(groups=8, peers=5, churn_down_rounds=30, churn_period_rounds=80,
              churn_seed=5, pipeline_applies=False)
    mesh = make_mesh(jax.devices()[:4], peers_axis=1)
    engs = [MultiEngine(make_cfg(tmp_path / "mesh", mesh=mesh, **kw)),
            MultiEngine(make_cfg(tmp_path / "one", **kw))]
    try:
        down = engs[0]._churn_down()
        assert down.sharding == flag_sharding(mesh)
        assert down.sharding == engs[0].st.state.sharding
        for _ in range(260):
            for eng in engs:
                eng.run_round()
        assert all_led(engs[0]) and all_led(engs[1])
        assert engs[0].churn_cuts == engs[1].churn_cuts >= 8
        for name in ("h_term", "h_commit", "h_last", "h_ring", "h_state",
                     "_down"):
            assert np.array_equal(getattr(engs[0], name),
                                  getattr(engs[1], name)), name
        assert engs[0].st.state.sharding == flag_sharding(mesh)
    finally:
        for eng in engs:
            eng.stop()


# ---------------------------------------------------------------------------
# (d) the process entry
# ---------------------------------------------------------------------------

def _http(method, url, form=None, timeout=30.0):
    data = urllib.parse.urlencode(form).encode() if form else None
    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _metric(text, name):
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return None


def test_the_three_flags_elect_export_and_survive_sigkill(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    cmd = [sys.executable, "-m", "etcd_tpu", "--engine-groups", "4",
           "--engine-peers", "7", "--engine-window", "8",
           "--engine-churn-down-rounds", "48",
           "--engine-churn-period-rounds", "96", "--engine-churn-seed", "38",
           "--data-dir", str(tmp_path / "d"), "--listen-client-urls", base]
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")

    def boot():
        proc = subprocess.Popen(cmd, cwd=REPO, env=env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        end = time.time() + 180
        while time.time() < end:
            assert proc.poll() is None, f"member exited rc={proc.poll()}"
            try:
                code, body = _http("GET", base + "/engine/status")
                st = json.loads(body)
                if code == 200 and st["groups_with_leader"] == st["groups"]:
                    return proc
            except (urllib.error.URLError, ConnectionError, OSError):
                pass
            time.sleep(0.25)
        proc.kill()
        raise AssertionError("member did not come up")

    proc = boot()
    acked = {}
    try:
        end = time.time() + 150
        i = 0
        reproposed = 0
        while time.time() < end and (reproposed < 1 or i < 300):
            g = i % 4
            code, body = _http("PUT", f"{base}/tenants/{g}/v2/keys/k{i}",
                               {"value": f"v{i}"})
            assert code in (200, 201), (i, code, body)      # no timeout
            acked[(g, f"k{i}")] = f"v{i}"
            i += 1
            if i % 50 == 0:
                reproposed = _metric(
                    _http("GET", base + "/metrics")[1],
                    "etcd_engine_reproposed_requests_total")
        text = _http("GET", base + "/metrics")[1]
        assert _metric(text, "etcd_engine_churn_cuts_total") >= 4
        assert _metric(text, "etcd_engine_leader_changes_total") >= 4 + 4
        assert _metric(text, "etcd_engine_reproposed_requests_total") >= 1
        assert _metric(text, "etcd_engine_leaderless_wait_seconds_count") >= 1
        assert _metric(text, "etcd_engine_leaderless_wait_seconds_sum") > 0
        assert 0 <= _metric(text, "etcd_engine_churn_down_slots") <= 4
        proc.send_signal(signal.SIGKILL)
        assert proc.wait(30) == -signal.SIGKILL
        proc = boot()
        for (g, key), val in acked.items():
            for _ in range(12):
                code, body = _http(
                    "GET", f"{base}/tenants/{g}/v2/keys/{key}?quorum=true")
                if code != 500 or json.loads(body).get("errorCode") != 300:
                    break
            assert code == 200 and json.loads(body)["node"]["value"] == val
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(60) == 0
        proc = None
    finally:
        if proc is not None:
            proc.kill()
            proc.wait()


def test_the_flags_are_refused_where_they_could_risk_a_quorum():
    from etcd_tpu.etcdmain.config import ConfigError, parse_args
    ok = parse_args(["--engine-groups", "4", "--engine-peers", "7",
                     "--engine-churn-down-rounds", "128",
                     "--engine-churn-period-rounds", "512",
                     "--engine-churn-seed", "38"], env={})
    assert (ok.engine_churn_down_rounds, ok.engine_churn_period_rounds,
            ok.engine_churn_seed) == (128, 512, 38)
    off = parse_args(["--engine-groups", "4"], env={})
    assert off.engine_churn_down_rounds == 0
    for argv in (["--engine-churn-down-rounds", "8", "--engine-peers", "2"],
                 ["--engine-churn-down-rounds", "512"],
                 ["--engine-churn-down-rounds", "-1"],
                 ["--engine-churn-down-rounds", "8",
                  "--engine-churn-period-rounds", "8"],
                 ["--engine-churn-down-rounds", "8",
                  "--engine-lag-share", "0.05"]):
        with pytest.raises(ConfigError):
            parse_args(["--engine-groups", "4"] + argv, env={})
    assert parse_args(["--engine-groups", "4"], env={
        "ETCD_ENGINE_CHURN_DOWN_ROUNDS": "16"}).engine_churn_down_rounds == 16
    from etcd_tpu.server.engine import EngineConfig, MultiEngine
    with pytest.raises(ValueError):
        MultiEngine(EngineConfig(groups=2, peers=5, data_dir="/nonexistent",
                                 churn_down_rounds=8, lag_share=0.1))
