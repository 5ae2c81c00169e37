"""Lagging-follower injection on the served path (EngineConfig.lag_share,
`--engine-lag-share`; BASELINE.json configs[3]: "5 % lagging followers
(Progress.Paused)").

(a) the schedule (server/lag.py): the share held in every round, never two
    slots of a group, never the leader, a pure function of (seed, g, round):
    the same slots after a restart from the WAL;
(b) a held follower receives no entries and no install while acknowledged
    writes go on, however far past the ring its leader runs; its term does
    not move and the group holds no election;
(c) released within the ring it catches up by appends, released beyond it by
    the host's snapshot install, and once quiet its log equals what the
    scalar reference (etcd_tpu/raft/core.py, which imports nothing of the
    kernel) reaches when fed the same proposals with the same follower
    paused for the same span;
(d) every acknowledged write is read back with a quorum read during the
    hold, and from a crash image of the data dir taken in the round of an
    install and in the round after it (the journalled install replays);
    the same through the process entry with the three flags and a real
    SIGKILL;
(e) with the feature off the lowered step programs are textually the
    recorded ones and take no extra argument, and a seeded run's trajectory
    is the parent's bit for bit (tests/step_programs_parent.json, written by
    `python tests/test_lagging_followers.py --record`). The three program
    hashes were recorded again in PR 40, on its own tree: the quiescence
    predicate is folded group by group there (and asks for at most one
    append a receiver where it asked for one LEADER row a group), a busy
    hop makes its sequential passes by rank (kernel._ranked_msgs) and the
    hops' counts of busy groups and passes come back with the state, so
    the text changed by design. `args` and `trajectory_sha256` are PR 35's parent's
    still, unedited: the unchanged digest is the proof that a seeded
    160-round run with elections is that parent's bit for bit.
    `by_sender_step_sha256` is new in PR 40 and is PR 40's parent's text
    (computed there from `_step_body(..., quiet=False)`, and the same on
    PR 40's tree): the step with the P passes by sender, which is what a
    busy hop runs in a mesh's programs and in the multi-host step
    (`by_sender`), lowers to what it was before the passes by rank came.
"""
import hashlib
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

if __name__ == "__main__":      # --record: the suite's platform, x64 and
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import conftest  # noqa: F401  devices, before anything touches jax

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from etcd_tpu.ops import kernel
from etcd_tpu.ops.state import (F_TYPE, KernelConfig, LEADER, M_APP, M_HB,
                                init_state)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
PARENT_JSON = os.path.join(HERE, "step_programs_parent.json")
STEPS = ("step_routed_auto", "step_routed_compact", "step_routed_read_auto")


# ---------------------------------------------------------------------------
# (e) the programs without the hold are the parent's
# ---------------------------------------------------------------------------

def _small():
    cfg = KernelConfig(groups=8, peers=5, window=16, max_ents=4,
                       election_tick=10, heartbeat_tick=3)
    inbox = jnp.zeros((8, 5, 5, cfg.fields), jnp.int32)
    return cfg, init_state(cfg, stagger=True), inbox


def _lowered(name, hold=None):
    cfg, st, inbox = _small()
    z = jnp.zeros(cfg.groups, jnp.int32)
    args = (cfg, st, inbox, z, z, jnp.asarray(True), None, 3)
    if hold is not None:
        args += (hold,)
    return getattr(kernel, name).lower(*args).as_text()


def _lowered_by_sender():
    """The step whose message phase is the P passes by sender, lowered
    alone: a busy hop of a program built with by_sender=True."""
    cfg, st, inbox = _small()
    z = jnp.zeros(cfg.groups, jnp.int32)

    def body(st, inbox, pc, ps, tick):
        return kernel._step_body(cfg, st, inbox, pc, ps, tick,
                                 kernel._full_msgs)[:2]

    return jax.jit(body).lower(st, inbox, z, z, jnp.asarray(True)).as_text()


def _n_args(text):
    head = text[text.index("func.func public @main("):]
    return head[:head.index("->")].count("%arg")


def _trajectory_digest(hold=None):
    """160 seeded rounds of the compact step at hops=3 (elections at the
    start, then proposals at the leaders); sha256 over every state field
    and the routed inbox after the last round."""
    cfg, st, inbox = _small()
    rng = np.random.default_rng(35)
    extra = () if hold is None else (hold,)
    for r in range(160):
        state = np.asarray(st.state)
        slots = jnp.asarray((state == LEADER).argmax(axis=1)
                            .astype(np.int32))
        pc = jnp.asarray((rng.integers(0, cfg.max_ents + 1, size=cfg.groups)
                          * (state == LEADER).any(axis=1)).astype(np.int32))
        st, inbox, *_ = kernel.step_routed_compact(
            cfg, st, inbox, pc, slots, jnp.asarray(True), None, 3, *extra)
    h = hashlib.sha256()
    for k, v in sorted(st._asdict().items()):
        h.update(k.encode() + np.ascontiguousarray(np.asarray(v)).tobytes())
    h.update(np.ascontiguousarray(np.asarray(inbox)).tobytes())
    assert int(np.asarray(st.commit).max()) > 50
    return h.hexdigest()


def _record():
    doc = {"jax": jax.__version__, "programs": {}}
    for name in STEPS:
        text = _lowered(name)
        doc["programs"][name] = {
            "sha256": hashlib.sha256(text.encode()).hexdigest(),
            "args": _n_args(text)}
    doc["by_sender_step_sha256"] = hashlib.sha256(
        _lowered_by_sender().encode()).hexdigest()
    doc["trajectory_sha256"] = _trajectory_digest()
    return doc


@pytest.fixture(scope="module")
def parent():
    with open(PARENT_JSON) as f:
        doc = json.load(f)
    if doc["jax"] != jax.__version__:
        pytest.skip(f"recorded with jax {doc['jax']}: record again")
    return doc


@pytest.mark.parametrize("name", STEPS)
def test_without_the_hold_the_step_program_is_the_parents(name, parent):
    text = _lowered(name)
    want = parent["programs"][name]
    assert _n_args(text) == want["args"]
    assert hashlib.sha256(text.encode()).hexdigest() == want["sha256"]
    # and the hold is one more argument of the program, a (G, P) i1
    held = _lowered(name, jnp.zeros((8, 5), bool))
    assert _n_args(held) == want["args"] + 1
    assert "tensor<8x5xi1>" in held[:held.index("->")]


def test_the_step_by_sender_is_the_parents_text(parent):
    """What by_sender=True puts on a busy hop (a mesh's programs,
    engine.py; step_routed_slots_auto) is the parent's full path, text
    for text: tests/test_tpu_compile.py counts its collectives."""
    assert (hashlib.sha256(_lowered_by_sender().encode()).hexdigest()
            == parent["by_sender_step_sha256"])


def test_without_the_hold_a_seeded_trajectory_is_the_parents(parent):
    assert _trajectory_digest() == parent["trajectory_sha256"]
    # a hold that holds nothing changes nothing either
    assert (_trajectory_digest(jnp.zeros((8, 5), bool))
            == parent["trajectory_sha256"])


def test_a_held_column_gets_heartbeats_and_no_append_in_the_kernel():
    cfg, st, inbox = _small()
    G, P = cfg.groups, cfg.peers
    z = jnp.zeros(G, jnp.int32)
    for _ in range(40):                         # elect
        st, inbox, _ = kernel.step_routed_auto(cfg, st, inbox, z, z,
                                               jnp.asarray(True), None, 1)
    state = np.asarray(st.state)
    assert (state == LEADER).sum(axis=1).tolist() == [1] * G
    lead = (state == LEADER).argmax(axis=1)
    victim = (lead + 1) % P
    hold = np.zeros((G, P), bool)
    hold[np.arange(G), victim] = True
    term0 = np.asarray(st.term).copy()
    last0 = np.asarray(st.last_index)[np.arange(G), victim].copy()
    apps = hbs = 0
    for r in range(120):
        pc = jnp.asarray(np.full(G, 2, np.int32))
        st, inbox, _ = kernel.step_routed_auto(
            cfg, st, inbox, pc, jnp.asarray(lead.astype(np.int32)),
            jnp.asarray(True), None, 1, jnp.asarray(hold))
        to_victim = np.asarray(inbox)[np.arange(G), victim][..., F_TYPE]
        apps += int((to_victim == M_APP).sum())
        hbs += int((to_victim == M_HB).sum())
    assert apps == 0 and hbs > G * 20
    assert np.array_equal(np.asarray(st.term), term0)
    assert not np.asarray(st.need_host).any()
    last = np.asarray(st.last_index)
    assert np.array_equal(last[np.arange(G), victim], last0)
    assert (last[np.arange(G), lead] - last0 > 3 * cfg.window).all()


# ---------------------------------------------------------------------------
# (a) the schedule
# ---------------------------------------------------------------------------

def _roles(G, P, rng, leaderless=()):
    state = np.zeros((G, P), np.int32)
    state[np.arange(G), rng.integers(0, P, size=G)] = LEADER
    for g in leaderless:
        state[g] = 0
    return state


def test_schedule_holds_the_share_in_every_round():
    from etcd_tpu.server.lag import LagSchedule
    G, P, share, hold = 200, 5, 0.05, 20
    sch = LagSchedule(G, P, share, hold, seed=3)
    assert sch.period == 100
    mask = np.ones((G, P), bool)
    rng = np.random.default_rng(1)
    state = _roles(G, P, rng)
    prev = None
    starts = []
    seen = {}
    for r in range(3 * sch.period):
        held = sch.held(r, mask, state)
        assert abs(int(held.sum()) - share * G * (P - 1)) <= 1, r
        assert held.sum(axis=1).max() <= 1
        assert not (held & (state == LEADER)).any()
        if prev is not None:
            starts.append(int((held & ~prev).sum()))
        prev = held
        for g, p in zip(*np.nonzero(held)):
            seen.setdefault((int(g), r // sch.period), set()).add(int(p))
    # holds start a few groups a round, never all at once
    assert max(starts) <= 2 * G // sch.period + 1
    # one slot a hold, and it moves on from one period to the next
    by_group = {}
    for (g, per), slots in seen.items():
        by_group.setdefault(g, set()).update(slots)
    assert all(len(s) >= 2 for s in by_group.values())
    # a pure function of (seed, g, round)
    again = LagSchedule(G, P, share, hold, seed=3)
    other = LagSchedule(G, P, share, hold, seed=4)
    assert np.array_equal(again.held(77, mask, state),
                          sch.held(77, mask, state))
    assert not np.array_equal(other.held(77, mask, state),
                              sch.held(77, mask, state))


def _held_plainly(sch, round_no, mask, state):
    """The schedule's rule written out over all G groups at once."""
    t = round_no + sch.phase
    holding, rank = (t % sch.period) < sch.hold, sch.rank0 + t // sch.period
    lead = mask & (state == LEADER)
    cand = mask & ~lead
    n_f = cand.sum(axis=1)
    ok = holding & lead.any(axis=1) & (n_f >= 2)
    pick = rank % np.maximum(n_f, 1)
    return (cand & (np.cumsum(cand, axis=1) - 1 == pick[:, None])
            & ok[:, None])


@pytest.mark.parametrize("G,P,share,hold", [
    (12_500, 5, 0.05, 256), (8, 5, 0.125, 12), (200, 5, 0.05, 20),
    (40, 3, 0.5, 7), (12, 5, 0.25, 50), (4, 5, 0.25, 1 << 20)])
def test_schedule_over_the_holding_groups_equals_the_rule_over_all(
        G, P, share, hold):
    """held() works on the window of groups that hold (two binary searches
    in the groups sorted by phase): the same slots as the rule applied to
    every group, whatever the masks and roles, wrapped windows included."""
    from etcd_tpu.server.lag import LagSchedule
    sch = LagSchedule(G, P, share, hold, seed=3)
    rng = np.random.default_rng(G)
    rounds = list(range(0, min(3 * sch.period, 4000), 7)) + [10**6 + 5]
    for r in rounds:
        mask = rng.random((G, P)) < 0.9
        state = _roles(G, P, rng)
        state[rng.random(G) < 0.1] = 0
        assert np.array_equal(sch.held(r, mask, state),
                              _held_plainly(sch, r, mask, state)), r
        assert np.array_equal(
            np.sort(sch.holding(r)),
            np.nonzero((r + sch.phase) % sch.period < sch.hold)[0])


def test_schedule_never_risks_a_quorum():
    from etcd_tpu.server.lag import LagSchedule
    G, P = 12, 5
    sch = LagSchedule(G, P, 0.25, 50, seed=9)       # every group, always
    rng = np.random.default_rng(2)
    state = _roles(G, P, rng, leaderless=(3,))
    mask = np.ones((G, P), bool)
    mask[5, 3:] = False                 # three active slots: one may be held
    mask[6, 2:] = False                 # two active slots: none
    mask[7] = False                     # not provisioned
    state = np.where(mask, state, 0)
    state[5] = [LEADER, 0, 0, 0, 0]
    state[6] = [0, LEADER, 0, 0, 0]
    held = sch.held(10, mask, state)
    assert not held[3].any() and not held[6].any() and not held[7].any()
    assert held[5].sum() == 1 and held[5, 1:3].any()
    assert (held.sum(axis=1)[[0, 1, 2, 4, 8, 9, 10, 11]] == 1).all()
    assert not (held & ~mask).any()
    for bad in ((2, 0.1), (5, 0.3), (5, 0.0), (5, -0.1)):
        with pytest.raises(ValueError):
            LagSchedule(G, bad[0], bad[1], 50, seed=0)
    with pytest.raises(ValueError):
        LagSchedule(G, 5, 0.05, 0, seed=0)


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------

def make_cfg(tmp, **kw):
    from etcd_tpu.server.engine import EngineConfig
    kw.setdefault("groups", 4)
    kw.setdefault("peers", 5)
    kw.setdefault("window", 16)
    kw.setdefault("max_ents", 4)
    kw.setdefault("heartbeat_tick", 3)
    kw.setdefault("request_timeout", 60.0)
    kw.setdefault("fsync", False)
    kw.setdefault("sync_interval", 0.0)
    return EngineConfig(data_dir=str(tmp), **kw)


def run_until(eng, pred, max_rounds=600, msg="condition"):
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
    from etcd_tpu.server.request import Request
    return settle(eng, *do_async(eng, g, Request(method="PUT", path=key,
                                                 val=val)))


def qread(eng, g, key):
    from etcd_tpu.server.request import Request
    return settle(eng, *do_async(eng, g, Request(
        method="GET", path=key, quorum=True))).node.value


def test_engine_holds_the_scheduled_slots_and_the_same_after_a_restart(
        tmp_path):
    from etcd_tpu.server.engine import MultiEngine
    from etcd_tpu.server.lag import LagSchedule
    kw = dict(groups=8, lag_share=0.125, lag_hold_rounds=12, lag_seed=5)
    sch = LagSchedule(8, 5, 0.125, 12, seed=5)
    assert sch.period == 24

    def drive(eng, upto, seen):
        while eng.round_no < upto:
            r, roles = eng.round_no, eng.h_state.copy()
            eng.run_round()
            held = eng._lag_held.copy()
            assert np.array_equal(held, sch.held(r, eng.h_mask, roles))
            assert held.sum(axis=1).max() <= 1
            assert not (held & (roles == LEADER)).any()
            if (roles == LEADER).any(axis=1).all():
                assert int(held.sum()) == 4         # 0.125 x 8 x 4
            seen[r] = (held, (roles == LEADER).argmax(axis=1))

    one, two = {}, {}
    eng = MultiEngine(make_cfg(tmp_path / "a", **kw))
    drive(eng, 120, one)
    eng.stop()
    eng = MultiEngine(make_cfg(tmp_path / "b", **kw))
    drive(eng, 50, two)
    put(eng, 0, "/k", "v")                  # something journalled late
    stopped_at = eng.round_no
    eng.stop()
    eng = MultiEngine(make_cfg(tmp_path / "b", **kw))
    assert stopped_at - 3 <= eng.round_no <= stopped_at   # the WAL's rounds
    run_until(eng, lambda: all_led(eng), msg="leaders after the restart")
    assert eng.round_no < 110
    drive(eng, 120, two)
    eng.stop()
    same = [r for r in range(110, 120)
            if np.array_equal(one[r][1], two[r][1])]
    assert same, "the restart elected other leaders in every group"
    for r in same:
        assert np.array_equal(one[r][0], two[r][0]), r


@pytest.fixture()
def always_held(tmp_path):
    """Four groups, each with one follower held for the whole test (share
    1/4 of the follower slots = one a group, a hold longer than the test)."""
    from etcd_tpu.server.engine import MultiEngine
    eng = MultiEngine(make_cfg(tmp_path / "held", lag_share=0.25,
                               lag_hold_rounds=1 << 20, lag_seed=11))
    run_until(eng, lambda: all_led(eng), msg="leaders")
    eng.run_round()
    yield eng
    eng.stop()


def test_a_held_follower_gets_no_entry_and_no_install(always_held):
    eng = always_held
    G, W = eng.cfg.groups, eng.cfg.window
    held = eng._lag_held.copy()
    assert held.sum(axis=1).tolist() == [1] * G
    gi, fi = np.nonzero(held)
    lead = np.array([eng.leader_slot(g) for g in range(G)])
    term0 = eng.h_term.copy()
    last0 = np.asarray(eng.st.last_index)[gi, fi].copy()
    acked = {}
    for i in range(3 * W):
        for g in range(G):
            put(eng, g, f"/k{i}", f"v{g}.{i}")
            acked[(g, f"/k{i}")] = f"v{g}.{i}"
        if i % 8 == 0:
            # an acknowledged write is read back during the hold
            assert qread(eng, i % G, f"/k{i}") == f"v{i % G}.{i}"
    for _ in range(4 * (2 * eng.cfg.heartbeat_tick + 2)):
        eng.run_round()         # long past the stale-ack retransmission
    assert np.array_equal(eng._lag_held, held)
    assert eng.snap_installs == 0
    st = eng.st
    last = np.asarray(st.last_index)
    assert np.array_equal(last[gi, fi], last0)
    assert (last[gi, lead] - last0 >= 3 * W).all()
    assert np.array_equal(np.asarray(st.term), term0)       # no election
    assert np.array_equal(eng.h_term, term0)
    assert [eng.leader_slot(g) for g in range(G)] == lead.tolist()
    assert not np.asarray(st.need_host).any()
    assert (np.asarray(st.commit)[gi, fi] <= last0).all()
    # the other three followers of every group are with the leader
    rest = eng.h_mask & ~held
    assert (np.where(rest, eng.h_last, 1 << 30).min(axis=1)
            == eng.h_last[gi, lead]).all()
    for (g, key), val in acked.items():
        assert qread(eng, g, key) == val


# ---------------------------------------------------------------------------
# (c) release: by appends within the ring, by install beyond it, and the
# scalar reference's log either way
# ---------------------------------------------------------------------------

def _reference_log(P, lead_id, term, held_id, n_before, n_held, n_after):
    """The scalar Raft, one group of P: `lead_id` leads at `term`, takes
    n_before proposals, then n_held with every append and snapshot to
    `held_id` withheld (heartbeats flow, as in the kernel), then is
    released and takes n_after more. It keeps its whole log, so it catches
    the follower up by appends whatever the lag; by log matching the end
    state is what a snapshot would have given. Returns the held follower's
    (last_index, committed, {index: term})."""
    from raft_fixtures import Network, msg
    from etcd_tpu.raftpb import Entry, MessageType

    class Held(Network):
        paused = False

        def filter(self, msgs):
            return [m for m in super().filter(msgs)
                    if not (self.paused and m.to == held_id and m.type in (
                        MessageType.APP, MessageType.SNAP))]

    nt = Held(*([None] * P))
    for r in nt.peers.values():
        r.become_follower(term - 1, 0)
    nt.send(msg(MessageType.HUP, frm=lead_id, to=lead_id))
    lead = nt.peers[lead_id]
    assert int(lead.state) == LEADER and lead.term == term

    def propose(n):
        for _ in range(n):
            nt.send(msg(MessageType.PROP, frm=lead_id, to=lead_id,
                        entries=(Entry(data=b"x"),)))

    propose(n_before)
    nt.paused = True
    propose(n_held)
    nt.send(msg(MessageType.BEAT, frm=lead_id, to=lead_id))
    nt.paused = False
    propose(n_after)
    for _ in range(4):                  # quiet: heartbeats carry the commit
        nt.send(msg(MessageType.BEAT, frm=lead_id, to=lead_id))
    f = nt.peers[held_id]
    assert f.term == term
    last = f.raft_log.last_index()
    assert last == lead.raft_log.last_index() == 1 + n_before + n_held + n_after
    return last, f.raft_log.committed, {
        i: f.raft_log.term(i) for i in range(1, last + 1)}


@pytest.mark.parametrize("how,n_held", [("appends", 6), ("install", 40)])
def test_release_catches_up_like_the_scalar_reference(tmp_path, how, n_held):
    """Group 0 alone takes writes. Its follower is held for 120 rounds out
    of 240: n_held entries go by meanwhile, fewer than the ring holds
    (W = 16) or more."""
    from etcd_tpu.server.engine import MultiEngine
    eng = MultiEngine(make_cfg(tmp_path / how, lag_share=0.125,
                               lag_hold_rounds=120, lag_seed=2))
    W, g = eng.cfg.window, 0
    try:
        run_until(eng, lambda: all_led(eng), msg="leaders")
        run_until(eng, lambda: not eng._lag_held[g].any(), msg="no hold")
        n_before = 3
        for i in range(n_before):
            put(eng, g, f"/b{i}", "b")
        run_until(eng, lambda: eng._lag_held[g].any(), msg="a hold starts")
        start = eng.round_no
        f = int(eng._lag_held[g].argmax())
        s = eng.leader_slot(g)
        term = int(eng.h_term[g, s])
        assert int(eng.h_last[g, f]) == 1 + n_before
        for i in range(n_held):
            put(eng, g, f"/h{i}", "h")
        assert eng._lag_held[g, f] and eng.round_no - start < 120
        assert int(np.asarray(eng.st.last_index)[g, f]) == 1 + n_before
        assert eng.snap_installs == 0
        run_until(eng, lambda: not eng._lag_held[g].any(), msg="release")
        assert eng.round_no - start == 120      # held 120 rounds, to the round
        n_after = 5
        for i in range(n_after):
            put(eng, g, f"/a{i}", "a")
        for _ in range(40):
            eng.run_round()                     # quiet
        assert eng.snap_installs == (1 if how == "install" else 0)
        assert eng.leader_slot(g) == s and (eng.h_term[g] == term).all()

        last, committed, terms = _reference_log(
            eng.cfg.peers, s + 1, term, f + 1, n_before, n_held, n_after)
        assert int(eng.h_last[g, f]) == last == int(eng.h_last[g, s])
        assert int(eng.h_commit[g, f]) == committed == last
        dev = {k: np.asarray(getattr(eng.st, k))[g, f]
               for k in ("last_index", "commit", "term")}
        assert (int(dev["last_index"]), int(dev["commit"]),
                int(dev["term"])) == (last, committed, term)
        ring = np.asarray(eng.st.log_term)[g, f]
        assert np.array_equal(ring, eng.h_ring[g, f])
        got = {i: int(ring[i % W]) for i in range(max(1, last - W + 1), last + 1)}
        assert got == {i: terms[i] for i in got}
        # and the writes of all three spans are served
        assert qread(eng, g, f"/h{n_held - 1}") == "h"
        assert qread(eng, g, f"/a{n_after - 1}") == "a"
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# (d) acknowledged writes across installs and crashes
# ---------------------------------------------------------------------------

def _crash_image(eng, dst):
    """The data dir as a SIGKILL at this instant would leave it: every
    record handed to the WAL writer so far is on disk, nothing else."""
    eng.wal.wait_durable(eng.wal.ticket)
    shutil.copytree(eng.cfg.data_dir, dst)


def test_acked_writes_survive_a_crash_around_an_install(tmp_path):
    from etcd_tpu.server.engine import MultiEngine
    kw = dict(groups=4, lag_share=0.125, lag_hold_rounds=40, lag_seed=7)
    eng = MultiEngine(make_cfg(tmp_path / "live", **kw))
    acked = {}
    images = []
    try:
        run_until(eng, lambda: all_led(eng), msg="leaders")
        i = 0
        while len(images) < 2 and i < 400:
            outs = []
            for g in range(4):
                from etcd_tpu.server.request import Request
                outs.append((g, f"/k{i}", do_async(eng, g, Request(
                    method="PUT", path=f"/k{i}", val=f"v{g}.{i}"))))
            pending = list(outs)
            while pending:
                before = eng.snap_installs
                eng.run_round()
                for item in list(pending):
                    g, key, (t, out) = item
                    t.join(timeout=0.001)
                    if not t.is_alive():
                        assert "res" in out, out
                        acked[(g, key)] = out["res"].node.value
                        pending.remove(item)
                if eng.snap_installs > before and not images:
                    # the round of the first install: the surgery is on the
                    # device only, no record holds it yet
                    assert eng._force_full
                    images.append(("install_round", dict(acked)))
                    _crash_image(eng, tmp_path / "install_round")
                elif len(images) == 1 and images[0][0] == "install_round":
                    # the round after: its full readback journalled it
                    assert not eng._force_full
                    images.append(("round_after", dict(acked)))
                    _crash_image(eng, tmp_path / "round_after")
            i += 1
        assert [n for n, _ in images] == ["install_round", "round_after"]
        installed_live = eng.snap_installs
        live_last = eng.h_last.copy()
    finally:
        eng.stop()
    assert installed_live >= 1 and len(acked) > 40
    for name, acked_then in images:
        eng2 = MultiEngine(make_cfg(tmp_path / name, **kw))
        try:
            if name == "round_after":
                # the journalled install replayed: no follower of any group
                # is more than the ring behind its group's longest log
                lag = eng2.h_last.max(axis=1) - eng2.h_last.min(axis=1)
                assert (lag <= live_last.max()).all()
            run_until(eng2, lambda: all_led(eng2), max_rounds=800,
                      msg=f"{name}: leaders after the crash")
            for (g, key), val in acked_then.items():
                assert qread(eng2, g, key) == val, (name, g, key)
            # and the restarted member goes on holding, releasing, installing
            for j in range(30):
                for g in range(4):
                    put(eng2, g, f"/z{j}", "z")
            assert qread(eng2, 3, "/z29") == "z"
        finally:
            eng2.stop()


def test_installs_are_counted_timed_and_annotated(tmp_path):
    """The four series move with what they name, and the surgery's
    profiler annotation lies inside `tail`, once a serviced round."""
    import importlib.util
    from etcd_tpu.server import obs
    from etcd_tpu.server.engine import MultiEngine
    spec = importlib.util.spec_from_file_location(
        "trace_reduce_for_lag_test",
        os.path.join(REPO, "benchmark", "lib", "trace_reduce.py"))
    trace_reduce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_reduce)
    eng = MultiEngine(make_cfg(tmp_path / "d", lag_share=0.125,
                               lag_hold_rounds=40, lag_seed=7))
    if not eng.obs.enabled:
        eng.stop()
        pytest.skip("ETCD_TPU_OBS=off")
    def full_rounds():
        return dict((lab["kind"], v) for _, lab, v in
                    obs.readback_rounds.samples())["full"]

    before = (obs.snapshot_installs.value, obs.lag_releases.value,
              obs.need_host_seconds.count, obs.need_host_seconds.sum,
              full_rounds())
    out = str(tmp_path / "trace")
    try:
        run_until(eng, lambda: all_led(eng), msg="leaders")
        with jax.profiler.trace(out):
            for i in range(60):
                put(eng, i % 4, f"/k{i // 4}", "v")
                put(eng, 0, f"/hot{i}", "v")
        assert obs.lag_held_slots.value == 2           # 1/8 of 16 slots
    finally:
        eng.stop()
    installs = obs.snapshot_installs.value - before[0]
    serviced = obs.need_host_seconds.count - before[2]
    assert installs == eng.snap_installs >= 1
    assert 1 <= serviced <= installs
    assert obs.need_host_seconds.sum > before[3]
    assert obs.lag_releases.value - before[1] >= 2
    assert full_rounds() - before[4] >= 2 * serviced
    _, host, _, _ = trace_reduce.read_xplane(trace_reduce.find_xplane(out))
    surgeries = [h for h in host if h[0] == "etcd.round.need_host"]
    tails = [h for h in host if h[0] == "etcd.round.tail"]
    assert len(surgeries) == serviced
    for _, s, e in surgeries:
        assert any(ts <= s and e <= te for _, ts, te in tails)
    # the fault map's upkeep and upload: inside `stage`, once a round
    maps = [h for h in host if h[0] == "etcd.round.fault_map"]
    stages = [h for h in host if h[0] == "etcd.round.stage"]
    assert len(maps) >= len(tails) - 1 and len(maps) >= 120
    for _, s, e in maps:
        assert any(ts <= s and e <= te for _, ts, te in stages)


def test_the_surgery_moves_rows_and_its_three_parts_tile_its_time(tmp_path):
    """The need-host surgery reads and writes the flagged groups' rows, a
    fixed number a pass whatever G: one blocking read of the six progress
    fields' rows and thirteen uploads (the indices and twelve fields'
    rows), counted to the byte; a pass over no group (how the programs are
    built before the first round) leaves the state as it is; and over a run
    with installs the three parts' sums add up to
    etcd_engine_need_host_seconds' sum, one observation of each a serviced
    round."""
    from etcd_tpu.server import obs
    from etcd_tpu.server.engine import MultiEngine, NEED_HOST_GROUPS as K
    eng = MultiEngine(make_cfg(tmp_path / "d", lag_share=0.125,
                               lag_hold_rounds=40, lag_seed=7))
    if not eng.obs.enabled:
        eng.stop()
        pytest.skip("ETCD_TPU_OBS=off")
    P, W = eng.cfg.peers, eng.cfg.window
    parts = {p: obs.need_host_part.labels(p) for p in obs.NEED_HOST_PARTS}
    before = (obs.need_host_seconds.count, obs.need_host_seconds.sum,
              {p: (h.count, h.sum) for p, h in parts.items()},
              obs.h2d_syncs.value, obs.h2d_bytes.value)
    try:
        run_until(eng, lambda: all_led(eng), msg="leaders")
        was = {f: np.asarray(getattr(eng.st, f)).copy()
               for f in eng.st._fields}
        eng._d2h_n = eng._d2h_b = eng._h2d_n = eng._h2d_b = 0
        assert eng._need_host_pass(np.zeros(0, np.int64))[0] == 0
        for f, a in was.items():
            assert np.array_equal(np.asarray(getattr(eng.st, f)), a), f
        assert eng._d2h_n == 1 and eng._h2d_n == 1 + len(
            kernel.NEED_HOST_WRITE) == 13
        assert eng._d2h_b == K * (3 * P * P * 4 + P * P + 2 * P * 4)
        assert eng._h2d_b == K * (4 + 7 * P * 4 + P * W * 4
                                  + 3 * P * P * 4 + P * P)
        rounds = eng.round_no
        for i in range(60):
            put(eng, i % 4, f"/k{i // 4}", "v")
            put(eng, 0, f"/hot{i}", "v")
        rounds = eng.round_no - rounds
    finally:
        eng.stop()
    assert eng.snap_installs >= 1
    serviced = obs.need_host_seconds.count - before[0]
    total = obs.need_host_seconds.sum - before[1]
    assert serviced >= 1 and total > 0
    tiled = 0.0
    for p, h in parts.items():
        assert h.count - before[2][p][0] == serviced, p
        assert h.sum - before[2][p][1] > 0, p
        tiled += h.sum - before[2][p][1]
    assert abs(tiled - total) <= 1e-9 + 1e-6 * total
    # every round uploads the hold map, a round that staged proposals its
    # one staged array besides (tests/test_round_dispatch.py counts them a
    # round); a serviced round the surgery's thirteen more
    assert obs.h2d_syncs.value - before[3] >= rounds + 13 * serviced
    assert obs.h2d_bytes.value - before[4] >= rounds * eng.cfg.groups * P


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


def test_the_three_flags_serve_installs_and_survive_sigkill(tmp_path):
    """Through `python -m etcd_tpu`: the deployment stated on the command
    line holds followers, installs the ones that fall out of the ring,
    exports the four series, and serves every acknowledged write after a
    real SIGKILL."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    cmd = [sys.executable, "-m", "etcd_tpu", "--engine-groups", "4",
           "--engine-peers", "5", "--engine-window", "8",
           "--engine-lag-share", "0.125", "--engine-lag-hold-rounds", "64",
           "--engine-lag-seed", "3", "--data-dir", str(tmp_path / "d"),
           "--listen-client-urls", base]
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
        end = time.time() + 120
        i = 0
        installs = 0
        while time.time() < end and (installs < 2 or i < 200):
            g = i % 4
            code, body = _http("PUT", f"{base}/tenants/{g}/v2/keys/k{i}",
                               {"value": f"v{i}"})
            if code in (200, 201):
                acked[(g, f"k{i}")] = f"v{i}"
            i += 1
            if i % 50 == 0:
                text = _http("GET", base + "/metrics")[1]
                installs = _metric(text,
                                   "etcd_engine_snapshot_installs_total")
        text = _http("GET", base + "/metrics")[1]
        assert _metric(text, "etcd_engine_snapshot_installs_total") >= 2
        assert _metric(text, "etcd_engine_lag_releases_total") >= 2
        assert _metric(text, "etcd_engine_lag_held_slots") == 2   # 1/8 of 16
        assert _metric(text, "etcd_engine_need_host_seconds_count") >= 1
        assert _metric(text, "etcd_engine_need_host_seconds_sum") > 0
        full = [ln for ln in text.splitlines() if ln.startswith(
            'etcd_engine_readback_rounds_total{kind="full"}')]
        assert float(full[0].split()[1]) >= 4       # two rounds an install
        proc.send_signal(signal.SIGKILL)
        assert proc.wait(30) == -signal.SIGKILL
        proc = boot()
        assert len(acked) >= 200
        for (g, key), val in acked.items():
            for _ in range(12):
                # (the read step is compiled at the first quorum read: the
                # engine answers "timed out", errorCode 300, meanwhile)
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


def test_the_flags_are_refused_where_they_would_risk_a_quorum():
    from etcd_tpu.etcdmain.config import ConfigError, parse_args
    ok = parse_args(["--engine-groups", "4", "--engine-lag-share", "0.05",
                     "--engine-lag-hold-rounds", "256",
                     "--engine-lag-seed", "35"], env={})
    assert (ok.engine_lag_share, ok.engine_lag_hold_rounds,
            ok.engine_lag_seed) == (0.05, 256, 35)
    off = parse_args(["--engine-groups", "4"], env={})
    assert off.engine_lag_share == 0.0
    for argv in (["--engine-lag-share", "0.3"],
                 ["--engine-lag-share", "-0.1"],
                 ["--engine-lag-share", "0.05", "--engine-peers", "2"],
                 ["--engine-lag-share", "0.05",
                  "--engine-lag-hold-rounds", "0"]):
        with pytest.raises(ConfigError):
            parse_args(["--engine-groups", "4"] + argv, env={})
    assert parse_args(["--engine-groups", "4"], env={
        "ETCD_ENGINE_LAG_SHARE": "0.1"}).engine_lag_share == 0.1


# ---------------------------------------------------------------------------
# on a mesh the hold is sharded like the state
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device CPU mesh")
def test_on_a_mesh_the_hold_is_sharded_like_the_state(tmp_path):
    from etcd_tpu.parallel.mesh import flag_sharding, make_mesh
    from etcd_tpu.server.engine import MultiEngine
    kw = dict(groups=8, lag_share=0.125, lag_hold_rounds=24, lag_seed=5,
              pipeline_applies=False)
    mesh = make_mesh(jax.devices()[:4], peers_axis=1)
    engs = [MultiEngine(make_cfg(tmp_path / "mesh", mesh=mesh, **kw)),
            MultiEngine(make_cfg(tmp_path / "one", **kw))]
    try:
        held = engs[0]._lag_hold()
        assert held.sharding == flag_sharding(mesh)
        assert held.sharding == engs[0].st.state.sharding
        from etcd_tpu.server.request import Request
        for r in range(140):
            # the same script, round for round: one write to group 0 in
            # each of the first 100 rounds, queued as the front queues it
            for eng in engs:
                if r < 100:
                    rid = eng.reqid.next()
                    rq = Request(method="PUT", path=f"/k{r}", val="v", id=rid)
                    eng.wait.register(rid)
                    with eng._lock:
                        eng._pending[0].append(
                            (rid, bytes([0]) + rq.encode(), rq))
                        eng._dirty.add(0)
                eng.run_round()
        assert all_led(engs[0]) and all_led(engs[1])
        assert engs[0].snap_installs == engs[1].snap_installs >= 1
        assert engs[0].round_no == engs[1].round_no
        for name in ("h_term", "h_commit", "h_last", "h_ring", "h_state"):
            assert np.array_equal(getattr(engs[0], name),
                                  getattr(engs[1], name)), name
        assert engs[0].st.state.sharding == flag_sharding(mesh)
    finally:
        for eng in engs:
            eng.stop()


if __name__ == "__main__":
    if sys.argv[1:] != ["--record"]:
        sys.exit("usage: python tests/test_lagging_followers.py --record  "
                 "(from the root of a checkout of the PARENT commit, with "
                 "this file copied into its tests/)")
    print(json.dumps(_record(), indent=1))
