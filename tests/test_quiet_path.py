"""The quiescent fast path (kernel.step_routed_auto) must be TRAJECTORY-
IDENTICAL to the full kernel: its on-device predicate may only select the
one-pass message phase when that phase is bit-exact with the P sequential
passes, so stepping the same schedule through both functions — elections,
proposals, partitions, re-elections — must agree on every state field and
the routed inbox after every round.

The predicate is folded group by group (kernel._quiet_pred); a hop with any
busy group takes the sequential passes for all, by rank and not by sender
(kernel._ranked_msgs: as many passes as the busiest receiver holds messages
that need one): test_the_step_programs_equal_always_full holds the three
step programs, with and without the hold and the down map, to the P passes
by sender (kernel._full_msgs) on every hop; test_the_passes_by_rank_equal_
the_passes_by_sender holds _ranked_msgs to _full_msgs phase by phase and
counts its passes; test_the_one_pass_is_exact_for_every_quiet_group holds
the predicate to its word: the one pass equals the P passes on the rows of
each group called quiet while other groups elect; test_two_leader_rows_...
pins what the predicate no longer asks (one LEADER row a group).
"""
import functools
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from etcd_tpu.ops import kernel
from etcd_tpu.ops.state import (F_COMMIT, F_HINT, F_INDEX, F_LOGTERM, F_NENT,
                                F_REJECT, F_TERM, F_TYPE, LEADER,
                                M_VOTE_RESP, N_FIXED_FIELDS, KernelConfig,
                                init_state)


def _fields(st):
    return {k: np.asarray(v) for k, v in st._asdict().items()}


def _assert_same(sa, sb, ia, ib, r):
    fa, fb = _fields(sa), _fields(sb)
    for k in fa:
        assert np.array_equal(fa[k], fb[k]), \
            f"round {r}: field {k} diverged\n{fa[k]}\n{fb[k]}"
    assert np.array_equal(np.asarray(ia), np.asarray(ib)), \
        f"round {r}: inbox diverged"


def test_auto_matches_full_trajectory():
    G, P = 8, 5
    cfg = KernelConfig(groups=G, peers=P, window=8, max_ents=2,
                       election_tick=10, heartbeat_tick=3)
    rng = np.random.default_rng(7)

    st_f = init_state(cfg, stagger=True)
    st_a = init_state(cfg, stagger=True)
    # Separate buffers: the stepping functions donate their inputs.
    in_f = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    in_a = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    zero = jnp.zeros(G, jnp.int32)

    quiet_rounds = 0
    drop = None
    for r in range(260):
        # Mid-run chaos: partition group 3's leader for 25 rounds to force
        # a re-election (auto must fall back to the full path), then heal.
        if r == 120 or r == 145:
            state = np.asarray(st_f.state)
            lead3 = int((state[3] == LEADER).argmax())
            m_to = np.ones((G, P, 1, 1), np.int32)
            m_from = np.ones((G, 1, P, 1), np.int32)
            if r == 120:
                m_to[3, lead3] = 0
                m_from[3, 0, lead3] = 0
                drop = jnp.asarray(m_to * m_from)
            else:
                drop = None

        # Proposals at the full-state's current leaders (identical states
        # => identical slots).
        state = np.asarray(st_f.state)
        has_lead = (state == LEADER).any(axis=1)
        slots = jnp.asarray((state == LEADER).argmax(axis=1)
                            .astype(np.int32))
        pc = jnp.asarray(
            (rng.integers(0, cfg.max_ents + 1, size=G)
             * has_lead).astype(np.int32)) if r % 3 else zero

        quiet_rounds += bool(kernel._quiet_pred(
            st_f, cfg, in_f, st_f.peer_mask, jnp.asarray(True)).all())

        st_f, in_f = kernel.step_routed(cfg, st_f, in_f, pc, slots,
                                        jnp.asarray(True))
        st_a, in_a, _ = kernel.step_routed_auto(cfg, st_a, in_a, pc, slots,
                                                jnp.asarray(True))
        if drop is not None:
            in_f = in_f * drop
            in_a = in_a * drop
        _assert_same(st_f, st_a, in_f, in_a, r)

    commit = np.asarray(st_f.commit)
    assert (commit.max(axis=1) > 10).all(), commit
    # The fast path must actually have engaged (and not always).
    assert quiet_rounds > 100, quiet_rounds
    assert quiet_rounds < 260, quiet_rounds


def test_multihop_equals_chained_single_hops():
    """hops=H must be bit-identical to H successive 1-hop invocations
    whose last H-1 carry no proposals and no tick — including under a
    drop mask, which the multi-hop kernel applies after every internal
    routing (the fault-injection contract)."""
    G, P, H = 6, 5, 3
    cfg = KernelConfig(groups=G, peers=P, window=8, max_ents=2,
                       election_tick=10, heartbeat_tick=3)
    rng = np.random.default_rng(11)

    st_m = init_state(cfg, stagger=True)
    st_s = init_state(cfg, stagger=True)
    in_m = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    in_s = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    zero = jnp.zeros(G, jnp.int32)
    false = jnp.asarray(False)

    drop = None
    for r in range(80):
        if r == 30:
            # Partition group 2's slot 1 (both directions).
            m_to = np.ones((G, P, 1, 1), np.int32)
            m_from = np.ones((G, 1, P, 1), np.int32)
            m_to[2, 1] = 0
            m_from[2, 0, 1] = 0
            drop = jnp.asarray(m_to * m_from)
        if r == 55:
            drop = None

        state = np.asarray(st_s.state)
        has_lead = (state == LEADER).any(axis=1)
        slots = jnp.asarray((state == LEADER).argmax(axis=1)
                            .astype(np.int32))
        pc = jnp.asarray(
            (rng.integers(0, cfg.max_ents + 1, size=G)
             * has_lead).astype(np.int32)) if r % 2 else zero

        st_m, in_m, _ = kernel.step_routed_auto(cfg, st_m, in_m, pc, slots,
                                                jnp.asarray(True), drop, H)
        for h in range(H):
            st_s, in_s, _ = kernel.step_routed_auto(
                cfg, st_s, in_s, pc if h == 0 else zero, slots,
                jnp.asarray(True) if h == 0 else false)
            if drop is not None:
                in_s = in_s * drop
        _assert_same(st_m, st_s, in_m, in_s, r)

    commit = np.asarray(st_m.commit)
    assert (commit.max(axis=1) > 10).all(), commit


def test_multihop_commits_proposal_in_one_round():
    """With hops=3 a proposal staged at an established leader must be
    COMMITTED by the same invocation's readback (the ack-latency
    contract the engine's cfg.hops relies on)."""
    G, P = 4, 5
    cfg = KernelConfig(groups=G, peers=P, window=8, max_ents=2,
                       election_tick=10, heartbeat_tick=3)
    st = init_state(cfg, stagger=True)
    inbox = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    zero = jnp.zeros(G, jnp.int32)
    # Let elections settle (multi-hop: one round does the whole exchange).
    for _ in range(6):
        st, inbox, _ = kernel.step_routed_auto(cfg, st, inbox, zero, zero,
                                               jnp.asarray(True), None, 3)
    state = np.asarray(st.state)
    assert ((state == LEADER).sum(axis=1) == 1).all()
    slots = jnp.asarray((state == LEADER).argmax(axis=1).astype(np.int32))
    commit0 = np.asarray(st.commit).max(axis=1)
    st, inbox, _ = kernel.step_routed_auto(cfg, st, inbox,
                                           jnp.full(G, 2, jnp.int32), slots,
                                           jnp.asarray(True), None, 3)
    commit1 = np.asarray(st.commit).max(axis=1)
    assert (commit1 >= commit0 + 2).all(), (commit0, commit1)


def test_slots_auto_matches_full_slots_kernel():
    """The multi-host step's auto+multi-hop variant must be trajectory-
    identical to the always-full step_routed_slots chained hop by hop
    (per-slot proposals + tick on hop 0 only)."""
    G, P, H = 6, 3, 3
    cfg = KernelConfig(groups=G, peers=P, window=8, max_ents=2,
                       election_tick=10, heartbeat_tick=3)
    rng = np.random.default_rng(13)

    st_a = init_state(cfg, stagger=True)
    st_f = init_state(cfg, stagger=True)
    in_a = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    in_f = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    zero_gp = jnp.zeros((G, P), jnp.int32)
    false = jnp.asarray(False)

    for r in range(60):
        state = np.asarray(st_f.state)
        cnt = np.zeros((G, P), np.int32)
        lead = (state == LEADER)
        cnt[lead] = rng.integers(0, cfg.max_ents + 1,
                                 size=int(lead.sum()))
        cnt_j = jnp.asarray(cnt)

        st_a, in_a = kernel.step_routed_slots_auto(
            cfg, st_a, in_a, cnt_j, jnp.asarray(True), None, H)
        for h in range(H):
            st_f, in_f = kernel.step_routed_slots(
                cfg, st_f, in_f, cnt_j if h == 0 else zero_gp,
                jnp.asarray(True) if h == 0 else false)
        _assert_same(st_a, st_f, in_a, in_f, r)

    commit = np.asarray(st_a.commit)
    assert (commit.max(axis=1) > 5).all(), commit


# ---------------------------------------------------------------------------
# The predicate, group by group
# ---------------------------------------------------------------------------

STEPS = ("step_routed_auto", "step_routed_compact", "step_routed_read_auto")


def _reference(name):
    # A function of its own: jit keys its traces on the function, and the
    # step's own must not be handed out for the patched one or back.
    def always_full(*args):
        return getattr(kernel, name).__wrapped__(*args)

    return jax.jit(always_full, static_argnums=kernel._STEP_STATICS[name])


_REF = {name: _reference(name) for name in STEPS}


def _always_full(name, *args):
    """The step program `name` with every group called busy and the passes
    made by sender: the P sequential passes over all G on every hop, what
    kernel.step is for one hop (the program is traced inside the patch;
    nothing else is)."""
    def none_quiet(st, *_):
        return jnp.zeros(st.term.shape[0], bool)

    with mock.patch.object(kernel, "_quiet_pred", none_quiet), \
            mock.patch.object(kernel, "_ranked_msgs", kernel._full_msgs):
        return _REF[name](*args)


def _assert_same_outputs(got, want, r):
    """Every output of a step program but the last (hop_stats)."""
    _assert_same(got[0], want[0], got[1], want[1], r)
    for i, (a, b) in enumerate(zip(got[2:-1], want[2:-1])):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"round {r}: output {2 + i} diverged"


def _leaders_by_term(st):
    lt = np.where(np.asarray(st.state) == LEADER, np.asarray(st.term), 0)
    return lt.argmax(axis=1), lt.max(axis=1)


G3 = 12
CUT = (1, 6, 9)         # the groups whose leader is cut off mid-run
HELD = (2, 6)           # the groups with a follower held mid-run
STRAY_AT, CUT_AT, HEAL_AT, END = 40, 46, 86, 110


def _script(cfg, hold, down, stray):
    """A seeded script in which three groups lose their leader and elect
    while the others go on taking proposals, followers are held and
    released and, in a quiet round, `stray` groups are handed a vote
    response nobody asked for (to a follower, from a follower, at the
    receiver's term: the P passes ignore it, the predicate may not). The
    cut is the down map where the program takes one, else the drop mask
    built from it. Yields (round, the inbox to use, the step's arguments
    from prop_count on); send() it the reference's (state, inbox)."""
    P = cfg.peers
    rng = np.random.default_rng(40)
    st = init_state(cfg, stagger=True)
    inbox = jnp.zeros((G3, P, P, cfg.fields), jnp.int32)
    none = np.zeros((G3, P), bool)
    cut = held = none
    for r in range(END):
        slots, terms = _leaders_by_term(st)
        if r == CUT_AT:
            cut = none.copy()
            cut[list(CUT), slots[list(CUT)]] = True
            held = none.copy()
            held[list(HELD), (slots[list(HELD)] + 1) % P] = True
        elif r == HEAL_AT:
            cut = held = none
        if r == STRAY_AT:
            box = np.array(inbox)
            for g in range(stray):
                p, q = (slots[g] + 1) % P, (slots[g] + 2) % P
                box[g, p, q, F_TYPE] = M_VOTE_RESP
                box[g, p, q, F_TERM] = int(np.asarray(st.term)[g, p])
            inbox = jnp.asarray(box)
        pc = jnp.asarray((rng.integers(0, cfg.max_ents + 1, size=G3)
                          * (terms > 0)).astype(np.int32)
                         if r % 3 else np.zeros(G3, np.int32))
        cut_d = jnp.asarray(cut)
        st, inbox = yield r, st, inbox, (
            pc, jnp.asarray(slots.astype(np.int32)), jnp.asarray(True),
            None if down else kernel.down_drop_mask(cut_d),
            jnp.asarray(held) if hold else None, cut_d if down else None)


# (program, P, hops, hold, down, by_sender): every P, both hop counts, every
# program, with and without each map; and each program as a mesh builds it.
PROGRAMS = [
    ("step_routed_auto", 3, 1, False, False, False),
    ("step_routed_auto", 5, 1, True, False, False),
    ("step_routed_auto", 7, 1, False, True, False),
    ("step_routed_compact", 7, 3, False, True, False),
    ("step_routed_read_auto", 5, 3, True, False, False),
    ("step_routed_auto", 3, 3, True, True, False),
    ("step_routed_compact", 3, 1, False, False, False),
    ("step_routed_read_auto", 3, 3, False, True, False),
    ("step_routed_auto", 5, 3, False, True, True),
    ("step_routed_compact", 5, 3, True, False, True),
    ("step_routed_read_auto", 7, 3, True, True, True),
]


@pytest.mark.parametrize("name,P,hops,hold,down,by_sender", PROGRAMS)
def test_the_step_programs_equal_always_full(name, P, hops, hold, down,
                                             by_sender):
    """After every round of the script the program that chooses per hop,
    and on a busy hop makes its passes by rank, equals the one that makes
    the P passes by sender on every hop, output for output; both paths
    run, the stray round is counted busy group for group, and a busy hop
    makes at most P passes, most of them one or none. Built by_sender (a
    mesh's programs) a busy hop makes the P passes and a quiet one none."""
    cfg = KernelConfig(groups=G3, peers=P, window=8, max_ents=2,
                       election_tick=10, heartbeat_tick=3)
    script = _script(cfg, hold, down, stray=5)
    r, st_f, in_f, (pc, ps, tick, drop, held, cut) = next(script)
    st_a = st_f
    quiet_hops = busy_hops = passes = 0
    while True:
        args = (pc, ps, tick, drop, hops, held, cut)
        want = _always_full(name, cfg, st_f, in_f, *args)
        got = getattr(kernel, name)(cfg, st_a, in_f, *args, by_sender)
        _assert_same_outputs(got, want, r)
        hop_busy, hop_passes = np.asarray(got[-1]).tolist()
        assert np.asarray(want[-1]).tolist() == [[G3] * hops, [P] * hops]
        if r == STRAY_AT:
            assert hop_busy[0] == 5, hop_busy
        assert all(n <= P and (b > 0 or n == 0)
                   for b, n in zip(hop_busy, hop_passes)), got[-1]
        if by_sender:
            assert hop_passes == [P * (b > 0) for b in hop_busy], got[-1]
        busy_hops += sum(n > 0 for n in hop_busy)
        quiet_hops += sum(n == 0 for n in hop_busy)
        passes += sum(hop_passes)
        st_a = got[0]
        try:
            r, st_f, in_f, (pc, ps, tick, drop, held, cut) = script.send(
                want[:2])
        except StopIteration:
            break
    assert busy_hops > 10 and quiet_hops > 10
    if not by_sender:
        assert 0 < passes < 2 * busy_hops, (passes, busy_hops)
    assert (np.asarray(st_a.commit).max(axis=1) > 10).all()


@functools.partial(jax.jit, static_argnums=0)
def _phases(cfg, st, inbox, tick):
    """(quiet (G,) as _hops asks it, the state and responses of the one
    pass, those of the P passes by sender, those of the passes by rank and
    their count), each message phase run where _step_body runs it: behind
    the hop's tick."""
    active = kernel.active_mask(st)
    quiet = kernel._quiet_pred(st, cfg, inbox, active, tick)
    st = st._replace(ack_age=jnp.minimum(st.ack_age + 1, 1 << 20))
    st, _, _ = kernel._tick(st, cfg, active, tick)
    return (quiet, kernel._quiet_msgs(st, cfg, inbox, active)[:2],
            kernel._full_msgs(st, cfg, inbox, active)[:2],
            kernel._ranked_msgs(st, cfg, inbox, active))


@pytest.mark.parametrize("stray", [0, 1, 5], ids=lambda n: f"stray{n}")
@pytest.mark.parametrize("P,hold,down", [
    (3, False, False), (5, True, False), (7, False, True), (5, True, True)])
def test_the_one_pass_is_exact_for_every_quiet_group(P, hold, down, stray):
    """What a choice made per group would rest on: in every round of the
    script, for every group the predicate calls quiet, the one pass leaves
    the group's rows and responses as the P passes do, while other groups
    elect; the stray round calls exactly the strayed groups busy."""
    cfg = KernelConfig(groups=G3, peers=P, window=8, max_ents=2,
                       election_tick=10, heartbeat_tick=3)
    script = _script(cfg, hold, down, stray)
    r, st, inbox, (pc, ps, tick, drop, held, cut) = next(script)
    mixed = 0
    while True:
        quiet, one, full, _ = _phases(cfg, st, inbox, tick)
        quiet = np.asarray(quiet)
        if r == STRAY_AT:
            assert (~quiet).sum() == stray and not quiet[:stray].any()
        mixed += 0 < quiet.sum() < G3
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(full)):
            assert np.array_equal(np.asarray(a)[quiet],
                                  np.asarray(b)[quiet]), r
        want = _always_full("step_routed_auto", cfg, st, inbox, pc, ps, tick,
                            drop, 1, held, cut)
        try:
            r, st, inbox, (pc, ps, tick, drop, held, cut) = script.send(
                want[:2])
        except StopIteration:
            break
    assert mixed > 20       # rounds with quiet and busy groups side by side


@pytest.mark.parametrize("stray", [0, 5], ids=lambda n: f"stray{n}")
@pytest.mark.parametrize("P,hold,down", [
    (3, False, False), (5, True, False), (7, False, True), (5, True, True),
    (7, True, False)])
def test_the_passes_by_rank_equal_the_passes_by_sender(P, hold, down, stray):
    """In every round of the script (quiet rounds, elections with one and
    with several candidates, the cut-off leaders' return, held followers'
    catch-up, stray vote responses) the message phase by rank leaves every
    row and every response as the P passes by sender do, in at most P
    passes; a round in which nobody elects needs one (each follower's
    append) or none, and over the script the mean is well under two."""
    cfg = KernelConfig(groups=G3, peers=P, window=8, max_ents=2,
                       election_tick=10, heartbeat_tick=3)
    script = _script(cfg, hold, down, stray)
    r, st, inbox, (pc, ps, tick, drop, held, cut) = next(script)
    counts = []
    while True:
        quiet, _, full, ranked = _phases(cfg, st, inbox, tick)
        for a, b in zip(jax.tree.leaves(ranked[:2]), jax.tree.leaves(full)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), r
        n = int(ranked[2])
        assert n <= P and (n <= 1 or not np.asarray(quiet).all()), (r, n)
        counts.append(n)
        want = _always_full("step_routed_auto", cfg, st, inbox, pc, ps, tick,
                            drop, 1, held, cut)
        try:
            r, st, inbox, (pc, ps, tick, drop, held, cut) = script.send(
                want[:2])
        except StopIteration:
            break
    assert max(counts) >= (2 if P > 3 else 1) and np.mean(counts) < 2, counts


def _any_state_and_inbox(cfg, seed, density):
    """A state and an inbox no run would reach, every field drawn at
    random: every kind of message at every kind of receiver, below, at and
    above its term, in any mix (no message to oneself), the leaders' own
    progress column as every path that makes or extends a leader leaves
    it (match = last index: _quiet_msgs rests on it too)."""
    G, P, W, E = cfg.groups, cfg.peers, cfg.window, cfg.max_ents
    rng = np.random.default_rng(seed)
    draw = lambda lo, hi, *shape: rng.integers(lo, hi, shape).astype(np.int32)
    term, last, state = draw(1, 4, G, P), draw(0, 2 * W, G, P), draw(0, 3, G, P)
    own = np.eye(P, dtype=bool)[None] & (state == LEADER)[..., None]
    st = init_state(cfg, stagger=True)._replace(
        term=term, vote=draw(0, P + 1, G, P), lead=draw(0, P + 1, G, P),
        commit=np.minimum(last, draw(0, 2 * W, G, P)), state=state,
        elapsed=draw(0, 12, G, P), last_index=last,
        log_term=np.minimum(draw(0, 4, G, P, W), term[..., None]),
        match=np.where(own, last[..., None], draw(0, 2 * W, G, P, P)),
        next=np.where(own, last[..., None] + 1, draw(1, 2 * W, G, P, P)),
        pr_state=draw(0, 2, G, P, P), paused=draw(0, 2, G, P, P) > 0,
        ack_age=draw(0, 12, G, P, P), votes=draw(0, 3, G, P, P),
        peer_mask=rng.random((G, P)) < 0.9)
    there = (rng.random((G, P, P)) < density) & ~np.eye(P, dtype=bool)[None]
    inbox = np.zeros((G, P, P, cfg.fields), np.int32)
    inbox[..., F_TYPE] = draw(1, 7, G, P, P)
    inbox[..., F_TERM] = term[..., None] + rng.choice([-1, 0, 0, 0, 1],
                                                      (G, P, P))
    inbox[..., F_INDEX] = np.maximum(0, last[..., None] + draw(-3, 3, G, P, P))
    inbox[..., F_LOGTERM] = draw(0, 4, G, P, P)
    inbox[..., F_COMMIT] = draw(0, 2 * W, G, P, P)
    inbox[..., F_REJECT] = draw(0, 2, G, P, P)
    inbox[..., F_HINT] = draw(0, 2 * W, G, P, P)
    inbox[..., F_NENT] = draw(0, E + 1, G, P, P)
    inbox[..., N_FIXED_FIELDS:] = draw(1, 4, G, P, P, E)
    return (jax.tree.map(jnp.asarray, st),
            jnp.asarray(inbox * there[..., None]))


@functools.partial(jax.jit, static_argnums=0)
def _ranked_and_full(cfg, st, inbox):
    active = kernel.active_mask(st)
    return (kernel._ranked_msgs(st, cfg, inbox, active),
            kernel._full_msgs(st, cfg, inbox, active))


@pytest.mark.parametrize("density", [0.15, 0.5, 0.9])
@pytest.mark.parametrize("P", [3, 5, 7])
def test_the_passes_by_rank_equal_the_passes_by_sender_on_any_inbox(
        P, density):
    """What no script reaches: on random states and inboxes (256 groups a
    seed: every kind of message at every kind of receiver, stale, current
    and of a higher term, one to P - 1 a receiver) the passes by rank, with
    what they take in one shot (a leader's responses, a candidate's tally,
    a voter's ballot, the stale dropped), leave every row and response as
    the P passes by sender do, in at most P - 1 passes."""
    cfg = KernelConfig(groups=256, peers=P, window=8, max_ents=2,
                       election_tick=10, heartbeat_tick=3)
    for seed in range(4):
        st, inbox = _any_state_and_inbox(cfg, seed, density)
        ranked, full = _ranked_and_full(cfg, st, inbox)
        names = st._fields + ("resp",)
        for name, a, b in zip(names, jax.tree.leaves(ranked[:2]),
                              jax.tree.leaves(full[:2])):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (seed, name)
        assert 1 <= int(ranked[2]) <= P - 1


def test_two_leader_rows_of_different_terms_equal_the_full_path():
    """What the predicate no longer asks: one LEADER row a group. A leader
    cut off from its peers stays LEADER in its old term beside its
    successor (no check-quorum); every message left in the group carries
    its receiver's term and a term has one leader, so a follower still
    holds at most one append-or-heartbeat and the one pass is exact: with
    appends in flight the group is called quiet while the old row is down,
    and once the row is back (the stale leader's messages cross terms) it
    is busy until the row steps down; every round equals the P passes."""
    G, P = 4, 5
    cfg = KernelConfig(groups=G, peers=P, window=8, max_ents=2,
                       election_tick=10, heartbeat_tick=3)
    st_a = st_f = init_state(cfg, stagger=True)
    in_a = in_f = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    down = np.zeros((G, P), bool)
    two_quiet = two_up = 0
    for r in range(140):
        slots, terms = _leaders_by_term(st_f)
        if r == 40:
            old = slots[1]
            down[1, old] = True
        elif r == 100:
            down[1, old] = False
        state = np.asarray(st_f.state)
        two = (state[1] == LEADER).sum() == 2
        if two:
            assert len(set(np.asarray(st_f.term)[1][state[1] == LEADER])) == 2
        pc = jnp.asarray(np.where(terms > 0, 1 + r % 2, 0).astype(np.int32))
        args = (pc, jnp.asarray(slots.astype(np.int32)), jnp.asarray(True),
                None, 1, None, jnp.asarray(down))
        want = _always_full("step_routed_auto", cfg, st_f, in_f, *args)
        got = kernel.step_routed_auto(cfg, st_a, in_a, *args)
        _assert_same_outputs(got, want, r)
        st_f, in_f = want[:2]
        st_a, in_a = got[:2]
        if two and down.any():
            two_quiet += int(got[-1][0, 0]) == 0
        two_up += bool(two and not down.any())
    # both halves ran: rounds with two LEADER rows that took the one pass,
    # and rounds with both rows up
    assert two_quiet > 20 and two_up >= 1, (two_quiet, two_up)
    assert (np.asarray(st_a.state)[1] == LEADER).sum() == 1
