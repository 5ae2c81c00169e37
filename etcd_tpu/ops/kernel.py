"""The batched consensus kernel: G Raft groups × P peer slots stepped as one
XLA program.

This replaces the reference's per-group goroutine loops (raft.MultiNode,
raft/multinode.go:166-322 — including the O(groups) tick scan flagged at
multinode.go:265-267) with dense array transforms:

- tick scan            -> vectorized elapsed/timeout update over (G, P)
- Step(m) per message  -> masked updates, one unrolled pass per sender slot
- maybeCommit sort     -> lax.top_k over the peers axis (raft/raft.go:323-332)
- bcastAppend/sendAppend -> gap-driven send assembly over the (G, P, P)
                            progress matrix (raft/raft.go:239-321)
- message routing      -> a transpose of the (G, P_from, P_to) outbox
                          (single host) or an all_to_all over the "peers"
                          mesh axis (distributed; etcd_tpu/parallel)

Design rules (why this diverges from a line-for-line port):
1. Message LOSS is always legal in Raft, so the dense mailbox keeps exactly
   one slot per (sender, target) pair and drops lower-priority collisions
   (response > append > heartbeat > vote) — the protocol retries via
   timeouts. This is what makes the mailbox a fixed-shape tensor.
2. Sends are gap-driven rather than event-driven: at the end of each step a
   leader emits an append to any unpaused follower whose `next` lags. This
   subsumes the reference's bcast-on-propose / send-on-ack triggers and
   needs no per-event control flow.
3. Rare/heavy transitions (snapshot install+send, conf change application,
   appends below the device log window) escape to the host scalar oracle
   (etcd_tpu/raft/core.py) via `need_host` flags; the hot path stays static.
4. Flow control is entries-in-flight (`next-1-match >= flow_window`) instead
   of the reference's message-count ring (progress.go:172-237): with one
   coalesced append per (peer, round), window-by-entries is the natural
   dense form.

Election timing is bit-identical to the scalar oracle: same xorshift32
streams, same draw points (reference raft.go:765-771 semantics).
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple


def _donate_at_import(argnums):
    """donate_argnums for the module-level jitted steps, decided at
    IMPORT time: XLA:CPU has a donated-buffer race (see "CPU donation
    hazard" below), so when the process has already pinned a non-TPU
    platform via JAX_PLATFORMS (the test suite, ./test, CI, Procfile
    all export cpu) the decorators skip donation entirely — this is
    what keeps kernel-direct tests (and the whole shared pytest heap)
    safe. When JAX_PLATFORMS is unset the platform isn't knowable
    without initializing the backend (illegal at import: multihost
    scripts set distributed state after importing this module), so the
    decorators keep donation and serving engines re-decide per live
    backend via donate_safe(). ETCD_TPU_DONATE=on|off
    overrides both layers."""
    mode = os.environ.get("ETCD_TPU_DONATE", "auto")
    if mode in ("on", "1"):
        return tuple(argnums)
    if mode in ("off", "0"):
        return ()
    plats = os.environ.get("JAX_PLATFORMS", "").lower()
    if plats and "tpu" not in plats:
        return ()
    return tuple(argnums)

import jax
import jax.numpy as jnp

from etcd_tpu.ops.state import (CANDIDATE, FOLLOWER, F_COMMIT, F_HINT,
                                F_INDEX, F_LOGTERM, F_NENT, F_REJECT, F_TERM,
                                F_TYPE, GroupState, KernelConfig, LEADER,
                                M_APP, M_APP_RESP, M_HB, M_HB_RESP, M_NONE,
                                M_VOTE, M_VOTE_RESP, N_FIXED_FIELDS,
                                NH_SNAP, NH_VIOLATION, PR_PROBE, PR_REPLICATE,
                                active_mask, in_window, quorum, ring_lookup,
                                term_at, xorshift32)


def _flag(need_host: jax.Array, mask: jax.Array, bit: int) -> jax.Array:
    """OR an NH_* bit into the (G, P) need_host bitmask where mask holds."""
    return need_host | jnp.where(mask, jnp.int32(bit), 0)


def _where(m, a, b):
    return jnp.where(m, a, b)


def _last_term(st: GroupState, cfg: KernelConfig) -> jax.Array:
    return term_at(st, cfg, st.last_index)


def _set_self_progress(st: GroupState) -> GroupState:
    """Leader's own match tracks its last index (reference appendEntry ->
    prs[self].maybeUpdate)."""
    G, P = st.term.shape
    eye = jnp.eye(P, dtype=bool)[None, :, :]
    is_ldr = (st.state == LEADER)[..., None]
    match = _where(eye & is_ldr, st.last_index[..., None], st.match)
    nxt = _where(eye & is_ldr, st.last_index[..., None] + 1, st.next)
    return st._replace(match=match, next=nxt)


def _become_follower(st: GroupState, mask: jax.Array, new_term: jax.Array,
                     new_lead: jax.Array) -> GroupState:
    """Masked becomeFollower(term, lead) (reference raft.go:384-391 +
    reset()); vote cleared only when the term actually changes."""
    term_changed = mask & (new_term != st.term)
    return st._replace(
        term=_where(mask, new_term, st.term),
        vote=_where(term_changed, 0, st.vote),
        lead=_where(mask, new_lead, st.lead),
        state=_where(mask, FOLLOWER, st.state),
        elapsed=_where(mask, 0, st.elapsed),
        votes=_where(mask[..., None], 0, st.votes),
    )


def _append_noop_and_lead(st: GroupState, cfg: KernelConfig,
                          win: jax.Array) -> GroupState:
    """Masked becomeLeader: reset progress, append the no-op entry of the new
    term (reference raft.go:406-427)."""
    G, P = st.term.shape
    new_last = st.last_index + 1
    # The no-op entry of the new term, via the shared ring-write primitive.
    st = _write_terms(st, cfg, anchor=st.last_index,
                      terms=st.term[..., None], lo=new_last,
                      count=win.astype(jnp.int32), mask=win)
    st = st._replace(
        state=_where(win, LEADER, st.state),
        lead=_where(win, jnp.arange(1, P + 1, dtype=jnp.int32)[None, :],
                    st.lead),
        elapsed=_where(win, 0, st.elapsed),
        last_index=_where(win, new_last, st.last_index),
        # Progress reset: probe from the PRE-no-op last+1 (= new_last), as
        # the reference's reset() runs before appendEntry — so the no-op
        # itself replicates to quiescent followers.
        match=_where(win[..., None], 0, st.match),
        next=_where(win[..., None], new_last[..., None], st.next),
        pr_state=_where(win[..., None], PR_PROBE, st.pr_state),
        paused=_where(win[..., None], False, st.paused),
        ack_age=_where(win[..., None], 0, st.ack_age),
    )
    return _set_self_progress(st)


# ---------------------------------------------------------------------------
# Phase 1: tick
# ---------------------------------------------------------------------------

def _tick(st: GroupState, cfg: KernelConfig, active: jax.Array,
          tick: jax.Array) -> Tuple[GroupState, jax.Array, jax.Array]:
    """Advance the logical clock one tick for every instance where the
    scalar `tick` flag is set (masked arithmetic, no lax.cond branch — the
    cond's per-field copies showed up in the TPU profile). Returns
    (state, hb_fire_term, vote_fire_term): (G, P) int32 arrays holding the
    term at which a heartbeat broadcast / vote broadcast was staged this
    round (0 = none) — the term lets send assembly cancel the broadcast if a
    same-round message bumped us off that term."""
    G, P = st.term.shape
    is_ldr = st.state == LEADER
    elapsed = st.elapsed + tick.astype(jnp.int32)

    # Leaders: heartbeat timeout (reference tickHeartbeat raft.go:376-382).
    hb_timeout = tick & active & is_ldr & (elapsed >= cfg.heartbeat_tick)
    hb_fire_term = _where(hb_timeout, st.term, 0)

    # Followers/candidates: randomized election timeout (reference
    # tickElection + isElectionTimeout raft.go:362-373,765-771).
    d = elapsed - cfg.election_tick
    draw = tick & active & ~is_ldr & (d >= 0)
    prng = _where(draw, xorshift32(st.prng), st.prng)
    timeout = draw & (d > (prng % jnp.uint32(cfg.election_tick)).astype(jnp.int32))

    st = st._replace(
        prng=prng,
        elapsed=_where(hb_timeout | timeout, 0, elapsed),
    )

    # Campaign (reference campaign() raft.go:429-443): term+1, vote self,
    # tally own vote; single-voter groups win instantly.
    camp = timeout
    self_id = jnp.arange(1, P + 1, dtype=jnp.int32)[None, :]
    votes = _where(camp[..., None], 0, st.votes)
    votes = _where(
        camp[..., None] & (jnp.arange(P)[None, None, :]
                           == jnp.arange(P)[None, :, None]),
        1, votes)
    st = st._replace(
        term=_where(camp, st.term + 1, st.term),
        vote=_where(camp, self_id, st.vote),
        lead=_where(camp, 0, st.lead),
        state=_where(camp, CANDIDATE, st.state),
        votes=votes,
        # reset() also clears progress; leaders-to-be re-reset on winning.
        paused=_where(camp[..., None], False, st.paused),
    )
    instant_win = camp & (quorum(st)[:, None] == 1)
    st = _append_noop_and_lead(st, cfg, instant_win)
    vote_fire_term = _where(camp & ~instant_win, st.term, 0)

    # Heartbeat broadcast resumes all paused probes (reference
    # bcastHeartbeat raft.go:313-321).
    st = st._replace(paused=_where(hb_timeout[..., None], False, st.paused))
    return st, hb_fire_term, vote_fire_term


# ---------------------------------------------------------------------------
# Phase 2: one sender slot's messages, for all instances at once
# ---------------------------------------------------------------------------

def _onehot(q: jax.Array, P: int) -> jax.Array:
    """(G, P, P) bool: the target column q[g, p] of each receiver's row."""
    return jnp.arange(P, dtype=jnp.int32)[None, None, :] == q[..., None]


def _col(x: jax.Array, q) -> jax.Array:
    """x[:, :, q] of a per-target (G, P, P) array. q is one slot for every
    receiver (an int: the passes by sender) or a (G, P) array, one slot a
    receiver (the passes by rank): a one-hot select there, not a gather
    (ring_lookup's reasoning)."""
    if isinstance(q, int):
        return x[:, :, q]
    oh = _onehot(q, x.shape[2])
    if x.dtype == jnp.bool_:
        return jnp.any(oh & x, axis=2)
    return jnp.sum(jnp.where(oh, x, 0), axis=2, dtype=x.dtype)


def _set_col(x: jax.Array, q, v: jax.Array) -> jax.Array:
    """x with x[:, :, q] = v, q as _col takes it."""
    if isinstance(q, int):
        return x.at[:, :, q].set(v)
    return jnp.where(_onehot(q, x.shape[2]), v[..., None], x)


def _step_msgs_from(st: GroupState, cfg: KernelConfig, q,
                    msg: jax.Array, active: jax.Array,
                    ) -> Tuple[GroupState, jax.Array]:
    """Process one message a receiver, `msg` (G, P, F) from sender `q`, on
    every instance; returns the updated state and the staged response
    (G, P, F) addressed back to q. `q` is an int (the inbox slot of one
    sender for all: _full_msgs) or a (G, P) int32 array (each receiver's
    own next sender: _ranked_msgs). Every update is local to the
    receiver's row.

    Mirrors raft.Step (reference raft.go:462-669) as masked dense updates.
    """
    G, P = st.term.shape
    F = cfg.fields
    mtype = msg[..., F_TYPE]
    mterm = msg[..., F_TERM]
    mindex = msg[..., F_INDEX]
    mlogterm = msg[..., F_LOGTERM]
    mcommit = msg[..., F_COMMIT]
    mreject = msg[..., F_REJECT]
    mhint = msg[..., F_HINT]
    mnent = msg[..., F_NENT]
    ent_terms = msg[..., N_FIXED_FIELDS:]

    has = active & (mtype != M_NONE)
    resp = jnp.zeros((G, P, F), jnp.int32)

    # -- term gate (reference raft.go:470-486) -----------------------------
    higher = has & (mterm > st.term)
    lead_on_higher = _where(mtype == M_VOTE, 0, q + 1)
    st = _become_follower(st, higher, mterm, lead_on_higher)
    live = has & (mterm == st.term)  # stale (lower-term) messages ignored

    is_f = st.state == FOLLOWER
    is_c = st.state == CANDIDATE
    is_l = st.state == LEADER

    # -- MsgApp / MsgHeartbeat demote same-term candidates (stepCandidate) --
    demote = live & is_c & ((mtype == M_APP) | (mtype == M_HB))
    st = _become_follower(st, demote, st.term, q + 1)
    is_f, is_c, is_l = (st.state == FOLLOWER, st.state == CANDIDATE,
                        st.state == LEADER)

    # -- MsgVote (uniform grant rule; reference stepFollower raft.go:636-647,
    #    leaders/candidates reject naturally because vote == self) ----------
    v = live & (mtype == M_VOTE)
    st, grant = _grant_vote(st, q, v, mindex, mlogterm, _last_term(st, cfg))
    resp = _stage(resp, v, M_VOTE_RESP, st.term, reject=~grant)

    # -- MsgVoteResp (reference stepCandidate raft.go:603-612) --------------
    st, win, lose = _tally_vote(st, q, live & is_c & (mtype == M_VOTE_RESP),
                                mreject)
    st = _append_noop_and_lead(st, cfg, win)
    st = _become_follower(st, lose, st.term, 0)
    is_f, is_c, is_l = (st.state == FOLLOWER, st.state == CANDIDATE,
                        st.state == LEADER)

    # -- MsgApp (reference handleAppendEntries raft.go:651-664) -------------
    a = live & (mtype == M_APP) & ~is_l
    st = st._replace(
        elapsed=_where(a, 0, st.elapsed),
        lead=_where(a, q + 1, st.lead),
    )
    below_commit = a & (mindex < st.commit)
    resp = _stage(resp, below_commit, M_APP_RESP, st.term,
                  index=st.commit)

    chk = a & ~below_commit
    prev_t = term_at(st, cfg, mindex)
    prev_in_win = in_window(st, cfg, mindex)
    # Below the device window (but >= commit): the host resolves it.
    escape = chk & ~prev_in_win & (mindex <= st.last_index)
    st = st._replace(need_host=_flag(st.need_host, escape, NH_SNAP))

    match_ok = chk & ~escape & prev_in_win & (prev_t == mlogterm)
    rej = chk & ~escape & ~match_ok
    resp = _stage(resp, rej, M_APP_RESP, st.term, index=mindex,
                  reject=True, hint=st.last_index)

    # Conflict scan + append over the E entry slots (reference
    # findConflict/truncateAndAppend log.go:98-123).
    E = cfg.max_ents
    idx_j = mindex[..., None] + 1 + jnp.arange(E, dtype=jnp.int32)[None, None]
    valid_j = jnp.arange(E)[None, None] < mnent[..., None]
    my_t = _terms_at_many(st, cfg, idx_j)
    mismatch = valid_j & (my_t != ent_terms)
    any_conf = match_ok & jnp.any(mismatch, axis=-1)
    first_j = jnp.argmax(mismatch, axis=-1)
    ci = _where(any_conf, mindex + 1 + first_j, 0)
    # Safety: conflicting with a committed entry is a protocol violation
    # (reference log.go maybeAppend panic); flag it distinctly so the host
    # dumps state and fails loudly instead of papering over it.
    st = st._replace(need_host=_flag(st.need_host,
                                     any_conf & (ci <= st.commit),
                                     NH_VIOLATION))

    do_append = any_conf
    st = _write_terms(st, cfg, anchor=mindex, terms=ent_terms, lo=ci,
                      count=mnent, mask=do_append)
    lastnewi = mindex + mnent
    old_last = st.last_index
    st = st._replace(
        last_index=_where(do_append, lastnewi, st.last_index))
    # A SHRINKING truncation strands ring slots: the discarded entries'
    # slots now alias indices W lower, which fall back INSIDE the valid
    # window — but those lower entries' true terms were overwritten long
    # ago. Zero the stranded slots so stale terms can never be read as
    # live ones (0 = unresolvable sentinel). The device itself only reads
    # terms at indices >= commit (all strands are strictly below commit:
    # the admission throttle keeps last-commit < W), but the host engine
    # diffs the whole ring into its WAL and must not record junk.
    shrink = do_append & (old_last > lastnewi)
    w_idx = jnp.arange(cfg.window, dtype=jnp.int32)[None, None, :]
    i_w = old_last[..., None] - jnp.mod(old_last[..., None] - w_idx,
                                        cfg.window)
    strand = shrink[..., None] & (i_w > lastnewi[..., None])
    st = st._replace(log_term=jnp.where(strand, 0, st.log_term))
    new_commit = jnp.maximum(st.commit,
                             jnp.minimum(mcommit, lastnewi))
    st = st._replace(commit=_where(match_ok, new_commit, st.commit))
    resp = _stage(resp, match_ok, M_APP_RESP, st.term, index=lastnewi)

    # -- MsgAppResp (reference stepLeader raft.go:514-546) ------------------
    ar = live & is_l & (mtype == M_APP_RESP)
    match_q = _col(st.match, q)
    next_q = _col(st.next, q)
    pr_q = _col(st.pr_state, q)
    paused_q = _col(st.paused, q)

    rej_resp = ar & (mreject != 0)
    # replicate: fall back to match+1 and probe (maybeDecrTo fast path)
    repl_rej = rej_resp & (pr_q == PR_REPLICATE) & (mindex > match_q)
    # probe: only the outstanding probe at next-1 counts
    probe_rej = rej_resp & (pr_q == PR_PROBE) & (next_q - 1 == mindex)
    next_q = _where(repl_rej, match_q + 1, next_q)
    next_q = _where(probe_rej,
                    jnp.maximum(jnp.minimum(mindex, mhint + 1), 1), next_q)
    pr_q = _where(repl_rej, PR_PROBE, pr_q)
    paused_q = _where(probe_rej, False, paused_q)

    ok_resp = ar & (mreject == 0)
    upd = ok_resp & (match_q < mindex)
    match_q = _where(upd, mindex, match_q)
    paused_q = _where(upd, False, paused_q)
    pr_q = _where(upd & (pr_q == PR_PROBE), PR_REPLICATE, pr_q)
    next_q = jnp.maximum(next_q, _where(ok_resp, mindex + 1, 0))

    st = st._replace(
        match=_set_col(st.match, q, match_q),
        next=_set_col(st.next, q, next_q),
        pr_state=_set_col(st.pr_state, q, pr_q),
        paused=_set_col(st.paused, q, paused_q),
        # Any append response (accept or reject) is replication-liveness
        # evidence from this target.
        ack_age=_set_col(st.ack_age, q,
                         _where(ar, 0, _col(st.ack_age, q))),
    )

    # -- MsgHeartbeat (reference handleHeartbeat raft.go:666-669) -----------
    h = live & (mtype == M_HB) & ~is_l
    st = st._replace(
        elapsed=_where(h, 0, st.elapsed),
        lead=_where(h, q + 1, st.lead),
        commit=_where(h, jnp.maximum(st.commit,
                                     jnp.minimum(mcommit, st.last_index)),
                      st.commit),
    )
    resp = _stage(resp, h, M_HB_RESP, st.term)

    # -- MsgHeartbeatResp: staleness-driven retransmission (reference
    #    stepLeader MsgHeartbeatResp -> sendAppend, raft.go:547-551).
    #    Gap-driven sends make the ordinary case a no-op, but appends can
    #    be lost (network drops, outbox slot collisions) with next already
    #    optimistically bumped — then nothing ever resends: match freezes
    #    whether unacked pinned at the flow window or the group just went
    #    idle. A heartbeat response while the target's append responses
    #    have been silent for > 2 heartbeat intervals pulls next back to
    #    match+1 so the gap-driven sender retransmits the window. The age
    #    gate keeps steady-state traffic (acks merely in flight) free of
    #    duplicate sends. --
    hrs = live & is_l & (mtype == M_HB_RESP)
    match_h = _col(st.match, q)
    next_h = _col(st.next, q)
    stale = (hrs & (_col(st.pr_state, q) == PR_REPLICATE)
             & (match_h < st.last_index)
             & (_col(st.ack_age, q) > 2 * cfg.heartbeat_tick + 2))
    st = st._replace(
        next=_set_col(st.next, q, _where(stale, match_h + 1, next_h)))
    return st, resp


def _grant_vote(st: GroupState, q, v: jax.Array, mindex: jax.Array,
                mlogterm: jax.Array, last_t: jax.Array
                ) -> Tuple[GroupState, jax.Array]:
    """MsgVote from sender `q` (as _col takes it) at the receivers `v`
    (G, P), which stand at the message's term: (state, grant). One grant
    rule for every role: leaders and candidates refuse because their vote
    is their own (reference stepFollower raft.go:636-647). `last_t` is
    the receivers' last log term."""
    up_to_date = (mlogterm > last_t) | ((mlogterm == last_t)
                                        & (mindex >= st.last_index))
    grant = v & ((st.vote == 0) | (st.vote == q + 1)) & up_to_date
    return st._replace(vote=_where(grant, q + 1, st.vote),
                       elapsed=_where(grant, 0, st.elapsed)), grant


def _tally_vote(st: GroupState, q, vr: jax.Array, mreject: jax.Array
                ) -> Tuple[GroupState, jax.Array, jax.Array]:
    """MsgVoteResp from sender `q` (as _col takes it) at the candidates
    `vr` (G, P): the first answer of a sender is recorded, and (state,
    win, lose) says whom the count now makes leader or follower
    (reference stepCandidate raft.go:603-612); the caller applies it."""
    first = _col(st.votes, q) == 0
    # int32 literals: under x64 test configs plain ints promote to int64
    # and the votes scatter would mix dtypes (FutureWarning today, error
    # in future jax).
    val = _where(mreject == 0, jnp.int32(1), jnp.int32(2))
    votes = _set_col(st.votes, q,
                     _where(vr & first, val, _col(st.votes, q)))
    granted = jnp.sum((votes == 1).astype(jnp.int32), axis=2)
    rejected = jnp.sum((votes == 2).astype(jnp.int32), axis=2)
    qr = quorum(st)[:, None]
    win = vr & (granted >= qr)
    return st._replace(votes=votes), win, vr & ~win & (rejected >= qr)


def _stage(resp: jax.Array, mask: jax.Array, mtype: int, term: jax.Array,
           index=None, reject=None, hint=None) -> jax.Array:
    """Write a response message into `resp` (G, P, F) where mask holds.
    Later stages win slot collisions, matching sequential Step semantics
    (each message produces at most one response in the scalar core)."""
    upd = resp
    upd = upd.at[..., F_TYPE].set(jnp.where(mask, mtype, upd[..., F_TYPE]))
    upd = upd.at[..., F_TERM].set(jnp.where(mask, term, upd[..., F_TERM]))
    if index is not None:
        upd = upd.at[..., F_INDEX].set(
            jnp.where(mask, index, upd[..., F_INDEX]))
    if reject is not None:
        rej = jnp.asarray(reject)
        upd = upd.at[..., F_REJECT].set(
            jnp.where(mask, rej.astype(jnp.int32), upd[..., F_REJECT]))
    if hint is not None:
        upd = upd.at[..., F_HINT].set(jnp.where(mask, hint, upd[..., F_HINT]))
    return upd


def _terms_at_many(st: GroupState, cfg: KernelConfig,
                   idx: jax.Array) -> jax.Array:
    """term_at for an extra trailing axis of indices: idx (G, P, E) ->
    terms (G, P, E); 0 outside the window / beyond last. The one-hot
    select-sum below IS the measured-fastest TPU formulation (it replaced
    the take_along_axis gathers that originally dominated the round). A
    Pallas kernel for this resolve was measured on a TPU: 2.3x faster in
    isolation but 9.3x SLOWER wired in here (the pallas_call boundary
    defeats the fusion this formulation exists for), so the jnp path
    stays; PERF.md section 6 keeps the finding."""
    slot = jnp.mod(idx, cfg.window)
    t = ring_lookup(st.log_term, slot)
    last = st.last_index[..., None]
    valid = (idx > last - cfg.window) & (idx <= last) & (idx >= 1)
    return jnp.where(valid, t, 0)


def _write_terms(st: GroupState, cfg: KernelConfig, anchor: jax.Array,
                 terms: jax.Array, lo: jax.Array, count: jax.Array,
                 mask: jax.Array) -> GroupState:
    """Write entry terms for the contiguous index range
    (max(lo, anchor+1) .. anchor+count] into the log ring, where entry
    anchor+1+j takes terms[..., j].

    Formulated ring-slot-wise (one gather + elementwise select over the W
    axis) instead of as a scatter: TPU scatters with computed indices
    serialize, and this runs on every message-phase pass. Each ring slot w
    maps to at most ONE index in the range (count <= E < W), namely
    j_w = (w - (anchor+1)) mod W.

    anchor/lo/count: (G, P); terms: (G, P, E); mask: (G, P).
    """
    W = cfg.window
    E = terms.shape[-1]
    w_idx = jnp.arange(W, dtype=jnp.int32)[None, None, :]
    j_w = jnp.mod(w_idx - (anchor[..., None] + 1), W)
    idx_w = anchor[..., None] + 1 + j_w
    write = (mask[..., None] & (j_w < count[..., None])
             & (idx_w >= lo[..., None]))
    val = ring_lookup(terms, jnp.minimum(j_w, E - 1))
    return st._replace(
        log_term=jnp.where(write, val, st.log_term))


# ---------------------------------------------------------------------------
# Phase 3: proposals
# ---------------------------------------------------------------------------

def _apply_proposals_slots(st: GroupState, cfg: KernelConfig,
                           cnt_gp: jax.Array,
                           active: jax.Array) -> GroupState:
    """Per-SLOT proposal admission for the multi-host engine: cnt_gp is
    (G, P), SHARDED like the state over the peers mesh axis — each host
    stages proposals only at its own local leader slots, so no replicated
    (and therefore cross-host-agreed) input is needed. Semantics match
    _apply_proposals with prop_slot = the slot whose count is nonzero;
    non-leader slots admit nothing."""
    is_ldr = active & (st.state == LEADER)
    tail = st.last_index - st.commit
    room = jnp.maximum(0, cfg.window // 2 - tail)
    cnt = jnp.minimum(jnp.minimum(cnt_gp, cfg.max_ents), room)
    cnt = cnt * is_ldr.astype(jnp.int32)
    E = cfg.max_ents
    terms = jnp.broadcast_to(st.term[..., None], (*st.term.shape, E))
    st = _write_terms(st, cfg, anchor=st.last_index, terms=terms,
                      lo=st.last_index + 1, count=cnt, mask=cnt > 0)
    st = st._replace(last_index=st.last_index + cnt)
    return _set_self_progress(st)


def _apply_proposals(st: GroupState, cfg: KernelConfig, prop_count: jax.Array,
                     prop_slot: jax.Array, active: jax.Array) -> GroupState:
    """The addressed leader appends `prop_count[g]` new entries of its term
    (reference appendEntry raft.go:351-360; payloads live in the host log
    store). `prop_slot[g]` names the slot the host routed the proposals to —
    during a transient two-leader window only that instance appends, so the
    host's (group, index)->payload map stays unambiguous."""
    P = st.term.shape[1]
    is_target = jnp.arange(P, dtype=jnp.int32)[None, :] == prop_slot[:, None]
    is_ldr = active & is_target & (st.state == LEADER)
    # Admission control: never let the uncommitted tail outrun half the
    # device log window, or followers' needed entries fall off the ring and
    # every group degrades to the host snapshot path. This is the batched
    # analogue of the reference's proposal backpressure (its raft channel
    # blocks; here the device itself throttles and the host engine retries
    # unaccepted proposals next round).
    tail = st.last_index - st.commit
    room = jnp.maximum(0, cfg.window // 2 - tail)
    cnt = jnp.minimum(jnp.minimum(prop_count[:, None], cfg.max_ents), room)
    cnt = cnt * is_ldr.astype(jnp.int32)
    E = cfg.max_ents
    terms = jnp.broadcast_to(st.term[..., None],
                             (*st.term.shape, E))
    st = _write_terms(st, cfg, anchor=st.last_index, terms=terms,
                      lo=st.last_index + 1, count=cnt,
                      mask=cnt > 0)
    st = st._replace(last_index=st.last_index + cnt)
    return _set_self_progress(st)


# ---------------------------------------------------------------------------
# Phase 4: quorum commit (THE reduction — reference maybeCommit
# raft.go:323-332 becomes one top_k over the peers axis)
# ---------------------------------------------------------------------------

def _quorum_commit(st: GroupState, cfg: KernelConfig, active: jax.Array,
                   lead_term0: jax.Array) -> GroupState:
    G, P = st.term.shape
    eye = jnp.eye(P, dtype=bool)[None, :, :]
    target_active = active[:, None, :]
    mrow = _where(eye, st.last_index[..., None], st.match)
    mrow = _where(target_active, mrow, -1)
    topk, _ = jax.lax.top_k(mrow, P)  # sorted descending
    qidx = jnp.broadcast_to((quorum(st) - 1)[:, None, None], (G, P, 1))
    mci = ring_lookup(topk, qidx)[..., 0]
    # Only entries from the leader's own term commit by counting
    # (raftLog.maybeCommit; Raft paper §5.4.2). The reference runs
    # maybeCommit inside each MsgAppResp (raft.go:514-545), BEFORE a
    # later message might demote the leader; this deferred phase must not
    # lose that advance, so an instance demoted DURING the message phase
    # still commits on behalf of the term it led at round start
    # (lead_term0): its match row was only updatable by same-term acks,
    # making this exactly the reference's per-response maybeCommit.
    eff_term = _where(st.state == LEADER, st.term, lead_term0)
    mci_term = term_at(st, cfg, jnp.maximum(mci, 0))
    ok = (eff_term > 0) & (mci > st.commit) & (mci_term == eff_term)
    return st._replace(commit=_where(ok, mci, st.commit))


# ---------------------------------------------------------------------------
# Phase 5: send assembly (gap-driven)
# ---------------------------------------------------------------------------

def _assemble_sends(st: GroupState, cfg: KernelConfig, resp: jax.Array,
                    hb_fire_term: jax.Array, vote_fire_term: jax.Array,
                    active: jax.Array, hold=None, down=None
                    ) -> Tuple[GroupState, jax.Array]:
    """Build the outbox (G, P_from, P_to, F) and apply optimistic progress
    updates for sent appends. `hold` (G, P) bool names HELD target slots
    (lagging-follower injection, the reference's Progress.Paused): a leader
    sends a held slot no append and raises no snapshot for it, however far
    behind it falls; heartbeats, votes and responses are not gated, so the
    held follower keeps its leader and its term. None (the default) leaves
    the program as it is without the hold. `down` (G, P) bool names slots
    cut off from their peers (their messages are dropped after the hop,
    down_drop_mask): a snapshot is a message too, so none is raised to or by a down
    slot; everything else is assembled as if the link were up, and lost."""
    G, P = st.term.shape
    F = cfg.fields
    E = cfg.max_ents
    eye = jnp.eye(P, dtype=bool)[None, :, :]
    tgt_ok = active[:, None, :] & active[:, :, None] & ~eye

    # ---- appends --------------------------------------------------------
    is_ldr = (st.state == LEADER)[..., None]
    last = st.last_index[..., None]
    unacked = st.next - 1 - st.match
    paused_eff = _where(st.pr_state == PR_PROBE, st.paused,
                        unacked >= cfg.effective_flow_window)
    if hold is not None:
        held = hold[:, None, :]                  # by target column
        paused_eff = paused_eff | held
    has_gap = st.next <= last
    prev = st.next - 1
    prev_in_win = in_window(st, cfg, prev)
    # Entries next..next+n-1 must ALSO be resolvable from the sender's ring
    # (next > last - W). prev == 0 passes in_window via the empty-log
    # special case, but once last > W the ring no longer holds entry 1 —
    # without this guard the term gather below would alias modulo W and
    # ship garbage terms to an empty/new follower.
    ents_ok = st.next > last - cfg.window
    sendable = prev_in_win & ents_ok
    # Target lags below the device window -> host must ship a snapshot.
    need_snap = is_ldr & tgt_ok & has_gap & ~sendable
    if hold is not None:
        # Without this gate a held follower of a busy group would be
        # installed every W entries DURING its hold and never lag.
        need_snap = need_snap & ~held
    if down is not None:
        need_snap = need_snap & ~down[:, None, :] & ~down[:, :, None]
    st = st._replace(need_host=_flag(st.need_host,
                                     jnp.any(need_snap, axis=2), NH_SNAP))

    send_app = is_ldr & tgt_ok & has_gap & ~paused_eff & sendable
    n = jnp.minimum(last - st.next + 1, E)
    n = _where(send_app, n, 0)

    # Entry terms for slots next .. next+n-1, from the SENDER's ring; the
    # one-hot select-sum broadcasts the (G,P,1,W) ring across targets
    # without materializing a (G,P,P,W) copy.
    idx_e = st.next[..., None] + jnp.arange(E, dtype=jnp.int32)[None, None, None]
    slot_e = jnp.mod(idx_e, cfg.window)
    terms_e = ring_lookup(st.log_term[:, :, None, :], slot_e)
    valid_e = jnp.arange(E)[None, None, None] < n[..., None]
    terms_e = jnp.where(valid_e, terms_e, 0)

    prev_term = _terms_at_many(st, cfg, prev)  # (G, P, P): per-sender ring

    out = jnp.zeros((G, P, P, F), jnp.int32)
    term_b = jnp.broadcast_to(st.term[..., None], (G, P, P))
    commit_b = jnp.broadcast_to(st.commit[..., None], (G, P, P))

    def put(out, mask, field, val):
        return out.at[..., field].set(jnp.where(mask, val, out[..., field]))

    out = put(out, send_app, F_TYPE, M_APP)
    out = put(out, send_app, F_TERM, term_b)
    out = put(out, send_app, F_INDEX, prev)
    out = put(out, send_app, F_LOGTERM, prev_term)
    out = put(out, send_app, F_COMMIT, commit_b)
    out = put(out, send_app, F_NENT, n)
    ents_cur = out[..., N_FIXED_FIELDS:]
    out = out.at[..., N_FIXED_FIELDS:].set(
        jnp.where(send_app[..., None], terms_e, ents_cur))

    # Optimistic update / probe pause (reference sendAppend raft.go:267-279).
    sent_n = _where(send_app, n, 0)
    st = st._replace(
        next=_where(send_app & (st.pr_state == PR_REPLICATE),
                    st.next + sent_n, st.next),
        paused=_where(send_app & (st.pr_state == PR_PROBE), True, st.paused),
    )

    # ---- heartbeats (lower priority than appends) -----------------------
    hb_ok = (hb_fire_term[..., None] == term_b) & (hb_fire_term[..., None] > 0)
    send_hb = is_ldr & tgt_ok & hb_ok & ~send_app
    hb_commit = jnp.minimum(st.match, commit_b)  # reference raft.go:285-298
    out = put(out, send_hb, F_TYPE, M_HB)
    out = put(out, send_hb, F_TERM, term_b)
    out = put(out, send_hb, F_COMMIT, hb_commit)

    # ---- vote requests --------------------------------------------------
    is_cand = (st.state == CANDIDATE)[..., None]
    vf = (vote_fire_term[..., None] == term_b) & (vote_fire_term[..., None] > 0)
    send_vote = is_cand & tgt_ok & vf & (out[..., F_TYPE] == M_NONE)
    last_t = _last_term(st, cfg)
    out = put(out, send_vote, F_TYPE, M_VOTE)
    out = put(out, send_vote, F_TERM, term_b)
    out = put(out, send_vote, F_INDEX,
              jnp.broadcast_to(last[..., 0][..., None], (G, P, P)))
    out = put(out, send_vote, F_LOGTERM,
              jnp.broadcast_to(last_t[..., None], (G, P, P)))

    # ---- responses override everything (drop-on-collision is safe) ------
    has_resp = resp[..., F_TYPE] != M_NONE
    out = jnp.where(has_resp[..., None], resp, out)
    return st, out


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=0, donate_argnums=_donate_at_import((1,)))
def step(cfg: KernelConfig, st: GroupState, inbox: jax.Array,
         prop_count: jax.Array, prop_slot: jax.Array, tick: jax.Array
         ) -> Tuple[GroupState, jax.Array]:
    """One batched consensus round for all G×P instances.

    inbox:      (G, P, P_from, F) int32 — inbox[g, p, q] is the message
                delivered to instance (g, p) from sender slot q this round
                (M_NONE-typed slots are empty).
    prop_count: (G,) int32 — entries proposed to each group's leader this
                round (payloads stay in the host log store).
    prop_slot:  (G,) int32 — which peer slot the host routed proposals to.
    tick:       () bool — whether this round advances the logical clock.

    Returns (new_state, outbox) with outbox (G, P_to_assignment...) shaped
    (G, P_from, P_to, F). Routing outbox->inbox is a transpose of the two
    peer axes (single host) or an all_to_all over the "peers" mesh axis.

    Phase order (the scalar equivalence harness mirrors it exactly):
    tick -> messages by sender slot 0..P-1 -> proposals -> quorum commit ->
    send assembly -> defensive invariant check (the reference's
    log.maybeAppend/commitTo panics: commit past the log end means
    corrupted state and raises NH_VIOLATION).
    """
    return _step_body(cfg, st, inbox, prop_count, prop_slot, tick,
                      _full_msgs)[:2]


# ---------------------------------------------------------------------------
# Quiescent fast path
#
# In steady state (every group led, no elections or term changes in flight)
# the P sequential message passes above are overkill: leaders receive ONLY
# append/heartbeat responses — whose progress updates live in per-sender
# columns and therefore commute across senders — and each follower receives
# AT MOST one append-or-heartbeat, from its leader (one leader per term;
# send assembly emits one message per (leader, target) per round). Both
# facts collapse the message phase into ONE vectorized pass. The step
# programs check the quiescence predicate on device, group by group
# (_quiet_pred), and lax.cond-select per hop (_hops): no busy group, the
# fast path (_quiet_msgs); any, the sequential passes for all, made by
# rank and not by sender (_ranked_msgs: what the P passes of _full_msgs
# compute, in as many passes as the busiest receiver holds messages that
# need one — one or none on most hops of a member whose groups elect all
# the time, where _full_msgs makes P). The paths are behaviorally
# identical (tests/test_quiet_path.py drives bit-exactness round by round,
# that of the one pass for every group the predicate calls quiet while
# others are busy, and that of the passes by rank on scripts and on
# random inboxes). The counts of busy groups and of passes come back with
# the state. Gathering the busy groups into a sub-batch, the other way to
# spare the quiet groups a busy hop's passes, costs more on the chip than
# the passes over all G (G lies in the lanes there: PERF.md section 6,
# PR 40).
# ---------------------------------------------------------------------------

def _quiet_pred(st: GroupState, cfg: KernelConfig, inbox: jax.Array,
                active: jax.Array, tick: jax.Array) -> jax.Array:
    """(G,) bool: the groups NOTHING of which can need the sequential
    message passes this hop: _quiet_msgs equals them bit for bit there.
    Conservative — a group called busy that the one pass would have served
    costs time, never correctness."""
    mtype = inbox[..., F_TYPE]
    present = mtype != M_NONE
    vote_ish = present & ((mtype == M_VOTE) | (mtype == M_VOTE_RESP))
    # Any cross-term message (stale or new-term) needs the term gate.
    term_mism = present & (inbox[..., F_TERM] != st.term[:, :, None])
    # What _quiet_msgs rests on, read where it shows: a receiver holds at
    # most one append-or-heartbeat. Every message left carries its
    # receiver's term and a term has one leader, so this holds however
    # many rows call themselves LEADER (a leader cut off from its peers
    # stays LEADER in its old term beside its successor: no check-quorum).
    many_app = jnp.sum((present & ((mtype == M_APP) | (mtype == M_HB)))
                       .astype(jnp.int32), axis=2) > 1
    is_c = active & (st.state == CANDIDATE)
    # A follower whose clock would reach its election timeout this round
    # might campaign (and must draw from the PRNG stream either way).
    could_campaign = (tick & active & (st.state != LEADER)
                      & (st.elapsed + 1 >= cfg.election_tick))
    pending_host = st.need_host != 0
    return ~(jnp.any(vote_ish | term_mism, axis=(1, 2))
             | jnp.any(many_app | is_c | could_campaign | pending_host,
                       axis=1))


def _leader_resps(st: GroupState, cfg: KernelConfig, inbox: jax.Array,
                  on: jax.Array) -> GroupState:
    """The append and heartbeat responses held at the (receiver, sender)
    cells `on` (G, P, P), all senders in one shot: a leader's per-sender
    progress columns are independent of one another, so as long as none of
    a leader's messages can change its role or term (the caller's `on`
    says where that holds) the order of the senders does not matter."""
    mtype_all = inbox[..., F_TYPE]
    mindex_all = inbox[..., F_INDEX]
    mreject_all = inbox[..., F_REJECT]
    mhint_all = inbox[..., F_HINT]
    ar = on & (mtype_all == M_APP_RESP)
    match, nxt = st.match, st.next
    prs, paused = st.pr_state, st.paused

    rej = ar & (mreject_all != 0)
    repl_rej = rej & (prs == PR_REPLICATE) & (mindex_all > match)
    probe_rej = rej & (prs == PR_PROBE) & (nxt - 1 == mindex_all)
    nxt = _where(repl_rej, match + 1, nxt)
    nxt = _where(probe_rej,
                 jnp.maximum(jnp.minimum(mindex_all, mhint_all + 1), 1), nxt)
    prs = _where(repl_rej, PR_PROBE, prs)
    paused = _where(probe_rej, False, paused)

    ok = ar & (mreject_all == 0)
    upd = ok & (match < mindex_all)
    match = _where(upd, mindex_all, match)
    paused = _where(upd, False, paused)
    prs = _where(upd & (prs == PR_PROBE), PR_REPLICATE, prs)
    nxt = jnp.maximum(nxt, _where(ok, mindex_all + 1, 0))
    ack_age = _where(ar, 0, st.ack_age)

    hrs = on & (mtype_all == M_HB_RESP)
    stale = (hrs & (prs == PR_REPLICATE)
             & (match < st.last_index[..., None])
             & (ack_age > 2 * cfg.heartbeat_tick + 2))
    nxt = _where(stale, match + 1, nxt)
    return st._replace(match=match, next=nxt, pr_state=prs, paused=paused,
                       ack_age=ack_age)


def _quiet_msgs(st: GroupState, cfg: KernelConfig, inbox: jax.Array,
                active: jax.Array
                ) -> Tuple[GroupState, jax.Array, jax.Array]:
    """One-pass message processing for quiescent rounds; returns (state,
    resp, 0 sequential passes) with resp shaped (G, P, P, F) like the full
    path's."""
    G, P = st.term.shape
    F = cfg.fields
    mtype_all = inbox[..., F_TYPE]
    is_l = st.state == LEADER
    recv = active[..., None]
    st = _leader_resps(st, cfg, inbox, recv & is_l[..., None])

    # -- the one append-or-heartbeat each follower may hold: reduce over
    # the sender axis (at most one slot is populated — one leader per
    # term), then process it exactly like the full path's single-message
    # case.
    fm = recv & ~is_l[..., None] & ((mtype_all == M_APP)
                                    | (mtype_all == M_HB))
    has_fm = jnp.any(fm, axis=2)
    s_idx = jnp.argmax(fm, axis=2).astype(jnp.int32)      # (G, P)
    onehot_s = _onehot(s_idx, P)
    # dtype pinned: under x64 test configs jnp.sum promotes int32 -> int64.
    msg = jnp.sum(inbox * (fm & onehot_s)[..., None].astype(jnp.int32),
                  axis=2, dtype=jnp.int32)                 # (G, P, F)
    mtype = jnp.where(has_fm, msg[..., F_TYPE], M_NONE)
    mindex = msg[..., F_INDEX]
    mlogterm = msg[..., F_LOGTERM]
    mcommit = msg[..., F_COMMIT]
    mnent = msg[..., F_NENT]
    ent_terms = msg[..., N_FIXED_FIELDS:]

    resp_f = jnp.zeros((G, P, F), jnp.int32)
    a = has_fm & (mtype == M_APP)
    h = has_fm & (mtype == M_HB)
    st = st._replace(
        elapsed=_where(a | h, 0, st.elapsed),
        lead=_where(a | h, s_idx + 1, st.lead),
    )

    below_commit = a & (mindex < st.commit)
    resp_f = _stage(resp_f, below_commit, M_APP_RESP, st.term,
                    index=st.commit)
    chk = a & ~below_commit
    prev_t = term_at(st, cfg, mindex)
    prev_in_win = in_window(st, cfg, mindex)
    escape = chk & ~prev_in_win & (mindex <= st.last_index)
    st = st._replace(need_host=_flag(st.need_host, escape, NH_SNAP))

    match_ok = chk & ~escape & prev_in_win & (prev_t == mlogterm)
    rej_m = chk & ~escape & ~match_ok
    resp_f = _stage(resp_f, rej_m, M_APP_RESP, st.term, index=mindex,
                    reject=True, hint=st.last_index)

    E = cfg.max_ents
    idx_j = mindex[..., None] + 1 + jnp.arange(E, dtype=jnp.int32)[None, None]
    valid_j = jnp.arange(E)[None, None] < mnent[..., None]
    my_t = _terms_at_many(st, cfg, idx_j)
    mismatch = valid_j & (my_t != ent_terms)
    any_conf = match_ok & jnp.any(mismatch, axis=-1)
    first_j = jnp.argmax(mismatch, axis=-1)
    ci = _where(any_conf, mindex + 1 + first_j, 0)
    st = st._replace(need_host=_flag(st.need_host,
                                     any_conf & (ci <= st.commit),
                                     NH_VIOLATION))
    st = _write_terms(st, cfg, anchor=mindex, terms=ent_terms, lo=ci,
                      count=mnent, mask=any_conf)
    lastnewi = mindex + mnent
    old_last = st.last_index
    st = st._replace(
        last_index=_where(any_conf, lastnewi, st.last_index))
    shrink = any_conf & (old_last > lastnewi)
    w_idx = jnp.arange(cfg.window, dtype=jnp.int32)[None, None, :]
    i_w = old_last[..., None] - jnp.mod(old_last[..., None] - w_idx,
                                        cfg.window)
    strand = shrink[..., None] & (i_w > lastnewi[..., None])
    st = st._replace(log_term=jnp.where(strand, 0, st.log_term))
    new_commit = jnp.maximum(st.commit, jnp.minimum(mcommit, lastnewi))
    st = st._replace(commit=_where(match_ok, new_commit, st.commit))
    resp_f = _stage(resp_f, match_ok, M_APP_RESP, st.term, index=lastnewi)

    st = st._replace(
        commit=_where(h, jnp.maximum(st.commit,
                                     jnp.minimum(mcommit, st.last_index)),
                      st.commit))
    resp_f = _stage(resp_f, h, M_HB_RESP, st.term)

    # Route each follower's response back to its sender slot.
    resp = (resp_f[:, :, None, :]
            * onehot_s[..., None].astype(jnp.int32))        # (G, P, P, F)
    return st, resp, jnp.int32(0)


def _full_msgs(st: GroupState, cfg: KernelConfig, inbox: jax.Array,
               active: jax.Array
               ) -> Tuple[GroupState, jax.Array, jax.Array]:
    """The message phase as P sequential passes, one a sender slot: what
    the message phase means (the scalar equivalence harness mirrors it).
    (state, resp, P) with resp shaped (G, P, P, F) like _quiet_msgs'."""
    G, P = st.term.shape
    resp = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    for q in range(P):
        st, r = _step_msgs_from(st, cfg, q, inbox[:, :, q, :], active)
        resp = resp.at[:, :, q, :].set(r)
    return st, resp, jnp.int32(P)


def _ranked_msgs(st: GroupState, cfg: KernelConfig, inbox: jax.Array,
                 active: jax.Array
                 ) -> Tuple[GroupState, jax.Array, jax.Array]:
    """_full_msgs bit for bit, in as many passes as the busiest receiver
    holds messages that need one: the message phase of a hop on which some
    group is busy, chosen receiver by receiver and dense over all G (no
    group is picked out of the state: on the chip G lies in the lanes).

    Every update of _step_msgs_from is local to its receiver's row, so a
    receiver only has to see ITS messages in sender order; which pass
    brings which is free. A message below its receiver's term is ignored
    wherever it stands (terms only rise) and is dropped here. Like
    _quiet_msgs this rests on a leader's own progress column standing at
    its last index (every path that makes or extends a leader leaves it
    so; the P passes re-set it at every pass). Then:
    - a leader all of whose messages are append or heartbeat responses at
      its own term: none can change its role or term and each touches its
      own sender's progress column, so they are taken in one shot
      (_leader_resps, _quiet_msgs' first half): no pass;
    - a candidate all of whose messages are vote responses at its own
      term: the tally (_count_votes), no pass;
    - a receiver all of whose messages are vote requests (a group whose
      leader was cut off has up to P - 1 candidates at one tick): the
      ballot (_take_votes) on the hard state alone, no pass;
    - every other receiver: pass k hands each its k-th message in sender
      order (the sender is then a (G, P) array, not one slot for all),
      until no receiver has one left. A follower behind its leader holds
      one, a returning leader's peers one.
    Returns (state, resp, the number of passes made)."""
    G, P = st.term.shape
    mtype = inbox[..., F_TYPE]
    mterm = inbox[..., F_TERM]
    term = st.term[..., None]
    present = active[..., None] & (mtype != M_NONE) & (mterm >= term)
    own = mterm == term
    only = lambda kind: jnp.all(~present | (own & kind), axis=2)
    leads = (st.state == LEADER) & only((mtype == M_APP_RESP)
                                        | (mtype == M_HB_RESP))
    counts = (st.state == CANDIDATE) & only(mtype == M_VOTE_RESP)
    ballots = jnp.all(~present | (mtype == M_VOTE), axis=2)
    st = _leader_resps(st, cfg, inbox, present & leads[..., None])
    st = _count_votes(st, cfg, inbox, present & counts[..., None])
    st, resp = _take_votes(st, cfg, inbox, present & ballots[..., None])

    def one_pass(carry):
        todo, n, st, resp = carry
        q = jnp.argmax(todo, axis=2).astype(jnp.int32)
        pick = todo & _onehot(q, P)
        msg = jnp.sum(inbox * pick[..., None].astype(jnp.int32), axis=2,
                      dtype=jnp.int32)
        st, r = _step_msgs_from(st, cfg, q, msg, active)
        resp = jnp.where(pick[..., None], r[:, :, None, :], resp)
        return todo & ~pick, n + 1, st, resp

    _, n, st, resp = jax.lax.while_loop(
        lambda carry: jnp.any(carry[0]), one_pass,
        (present & ~(leads | counts | ballots)[..., None], jnp.int32(0),
         st, resp))
    return st, resp, n


def _count_votes(st: GroupState, cfg: KernelConfig, inbox: jax.Array,
                 on: jax.Array) -> GroupState:
    """The vote responses held at the (receiver, sender) cells `on`
    (G, P, P), for candidates that hold nothing else and all at their own
    term: _step_msgs_from's MsgVoteResp block (_tally_vote) sender by
    sender, and what the count decides (the win's no-op entry and progress
    reset, the loss's step-down) applied once, where the P passes apply it
    at the deciding sender and ignore the responses behind it (the
    receiver is no candidate any more)."""
    G, P = st.term.shape
    cell = on & (inbox[..., F_TYPE] == M_VOTE_RESP)
    win = lose = jnp.zeros((G, P), bool)
    for q in range(P):
        st, won, lost = _tally_vote(st, q, cell[:, :, q] & ~(win | lose),
                                    inbox[:, :, q, F_REJECT])
        win, lose = win | won, lose | lost
    st = _append_noop_and_lead(st, cfg, win)
    return _become_follower(st, lose, st.term, 0)


def _take_votes(st: GroupState, cfg: KernelConfig, inbox: jax.Array,
                on: jax.Array) -> Tuple[GroupState, jax.Array]:
    """The vote requests held at the (receiver, sender) cells `on`
    (G, P, P), for receivers that hold nothing else: _step_msgs_from's
    term gate and MsgVote block (_grant_vote) sender by sender (a vote
    moves no log: the receiver's last entry is read once). Returns the
    state and the responses, (G, P, P, F), zero elsewhere."""
    G, P = st.term.shape
    cell = on & (inbox[..., F_TYPE] == M_VOTE)
    last_t = _last_term(st, cfg)
    asked, at_term, refused = [], [], []
    for q in range(P):
        mterm = inbox[:, :, q, F_TERM]
        st = _become_follower(st, cell[:, :, q] & (mterm > st.term), mterm,
                              0)
        v = cell[:, :, q] & (mterm == st.term)
        st, grant = _grant_vote(st, q, v, inbox[:, :, q, F_INDEX],
                                inbox[:, :, q, F_LOGTERM], last_t)
        asked.append(v)
        at_term.append(st.term)
        refused.append(v & ~grant)
    resp = _stage(jnp.zeros((G, P, P, cfg.fields), jnp.int32),
                  jnp.stack(asked, axis=2), M_VOTE_RESP,
                  jnp.stack(at_term, axis=2),
                  reject=jnp.stack(refused, axis=2))
    return st, resp


def _step_body(cfg: KernelConfig, st: GroupState, inbox: jax.Array,
               prop_count: jax.Array, prop_slot: Optional[jax.Array],
               tick: jax.Array, msgs,
               force_hb: bool = False, hold=None, down=None
               ) -> Tuple[GroupState, jax.Array, jax.Array]:
    """Shared round skeleton, (state, outbox, sequential passes made);
    `msgs` (_quiet_msgs, _ranked_msgs or _full_msgs) is the message-phase
    implementation. prop_slot=None selects per-SLOT proposal admission
    (prop_count is then (G, P) — the multi-host engine's sharded input).
    `force_hb` (Python bool) makes
    every active leader broadcast a heartbeat this pass regardless of its
    heartbeat clock — the ReadIndex step uses it to solicit the quorum
    acks that confirm leadership (reference bcastHeartbeat on a pending
    read, raft.go:313-321 via step MsgReadIndex)."""
    # The stages carry jax.named_scope names (metadata only: the program
    # is byte-identical) so a device trace's ops can be told apart after
    # a refactor.
    active = active_mask(st)
    st = st._replace(ack_age=jnp.minimum(st.ack_age + 1, 1 << 20))
    with jax.named_scope("etcd.tick"):
        st, hb_fire, vote_fire = _tick(st, cfg, active, tick)
    if force_hb:
        ldr = active & (st.state == LEADER)
        hb_fire = _where(ldr, st.term, hb_fire)
        # The broadcast resumes paused probes, exactly like a timed one.
        st = st._replace(paused=_where(ldr[..., None], False, st.paused))
    lead_term0 = _where(st.state == LEADER, st.term, 0)
    with jax.named_scope("etcd.step_msgs"):
        st, resp, passes = msgs(st, cfg, inbox, active)
    with jax.named_scope("etcd.apply_proposals"):
        if prop_slot is None:
            st = _apply_proposals_slots(st, cfg, prop_count, active)
        else:
            st = _apply_proposals(st, cfg, prop_count, prop_slot, active)
    with jax.named_scope("etcd.quorum_commit"):
        st = _quorum_commit(st, cfg, active, lead_term0)
    with jax.named_scope("etcd.assemble_sends"):
        st, outbox = _assemble_sends(st, cfg, resp, hb_fire, vote_fire,
                                     active, hold, down)
    bad = active & (st.commit > st.last_index)
    st = st._replace(need_host=_flag(st.need_host, bad, NH_VIOLATION))
    return st, outbox, passes


@functools.partial(jax.jit, static_argnums=(0, 7, 10), donate_argnums=_donate_at_import((1, 2)))
def step_routed_auto(cfg: KernelConfig, st: GroupState, inbox: jax.Array,
                     prop_count: jax.Array, prop_slot: jax.Array,
                     tick: jax.Array, drop_mask=None,
                     hops: int = 1, hold=None, down=None,
                     by_sender: bool = False
                     ) -> Tuple[GroupState, jax.Array, jax.Array]:
    """step + route_local with on-device fast-path selection: quiescent
    hops (the steady-state common case) skip the P sequential message
    passes. ONE compiled program; lax.cond executes exactly one branch at
    runtime. Returns (state, inbox, hop_stats), the last _hops' (2, hops)
    counts of busy groups and sequential passes.

    `hops` chains that many message-phase+routing passes INSIDE the one
    compiled program: proposals and the tick fire only on the first hop,
    so `hops=H` is bit-identical to H successive 1-hop calls whose last
    H-1 carry no proposals and no tick (tests/test_kernel.py pins this).
    With hops=3 a proposal admitted on hop 0 is replicated (hop 0 send ->
    hop 1 append+ack -> hop 2 commit) within ONE invocation — the
    propose->commit pipeline collapses from 3 round-trips through the
    host to one device program, which is what makes sub-round ack
    latencies possible on the serving path. `drop_mask` (G, P_to, P_from,
    1) int32, applied to the routed inbox after EVERY hop, keeps
    fault-injection (partitions, message drops) hop-accurate. `hold`
    (G, P) bool, the held follower slots of _assemble_sends, applies on
    every hop too; like drop_mask it is no argument of the program when
    None. `down` (G, P) bool, the slots cut off from their peers (leader-
    election churn, server/lag.py): every message to and from a down slot
    is dropped after every hop, as the drop_mask built from it would, and
    no snapshot is raised for one; no argument of the program when None.

    `by_sender` (trace-time) gives a busy hop the P passes by sender
    (_full_msgs) where it would make the passes by rank: those end on
    "does any receiver hold another message", which is one more collective
    a pass once the groups are sharded, so a mesh's programs are built
    with it (engine.py) and keep one scalar all-reduce a hop."""
    return _hops(cfg, st, inbox, prop_count, prop_slot, tick, drop_mask,
                 hops, hold, down, by_sender)[:3]


def _hops(cfg: KernelConfig, st: GroupState, inbox: jax.Array,
          prop_count: jax.Array, prop_slot: Optional[jax.Array],
          tick: jax.Array, drop_mask, hops: int, hold, down,
          by_sender: bool = False, force_hb: bool = False, after_hop=None,
          seen=None):
    """The hop loop of the step programs: (state, inbox, hop_stats, seen),
    hop_stats a (2, hops) int32: how many groups were busy on each hop
    (_quiet_pred) and how many sequential passes its message phase made.
    A hop with no busy group runs the one-pass message phase
    (_quiet_msgs: 0 passes), any other _ranked_msgs (_full_msgs where
    `by_sender`: the state sharded over chips or hosts), over all G.
    `force_hb` forces the leaders' heartbeat on hop 0 and
    `seen = after_hop(seen, inbox)` folds every hop's routed inbox (the
    read step's ack tally)."""
    hop_busy, hop_passes = [], []
    busy = _full_msgs if by_sender else _ranked_msgs
    for h in range(hops):
        pc = prop_count if h == 0 else jnp.zeros_like(prop_count)
        tk = tick if h == 0 else jnp.asarray(False)
        n = jnp.sum(~_quiet_pred(st, cfg, inbox, active_mask(st), tk),
                    dtype=jnp.int32)
        hop_busy.append(n)

        def path(msgs, _h=h):
            def run(ops):
                st, inbox, pc, ps, tick = ops
                s, out, passes = _step_body(
                    cfg, st, inbox, pc, ps, tick, msgs,
                    force_hb=force_hb and _h == 0, hold=hold, down=down)
                return s, route_local(out), passes
            return run

        with jax.named_scope(f"etcd.hop{h}"):
            st, inbox, passes = jax.lax.cond(
                n == 0, path(_quiet_msgs), path(busy),
                (st, inbox, pc, prop_slot, tk))
        hop_passes.append(passes)
        if drop_mask is not None:
            inbox = inbox * drop_mask
        if down is not None:
            inbox = inbox * down_drop_mask(down)
        if after_hop is not None:
            seen = after_hop(seen, inbox)
    return st, inbox, jnp.stack([jnp.stack(hop_busy),
                                 jnp.stack(hop_passes)]), seen


def down_drop_mask(down: jax.Array) -> jax.Array:
    """The (G, P_to, P_from, 1) int32 drop_mask that cuts every `down`
    (G, P) slot off from its peers, both ways."""
    up = ~down
    return (up[:, :, None] & up[:, None, :])[..., None].astype(jnp.int32)


def route_local(outbox: jax.Array) -> jax.Array:
    """Single-host message routing: outbox[g, from, to] -> inbox[g, to, from]
    is just a transpose of the peer axes — the entire rafthttp layer
    (reference rafthttp/, 4187 lines) collapses to this when peers are
    co-located as array rows."""
    return jnp.swapaxes(outbox, 1, 2)


# ---------------------------------------------------------------------------
# Batched ReadIndex (the zero-append linearizable read plane)
# ---------------------------------------------------------------------------

def _at_slot(x: jax.Array, slot: jax.Array) -> jax.Array:
    """x[g, slot[g]] for x (G, P), slot (G,) — one-hot select-sum instead
    of a computed-index gather (same TPU reasoning as ring_lookup)."""
    P = x.shape[1]
    oh = jnp.arange(P, dtype=jnp.int32)[None, :] == slot[:, None]
    return jnp.sum(jnp.where(oh, x, 0), axis=1, dtype=x.dtype)


def _read_register(st: GroupState, cfg: KernelConfig
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Register a batched ReadIndex for every group at once: capture
    (read_slot, read_term, read_commit, has_ldr), all (G,).

    read_commit is the leader's commit index AT REGISTRATION — the index
    the reference's ReadIndex protocol hands back (raft.go step
    MsgReadIndex: r.readOnly.addRequest captures r.raftLog.committed).
    has_ldr additionally requires the leader to have committed an entry
    of its OWN term (the no-op): until then its commit index may lag
    entries a prior leader already committed (Raft §8; the reference
    rejects ReadIndex before the no-op commits, raft.go:872-880). The
    term of the entry at `commit` is resolved from the leader's ring —
    unresolvable (outside the device window) reads as not-confirmed,
    which is conservative: the engine just retries next round."""
    lead_term = jnp.where(active_mask(st) & (st.state == LEADER),
                          st.term, 0)
    read_slot = jnp.argmax(lead_term, axis=1).astype(jnp.int32)
    read_term = jnp.max(lead_term, axis=1)
    read_commit = _at_slot(st.commit, read_slot)
    commit_term = _at_slot(term_at(st, cfg, st.commit), read_slot)
    has_ldr = (read_term > 0) & (commit_term == read_term)
    return read_slot, read_term, read_commit, has_ldr


@functools.partial(jax.jit, static_argnums=(0, 7, 10), donate_argnums=_donate_at_import((1, 2)))
def step_routed_read_auto(cfg: KernelConfig, st: GroupState,
                          inbox: jax.Array, prop_count: jax.Array,
                          prop_slot: jax.Array, tick: jax.Array,
                          drop_mask=None, hops: int = 1, hold=None,
                          down=None, by_sender: bool = False
                          ) -> Tuple[GroupState, jax.Array, jax.Array,
                                     jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
    """step_routed_auto plus a batched ReadIndex pass: returns
    (st, inbox, confirmed (G,) bool, read_commit (G,) int32, flags,
    any_need_host, hop_stats), flags and any_need_host being
    step_routed_compact's on-device diff against the pre-step state
    (_compact_flags), so a read round's record is built from what
    changed, like a write round's, and hop_stats step_routed_auto's.

    Protocol (reference raft.go step MsgReadIndex + ReadOnlySafe recvAck,
    data-parallel over (groups, peers)): each group's leader registers
    the read at invocation start — capturing its commit index — then
    hop 0 forces a heartbeat broadcast (`force_hb`) and every subsequent
    hop counts the M_HB_RESP / M_APP_RESP messages routed back to the
    leader slot AT the registered term. A group is `confirmed` when the
    leader (still leader, same term, own-term entry committed) holds
    acks from a quorum including itself. Nothing is appended: the whole
    pass piggybacks on the existing heartbeat/append-response machinery,
    so a confirmed read costs zero log entries and zero WAL bytes.

    Freshness: only messages produced INSIDE this invocation are counted
    (the ack scan runs after each hop's routing, never on the caller's
    initial inbox). A response carrying term T generated here proves the
    sender's term was still T after registration — so no term>T leader
    can have committed anything the registered read_commit misses. Stale
    mailbox contents predate registration and prove nothing; they are
    consumed by hop 0 but never counted.

    With hops >= 2 a quiescent group confirms within ONE invocation
    (hop 0 emits the forced heartbeat, hop 1 delivers + responds, the
    ack scan after hop 1 sees it). At hops == 1 confirmation still
    arrives opportunistically (responses to the previous round's
    traffic) or on the NEXT invocation — callers just retry unconfirmed
    groups. Proposals/tick fire on hop 0 exactly like step_routed_auto:
    a read round is also a full write round."""
    G, P = st.term.shape
    st0 = st
    read_slot, read_term, read_commit, has_ldr = _read_register(st, cfg)
    oh_lead = (jnp.arange(P, dtype=jnp.int32)[None, :]
               == read_slot[:, None])                        # (G, P)

    def count_acks(acks, inbox):
        # Messages routed to the registered leader slot this hop.
        to_lead = jnp.sum(
            inbox * oh_lead[:, :, None, None].astype(jnp.int32),
            axis=1, dtype=jnp.int32)                         # (G, P_from, F)
        mt = to_lead[..., F_TYPE]
        fresh = (((mt == M_HB_RESP) | (mt == M_APP_RESP))
                 & (to_lead[..., F_TERM] == read_term[:, None]))
        return acks | fresh

    st, inbox, hop_stats, acks = _hops(
        cfg, st, inbox, prop_count, prop_slot, tick, drop_mask, hops, hold,
        down, by_sender, force_hb=True, after_hop=count_acks,
        seen=jnp.zeros((G, P), bool))
    n_acks = jnp.sum((acks & ~oh_lead).astype(jnp.int32), axis=1)
    still = ((_at_slot(st.state, read_slot) == LEADER)
             & (_at_slot(st.term, read_slot) == read_term))
    confirmed = has_ldr & still & (n_acks + 1 >= quorum(st))
    return ((st, inbox, confirmed, read_commit) + _compact_flags(st0, st)
            + (hop_stats,))


# Per-(g, p) change flags emitted by step_routed_compact and
# step_routed_read_auto.
CHG_HS = 1       # term | vote | commit changed (the WAL HardState diff)
CHG_LAST = 2     # last_index changed
CHG_RING = 4     # any ring (log-term window) slot changed
CHG_STATE = 8    # role changed (host mirror only; never journaled)


def _compact_flags(st0: GroupState, st: GroupState
                   ) -> Tuple[jax.Array, jax.Array]:
    """The on-device state diff of one round: ((G, P) uint8 CHG_* bitmask
    of st against the pre-step st0, any_need_host scalar)."""
    with jax.named_scope("etcd.compact_flags"):
        hs = ((st.term != st0.term) | (st.vote != st0.vote)
              | (st.commit != st0.commit))
        flags = (hs.astype(jnp.uint8) * CHG_HS
                 | (st.last_index != st0.last_index).astype(jnp.uint8)
                 * CHG_LAST
                 | jnp.any(st.log_term != st0.log_term, axis=2)
                 .astype(jnp.uint8) * CHG_RING
                 | (st.state != st0.state).astype(jnp.uint8) * CHG_STATE)
        any_nh = jnp.any(st.need_host != 0)
    return flags, any_nh


@functools.partial(jax.jit, static_argnums=(0, 7, 10), donate_argnums=_donate_at_import((1, 2)))
def step_routed_compact(cfg: KernelConfig, st: GroupState, inbox: jax.Array,
                        prop_count: jax.Array, prop_slot: jax.Array,
                        tick: jax.Array, drop_mask=None, hops: int = 1,
                        hold=None, down=None, by_sender: bool = False
                        ) -> Tuple[GroupState, jax.Array, jax.Array,
                                   jax.Array, jax.Array]:
    """step_routed_auto plus an ON-DEVICE state diff: returns (st, inbox,
    flags, any_need_host, hop_stats) where flags is a (G, P) uint8 CHG_*
    bitmask of what changed this round vs the pre-step state.

    Why: the serving engine's per-round full-state readback is O(G*P*W)
    bytes (the ring alone is 32 MB at G=100k) even when a round changed
    almost nothing — the common case at sub-saturated load. With the
    diff computed where the state lives, a quiet round reads back G*P
    bytes of flags + one bool, and the host fetches values only for rows
    that actually changed (gather_rows). A round that changed more rows
    than the engine's cap falls back to the full readback — at
    saturation the full transfer is amortized by the huge batch it
    carries, so the fallback costs throughput nothing.

    The flag set covers exactly the fields the engine mirrors on the
    host (term/vote/commit -> WAL HardState diff, last_index, ring,
    state): a round leaving all four bits clear for a row is a round the
    full path would have read back byte-identical mirror values for.
    any_need_host folds the (G, P) need_host bitmask to one scalar; a
    true value sends the whole round down the full-readback path (need-
    host rounds do snapshot/violation surgery that reads bulk state
    anyway)."""
    st0 = st
    st, inbox, hop_stats = _hops(cfg, st, inbox, prop_count, prop_slot,
                                 tick, drop_mask, hops, hold, down,
                                 by_sender)[:3]
    return (st, inbox) + _compact_flags(st0, st) + (hop_stats,)


# gather_rows' packed row: its linear index g*P + p, its CHG_* flags, the
# five mirrored scalars, then the W ring slots.
ROW_LIN, ROW_FLAGS, ROW_TERM, ROW_VOTE, ROW_COMMIT, ROW_STATE, ROW_LAST = (
    range(7))
ROW_RING = 7
HEAD_STATS = 2       # the header row's first hop_stats column


# The state fields gather_rows reads (the ones the host mirrors): a caller
# may hand it anything that carries these six in place of a GroupState
# (server/engine.py gather_program does, ten buffers a call for 22).
GATHER_FIELDS = ("term", "vote", "commit", "state", "last_index", "log_term")


def _pick_rows(mark: jax.Array, kp: int) -> Tuple[jax.Array, jax.Array]:
    """(the ascending linear indices g*P + p of the first kp set entries
    of the (G, P) bool `mark`, padded with G*P; how many are set). What
    np.nonzero gives the host, shaped for the chip: a count per group,
    one cumulative sum over G, a binary search for the group that holds
    the j-th set entry and a P-wide rank inside it. No sort, no scatter."""
    G, P = mark.shape
    m = mark.astype(jnp.int32)
    upto = jnp.cumsum(jnp.sum(m, axis=1), dtype=jnp.int32)      # (G,)
    j = jnp.arange(kp, dtype=jnp.int32)
    g = jnp.minimum(jnp.searchsorted(upto, j, side="right"),
                    G - 1).astype(jnp.int32)
    within = jnp.cumsum(m[g], axis=1)                           # (kp, P)
    rank = j - (upto[g] - within[:, -1])            # j's place in group g
    p = jnp.argmax(within == rank[:, None] + 1, axis=1).astype(jnp.int32)
    k = upto[G - 1]
    return jnp.where(j < k, g * P + p, G * P), k


@functools.partial(jax.jit, static_argnums=6)
def gather_rows(st: GroupState, flags: jax.Array, any_need_host: jax.Array,
                hop_stats: jax.Array, prop_count: jax.Array,
                prop_slot: jax.Array, kp: int) -> jax.Array:
    """The compact round's one readback, built on the device right behind
    the step: picks the rows the host has to see and packs them, with
    their values, into ONE (1 + kp, ROW_RING + W) int32 buffer.

    `flags`, `any_need_host` and `hop_stats` are what step_routed_compact
    / step_routed_read_auto returned for the round (still on the device),
    `st` the state after it, prop_count / prop_slot the very arrays the
    step was given: a group with proposals staged contributes its leader
    row (g, prop_slot[g]) whether or not it changed, because admission
    reads it. The picked rows are that union in ascending g*P + p, the
    order np.nonzero walks a flag map in.

    Row 0 is the header (any_need_host, K = the union's true size, the
    hops' hop_stats, busy groups then passes, from HEAD_STATS on, zeros);
    row 1 + j is the j-th picked row (ROW_* columns). kp is a trace-time
    constant, one program a size bucket; K > kp means the bucket missed
    and rows kp.. are not there: the caller asks again with a larger kp.
    Rows past K are padding (ROW_LIN == G*P, values of the last row)."""
    G, P = flags.shape
    staged = ((prop_count > 0)[:, None]
              & (prop_slot[:, None] == jnp.arange(P, dtype=prop_slot.dtype)))
    lin, k = _pick_rows((flags != 0) | staged, kp)
    at = jnp.minimum(lin, G * P - 1)
    gi, pi = at // P, at % P
    cols = [lin, flags[gi, pi].astype(jnp.int32), st.term[gi, pi],
            st.vote[gi, pi], st.commit[gi, pi], st.state[gi, pi],
            st.last_index[gi, pi]]
    rows = jnp.concatenate(
        [jnp.stack(cols, axis=1), st.log_term[gi, pi]], axis=1)
    head = jnp.zeros((1, rows.shape[1]), jnp.int32)
    head = head.at[0, 0].set(any_need_host.astype(jnp.int32)).at[0, 1].set(k)
    head = head.at[0, HEAD_STATS:HEAD_STATS + hop_stats.size].set(
        hop_stats.reshape(-1))
    return jnp.concatenate([head, rows], axis=0)


# The bodies of the need-host surgery's two device programs (server/
# engine.py _service_need_host jits them, with a mesh's shardings where
# there is one): the host works on the flagged groups' rows alone, K groups
# a call (one program each whatever the number flagged), and never brings a
# whole state array across.
NEED_HOST_READ = ("next", "match", "pr_state", "paused", "lead", "elapsed")
NEED_HOST_WRITE = ("term", "vote", "commit", "last_index", "log_term",
                   "lead", "state", "elapsed", "match", "next", "pr_state",
                   "paused")


def pick_groups(fields: Tuple[jax.Array, ...], idx: jax.Array
                ) -> Tuple[jax.Array, ...]:
    """The groups `idx` (K,) of each of `fields` (leading axis G): what the
    surgery reads of the state the host keeps no mirror of. An index past
    the end (the padding of a short call) reads the last group."""
    at = jnp.minimum(idx, fields[0].shape[0] - 1)
    return tuple(x[at] for x in fields)


def put_groups(fields: Tuple[jax.Array, ...], need_host: jax.Array,
               idx: jax.Array, rows: Tuple[jax.Array, ...]):
    """`fields` with the groups `idx` (K,) set to `rows` (K, ...), and
    need_host cleared: the surgery's write-back. An index past the end is
    dropped; every other group keeps what it holds."""
    out = tuple(x.at[idx].set(r.astype(x.dtype), mode="drop")
                for x, r in zip(fields, rows))
    return out, jnp.zeros_like(need_host)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=_donate_at_import((1, 2)))
def step_routed_slots(cfg: KernelConfig, st: GroupState, inbox: jax.Array,
                      cnt_gp: jax.Array, tick: jax.Array
                      ) -> Tuple[GroupState, jax.Array]:
    """Multi-host serving step: per-SLOT proposal counts (G, P) sharded
    like the state (see _apply_proposals_slots), full sequential message
    path, fused routing — an all_to_all over the peers mesh axis when the
    state is sharded across hosts (the ICI/DCN consensus transport of
    SURVEY §2.4)."""
    st, outbox, _ = _step_body(cfg, st, inbox, cnt_gp, None, tick,
                               _full_msgs)
    return st, route_local(outbox)


@functools.partial(jax.jit, static_argnums=(0, 6), donate_argnums=_donate_at_import((1, 2)))
def step_routed_slots_auto(cfg: KernelConfig, st: GroupState,
                           inbox: jax.Array, cnt_gp: jax.Array,
                           tick: jax.Array, drop_mask=None,
                           hops: int = 1) -> Tuple[GroupState, jax.Array]:
    """step_routed_slots with the quiescent fast path (and the same
    multi-hop/drop-mask machinery as step_routed_auto — this IS that
    function with per-slot admission selected via prop_slot=None). A busy
    hop makes the P passes by sender (by_sender): the peers are sharded
    over hosts, where a pass by rank would end on a collective of its own.

    DURABILITY CONSTRAINT (multi-host callers): hops MUST stay 1 when
    peers are sharded across independently-failing hosts. With hops>1
    the leader consumes follower acks produced ON DEVICE, before those
    followers' hosts have journaled the appended entries — quorum commit
    would then cover unpersisted replicas, and a follower-host crash
    after the collective but before its WAL append could elect a new
    quorum WITHOUT an acked entry (the exact loss the persist-before-
    send contract exists to prevent). Multi-hop is safe only where all
    peers share one failure domain (the single-host MultiEngine)."""
    return _hops(cfg, st, inbox, cnt_gp, None, tick, drop_mask, hops, None,
                 None, by_sender=True)[:2]


@functools.partial(jax.jit, static_argnums=0, donate_argnums=_donate_at_import((1, 2)))
def step_routed(cfg: KernelConfig, st: GroupState, inbox: jax.Array,
                prop_count: jax.Array, prop_slot: jax.Array,
                tick: jax.Array) -> Tuple[GroupState, jax.Array]:
    """step + route_local fused into ONE device program: returns
    (new_state, next_inbox). Saves a dispatch + transpose copy per round
    for single-host callers that always route locally (the engine)."""
    st, outbox = step.__wrapped__(cfg, st, inbox, prop_count, prop_slot,
                                  tick)
    return st, route_local(outbox)


# ---------------------------------------------------------------------------
# CPU donation hazard
# ---------------------------------------------------------------------------
# XLA:CPU's thunk executor has a buffer-aliasing race under the donated
# multi-hop step: a donated input that an output merely passes through
# (peer_mask — the kernel never writes it, so XLA aliases input buffer
# to output) occasionally comes back holding a DIFFERENT intermediate of
# the same program (the step's is-leader mask). Bisected at G=4/P=5:
# 21/40 boots corrupted with donation, 0/40 without, with bit-identical
# trajectories both ways — a runtime race, not a miscompile. The same
# race scribbles freed heap: long engine workloads segfault or hang at
# shutdown ~1/3 of runs with donation and never without (12/12 clean).
# Two gates keep cpu runs off donation: the module-level jits import
# undonated whenever JAX_PLATFORMS pins a non-TPU platform
# (_donate_at_import — covers the test suite and every kernel-direct
# caller), and serving engines build their own entry points with what
# donate_safe says of the LIVE backend (server/engine.py step_program;
# covers the JAX_PLATFORMS-unset cpu fallback). TPU keeps donation — the state
# arrays ARE the HBM budget there, and the race has only ever been
# observed on cpu. The engine's
# peer_mask watchdog (EngineConfig.mask_check_rounds) stays on as
# defense-in-depth for donating backends. ETCD_TPU_DONATE=on|off
# overrides the auto choice (e.g. `on` to A/B the race, `off` to run a
# TPU box conservatively).

_STEP_STATICS = {
    "step_routed_auto": (0, 7, 10),
    "step_routed_compact": (0, 7, 10),
    "step_routed_read_auto": (0, 7, 10),
    "step_routed_slots_auto": (0, 6),
}


def donate_safe(argnums):
    """`argnums` if donation is safe on the LIVE backend, else ().

    Calls jax.default_backend(), which initializes the backend — only
    call this from engine/serving init (platform flags final), never at
    import time (multihost scripts set JAX_PLATFORMS/distributed state
    after importing this module)."""
    mode = os.environ.get("ETCD_TPU_DONATE", "auto")
    if mode in ("on", "1"):
        return tuple(argnums)
    if mode in ("off", "0"):
        return ()
    return () if jax.default_backend() == "cpu" else tuple(argnums)
