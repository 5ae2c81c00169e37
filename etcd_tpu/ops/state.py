"""Dense struct-of-arrays state for the batched consensus kernel.

This is the TPU-native re-expression of the reference's per-goroutine state
(raft struct raft/raft.go:125-155, Progress map raft/progress.go:37-67,
raftLog raft/log.go:24-39): G groups × P peer slots stepped as ONE XLA
program. Layout conventions:

- Arrays are shaped (G, P, ...) — group axis first (shardable over the mesh
  "groups" axis), peer-slot axis second (local in single-host mode, sharded
  over the mesh "peers" axis in the distributed deployment).
- Peer slots are 0-based; `vote`/`lead` fields store slot+1 with 0 = none
  (mirroring the reference's None=0 node id convention).
- The on-device log is a fixed ring of entry TERMS addressed by absolute
  index modulo WINDOW (entry i lives at slot i % W); entry payloads never
  touch the device — they stay in the host log store (the msgappv2 insight,
  reference rafthttp/msgappv2.go:29-63: the hot path is index bookkeeping).
- All state is int32 (uint32 for the xorshift PRNG lanes); indices are
  int32 which bounds a single group's log index at 2^31 — compaction keeps
  real indices far below this.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Roles (shared with etcd_tpu.raftpb.StateType).
FOLLOWER, CANDIDATE, LEADER = 0, 1, 2

# Progress states (shared with etcd_tpu.raft.progress.ProgressState);
# SNAPSHOT transfers are host-side, so the device only tracks probe/replicate.
PR_PROBE, PR_REPLICATE = 0, 1

# Kernel message types (dense codes; NONE=0 means empty slot).
M_NONE, M_APP, M_APP_RESP, M_VOTE, M_VOTE_RESP, M_HB, M_HB_RESP = range(7)

# need_host bitmask values (see GroupState.need_host).
NH_SNAP = 1        # lagging peer: entries fell below the device ring window;
                   # host must ship a snapshot / resolve the append
NH_VIOLATION = 2   # conflict at/below commit: a PROTOCOL VIOLATION (the
                   # reference panics in log.maybeAppend) — the host engine
                   # must dump state and fail loudly, never paper over it

# Message field offsets in the last axis of inbox/outbox arrays.
F_TYPE, F_TERM, F_INDEX, F_LOGTERM, F_COMMIT, F_REJECT, F_HINT, F_NENT = range(8)
N_FIXED_FIELDS = 8


class KernelConfig(NamedTuple):
    """Static (compile-time) parameters of the batched kernel."""

    groups: int            # G
    peers: int             # P: padded peer-slot count (>= max group size)
    window: int = 16       # W: on-device log ring length (uncommitted tail cap)
    max_ents: int = 4      # E: max entries per append message
    election_tick: int = 10
    heartbeat_tick: int = 1
    # Max un-acked entries per follower before replication pauses
    # (entries-in-flight redesign of the reference inflights ring,
    # progress.go:172-237). 0 = derive window//2, so the pause always
    # engages BEFORE a silent follower's needed entries can fall off the
    # on-device log ring.
    flow_window: int = 0

    @property
    def fields(self) -> int:
        return N_FIXED_FIELDS + self.max_ents

    @property
    def effective_flow_window(self) -> int:
        return self.flow_window if self.flow_window > 0 else self.window // 2


class GroupState(NamedTuple):
    """SoA consensus state; a JAX pytree. Shapes in comments use
    G=groups, P=peer slots, W=window, E=max_ents."""

    # Per-instance HardState/SoftState (reference raftpb HardState +
    # raft.lead/state):
    term: jax.Array          # (G, P) int32
    vote: jax.Array          # (G, P) int32, slot+1, 0 = none
    commit: jax.Array        # (G, P) int32
    lead: jax.Array          # (G, P) int32, slot+1, 0 = none
    state: jax.Array         # (G, P) int32 in {FOLLOWER, CANDIDATE, LEADER}

    # Tick machinery (reference raft.go:149-152,765-771):
    elapsed: jax.Array       # (G, P) int32
    prng: jax.Array          # (G, P) uint32 xorshift32 lanes

    # On-device log: ring of entry terms + cursors (reference raftLog):
    log_term: jax.Array      # (G, P, W) int32; entry i at slot i % W
    last_index: jax.Array    # (G, P) int32

    # Leader replication state, per target slot (reference Progress):
    match: jax.Array         # (G, P, P) int32
    next: jax.Array          # (G, P, P) int32
    pr_state: jax.Array      # (G, P, P) int32 in {PR_PROBE, PR_REPLICATE}
    paused: jax.Array        # (G, P, P) bool (probe in-flight pause)
    # Rounds since the last append response from each target — the staleness
    # signal behind heartbeat-response retransmission (the dense form of the
    # reference's MsgHeartbeatResp -> sendAppend liveness rule,
    # raft.go:547-551).
    ack_age: jax.Array       # (G, P, P) int32

    # Candidate vote tally (reference raft.votes): 0 unknown / 1 granted /
    # 2 rejected, per voter slot:
    votes: jax.Array         # (G, P, P) int32

    # Membership: which peer slots are live. A device-side ConfChange is a
    # bit flip here (add = set a free slot, remove = clear it — the removed
    # slot's rows go inert, no compaction), applied by the host engine at a
    # committed boundary (reference multinode.go:181-218 CreateGroup/
    # RemoveGroup + raft.go:709-744 addNode/removeNode).
    peer_mask: jax.Array     # (G, P) bool

    # Host-escape flags: NH_* bitmask — why this instance needs the host
    # slow path (snapshot send, append below the device window) or, worse,
    # detected a safety violation (NH_VIOLATION).
    need_host: jax.Array     # (G, P) int32 bitmask of NH_*


def _seed(groups: int, peers: int) -> np.ndarray:
    """Per-(group, slot) xorshift32 seeds, identical to the scalar oracle's
    prng_seed(group, node_id=slot+1) (etcd_tpu/raft/core.py)."""
    g = np.arange(groups, dtype=np.uint64)[:, None]
    p = np.arange(1, peers + 1, dtype=np.uint64)[None, :]
    s = (g * np.uint64(0x9E3779B9) + p * np.uint64(0x85EBCA6B) + np.uint64(1))
    s = (s & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    s[s == 0] = 1
    return s


def init_state(cfg: KernelConfig, n_peers=None,
               stagger: bool = False) -> GroupState:
    """Fresh-boot state: every instance a follower at term 0 with an empty
    log. `n_peers` may be an int (uniform group size) or a (G,) array.

    `stagger=True` pre-ages exactly one instance per group (slot g mod n)
    past its election timeout so it campaigns on the FIRST tick and wins
    uncontested ~3 rounds later — the deterministic fast-boot the reference
    gets probabilistically from randomized timeouts (raft.go:765-771).
    The engine and the multichip dryrun use this to reach steady state in
    O(1) rounds instead of O(election_tick) with tie retries."""
    G, P = cfg.groups, cfg.peers
    if n_peers is None:
        n_peers = P
    n_peers_np = np.broadcast_to(np.asarray(n_peers, np.int32), (G,))
    mask0 = np.arange(P, dtype=np.int32)[None, :] < n_peers_np[:, None]
    elapsed0 = np.zeros((G, P), np.int32)
    if stagger:
        g = np.arange(G)
        # Guard mod-by-zero: groups with n_peers == 0 are unprovisioned
        # pool slots (engine tenant lifecycle) — no staggered campaigner.
        slot = (g % np.maximum(n_peers_np, 1)).astype(np.int64)
        # After the first tick, d = 2*tick+1 - tick = tick+1 > any draw in
        # [0, tick-1] -> guaranteed immediate campaign (see kernel._tick).
        elapsed0[g, slot] = np.where(n_peers_np > 0,
                                     2 * cfg.election_tick, 0)

    # Each field gets its OWN buffer: step() donates the whole state pytree,
    # and XLA rejects donating one buffer twice.
    def zeros_gp():
        return jnp.zeros((G, P), jnp.int32)

    def zeros_gpp():
        return jnp.zeros((G, P, P), jnp.int32)

    return GroupState(
        term=zeros_gp(),
        vote=zeros_gp(),
        commit=zeros_gp(),
        lead=zeros_gp(),
        state=zeros_gp(),
        elapsed=jnp.asarray(elapsed0),
        prng=jnp.asarray(_seed(G, P)),
        log_term=jnp.zeros((G, P, cfg.window), jnp.int32),
        last_index=zeros_gp(),
        match=zeros_gpp(),
        next=jnp.ones((G, P, P), jnp.int32),
        pr_state=zeros_gpp(),
        paused=jnp.zeros((G, P, P), bool),
        ack_age=zeros_gpp(),
        votes=zeros_gpp(),
        peer_mask=jnp.asarray(mask0),
        need_host=jnp.zeros((G, P), jnp.int32),
    )


def active_mask(st: GroupState) -> jax.Array:
    """(G, P) bool: which peer slots exist."""
    return st.peer_mask


def quorum(st: GroupState) -> jax.Array:
    """(G,) int32: n//2 + 1 (reference raft.go:215)."""
    return jnp.sum(st.peer_mask.astype(jnp.int32), axis=1) // 2 + 1


def ring_lookup(ring: jax.Array, slot: jax.Array) -> jax.Array:
    """ring[..., W] indexed at slot[..., K] -> [..., K]. Backend-dispatched
    at trace time:

    - TPU: one-hot select-sum over the W axis — compiles to a fused
      broadcast-multiply-reduce on the vector unit; the equivalent
      take_along_axis gather lowers to serialized dynamic slices and
      dominated the whole kernel's round time (profiled: the two ring
      gathers were ~55% of a step at G=100k).
    - CPU (and other backends): take_along_axis — the one-hot form
      materializes an extra (..., K, W) intermediate (104MB at the G=4096
      bench shape in send assembly alone) that a CPU gather avoids.

    Both are elementwise-exact; the trajectory tests drive them against
    the same oracle."""
    if jax.default_backend() == "tpu":
        W = ring.shape[-1]
        iota = jnp.arange(W, dtype=slot.dtype)
        onehot = (slot[..., None] == iota).astype(ring.dtype)
        # dtype pinned: under x64 configs jnp.sum promotes int32 -> int64.
        return jnp.sum(ring[..., None, :] * onehot, axis=-1,
                       dtype=ring.dtype)
    shape = jnp.broadcast_shapes(ring.shape[:-1], slot.shape[:-1])
    ring_b = jnp.broadcast_to(ring, shape + ring.shape[-1:])
    slot_b = jnp.broadcast_to(slot, shape + slot.shape[-1:])
    return jnp.take_along_axis(ring_b, slot_b, axis=-1)


def term_at(st: GroupState, cfg: KernelConfig, index: jax.Array) -> jax.Array:
    """Term of entry `index` per instance; 0 for index 0 (the empty-log
    sentinel) and for indices outside the device window (callers must treat
    out-of-window as escape-to-host where it matters).

    index: (G, P) absolute entry indices. Returns (G, P) int32.
    """
    slot = jnp.mod(index, cfg.window)
    t = ring_lookup(st.log_term, slot[..., None])[..., 0]
    in_window = (index > st.last_index - cfg.window) & (index <= st.last_index)
    valid = in_window & (index >= 1)
    return jnp.where(valid, t, 0)


def in_window(st: GroupState, cfg: KernelConfig, index: jax.Array) -> jax.Array:
    """bool mask: entry `index` is resolvable on device (or is index 0).
    `index` may be (G, P) or carry extra trailing axes ((G, P, K))."""
    last = st.last_index
    while last.ndim < index.ndim:
        last = last[..., None]
    return ((index > last - cfg.window) & (index <= last)) | (index == 0)


def xorshift32(x: jax.Array) -> jax.Array:
    """Vectorized Marsaglia xorshift32, bit-identical to the scalar oracle
    (etcd_tpu/raft/core.py xorshift32)."""
    x = x ^ (x << 13)
    x = x ^ (x >> 17)
    x = x ^ (x << 5)
    return x
