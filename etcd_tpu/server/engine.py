"""MultiEngine: the batched MultiNode host engine — G Raft groups served
from ONE TPU kernel, the north star's serving path.

This is the integrated run loop the reference implements per-process in
raft.MultiNode (raft/multinode.go:166-322) + raftNode (etcdserver/raft.go:
112-172), re-expressed for the batched kernel (etcd_tpu/ops/kernel.py):

  one engine round =
    batch proposals -> ASYNC kernel.step dispatch (ONE XLA program for all
    G x P) -> flush the PREVIOUS round while the device computes: hand the
    round record to the WAL-writer compartment (walwriter.WALWriter, which
    group-commits queued rounds with ONE fsync on its own thread[s]), then
    hand committed entries to the applier pool — workers apply to the
    per-group stores and trigger client waiters only after the writer's
    durability watermark passes the round's ticket (acks strictly follow
    their round's fsync — the doc.go:31-39 ordering contract, enforced by
    GATING rather than inline ordering; the pipeline overlap is the
    batched form of the reference's apply/persist pipeline,
    etcdserver/raft.go:112-172) -> read back state deltas -> consume
    need_host flags (snapshot-install lagging followers via host-side
    state surgery). On the single-host crash model, letting round k+1's
    device step start before round k's fsync completes is safe: a crash
    truncates the WAL at a round boundary no client ever observed (applies
    may run ahead of durability, but acks never do, and in-memory store
    state dies with the process), and device state never survives a crash
    anyway.

Entry payloads never touch the device: the kernel commits (index, term)
metadata; payloads live in the host log store keyed (group, index, term) —
the Raft log-matching invariant makes that key unique, so leader turnover
overwrites at an index can never alias a committed payload. Leader no-op
entries are simply absent from the payload store and skip application.

Crash model: ALL P peer slots of a group live in this process, so a crash
is a whole-cluster crash — restart reconstructs every slot from the newest
checkpoint + WAL replay at the last durable round boundary. Nothing after
that boundary was ever acked to a client (applies happen after the WAL
fsync), so the restart is externally indistinguishable from a crash of a
real P-member cluster at that instant. In the multi-host deployment (peers
axis sharded over the mesh, parallel/mesh.py) each host persists only its
own slots; this engine is the single-host/multi-tenant serving path.

Membership changes are committed entries (reference multinode.go:181-218
CreateGroup-/RemoveGroup-at-commit semantics): applying one flips a bit in
the device peer_mask and resets the affected progress column; a joining
empty slot is then caught up by the leader (direct appends while within the
ring window, host snapshot-install beyond it).
"""
from __future__ import annotations

import bisect
import collections
import functools
import json
import logging
import queue
import struct
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from etcd_tpu import errors
from etcd_tpu.server import obs as obs_mod
from etcd_tpu.server.enginewal import (CONF_ADD, CONF_REMOVE, EngineWAL,
                                       RoundRecord, b64_np, np_b64)
from etcd_tpu.server.lag import ChurnSchedule, LagSchedule
from etcd_tpu.server.walwriter import WALWriter
from etcd_tpu.utils import metrics
from etcd_tpu.server.request import (METHOD_DELETE, METHOD_GET, METHOD_POST,
                                     METHOD_PUT, METHOD_QGET, METHOD_SYNC,
                                     Request)
from etcd_tpu.store import NativeStore, new_store, set_applied_view
from etcd_tpu.store.event import LazyWriteEvent
from etcd_tpu.utils import idutil
from etcd_tpu.utils.wait import Sink, Wait

log = logging.getLogger("etcd_tpu.engine")

# Payload tags (first byte of every entry payload).
P_REQ = 0x00    # etcd v2 Request (JSON)
P_CONF = 0x01   # membership change (JSON {"id", "op", "slot"})
P_MULTI = 0x02  # batched Requests: u32 count, then (u32 len, Request JSON)*

_LEADER = 2  # ops.state.LEADER (kept in sync; imported lazily with jax)


try:
    from etcd_tpu.native.walcodec import pack_multi as _c_pack_multi
except ImportError:          # pure-Python fallback (un-built tree)
    _c_pack_multi = None


def _pack_entry(items: List[tuple]) -> bytes:
    """One log entry's payload from its coalesced (rid, tagged-payload,
    ...) items: singletons keep their original tagged bytes (P_REQ/P_CONF,
    replay-compatible with pre-batching WALs); multi-request entries pack
    as P_MULTI + u32 count + (u32 len + Request JSON)*. The C packer
    (walcodec.pack_multi, byte-identical — tests/test_native.py) carries
    the deep-queue stage phase; the Python body is the un-built-tree
    fallback and the reference implementation."""
    if len(items) == 1:
        return items[0][1]
    if _c_pack_multi is not None:
        return _c_pack_multi(items, P_MULTI)
    out = [bytes([P_MULTI]), struct.pack("<I", len(items))]
    for it in items:
        blob = it[1][1:]            # strip the P_REQ tag
        out.append(struct.pack("<I", len(blob)))
        out.append(blob)
    return b"".join(out)


def _unpack_multi(payload: bytes) -> List[bytes]:
    (n,) = struct.unpack_from("<I", payload, 1)
    off = 5
    blobs = []
    for _ in range(n):
        (ln,) = struct.unpack_from("<I", payload, off)
        off += 4
        blobs.append(payload[off:off + ln])
        off += ln
    return blobs


@functools.lru_cache(maxsize=None)
def step_program(name: str, kcfg, hops: int, donate: tuple,
                 by_sender: bool = False, out_shardings=None):
    """The engine's jitted entry point for the kernel step `name`
    (step_routed_auto / _compact / _read_auto): the kernel's own body, the
    round's staged proposals arriving as ONE (2, G) int32 array (row 0 the
    count, row 1 the leader slot a group) and unpacked inside the trace, so
    a round hands the runtime one buffer where the kernel's signature has
    two. Called fn(st, inbox, prop, tick, drop_mask, hold, down); the last
    three are no argument of the program when None. `donate`: (0, 1) where
    donating the state and the inbox is safe (kernel.donate_safe), `by_sender`
    and `out_shardings` what a mesh's programs are built with. One jit a
    geometry in a process: engines of the same shape share its programs, and
    the program keeps the kernel step's name (profiler traces find it by)."""
    import jax
    from etcd_tpu.ops import kernel
    body = getattr(kernel, name).__wrapped__

    def step(st, inbox, prop, tick, drop_mask, hold, down):
        return body(kcfg, st, inbox, prop[0], prop[1], tick, drop_mask, hops,
                    hold, down, by_sender)

    step.__name__ = name
    return jax.jit(step, donate_argnums=donate, out_shardings=out_shardings)


@functools.lru_cache(maxsize=None)
def gather_program(rep=None):
    """The engine's jitted kernel.gather_rows, handed what it reads and no
    more: the state's six mirrored fields (kernel.GATHER_FIELDS, in that
    order) in place of its 17 leaves, and the staged proposals as the step's
    one (2, G) array: fn(fields, flags, any_need_host, hop_stats, prop, kp),
    ten buffers a call.

    With `rep` (a mesh's replicated sharding) the state is sharded and the
    packed buffer comes back replicated: one all-gather brings the flag
    map's shards together (G*P bytes) so that every chip makes the same
    pick, each gathers the picked rows it holds, and one all-reduce of those
    rows brings them together. Pinning the flag map is what keeps the
    pick's sums and searches off the groups axis (tests/test_tpu_compile.py
    holds the collectives to these two)."""
    import jax
    from etcd_tpu.ops import kernel
    body = kernel.gather_rows.__wrapped__
    Fields = collections.namedtuple("GatherFields", kernel.GATHER_FIELDS)

    def gather_rows(fields, flags, any_need_host, hop_stats, prop, kp):
        if rep is not None:
            flags = jax.lax.with_sharding_constraint(flags, rep)
        return body(Fields(*fields), flags, any_need_host, hop_stats,
                    prop[0], prop[1], kp)

    return jax.jit(gather_rows, static_argnums=5, out_shardings=rep)


# The longest the engine thread waits for work after a round that found
# none (MultiEngine._run): the idle member's logical tick. Longer than the
# front's pass over a full cohort, so a loaded member runs no empty round
# while its front parses; short against anything that counts wall time
# (the TTL scan's sync_interval, request timeouts).
IDLE_TICK_S = 0.05


# The need-host surgery works on this many flagged groups a pass (one
# compiled kernel.pick_groups / put_groups each, whatever the number
# flagged: a round flags a few), and takes these fields' rows from the
# host's mirrors.
NEED_HOST_GROUPS = 64
_NEED_HOST_MIRRORS = (("term", "h_term"), ("vote", "h_vote"),
                      ("commit", "h_commit"), ("last_index", "h_last"),
                      ("log_term", "h_ring"), ("state", "h_state"))


def _bucket(k: int) -> int:
    """The smallest gather_rows size bucket that holds k rows."""
    return max(256, 1 << (k - 1).bit_length())


class EngineViolation(RuntimeError):
    """A consensus safety violation detected by the kernel (NH_VIOLATION:
    an append conflicted with a committed entry — the condition the
    reference panics on in log.maybeAppend). The engine dumps the affected
    groups' state and refuses to continue; state after this point cannot
    be trusted."""


@dataclass
class EngineConfig:
    groups: int
    peers: int
    data_dir: str
    window: int = 32
    max_ents: int = 8
    election_tick: int = 10
    heartbeat_tick: int = 3
    fsync: bool = True
    checkpoint_rounds: int = 2048     # rounds between full checkpoints
    request_timeout: float = 5.0
    # How often the host scans tenant stores for DUE TTL expirations and
    # stages a replicated SYNC into those groups (reference SyncTicker,
    # etcdserver/server.go:667-681; expiry must ride the log so replay
    # after restart deletes identically). 0 disables.
    sync_interval: float = 0.5
    # Max client requests coalesced into ONE log entry (group commit). The
    # device commits (index, term) metadata only, so entry payloads are
    # free to carry many requests — this is what lets a hot tenant drain
    # max_ents*batch_max writes per round while the on-device ring stays
    # statically shaped (the Zipf-skew answer; the reference's analogue is
    # batching many Ready entries into one WAL fsync, wal.go:459-487).
    # The REAL cap is bytes (batch_bytes, mirroring the reference's 1MB
    # maxSizePerMsg, etcdserver/raft.go:48): a hot tenant's admission
    # scales with its queue depth up to ~max_ents MB/round instead of
    # pinning at a fixed request count.
    batch_max: int = 4096
    batch_bytes: int = 1 << 20
    ticks_per_round: int = 1          # logical clock rate
    stagger: bool = True              # deterministic fast first election
    initial_peers: Optional[int] = None  # active slots at fresh boot (<= peers)
    # Tenants (groups) provisioned at fresh boot. None = all `groups` (the
    # pre-lifecycle behavior); smaller values leave the rest of the pool
    # inactive (peer_mask all-false: no elections, no ticks) for runtime
    # create_tenant()/remove_tenant() — the engine's CreateGroup/
    # RemoveGroup (reference raft/multinode.go:181-218), without
    # recompilation: the kernel shape is the POOL, liveness is the mask.
    initial_tenants: Optional[int] = None
    # Optional jax.sharding.Mesh with ("groups", "peers") axes
    # (parallel/mesh.py): the kernel state shards over it and the per-round
    # message routing becomes an all_to_all over the "peers" mesh axis —
    # the multi-chip serving path. None = single-device arrays.
    mesh: Any = None
    # Store applies + client acks run on a dedicated applier thread,
    # decoupling the round cadence (device step + WAL fsync + diff) from
    # the O(committed requests) Python apply work — the engine's version
    # of the reference's separate apply goroutine (etcdserver/raft.go:
    # 112-172 hands committed entries to the server loop and only waits
    # at the NEXT Ready). False = apply inline each round (deterministic
    # single-thread mode).
    pipeline_applies: bool = True
    # Backpressure: how many rounds of committed-but-unapplied work may
    # queue at the applier before the round loop blocks. Bounds ack
    # latency at ~(this+1) x apply-time-per-round under saturation.
    # With applier_shards > 1 this bounds the DEEPEST shard's backlog,
    # not the sum — one hot shard cannot borrow the others' budget.
    apply_queue_rounds: int = 2
    # Compartmentalized applier pool (PAPERS.md "Scaling Replicated
    # State Machines with Compartmentalization"): partition each round's
    # committed-entry view by tenant range into this many shards, each
    # applied+acked by its own worker thread. storecore.c releases the
    # GIL around batched mutations and every shard owns a disjoint set
    # of tenant stores, so K workers make real parallel progress on a
    # multi-core box while per-group apply order stays FIFO (a group
    # lives in exactly one shard). 1 = today's single-applier behavior.
    applier_shards: int = 1
    # WAL-writer compartment (walwriter.WALWriter): the round loop hands
    # each non-empty RoundRecord to a dedicated writer stage and steps
    # the device ahead; the writer group-commits queued rounds (ONE
    # fsync covers every round queued when it starts) and publishes a
    # durability watermark that applier workers gate acks on — fsync
    # leaves the round loop's critical path without weakening the
    # ack-after-fsync contract. Rounds that carry conf flips (and every
    # round under pipeline_applies=False) append+fsync inline instead:
    # device surgery must follow a durable record.
    #
    # Per-tenant-range WAL segment streams (aligned with applier_shards
    # ranges): each RoundRecord splits into per-range sub-records
    # appended to its range's own stream by its own writer thread, so S
    # fsyncs proceed in parallel on a multi-core box. Replay reassembles
    # the streams at the consistent round boundary (min over stream
    # tails) and truncates whole records beyond it. 1 = one stream, in
    # the pre-compartment root-dir layout (byte-compatible). The value
    # is pinned in geometry.json; an existing dir may go 1 -> S once
    # (the root stream freezes as legacy history) but never change
    # between sharded values.
    wal_shards: int = 1
    # Backpressure: rounds that may queue at a writer shard before
    # submit() blocks. Deeper = bigger group commits under load; ack
    # latency stays bounded at ~(this x append + 1 fsync).
    wal_queue_rounds: int = 64
    # Message hops chained inside ONE kernel invocation (both the
    # single-device and the mesh path). 3 = propose -> replicate ->
    # commit completes within the round it was staged, cutting ack
    # latency from ~4 round-trips to ~1.5 (kernel.step_routed_auto).
    hops: int = 3
    # Compact readback (kernel.step_routed_compact + gather_rows): the
    # round's state diff is computed ON DEVICE, a (G, P) uint8 flag map
    # that never leaves it: gather_rows, enqueued right behind the step
    # with no host read in between, picks the rows that changed (and the
    # staged leader rows), gathers their values and packs them with the
    # need-host attestation into one buffer, and the host makes ONE
    # blocking read a round instead of the full O(G*P*W) state (32 MB of
    # ring alone at G=100k). Rounds that change more rows than
    # compact_cap, that raise need_host or that follow a snapshot-install
    # surgery take the full readback, so saturated throughput is
    # untouched; a round that carries quorum reads is built like any other
    # (its step returns the same diff). None = on, mesh or not: on a mesh
    # the flag map is sharded like the state, the attestation and the
    # packed buffer come back replicated, and gather_rows holds the
    # round's two data-carrying collectives across the groups axis (one
    # all-gather of the flag map, one all-reduce of the K gathered rows).
    # Which path built a round's record is counted in
    # etcd_engine_readback_rounds_total{kind}.
    compact_readback: Optional[bool] = None
    # Max changed+staged rows served by the gather path before a round
    # falls back to full readback. 0 = auto: max(2048, G*P//8). It also
    # bounds gather_rows' size buckets (powers of two from 256 to the one
    # that holds the cap, one compiled program each, all built before the
    # engine thread's first round): a round asks for the bucket that
    # holds max(the most rows any of the last heartbeat_tick + 1 compact
    # rounds picked, P x (groups staged now + last round)), and one that
    # picks more is read a second time
    # at the bucket that holds it (etcd_engine_gather_rebuckets_total).
    compact_cap: int = 0
    # Liveness watchdog cadence (rounds): every N rounds verify the
    # DEVICE peer_mask still equals the host h_mask and repair it from
    # the host copy if not. Membership only ever flows host -> device
    # (_apply_conf / _restore surgery), so any divergence is device
    # buffer corruption — observed on the CPU backend under the donated
    # multi-hop step, where the mask buffer occasionally comes back
    # holding the step's is-leader intermediate. A corrupt mask is a
    # PERMANENT wedge (it silences every cross-slot send and suppresses
    # campaigns, and feeds the next round's donated step), so the check
    # is on by default; it costs one (G, P) bool readback per N rounds.
    # The root cause is gated at source — cpu engines run an UNDONATED
    # step (kernel.py "CPU donation hazard") — so on cpu this is pure
    # defense-in-depth (repairs only fire with ETCD_TPU_DONATE=on);
    # donating backends keep the safety net. 0 disables.
    mask_check_rounds: int = 64
    # Leader-lease read fast path (OFF by default). After a ReadIndex
    # round confirms a group's leader, quorum reads arriving within the
    # next read_lease_ms milliseconds skip the confirmation round and
    # park directly at the current commit mirror. This trades the strict
    # message-proven ReadIndex guarantee for the classic clock-bound
    # lease assumption (bounded drift: a deposed leader's host notices
    # within the lease window); 0 keeps every quorum read on the full
    # confirmation path.
    read_lease_ms: int = 0
    # Lagging-follower injection (server/lag.py; fault injection for
    # measurement, OFF by default: BASELINE.json configs[3]'s "5% lagging
    # followers (Progress.Paused)"). lag_share > 0 holds that share of
    # all follower slots at every round, at most one a group, each for
    # lag_hold_rounds rounds, on a schedule that is a pure function of
    # (lag_seed, group, round number). The held slots ride into the step
    # as drop_mask does: 0 = no such argument, today's programs.
    lag_share: float = 0.0
    lag_hold_rounds: int = 256
    lag_seed: int = 0
    # Leader-election churn (server/lag.py ChurnSchedule; fault injection
    # for measurement, OFF by default: BASELINE.json configs[4]'s "leader-
    # election churn + snapshot install mixed in"). churn_down_rounds > 0
    # cuts every group's working leader off from its peers, both ways, for
    # that many of every churn_period_rounds rounds, the groups' phases
    # spread over the period by churn_seed. The down slots ride into the
    # step like the hold: 0 = no such argument, today's programs. Not
    # together with lag_share (two slots of one group could be out).
    churn_down_rounds: int = 0
    churn_period_rounds: int = 512
    churn_seed: int = 0


class _AckCounter:
    """Mutable ack tally. _apply_committed increments whichever tally it
    is handed — a shard worker's own, or the engine's synchronous-path
    one — so the counters need no locking (one writer each) and
    MultiEngine.acked_requests sums them."""

    __slots__ = ("acked",)

    def __init__(self) -> None:
        self.acked = 0


class _AckBatch:
    """Deferred waiter wakeups: an applier worker collects its pass's
    (rid, result) triggers and ack tally here instead of firing them
    inline, then releases everything after wait_durable(ticket) — the
    apply work may run AHEAD of the WAL pipeline (stores are in-memory
    and die with the process anyway), but no client observes a result
    before its round's record is fsynced (doc.go:31-39). Synchronous
    paths pass no sink and keep the inline trigger."""

    __slots__ = ("items", "acked")

    def __init__(self) -> None:
        self.items: List[Tuple[int, Any]] = []
        self.acked = 0


class _Submitted:
    """One request staged through submit_pairs: what settle() / expire()
    need to close its accounts. The front keys its in-flight table on
    `rid`."""

    __slots__ = ("rid", "g", "read", "t0")

    def __init__(self, rid: int, g: int, read: bool, t0: float) -> None:
        self.rid = rid
        self.g = g
        self.read = read
        self.t0 = t0


class _ViewBatch:
    """The plain PUTs one pass of _apply_committed applies in one native
    call: stores[k] takes the next counts[k] of the flat paths / vals;
    need / rids are the flat positions and ids of the waiter-held ones;
    cur_g / cur_i / cur_end say, entry by entry in flat order, which
    group's cursor moves to which index once the flat lists are applied
    up to cur_end."""

    __slots__ = ("stores", "counts", "paths", "vals", "need", "rids",
                 "cur_g", "cur_i", "cur_end")

    def __init__(self) -> None:
        for name in self.__slots__:
            setattr(self, name, [])


class _ApplierShard:
    """One compartment of the applier pool: a worker thread owning the
    contiguous tenant range [g_lo, g_hi), with its own commit-view
    queue, its own backpressure/condition variable, and its own ack
    tally. Shards share no mutable state except disjoint slices of
    engine.applied and disjoint tenant stores: each worker makes its
    view's one native call over its own tenants' cores."""

    __slots__ = ("idx", "g_lo", "g_hi", "cv", "q", "stop", "exc",
                 "thread", "acct")

    def __init__(self, idx: int, g_lo: int, g_hi: int) -> None:
        self.idx = idx
        self.g_lo = g_lo
        self.g_hi = g_hi
        self.cv = threading.Condition()
        self.q: deque = deque()
        self.stop = False
        self.exc: Optional[Exception] = None
        self.thread: Optional[threading.Thread] = None
        self.acct = _AckCounter()


class MultiEngine:
    """G consensus groups stepped by the batched kernel, served as G
    independent etcd v2 keyspaces ("tenants")."""

    def __init__(self, cfg: EngineConfig) -> None:
        # jax imports deferred so constructing configs stays cheap.
        import jax
        import jax.numpy as jnp
        from etcd_tpu.ops import kernel
        from etcd_tpu.ops.state import (KernelConfig, LEADER, init_state)

        assert LEADER == _LEADER
        self._jax, self._jnp, self._kernel = jax, jnp, kernel
        self.cfg = cfg
        self.kcfg = KernelConfig(
            groups=cfg.groups, peers=cfg.peers, window=cfg.window,
            max_ents=cfg.max_ents, election_tick=cfg.election_tick,
            heartbeat_tick=cfg.heartbeat_tick)
        G, P, W = cfg.groups, cfg.peers, cfg.window

        # The round's three step programs, all built the same way:
        # step_routed_auto (quiescent rounds, the serving steady state,
        # take the one-pass fast path, election/term-change rounds the
        # full sequential path, selected on device with bit-identical
        # trajectories, tests/test_quiet_path.py; cfg.hops chains
        # propose->replicate->commit inside the one program; the drop
        # mask rides into the kernel so fault injection cuts EVERY hop),
        # step_routed_compact (the same plus the on-device diff: a (G, P)
        # flag map and the need-host attestation) and
        # step_routed_read_auto (the zero-append read plane: the same
        # round plus a forced leader heartbeat and a per-group
        # read-quorum tally; a (G,) confirmed flag and a (G,) captured
        # commit index come back with the state, and the compact step's
        # flag map and attestation). Each returns last the (2, hops) counts
        # of groups busy on each hop and of the sequential passes its
        # message phase made (kernel._hops): a hop with no busy group
        # ran the one-pass message phase, any other as many passes as
        # its busiest receiver needed (P on a mesh, below).
        self._st_sh = self._mb_sh = None
        rep = extra_out = None
        if cfg.mesh is not None:
            # Mesh placement: pinned out_shardings keep the state AND the
            # routed inbox on their canonical shardings round over round
            # (one compile; the outbox->inbox peer-axis swap lowers to an
            # all_to_all over the "peers" mesh axis, the ICI transport of
            # SURVEY §2.4; lax.cond keeps the sharded layouts:
            # tests/test_tpu_compile.py holds the collectives to one
            # scalar all-reduce per hop: by_sender gives a busy hop the P
            # passes by sender, since the loop of passes by rank would
            # end every pass on one more). What a step returns beside them
            # is pinned too: the flag map sharded like the state, the
            # attestation replicated, the read plane's two (G,) arrays
            # sharded on groups (the read step returns all four), the
            # hops' counts replicated.
            from etcd_tpu.parallel.mesh import (flag_sharding,
                                                group_sharding,
                                                mailbox_sharding,
                                                replicated_sharding,
                                                state_sharding)
            self._st_sh = state_sharding(cfg.mesh)
            self._mb_sh = mailbox_sharding(cfg.mesh)
            rep = replicated_sharding(cfg.mesh)
            g_sh = group_sharding(cfg.mesh)
            diff_out = (flag_sharding(cfg.mesh), rep)
            extra_out = {"step_routed_auto": (),
                         "step_routed_compact": diff_out,
                         "step_routed_read_auto": (g_sh, g_sh) + diff_out}
        # The engine's own entry points (step_program, gather_program): st
        # and inbox donated on the chip, undonated on the cpu backend
        # (XLA:CPU has a donated-buffer race, see kernel.py "CPU donation
        # hazard").
        donate = kernel.donate_safe((0, 1))

        def step_fn(name):
            outs = None if rep is None else (
                self._st_sh, self._mb_sh, *extra_out[name], rep)
            fn = step_program(name, self.kcfg, cfg.hops, donate,
                              rep is not None, outs)
            return lambda st, inbox, prop, t, hold, down=None: fn(
                st, inbox, prop, t, self.drop_mask, hold, down)

        self._gather_rows = gather_program(rep)
        # Where a round's uploads land, the one placement both programs
        # are compiled to take them in: the device, or on a mesh every chip
        # (replicated: the gather wants all of the staged array on each,
        # the step slices its shard of it locally), so that neither call
        # re-shards what the other was handed.
        self._put = (jax.device_put if rep is None
                     else functools.partial(jax.device_put, device=rep))
        self._step_fn = step_fn("step_routed_auto")
        self._step_fn_c = step_fn("step_routed_compact")
        self._step_fn_r = step_fn("step_routed_read_auto")
        # The need-host surgery's two programs (_need_host_pass): on a mesh
        # the picked rows come back replicated and the write-back lands on
        # the fields' pinned shardings; in place where donation is safe.
        pick_out = put_out = None
        if self._st_sh is not None:
            pick_out = rep
            put_out = (tuple(getattr(self._st_sh, f)
                             for f in kernel.NEED_HOST_WRITE),
                       self._st_sh.need_host)
        self._pick_groups = jax.jit(kernel.pick_groups,
                                    out_shardings=pick_out)
        self._put_groups = jax.jit(kernel.put_groups,
                                   donate_argnums=kernel.donate_safe((0, 1)),
                                   out_shardings=put_out)
        # None = on, mesh or not (EngineConfig.compact_readback).
        self._compact = (cfg.compact_readback is None
                         or bool(cfg.compact_readback))
        self._compact_cap = cfg.compact_cap or max(2048, G * P // 8)
        # What the next compact round's gather bucket is chosen from
        # (_gather_bucket): the rows each of the last few compact rounds
        # picked (a follower learns a commit with its leader's next
        # heartbeat, so a round's rows can follow its writes by that many
        # rounds) and the groups the last round staged.
        self._gather_ks: deque = deque([0], maxlen=cfg.heartbeat_tick + 1)
        self._staged_prev = 0
        # The round loop's pacing (_run): set by whoever queues work for a
        # round, and whether the last round found none and changed nothing.
        self._work = threading.Event()
        self._quiet = False
        # Set whenever device state was mutated WITHOUT updating the
        # h_* mirrors (the snapshot-install surgery leaves mirrors stale
        # on purpose so the NEXT round's full diff journals the install,
        # _service_need_host). A compact diff is device-vs-device and
        # would never see the surgery — the next round must take the
        # full-readback path to re-sync mirrors and journal it.
        self._force_full = False
        # Count of peer_mask watchdog repairs (EngineConfig.
        # mask_check_rounds); >0 means the device mask diverged from the
        # host's and was restored.
        self.mask_repairs = 0
        # Lagging-follower injection (EngineConfig.lag_share): the
        # schedule, the slots held in the round last dispatched (a
        # release is a slot held then and not in the next) and the
        # snapshot installs done.
        self._lag = (LagSchedule(G, P, cfg.lag_share, cfg.lag_hold_rounds,
                                 cfg.lag_seed) if cfg.lag_share else None)
        self._lag_held = np.zeros((G, P), bool)
        self.snap_installs = 0
        # Leader-election churn (EngineConfig.churn_down_rounds): the
        # schedule, the slots cut off now (host map and its device copy,
        # uploaded again only when a cut began or ended) and the cuts begun.
        if cfg.churn_down_rounds and cfg.lag_share:
            raise ValueError(
                "churn_down_rounds and lag_share together could take two "
                "slots of one group out at once: set one of them")
        self._churn = (ChurnSchedule(G, P, cfg.churn_down_rounds,
                                     cfg.churn_period_rounds, cfg.churn_seed)
                       if cfg.churn_down_rounds else None)
        self._down = np.zeros((G, P), bool)
        self._down_d = None
        self.churn_cuts = 0
        # Leader changes as the mirrors show them: when each group's
        # routable leader last changed (perf_counter; a write older than
        # that waited for this leader), and the groups in which an entry a
        # deposed leader admitted may have been overwritten
        # (_repropose_lost).
        self._lead_since = np.zeros(G, np.float64)
        self._resolve: set = set()
        # The groups that show more than one active LEADER row (kept where
        # the mirrors change role, _leaders_moved): only there does the
        # first LEADER row differ from the routable one, so staging picks
        # as it always did and looks again at these alone.
        self._multi_lead: set = set()
        self.reproposed = 0

        # Geometry guard BEFORE anything touches the data dir: a mismatch
        # must refuse the dir before the WAL opens/creates any file in it.
        self._check_geometry()
        self.wait = Wait()
        self.reqid = idutil.Generator(1)
        self._pending: List[deque] = [deque() for _ in range(G)]
        self._dirty: set = set()            # groups with queued proposals
        self._confs_outstanding = 0         # enqueued, not-yet-applied
        # Per group: the entries staged this round, each a list of
        # (request id, tagged payload) items coalesced into one log entry.
        # g -> (leader_slot, [entry batches]) staged this round
        self._staged: Dict[int, Tuple[int, list]] = {}
        # The read plane's two parking lots (both under self._lock):
        # _reads holds quorum reads waiting for a ReadIndex confirmation
        # (rid, Request); _ripe holds confirmed reads waiting for the
        # apply cursor to reach their read index (rid, Request, index).
        # The waiting counters let run_round skip the plane when idle,
        # and the dirty sets bound per-round scans to active groups.
        self._reads: List[deque] = [deque() for _ in range(G)]
        self._read_dirty: set = set()
        self._ripe: List[deque] = [deque() for _ in range(G)]
        self._ripe_dirty: set = set()
        self._reads_waiting = 0
        self._ripe_waiting = 0
        # Leader-lease fast path state (cfg.read_lease_ms): per-group
        # monotonic-clock deadline and the term the lease was granted
        # under — a lease dies with its term.
        self._lease_until = np.zeros(G, np.float64)
        self._lease_term = np.zeros(G, np.int64)
        self._stores: Dict[int, Any] = {}
        self._lock = threading.Lock()       # guards _pending/_dirty enqueue
        self._stop_ev = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.round_no = 0
        # Smoothed wall time of run_round, seeded by the first round that
        # STARTS with every provisioned group led (0.0 until then).
        self.round_ms_ewma = 0.0
        self._ewma_live = self._ewma_armed = False
        self._looping = False      # run_round is being driven by _run
        # Observability plane (obs.py): per-compartment Prometheus
        # series with children pre-bound to this engine's shard
        # geometry, the round flight recorder, and the sampled proposal
        # tracer. Constructed before the WAL writer and applier pool so
        # both compartments can record into it. ETCD_TPU_OBS=off keeps
        # it inert (the overhead A/B's baseline side).
        self.obs = obs_mod.EngineObs(
            wal_shards=max(1, min(cfg.wal_shards, G)),
            applier_shards=max(1, min(cfg.applier_shards, G)))
        # Requests admitted into this round's entries / sampled rids
        # admitted this round (round-thread-private, reset per round).
        self._last_admitted = 0
        self._trace_rids: List[int] = []
        # This round's blocking device->host reads and host->device
        # uploads (count, bytes) and the record phase's clocked parts;
        # round-thread-private, reset per round, written only under
        # obs.enabled.
        self._d2h_n = self._d2h_b = 0
        self._h2d_n = self._h2d_b = 0
        self._rec_gather = self._rec_admit = 0.0
        # The WAL compartment: submit() hands records to the writer
        # stage; acks gate on its durability watermark (wait_durable).
        self.wal = WALWriter(cfg.data_dir, groups=G,
                             shards=cfg.wal_shards, fsync=cfg.fsync,
                             queue_rounds=cfg.wal_queue_rounds,
                             obs=self.obs)
        # Last few durable round records, kept for the violation dump.
        self._recent_recs: deque = deque(maxlen=8)
        self.failed: Optional[Exception] = None
        # Applier pool (cfg.pipeline_applies): committed spans are handed
        # off as immutable views and applied+acked concurrently with the
        # next rounds' device steps and WAL fsyncs (both of which release
        # the GIL, so the appliers make real progress under them). With
        # applier_shards=K the tenant pool is partitioned into K
        # contiguous ranges — shard k owns [k*ceil(G/K), ...), the same
        # convention scripts/pool_serve.py uses — each applied by its own
        # worker. Empty tail shards (K not dividing G) get no thread.
        K = max(1, min(cfg.applier_shards, G))
        per = -(-G // K)
        self._appliers = [
            _ApplierShard(k, min(k * per, G), min((k + 1) * per, G))
            for k in range(K)]
        self._appliers = [sh for sh in self._appliers if sh.g_lo < sh.g_hi]
        # Acks from synchronous applies (conf rounds, pipeline off,
        # restore); shard workers tally into their own counters.
        self._acks = _AckCounter()
        self._last_sync_scan = 0.0
        # g -> redeadline for the one in-flight SYNC allowed per tenant.
        self._sync_pending: Dict[int, float] = {}
        # Tenant-lifecycle admin ops: (op dict, done Event, result dict),
        # processed at a round boundary by the engine loop; acks fire only
        # after the record carrying the flips is fsynced.
        self._admin_q: deque = deque()
        self._admin_flips: List[Tuple[int, int, int]] = []
        self._admin_acks: List[threading.Event] = []
        # Per-slot lifecycle generation: bumped on every create/remove so
        # frontends can invalidate per-tenant caches (an HTTP layer that
        # cached handlers for generation k must not serve a recycled slot's
        # generation k+1 keyspace through them).
        self.tenant_gen = np.zeros(G, np.int64)

        # Host mirrors of the last read-back device state.
        self.h_term = np.zeros((G, P), np.int32)
        self.h_vote = np.zeros((G, P), np.int32)
        self.h_commit = np.zeros((G, P), np.int32)
        self.h_state = np.zeros((G, P), np.int32)
        self.h_last = np.zeros((G, P), np.int32)
        self.h_ring = np.zeros((G, P, W), np.int32)
        self.h_mask = np.zeros((G, P), bool)
        self.applied = np.zeros(G, np.int64)
        self.payloads: Dict[Tuple[int, int, int], bytes] = {}
        # Live-path sidecar of self.payloads: the already-decoded Requests
        # of an admitted entry, so the apply loop skips re-parsing JSON it
        # produced moments ago (restart replay decodes from bytes). Popped
        # at apply; GC'd with the payload store.
        # Value: (the Requests, the enqueue time of the oldest).
        self.payload_reqs: Dict[Tuple[int, int, int], tuple] = {}

        ckpt_round, ckpt = self.wal.load_checkpoint()
        # Full consumption also positions the writer (next segment seq) and
        # seeds the rolling CRC for appends.
        recs = list(self.wal.replay(after_round=ckpt_round))
        if ckpt is not None or recs:
            self._restore(ckpt_round, ckpt, recs)
        else:
            self.st = init_state(self.kcfg, n_peers=self._boot_peers(),
                                 stagger=cfg.stagger)
            self.h_mask = np.asarray(self.st.peer_mask).copy()
        if self._churn is not None and self.round_no:
            # Restarted inside some groups' cuts: who was cut off is told
            # from the journalled terms and votes (ChurnSchedule.recover).
            self._down = self._churn.recover(
                self.round_no - 1, self.h_mask, self.h_term, self.h_vote)
        if self._st_sh is not None:
            from etcd_tpu.parallel.mesh import shard_state
            self.st = shard_state(self.st, cfg.mesh)
        inbox0 = jnp.zeros((G, P, P, self.kcfg.fields), jnp.int32)
        self.inbox = (jax.device_put(inbox0, self._mb_sh)
                      if self._mb_sh is not None else inbox0)
        # What the dispatch lap hands over without an upload, made once and
        # placed as a round's own upload is (_put): the staged array of a
        # round that staged nothing, and the two values of the tick.
        self._prop_zero = self._put(np.zeros((2, G), np.int32))
        self._ticks = (self._put(np.bool_(False)), self._put(np.bool_(True)))
        # Chaos hook: (G, P_to, P_from, 1)-broadcastable 0/1 mask applied to
        # the routed inbox (tests inject drops/partitions here).
        self.drop_mask = None

    def _boot_peers(self):
        """Per-group active-slot counts at fresh boot: the first
        initial_tenants groups get initial_peers (or all P) slots, the
        rest of the pool stays unprovisioned (all-false mask rows)."""
        n = self.cfg.initial_peers or self.cfg.peers
        if self.cfg.initial_tenants is None:
            return n
        arr = np.zeros(self.cfg.groups, np.int32)
        arr[:min(self.cfg.initial_tenants, self.cfg.groups)] = n
        return arr

    def _check_geometry(self) -> None:
        """Persist (groups, peers, window) beside the WAL and refuse a
        restart with different values — the checkpoint/WAL arrays are
        shaped by them, and restoring a (G,P)-shaped checkpoint into a
        different-shaped state would crash at best and silently corrupt
        consensus state at worst. (max_ents shapes only the mailbox, not
        persisted state, so it may change.)"""
        import os
        from etcd_tpu.utils.fileutil import touch_dir_all
        touch_dir_all(self.cfg.data_dir)
        self._grew_from: Optional[int] = None
        path = os.path.join(self.cfg.data_dir, "geometry.json")
        S = max(1, min(self.cfg.wal_shards, self.cfg.groups))
        want = {"groups": self.cfg.groups, "peers": self.cfg.peers,
                "window": self.cfg.window, "wal_shards": S}

        def write(d):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(d, f)
            os.replace(tmp, path)

        if os.path.exists(path):
            with open(path) as f:
                have = json.load(f)
            # WAL shard layout is pinned separately from the array
            # shapes: an unsharded dir (including pre-wal_shards dirs,
            # where the key is absent) may upgrade 1 -> S once — the
            # root stream freezes as legacy history and new records go
            # to the shard streams. Any OTHER change is refused: a
            # shrunk/re-grown stream set would leave frozen streams
            # whose stale tails drag the min-over-streams replay
            # boundary below live records forever.
            have_ws = have.pop("wal_shards", 1)
            core = {k: want[k] for k in ("groups", "peers", "window")}
            if have_ws != S and have_ws != 1:
                raise ValueError(
                    f"engine data dir {self.cfg.data_dir} was written "
                    f"with wal_shards={have_ws}, refusing to open with "
                    f"wal_shards={S} — the segment-stream layout may "
                    "only go 1 -> S once; move the data dir aside or "
                    "match the flag")
            if have != core:
                # The pool may GROW (tenant lifecycle: restart with more
                # groups; restore pads the arrays, WAL group ids stay
                # valid). Peer/window shapes and shrinking still refuse.
                if (have["peers"] == core["peers"]
                        and have["window"] == core["window"]
                        and core["groups"] > have["groups"]):
                    # Remember the old pool size: groups beyond it were
                    # never provisioned, whatever the boot defaults say.
                    self._grew_from = have["groups"]
                    write(want)
                    return
                raise ValueError(
                    f"engine data dir {self.cfg.data_dir} was initialized "
                    f"with geometry {have}, refusing to open with {core} — "
                    "move the data dir aside or match the flags (only the "
                    "group pool may grow)")
            if have_ws != S:
                write(want)
        else:
            write(want)

    def _dev(self, name: str, arr) -> Any:
        """Host array -> device, on the field's canonical sharding when a
        mesh is configured (host-surgery writebacks must not knock fields
        off their sharding, or the pinned-sharding step would silently
        reshard every round)."""
        x = self._jnp.asarray(arr)
        if self._st_sh is not None:
            x = self._jax.device_put(x, getattr(self._st_sh, name))
        if self.obs.enabled:
            self._h2d(x)
        return x

    # ------------------------------------------------------------------
    # restore
    # ------------------------------------------------------------------

    def _restore(self, ckpt_round: int, ckpt: Optional[dict],
                 recs: List[RoundRecord]) -> None:
        """Rebuild host mirrors + device state from checkpoint + WAL replay.
        Every slot restarts as a follower with its replayed log, term, vote
        and commit (reference RestartNode semantics, raft/node.go:186-192)."""
        from etcd_tpu.ops.state import init_state
        jnp = self._jnp
        G, P, W = self.cfg.groups, self.cfg.peers, self.cfg.window

        base = init_state(self.kcfg, n_peers=self._boot_peers(),
                          stagger=self.cfg.stagger)
        self.h_mask = np.asarray(base.peer_mask).copy()
        if self._grew_from is not None:
            # Pool slots added by a post-boot growth were never
            # provisioned — the checkpoint pad and the WAL both know
            # nothing of them.
            self.h_mask[self._grew_from:] = False
        def pool_pad(a):
            """Pad checkpoint arrays along the group axis when the pool
            grew since the checkpoint (new slots: zeroed, unprovisioned)."""
            if a.shape[0] < G:
                pad = np.zeros((G - a.shape[0],) + a.shape[1:], a.dtype)
                return np.concatenate([a, pad], axis=0)
            return a

        if ckpt is not None:
            self.h_term = pool_pad(b64_np(ckpt["term"]).astype(np.int32))
            self.h_vote = pool_pad(b64_np(ckpt["vote"]).astype(np.int32))
            self.h_commit = pool_pad(b64_np(ckpt["commit"])
                                     .astype(np.int32))
            self.h_last = pool_pad(b64_np(ckpt["last"]).astype(np.int32))
            self.h_ring = pool_pad(b64_np(ckpt["ring"]).astype(np.int32))
            self.h_mask = pool_pad(b64_np(ckpt["mask"]).astype(bool))
            self.applied = pool_pad(b64_np(ckpt["applied"])
                                    .astype(np.int64))
            for g_s, blob in ckpt["stores"].items():
                st = new_store(namespaces=("/0", "/1"))
                st.recovery(blob.encode())
                self._stores[int(g_s)] = st
            for g, i, t, b64p in ckpt["payloads"]:
                import base64 as _b64
                self.payloads[(g, i, t)] = _b64.b64decode(b64p)

        # Per-slot log terms reconstructed from history: the final ring only
        # covers the last W entries, but the restart apply span can reach
        # further back (committed-but-unapplied suffix). Seed from the
        # checkpoint's ring, then track BOTH ring deltas (term rewrites —
        # conflicts always change the term) and last_index advances (a
        # same-term append leaves its ring slot's VALUE unchanged when it
        # aliases an equal-term entry, so it is only visible as growth).
        slot_log: Dict[Tuple[int, int], Dict[int, int]] = {}

        def _log_set(g, p, i, t):
            slot_log.setdefault((int(g), int(p)), {})[int(i)] = int(t)

        if ckpt is not None:
            for g in range(G):
                for p in range(P):
                    lastv = int(self.h_last[g, p])
                    for w in range(W):
                        i = lastv - ((lastv - w) % W)
                        if i >= 1:
                            _log_set(g, p, i, self.h_ring[g, p, w])

        last_round = ckpt_round
        for rec in recs:
            last_round = max(last_round, rec.round_no)
            # (what each slot whose log end moves had committed before)
            commit_was = self.h_commit[rec.last_g.astype(np.int64),
                                       rec.last_p.astype(np.int64)].tolist()
            gi = rec.hs_g.astype(np.int64)
            pi = rec.hs_p.astype(np.int64)
            self.h_term[gi, pi] = rec.hs_term
            self.h_vote[gi, pi] = rec.hs_vote
            self.h_commit[gi, pi] = rec.hs_commit
            # Ring deltas first: the round's appends need the post-round
            # ring to resolve their terms.
            gi = rec.ring_g.astype(np.int64)
            pi = rec.ring_p.astype(np.int64)
            self.h_ring[gi, pi, rec.ring_i.astype(np.int64) % W] = rec.ring_t
            for g, p, i, t in zip(rec.ring_g, rec.ring_p, rec.ring_i,
                                  rec.ring_t):
                _log_set(g, p, i, t)
            for g, p, new, c_was in zip(rec.last_g.astype(np.int64),
                                        rec.last_p.astype(np.int64),
                                        rec.last_v.astype(np.int64),
                                        commit_was):
                prev = int(self.h_last[g, p])
                self.h_last[g, p] = new
                for i in range(max(prev + 1, int(new) - W + 1), int(new) + 1):
                    _log_set(g, p, i, self.h_ring[g, p, i % W])
                if int(new) - W > c_was:
                    # The log's end passed W beyond what this slot had
                    # committed: a snapshot install put it there (appends
                    # move commit along). What the slot held uncommitted
                    # below the installed window (a deposed leader's lost
                    # tail) was replaced unseen, not verified: it is no
                    # evidence of the committed term at those indices.
                    log_gp = slot_log.get((int(g), int(p)), {})
                    for i in [i for i in log_gp if c_was < i <= new - W]:
                        del log_gp[i]
            for g, i, t, payload in rec.entries:
                self.payloads[(g, i, t)] = payload
            for g, slot, op in rec.confs:
                self.h_mask[g, slot] = (op == CONF_ADD)
                if op == CONF_ADD:
                    # Live _apply_conf zeroes a joining slot's state (it may
                    # have a stale former life); replay must match, or the
                    # restarted slot would claim a log it no longer has.
                    self.h_term[g, slot] = 0
                    self.h_vote[g, slot] = 0
                    self.h_commit[g, slot] = 0
                    self.h_last[g, slot] = 0
                    self.h_ring[g, slot] = 0
                    slot_log.pop((int(g), int(slot)), None)
                elif not self.h_mask[g].any():
                    # This REMOVE flip deprovisioned the tenant: replay the
                    # host-side reset AT THIS POINT in the flip sequence —
                    # a remove+re-create batched into the same record must
                    # reset between the two, or the re-created tenant's
                    # fresh indices land below the stale apply cursor and
                    # acked writes vanish while old data resurfaces.
                    g = int(g)
                    self.applied[g] = 0
                    self._stores.pop(g, None)
                    for k in [k for k in self.payloads if k[0] == g]:
                        del self.payloads[k]
        self.round_no = last_round + 1

        # Device state: followers everywhere, logs/HS restored.
        self.st = base._replace(
            term=jnp.asarray(self.h_term),
            vote=jnp.asarray(self.h_vote),
            commit=jnp.asarray(self.h_commit),
            last_index=jnp.asarray(self.h_last),
            log_term=jnp.asarray(self.h_ring),
            peer_mask=jnp.asarray(self.h_mask),
        )
        self.h_state = np.zeros((G, P), np.int32)  # all followers
        # Committed terms across ALL slots: where committed, every slot's
        # log agrees at an index (log matching), so any slot with
        # commit >= i supplies THE term. Zero terms are placeholder slots
        # (e.g. zeroed by a snapshot install) and are skipped.
        hist: Dict[Tuple[int, int], int] = {}
        for (g, p), entries in slot_log.items():
            c = int(self.h_commit[g, p])
            lastv = int(self.h_last[g, p])
            for i, t in entries.items():
                if t > 0 and i <= c and i <= lastv:
                    hist.setdefault((g, i), t)
        # Re-apply the committed-but-unapplied suffix; hist supplies entry
        # terms older than the live ring window.
        self._apply_committed(trigger=False, hist=hist)
        self._gc_payloads()
        # Admitted-but-uncommitted conf entries survive restart in the
        # payload store; the committed-conf scan must stay armed for them
        # (its short-circuit would otherwise skip binding the mask flip
        # into the committing round's durable record).
        self._confs_outstanding = sum(
            1 for (g, i, t), p in self.payloads.items()
            if p and p[0] == P_CONF and i > self.applied[g])

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def start(self) -> None:
        self._install_flight_signal()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="multi-engine")
        self._thread.start()

    def dump_flight(self, reason: str = "manual") -> Optional[str]:
        """Write the flight-recorder ring as Chrome trace-event JSON
        under <data_dir>/diagnostics; returns the path (None on
        failure). Also reachable via SIGUSR2 and GET /debug/flight."""
        return self.obs.flight.dump(self.cfg.data_dir, reason)

    def _install_flight_signal(self) -> None:
        """SIGUSR2 -> flight dump. Best-effort: only the main thread
        may install handlers (tests start engines from worker threads),
        and with several engines in one process the last one started
        owns the signal — the /debug/flight endpoint and fail-stop
        auto-dump cover the rest."""
        import signal as _signal
        if not hasattr(_signal, "SIGUSR2"):
            return
        try:
            _signal.signal(_signal.SIGUSR2,
                           lambda _s, _f: self.dump_flight("sigusr2"))
        except ValueError:
            pass

    def stop(self) -> None:
        self._stop_ev.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                # A wedged device round still owns the WAL and the applier
                # queue; draining or closing under it would race.
                log.error("engine thread did not stop in 10s; leaving "
                          "final round unflushed")
                return
        if self.failed is None:
            try:
                self._drain_applies()
            except Exception as e:  # noqa: BLE001 — applier's deferred error
                self.failed = e
        for sh in self._appliers:
            with sh.cv:
                sh.stop = True
                sh.cv.notify_all()
        for sh in self._appliers:
            if sh.thread is not None:
                sh.thread.join(timeout=10)
        # Parked quorum reads can never ripen once the round loop is
        # down; fail them now instead of letting clients ride out the
        # request timeout.
        self._fail_parked_reads("engine stopped")
        self.wal.close()

    # ------------------------------------------------------------------
    # applier pool (cfg.pipeline_applies, cfg.applier_shards)
    # ------------------------------------------------------------------

    @property
    def acked_requests(self) -> int:
        """Client REQUESTS acked in LIVE rounds (not entries: a batched
        entry carries many; restart replay does not count). The
        serving-throughput counter — meters measure deltas. Summed across
        the synchronous path and every applier shard's own tally."""
        return self._acks.acked + sum(sh.acct.acked
                                      for sh in self._appliers)

    def _commit_view(self) -> tuple:
        """Immutable snapshot of what the applier needs from this round's
        mirrors: per-group commit (masked max over live slots), the slot
        holding it, the ring/last arrays it resolves terms from, and the
        WAL durability ticket ack release gates on (wait_durable). The
        mirror arrays are replaced (never mutated) each round, so handing
        references across threads is safe. The trailing round number is
        for the flight recorder's applied/acked marks."""
        c = np.where(self.h_mask, self.h_commit, 0)
        return (c.max(axis=1), c.argmax(axis=1), self.h_ring, self.h_last,
                self.wal.ticket, self.round_no)

    def _ensure_appliers(self) -> None:
        for sh in self._appliers:
            t = sh.thread
            if t is None or not t.is_alive():
                if sh.exc is not None:
                    # The worker HALTed mid-span; respawning would
                    # re-apply (and re-ack) the queued view from the
                    # top. Stay down — the seam re-raises.
                    continue
                sh.stop = False
                sh.thread = threading.Thread(
                    target=self._applier_loop, args=(sh,), daemon=True,
                    name=f"engine-applier-{sh.idx}")
                sh.thread.start()

    def _applier_loop(self, sh: _ApplierShard) -> None:
        o = self.obs if self.obs.enabled else None
        tr = self.obs.tracer
        if o:
            o.thread_cpu.register("applier")
        while True:
            with sh.cv:
                while not sh.q and not sh.stop:
                    sh.cv.wait(0.2)
                if not sh.q:
                    return           # stop requested and queue drained
                view = sh.q[0]       # stays queued while in progress
            try:
                # Applies run ahead of the WAL pipeline; the acks they
                # produce are collected and released only once the
                # view's durability ticket clears the writer's
                # watermark (ack-after-fsync, gated not ordered).
                batch = _AckBatch()
                self._apply_committed(trigger=True, view=view,
                                      g_lo=sh.g_lo, g_hi=sh.g_hi,
                                      acct=sh.acct, sink=batch)
                if o:
                    o.flight.mark(view[5], obs_mod.APPLIED)
                if batch.acked or batch.items:
                    t_gate = time.perf_counter()
                    self.wal.wait_durable(view[4])
                    t_acked = time.perf_counter()
                    if o:
                        o.h_ack_wait.observe(t_acked - t_gate)
                    every = tr.every
                    if every:
                        # `durable` is the writer's own stamp for the
                        # view's ticket (a side stamp: applies run ahead
                        # of the WAL); `acked` ends the gate segment.
                        t_dur = None
                        for rid, _res in batch.items:
                            if rid % every == 0:
                                if t_dur is None:
                                    t_dur = (self.wal.durable_at(view[4])
                                             or t_acked)
                                tr.mark(rid, "durable", t=t_dur,
                                        ticket=view[4])
                                tr.mark(rid, "acked", t=t_acked,
                                        acked_round=view[5])
                    # The whole ack batch at once: the waiters the event
                    # loop front registered are released under one signal.
                    self.wait.trigger_many(batch.items)
                    sh.acct.acked += batch.acked
                    if o:
                        o.c_acked.inc(batch.acked)
                        o.h_appl_batch[sh.idx].observe(batch.acked)
                        o.flight.mark(view[5], obs_mod.ACKED)
            except Exception as e:  # noqa: BLE001 — re-raised at the seam
                log.exception("engine applier shard %d failed", sh.idx)
                self.obs.flight.dump(self.cfg.data_dir,
                                     f"applier-shard-{sh.idx}")
                with sh.cv:
                    sh.exc = e
                    sh.cv.notify_all()
                # HALT — consuming further views after a mid-span failure
                # would re-apply and re-ack around the hole. The engine
                # fail-stops at the next enqueue/drain, which re-raises.
                return
            with sh.cv:
                sh.q.popleft()
                sh.cv.notify_all()

    def _enqueue_apply(self, view: tuple) -> None:
        """Hand one round's committed work to every applier shard,
        blocking while the DEEPEST shard's backlog is at the cap (bounds
        ack latency under saturation; a sum-bound would let one hot
        shard spend the other shards' latency budget)."""
        self._ensure_appliers()
        o = self.obs if self.obs.enabled else None
        for sh in self._appliers:
            with sh.cv:
                while (len(sh.q) >= self.cfg.apply_queue_rounds
                       and sh.exc is None):
                    sh.cv.wait(0.5)
                sh.q.append(view)
                if o:
                    o.g_appl_queue[sh.idx].set(len(sh.q))
                sh.cv.notify_all()
        self._raise_apply_exc()

    def _drain_applies(self) -> None:
        """Block until every queued apply on every shard finished; then
        surface any applier error. All synchronous seams (conf changes,
        checkpoints, admin surgery, stop) come through here before
        touching state the appliers also own (stores, applied, payload
        GC)."""
        for sh in self._appliers:
            if sh.thread is not None:
                with sh.cv:
                    while (sh.q and sh.exc is None
                           and sh.thread.is_alive()):
                        sh.cv.notify_all()
                        sh.cv.wait(0.5)
        self._raise_apply_exc()
        for sh in self._appliers:
            if sh.q and (sh.thread is None or not sh.thread.is_alive()):
                raise RuntimeError(
                    f"applier shard {sh.idx} died with work queued")

    def _raise_apply_exc(self) -> None:
        # sh.exc stays set: a HALTed shard is terminally failed (its
        # worker never respawns — see _ensure_appliers), so EVERY later
        # seam re-raises rather than letting one caller absorb the
        # error and the next one sail past a dead compartment.
        for sh in self._appliers:
            if sh.exc is not None:
                raise sh.exc

    def store(self, g: int):
        s = self._stores.get(g)
        if s is None:
            # Lock: HTTP handler threads race the engine apply thread on
            # first touch of a tenant; an unsynchronized check-then-set
            # could discard a Store already holding applied writes.
            # Namespaces match the classic server's store (reference
            # store.New(StoreClusterPrefix, StoreKeysPrefix)) so an empty
            # tenant serves GET /v2/keys/ identically.
            with self._lock:
                s = self._stores.get(g)
                if s is None:
                    s = self._stores[g] = new_store(namespaces=("/0", "/1"))
        return s

    def leader_slot(self, g: int) -> int:
        """The group's current leader slot (of the highest term, where a
        deposed leader has not heard of its successor yet), or -1. Only
        ACTIVE slots count —
        a just-removed slot's device row freezes in whatever state it held
        (reference removed-member tombstones make its traffic inert the same
        way, server.go:387-391)."""
        slot, term = self._leaders(slice(g, g + 1))
        return int(slot[0]) if term[0] > 0 else -1

    def _leaders(self, gs=slice(None)) -> Tuple[np.ndarray, np.ndarray]:
        """(slot, term) of the routable leader of each group in `gs` (all
        by default): the active LEADER row of the HIGHEST term, term 0
        where there is none. A leader cut off from its peers stays LEADER
        in its old term until it hears a higher one (no check-quorum), so
        a group can show two (_multi_lead); the device's read plane
        registers at the same row (kernel._read_register)."""
        lt = np.where(self.h_mask[gs] & (self.h_state[gs] == _LEADER),
                      self.h_term[gs], 0)
        return lt.argmax(axis=1), lt.max(axis=1)

    def wait_leaders(self, timeout: float = 30.0, groups=None) -> bool:
        """Block until every (requested) PROVISIONED group has a leader —
        unprovisioned pool slots have no peers and never elect."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            gs = (np.nonzero(self.h_mask.any(axis=1))[0]
                  if groups is None else groups)
            if all(self.leader_slot(int(g)) >= 0 for g in gs):
                return True
            time.sleep(0.005)
        return False

    def do(self, g: int, r: Request, timeout: Optional[float] = None) -> Any:
        """Serve one request against group g (the engine's Do,
        reference server.go:519-576). Reads are local; writes ride the
        kernel's consensus."""
        if r.method == METHOD_GET:
            if r.quorum:
                if not r.wait:
                    # The zero-append read plane: ReadIndex confirmation
                    # + local serve; no log entry, no WAL bytes, no
                    # fsync. (A quorum WATCH still rides the propose
                    # path below, unchanged.)
                    return self._quorum_read(g, r, timeout)
                r = Request(**{**r.__dict__, "method": METHOD_QGET})
            elif r.wait:
                # A long-poll holds its handler thread for as long as the
                # client waits: the front's span leaves watches out.
                obs_mod.front.kind = "watch"
                return self.store(g).watch(r.path, r.recursive, r.stream,
                                           r.since)
            else:
                return self.store(g).get(r.path, r.recursive, r.sorted)
        if r.method not in (METHOD_PUT, METHOD_POST, METHOD_DELETE,
                            METHOD_QGET, METHOD_SYNC):
            raise errors.EtcdError(errors.ECODE_INVALID_FORM,
                                   cause=f"bad method {r.method}")
        if r.id == 0:
            r = Request(**{**r.__dict__, "id": self.reqid.next()})
        obs_on = self.obs.enabled
        tr = self.obs.tracer
        q = self.wait.register(r.id)
        payload = bytes([P_REQ]) + r.encode()
        t0 = time.perf_counter()
        if tr.every and r.id % tr.every == 0:
            tr.mark(r.id, "submit", t=t0, g=g)
        with self._lock:
            # The decoded Request rides along so the live apply path never
            # re-parses JSON it already has (replay still decodes bytes);
            # the enqueue time rides too, for the staging-queue wait.
            self._pending[g].append((r.id, payload, r, t0))
            self._dirty.add(g)
        self._work.set()
        # Reference proposal metrics (etcdserver/metrics.go), previously
        # observed only by the legacy server.py path.
        if obs_on:
            metrics.propose_pending.inc()
        try:
            result = q.get(timeout=timeout or self.cfg.request_timeout)
        except queue.Empty:
            if obs_on:
                metrics.propose_failed.inc()
                self._front_handoff("write", time.perf_counter() - t0,
                                    r.id)
            self.wait.cancel(r.id)
            raise errors.EtcdError(errors.ECODE_RAFT_INTERNAL,
                                   cause="request timed out",
                                   index=int(self.applied[g]))
        finally:
            if obs_on:
                metrics.propose_pending.dec()
        if obs_on:
            dt = time.perf_counter() - t0
            metrics.propose_durations.observe(dt * 1000.0)
            self._front_handoff("write", dt, r.id)
        if isinstance(result, errors.EtcdError):
            # Application-level error (e.g. a failed CAS) — served, not
            # a failed proposal; propose_failed counts only proposals
            # that never produced a result.
            raise result
        if type(result) is LazyWriteEvent:
            # The ack/waiter stage woke us with raw C descriptors; the
            # Event/NodeExtern churn happens HERE, on the serving thread,
            # off the (serialized) apply stage.
            return result.resolve()
        return result

    def _front_handoff(self, kind: str, blocked: float,
                       rid: Optional[int] = None) -> None:
        """The serving thread runs again after its ack: hand the HTTP
        front (etcdhttp/web.py) the time it spent blocked in here and the
        request's kind through obs.front, instead of clocking the same
        wait twice; a sampled rid also gets its front_in (the handler's
        start, recorded now that the rid exists) and woke marks."""
        fl = obs_mod.front
        fl.blocked += blocked
        fl.kind = kind
        tr = self.obs.tracer
        if rid is not None and tr.every and tr.sampled(rid):
            if fl.t_in:
                tr.mark(rid, "front_in", t=fl.t_in)
            tr.mark(rid, "woke")
            fl.trace = (tr, rid)

    # ------------------------------------------------------------------
    # the event-loop front's submit (etcdhttp/web.py): nobody parks a
    # thread; results reach the front through a utils.wait.Sink
    # ------------------------------------------------------------------

    @property
    def tracer(self):
        """The sampled-request tracer, for the front's own marks."""
        return self.obs.tracer

    def submit_pairs(self, pairs: List[Tuple[int, Request]],
                     sink: Sink) -> List[Any]:
        """Stage one select pass of the front's requests, writes and
        `?quorum=true` reads of any tenants, under ONE lock acquisition,
        and return at once with one token per pair (settle() or expire()
        takes it back; a pair that cannot be staged has an EtcdError in
        its token's place and is not staged). A write is enqueued exactly
        as do() enqueues it; a quorum read is parked exactly as
        _quorum_read parks it, under the same lock the round takes its
        read_take snapshot under, so a read parked after a round's
        snapshot waits for its own round's confirmation. Results come
        through `sink` from the same trigger calls that release a blocked
        do(): a write's only after its round's fsync (the applier's
        release behind wait_durable), a read's from _serve_ripe_reads."""
        obs_on = self.obs.enabled
        tr = self.obs.tracer
        every = tr.every
        register = self.wait.register
        writes: List[tuple] = []
        reads: List[tuple] = []
        tokens: List[Any] = []
        t0 = time.perf_counter()
        for g, r in pairs:
            # One request's fault refuses that request: its place in
            # `tokens` holds the error, nothing of it is registered or
            # staged, and the rest of the pass (other tenants') goes on.
            try:
                if r.id == 0:
                    r = Request(**{**r.__dict__, "id": self.reqid.next()})
                read = r.method == METHOD_GET
                if read:
                    if not r.quorum or r.wait:
                        raise errors.EtcdError(
                            errors.ECODE_INVALID_FORM,
                            cause="submit_pairs takes writes and quorum "
                                  "reads only")
                    item = (g, r)
                elif r.method in (METHOD_PUT, METHOD_POST, METHOD_DELETE):
                    item = (g, (r.id, bytes([P_REQ]) + r.encode(), r, t0))
                else:
                    raise errors.EtcdError(errors.ECODE_INVALID_FORM,
                                           cause=f"bad method {r.method}")
                register(r.id, sink)        # ValueError: a duplicate id
            except Exception as e:  # noqa: BLE001 — answered, per request
                tokens.append(e if isinstance(e, errors.EtcdError) else
                              errors.EtcdError(errors.ECODE_RAFT_INTERNAL,
                                               cause=str(e)))
                if every and r.id:
                    tr.drop(r.id)
                continue
            (reads if read else writes).append(item)
            tokens.append(_Submitted(r.id, g, read, t0))
            if every and r.id % every == 0:
                tr.mark(r.id, "submit", t=t0, g=g)
        lease = 0
        try:
            with self._lock:
                pending, dirty = self._pending, self._dirty
                for g, item in writes:
                    pending[g].append(item)
                    dirty.add(g)
                for g, r in reads:
                    lease += self._park_read(g, r)
            self._work.set()
        except BaseException:
            # Nobody will hold these tokens: leave no waiter behind.
            for tok in tokens:
                if type(tok) is _Submitted:
                    self.wait.cancel(tok.rid)
            raise
        if obs_on:
            if writes:
                metrics.propose_pending.inc(len(writes))
            if reads:
                self.obs.g_read_parked.inc(len(reads))
                if lease:
                    self.obs.c_reads_lease.inc(lease)
        return tokens

    def settle(self, toks: List["_Submitted"],
               results: List[Any]) -> List[Any]:
        """The front drained these tokens' results from its sink in one
        wake: observe what do() / _quorum_read observe when their thread
        wakes (a wake's writes and its reads in one call each), and
        return the results with every LazyWriteEvent resolved (here, off
        the apply stage). An EtcdError result is returned, not raised."""
        if self.obs.enabled:
            now = time.perf_counter()
            writes = [(now - t.t0) * 1000.0 for t in toks if not t.read]
            reads = [(now - t.t0) * 1000.0 for t in toks if t.read]
            if writes:
                metrics.propose_pending.dec(len(writes))
                metrics.propose_durations.observe_many(writes)
            if reads:
                self.obs.g_read_parked.dec(len(reads))
                self.obs.s_read_dur.observe_many(reads)
        return [r.resolve() if type(r) is LazyWriteEvent else r
                for r in results]

    def expire(self, tok: "_Submitted") -> errors.EtcdError:
        """The front's sweep found `tok` past cfg.request_timeout (or is
        shutting down): cancel the waiter and answer what a timed-out
        do() / _quorum_read raises, with their counters."""
        self.wait.cancel(tok.rid)
        if self.obs.enabled:
            if tok.read:
                self.obs.c_reads_failed.inc()
                self.obs.g_read_parked.dec()
            else:
                metrics.propose_failed.inc()
                metrics.propose_pending.dec()
        return errors.EtcdError(
            errors.ECODE_RAFT_INTERNAL,
            cause=("quorum read timed out" if tok.read
                   else "request timed out"),
            index=int(self.applied[tok.g]))

    def do_many(self, g: int, reqs: List[Request],
                timeout: Optional[float] = None) -> List[Any]:
        """Serve a BATCH of write requests against group g from one
        caller (the ingress tier's coalesced submission surface): all of
        them are enqueued under ONE lock acquisition, so the next round's
        staging packs them into deep P_MULTI log entries — the exact
        multi-request packing `do()` traffic already coalesces into, which
        keeps the WAL format and replay path unchanged (an entry written
        through this path is indistinguishable from one that coalesced
        out of N concurrent `do()` calls).

        Returns one result per request, in request order. Application
        errors (failed CAS, auth, timeout) come back IN-SLOT as EtcdError
        instances instead of raising — the caller is a demultiplexer that
        must fan each slot's outcome back to a different waiting client,
        so one bad request must never poison its batch-mates. Results are
        only produced after the engine's ack path released the waiters,
        i.e. after this batch's round is fsync-durable — an ingress crash
        after `do_many` returns can never lose an acked write."""
        return self.collect_many(g, self.submit_many(g, reqs), timeout)

    def submit_many(self, g: int, reqs: List[Request]) -> List[tuple]:
        """The NON-BLOCKING half of do_many: validate, assign request
        ids, register wait queues and stage everything under one lock
        acquisition — then return immediately with the (rid, queue)
        tokens collect_many() blocks on. The batchframe channel
        (etcdhttp/tenants.py) submits frame N+1 through this before
        frame N's round has committed, which is what lets a pipelined
        ingress window keep the staging queue deep instead of draining
        it to zero between flushes. Submission order IS log-staging
        order per group, so frames submitted in channel-arrival order
        keep the lane's FIFO."""
        for r in reqs:
            if r.method not in (METHOD_PUT, METHOD_POST, METHOD_DELETE,
                                METHOD_QGET, METHOD_SYNC):
                raise errors.EtcdError(errors.ECODE_INVALID_FORM,
                                       cause=f"bad batch method {r.method}")
        obs_on = self.obs.enabled
        tr = self.obs.tracer
        items = []
        queues = []
        t0 = time.perf_counter()
        for r in reqs:
            if r.id == 0:
                r = Request(**{**r.__dict__, "id": self.reqid.next()})
            if tr.every and r.id % tr.every == 0:
                tr.mark(r.id, "submit", t=t0, g=g)
            queues.append((r.id, self.wait.register(r.id)))
            items.append((r.id, bytes([P_REQ]) + r.encode(), r, t0))
        with self._lock:
            self._pending[g].extend(items)
            if items:
                self._dirty.add(g)
        self._work.set()
        if obs_on:
            for _ in range(len(items)):
                metrics.propose_pending.inc()
        return queues

    def collect_many(self, g: int, queues: List[tuple],
                     timeout: Optional[float] = None) -> List[Any]:
        """The BLOCKING half of do_many: gather one result per submitted
        (rid, queue) token, in submission order, timing out slots that
        never produce one. Only returns results the ack path released —
        i.e. after their round's fsync."""
        obs_on = self.obs.enabled
        n = len(queues)
        t0 = time.perf_counter()
        deadline = t0 + (timeout or self.cfg.request_timeout)
        out = []
        try:
            for rid, q in queues:
                try:
                    result = q.get(
                        timeout=max(0.0, deadline - time.perf_counter()))
                except queue.Empty:
                    if obs_on:
                        metrics.propose_failed.inc()
                    self.wait.cancel(rid)
                    out.append(errors.EtcdError(
                        errors.ECODE_RAFT_INTERNAL,
                        cause="request timed out",
                        index=int(self.applied[g])))
                    continue
                if type(result) is LazyWriteEvent:
                    result = result.resolve()
                out.append(result)
        finally:
            if obs_on:
                for _ in range(n):
                    metrics.propose_pending.dec()
        if obs_on and n:
            # One batch = one client-visible submission window; the
            # per-request proposal latency is the window's mean.
            window = time.perf_counter() - t0
            dt = window * 1000.0 / n
            for _ in range(n):
                metrics.propose_durations.observe(dt)
            self._front_handoff("write", window)
        return out

    # ------------------------------------------------------------------
    # the read plane (batched ReadIndex; zero-append quorum reads)
    # ------------------------------------------------------------------

    def _mirror_term(self, g: int) -> int:
        return int(np.where(self.h_mask[g], self.h_term[g], 0).max())

    def _mirror_commit(self, g: int) -> int:
        return int(np.where(self.h_mask[g], self.h_commit[g], 0).max())

    def _quorum_read(self, g: int, r: Request,
                     timeout: Optional[float] = None) -> Any:
        """Linearizable GET without a log entry (the reference's
        ReadIndex protocol, raft read_only.go, batched over all G
        groups): park the read, let the next round's ReadIndex step
        confirm the group's leader still holds a quorum and capture its
        commit index, then serve from the local store once the apply
        cursor reaches that index. Quorum reads leave the
        etcd_server_proposal_* families entirely (nothing is proposed)
        and meter the read_index_* families instead."""
        if r.id == 0:
            r = Request(**{**r.__dict__, "id": self.reqid.next()})
        obs_on = self.obs.enabled
        tr = self.obs.tracer
        q = self.wait.register(r.id)
        t0 = time.perf_counter()
        if tr.every and r.id % tr.every == 0:
            tr.mark(r.id, "submit", t=t0, g=g)
        with self._lock:
            lease = self._park_read(g, r)
        self._work.set()
        if obs_on:
            if lease:
                self.obs.c_reads_lease.inc()
            self.obs.g_read_parked.inc()
        try:
            result = q.get(timeout=timeout or self.cfg.request_timeout)
        except queue.Empty:
            if obs_on:
                self.obs.c_reads_failed.inc()
                self._front_handoff("qread", time.perf_counter() - t0,
                                    r.id)
            self.wait.cancel(r.id)
            raise errors.EtcdError(errors.ECODE_RAFT_INTERNAL,
                                   cause="quorum read timed out",
                                   index=int(self.applied[g]))
        finally:
            if obs_on:
                self.obs.g_read_parked.dec()
        if obs_on:
            dt = time.perf_counter() - t0
            self.obs.s_read_dur.observe(dt * 1000.0)
            self._front_handoff("qread", dt, r.id)
        if isinstance(result, errors.EtcdError):
            raise result
        return result

    def _park_read(self, g: int, r: Request) -> int:
        """Park one quorum read (self._lock held): on the group's parked
        queue for the next round's ReadIndex confirmation or, inside a
        live read lease, straight on the ripe queue. Returns 1 for the
        lease path, else 0."""
        if (self.cfg.read_lease_ms > 0
                and time.monotonic() < float(self._lease_until[g])
                and int(self._lease_term[g]) == self._mirror_term(g)):
            # Lease fast path: a confirmation round within the lease
            # window proved leadership, and the lease term still
            # matches — skip the confirmation and park directly at
            # the CURRENT commit mirror (>= every acked write's
            # index, so acked writes stay visible).
            self._ripe[g].append((r.id, r, self._mirror_commit(g)))
            self._ripe_dirty.add(g)
            self._ripe_waiting += 1
            return 1
        self._reads[g].append((r.id, r))
        self._read_dirty.add(g)
        self._reads_waiting += 1
        return 0

    def _confirm_reads(self, read_take: Dict[int, int], conf: np.ndarray,
                       rc: np.ndarray) -> List[int]:
        """Move snapshotted parked reads of confirmed groups to the ripe
        queue at this round's captured read index. Only the
        PRE-DISPATCH snapshot count moves — a read that parked after the
        step was dispatched could postdate a write acked at a commit
        index above the captured one, so it waits for its own round.
        Unconfirmed groups keep their reads parked: a deposed leader's
        reads either re-confirm under the next leader (at its >= read
        index — still linearizable) or time out; never served stale.
        Returns the sampled rids among the reads moved (the round stamps
        their `staged` and `confirmed` from its own clock readings)."""
        o = self.obs if self.obs.enabled else None
        every = self.obs.tracer.every
        sampled: List[int] = []
        n_conf = 0
        now = time.monotonic()
        lease_s = self.cfg.read_lease_ms / 1000.0
        with self._lock:
            for g, take in read_take.items():
                if not conf[g]:
                    continue
                n_conf += 1
                ri = int(rc[g])
                dq = self._reads[g]
                moved = min(take, len(dq))
                for _ in range(moved):
                    item = dq.popleft()
                    self._ripe[g].append(item + (ri,))
                    if every and item[0] % every == 0:
                        sampled.append(item[0])
                if moved:
                    self._ripe_dirty.add(g)
                    self._ripe_waiting += moved
                    self._reads_waiting -= moved
                if not dq:
                    self._read_dirty.discard(g)
                if lease_s > 0:
                    # A confirmed quorum round proves leadership NOW;
                    # the clock bound extends it lease_ms forward.
                    self._lease_until[g] = now + lease_s
                    self._lease_term[g] = self._mirror_term(g)
        if o:
            o.h_read_confirms.observe(n_conf)
        return sampled

    def _serve_ripe_reads(self) -> None:
        """Serve every ripe read whose group's apply cursor has reached
        its read index. Queue surgery holds self._lock; the store gets
        (GIL-released in the C core) and waiter triggers run outside
        it. Per group the ripe queue is FIFO and read indexes are
        nondecreasing (commit is monotone within a term, and a new
        leader's own-term-committed index covers everything previously
        committed), so serving stops at the first not-yet-applied
        head."""
        served: List[Tuple[int, Request, int]] = []
        with self._lock:
            for g in list(self._ripe_dirty):
                dq = self._ripe[g]
                a = int(self.applied[g])
                while dq and dq[0][2] <= a:
                    rid, r, _ri = dq.popleft()
                    served.append((rid, r, g))
                if not dq:
                    self._ripe_dirty.discard(g)
            self._ripe_waiting -= len(served)
        if not served:
            return
        o = self.obs if self.obs.enabled else None
        tr = self.obs.tracer
        every = tr.every
        # Read coalescing: every read in this pass is at-or-past its
        # read index NOW, so one store get per distinct (group, path,
        # recursive, sorted) answers all of them — the get's instant
        # lies inside every coalesced read's [park, serve] window,
        # which is all linearizability requires. (The reference serves
        # a whole ReadIndex batch from one state the same way,
        # read_only.go advance; hot-key read storms collapse to one
        # tree walk per key per round.)
        memo: Dict[Tuple[int, str, bool, bool], Any] = {}
        results: List[Tuple[int, Any]] = []
        for rid, r, g in served:
            k = (g, r.path, r.recursive, r.sorted)
            result = memo.get(k)
            if result is None:
                try:
                    result = self.store(g).get(r.path, r.recursive,
                                               r.sorted)
                except errors.EtcdError as err:
                    result = err
                memo[k] = result
            results.append((rid, result))
            if every and rid % every == 0:
                tr.mark(rid, "acked", acked_round=self.round_no)
        self.wait.trigger_many(results)
        if o:
            o.c_reads_served.inc(len(served))

    def _fail_parked_reads(self, why: str) -> None:
        """Fail every parked and ripe quorum read (engine shutdown) so
        serving threads don't ride out the full request timeout."""
        rids: List[int] = []
        with self._lock:
            for g in self._read_dirty:
                rids.extend(rid for rid, _r in self._reads[g])
                self._reads[g].clear()
            for g in self._ripe_dirty:
                rids.extend(rid for rid, _r, _i in self._ripe[g])
                self._ripe[g].clear()
            self._read_dirty.clear()
            self._ripe_dirty.clear()
            self._reads_waiting = 0
            self._ripe_waiting = 0
        for rid in rids:
            self.wait.trigger(rid, errors.EtcdError(
                errors.ECODE_RAFT_INTERNAL, cause=why))

    def conf_change(self, g: int, op: str, slot: int,
                    timeout: Optional[float] = None) -> List[int]:
        """Propose a membership change for group g through its own
        consensus; returns the new active slot list (reference
        configure() server.go:640-662 + multinode group management)."""
        if not 0 <= slot < self.cfg.peers:
            raise ValueError(f"slot {slot} out of range")
        if op == "add":
            if self.h_mask[g, slot]:
                raise errors.EtcdError(errors.ECODE_NODE_EXIST,
                                       cause=f"slot {slot} already active")
        elif op == "remove":
            if not self.h_mask[g, slot]:
                raise errors.EtcdError(errors.ECODE_KEY_NOT_FOUND,
                                       cause=f"slot {slot} not active")
        else:
            raise ValueError(op)
        rid = self.reqid.next()
        payload = bytes([P_CONF]) + json.dumps(
            {"id": rid, "op": op, "slot": slot}).encode()
        q = self.wait.register(rid)
        with self._lock:
            self._pending[g].append((rid, payload, None))
            self._dirty.add(g)
            self._confs_outstanding += 1
        self._work.set()
        try:
            result = q.get(timeout=timeout or self.cfg.request_timeout)
        except queue.Empty:
            self.wait.cancel(rid)
            raise errors.EtcdError(errors.ECODE_RAFT_INTERNAL,
                                   cause="conf change timed out")
        if isinstance(result, errors.EtcdError):
            raise result
        return result

    # ------------------------------------------------------------------
    # tenant lifecycle (the engine's CreateGroup/RemoveGroup — reference
    # raft/multinode.go:181-218 — over a fixed pre-compiled pool)
    # ------------------------------------------------------------------

    def tenant_active(self, g: int) -> bool:
        """Provisioned = at least one active peer slot."""
        return True in self.h_mask[g].tolist()

    def tenants(self) -> List[int]:
        return [int(g) for g in np.nonzero(self.h_mask.any(axis=1))[0]]

    def create_tenant(self, g: Optional[int] = None,
                      n_peers: Optional[int] = None,
                      timeout: Optional[float] = None) -> int:
        """Provision a tenant group at runtime (g=None allocates the
        lowest free pool slot). Returns the group id once the creation is
        DURABLE (its conf flips fsynced in a round record). No
        recompilation: the kernel shape is the pool; creation is a masked
        state reset + peer-mask flips, exactly the shape a committed
        membership change already takes in the WAL — so replay needs no
        new machinery."""
        n = n_peers or self.cfg.initial_peers or self.cfg.peers
        if not 1 <= n <= self.cfg.peers:
            raise ValueError(f"n_peers {n} out of range 1..{self.cfg.peers}")
        return self._admin({"op": "create", "g": g, "n": n}, timeout)

    def remove_tenant(self, g: int,
                      timeout: Optional[float] = None) -> int:
        """Deprovision a tenant: all peer slots go inactive, its store,
        payloads and pending proposals are dropped (pending waiters get an
        error), and the pool slot becomes reusable."""
        return self._admin({"op": "remove", "g": int(g)}, timeout)

    def _admin(self, op: dict, timeout: Optional[float]) -> int:
        done = threading.Event()
        out: dict = {}
        item = (op, done, out)
        with self._lock:
            self._admin_q.append(item)
        self._work.set()
        if not done.wait(timeout or self.cfg.request_timeout):
            # Withdraw the op if it never started — a timed-out create must
            # not silently provision later (a client retry would then
            # consume a second pool slot). If it already left the queue,
            # give the in-flight execution a short grace.
            with self._lock:
                try:
                    self._admin_q.remove(item)
                    withdrawn = True
                except ValueError:
                    withdrawn = False
            if withdrawn or not done.wait(2.0):
                raise errors.EtcdError(errors.ECODE_RAFT_INTERNAL,
                                       cause="tenant admin op timed out")
        if "err" in out:
            raise out["err"]
        return out["g"]

    def _process_admin(self) -> None:
        """Apply queued tenant ops at a round boundary: device surgery via
        the shared per-slot conf machinery (CONF_ADD zeroes the slot on
        both live and replay paths — a freshly created tenant IS a set of
        added slots). The flips are persisted in their OWN record at this
        boundary, BEFORE the upcoming round's record: live surgery happens
        before the round runs, so replay must zero the slot before it sees
        that round's term/vote/commit deltas — appending the flips to the
        round's record would replay them AFTER its HS deltas and wipe the
        new group's first campaign (a restarted slot could then re-vote at
        a term it already voted in). Requester acks fire after the flips'
        fsync."""
        self._drain_applies()    # applies must not straddle the surgery
        with self._lock:
            ops = list(self._admin_q)
            self._admin_q.clear()
        for op, done, out in ops:
            try:
                if op["op"] == "create":
                    g = op["g"]
                    if g is None:
                        free = np.nonzero(~self.h_mask.any(axis=1))[0]
                        if not len(free):
                            raise errors.EtcdError(
                                errors.ECODE_RAFT_INTERNAL,
                                cause=f"tenant pool exhausted "
                                      f"({self.cfg.groups} groups)")
                        g = int(free[0])
                    g = int(g)
                    if not 0 <= g < self.cfg.groups:
                        raise errors.EtcdError(
                            errors.ECODE_KEY_NOT_FOUND,
                            cause=f"group {g} outside pool")
                    if self.h_mask[g].any():
                        raise errors.EtcdError(
                            errors.ECODE_NODE_EXIST,
                            cause=f"tenant {g} already provisioned")
                    self._tenant_reset(g)
                    for s in range(op["n"]):
                        self._apply_conf(g, "add", s, admin=True)
                        self._admin_flips.append((g, s, CONF_ADD))
                    # Fast first election (same trick as boot stagger).
                    el = np.asarray(self.st.elapsed).copy()
                    el[g, g % op["n"]] = 2 * self.cfg.election_tick
                    self.st = self.st._replace(
                        elapsed=self._dev("elapsed", el))
                    out["g"] = g
                else:
                    g = int(op["g"])
                    if not (0 <= g < self.cfg.groups
                            and self.h_mask[g].any()):
                        raise errors.EtcdError(
                            errors.ECODE_KEY_NOT_FOUND,
                            cause=f"no such tenant {g}")
                    for s in np.nonzero(self.h_mask[g])[0]:
                        self._apply_conf(g, "remove", int(s), admin=True)
                        self._admin_flips.append((g, int(s), CONF_REMOVE))
                    self._tenant_reset(g)
                    out["g"] = g
            except Exception as e:  # noqa: BLE001 — relayed to requester
                out["err"] = e
                done.set()
                continue
            self._admin_acks.append(done)
        if self._admin_flips:
            rec = RoundRecord(round_no=self.round_no)
            rec.confs.extend(self._admin_flips)
            self._admin_flips = []
            self.wal.append_sync(rec)     # fsync: the op is durable NOW
            self._recent_recs.append(rec)
        for done in self._admin_acks:
            done.set()
        self._admin_acks = []

    def _tenant_reset(self, g: int) -> None:
        """Drop all host-side state of a pool slot (store, payloads,
        apply cursor, queued proposals)."""
        self.tenant_gen[g] += 1
        st = self._stores.pop(g, None)
        if st is not None:
            st.watcher_hub.clear()   # wake/close blocked watchers
        self.applied[g] = 0
        self._sync_pending.pop(g, None)
        for k in [k for k in self.payloads if k[0] == g]:
            del self.payloads[k]
            self.payload_reqs.pop(k, None)
        with self._lock:
            dq = self._pending[g]
            while dq:
                rid = dq.popleft()[0]
                self.wait.trigger(rid, errors.EtcdError(
                    errors.ECODE_RAFT_INTERNAL, cause="tenant removed"))
            self._dirty.discard(g)

    def _stage_syncs(self, now: float) -> None:
        """Enqueue METHOD_SYNC for every tenant whose store holds an
        expiration <= now. At most one SYNC in flight per tenant (a
        leaderless group must not accumulate one queued SYNC per interval);
        the inflight marker self-heals by deadline in case the SYNC entry
        is orphaned by a leader change and never applies."""
        # A snapshot of the keys, not of the items: appliers add stores
        # while this runs, and G (g, store) tuples twice a second are G
        # containers for the collector to count and promote.
        stores = self._stores
        due = [g for g in list(stores)
               if (s := stores.get(g)) is not None
               and (x := s.next_expiration()) is not None and x <= now
               and self._sync_pending.get(g, 0.0) <= now]
        if not due:
            return
        redeadline = now + max(2.0, 10 * self.cfg.sync_interval)
        with self._lock:
            for g in due:
                self._sync_pending[g] = redeadline
                r = Request(method=METHOD_SYNC, time=now,
                            id=self.reqid.next())
                self._pending[g].append((r.id, bytes([P_REQ]) + r.encode(),
                                         r))
                self._dirty.add(g)

    def status(self, g: int) -> dict:
        """Introspection snapshot for one group (/debug/vars analogue)."""
        lead = self.leader_slot(g)
        return {
            "group": g,
            "lead": lead,
            "term": int(self.h_term[g].max()),
            "commit": int(self.h_commit[g].max()),
            "applied": int(self.applied[g]),
            "active_slots": [int(s) for s in np.nonzero(self.h_mask[g])[0]],
        }

    def device_info(self) -> dict:
        """Where the kernel state lives, for /engine/status: the backend
        as JAX reports it, and per device the group rows it holds of the
        state arrays and the inbox (one int where every array agrees —
        G on one device, G/n on each device of a groups-sharded mesh — a
        sorted list where they differ). Read from the live buffers
        (addressable_shards), not from the requested sharding."""
        devs = self._jax.devices()
        for _ in range(100):
            try:
                rows: Dict[int, set] = {}
                for a in (*self.st, self.inbox):
                    for sh in a.addressable_shards:
                        rows.setdefault(sh.device.id, set()).add(
                            sh.data.shape[0])
                break
            except RuntimeError:
                # The round thread donated these buffers to the step
                # between our read of self.st and the shard walk.
                time.sleep(0.001)
        else:
            raise RuntimeError("engine state buffers stayed deleted")
        info = {
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "device_rows": {
                str(d): (r.pop() if len(r) == 1 else sorted(r))
                for d, r in sorted(rows.items())},
        }
        # memory_stats() is None where the backend keeps none (cpu).
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        if None not in peaks:
            info["device_peak_bytes"] = max(peaks)
        return info

    # ------------------------------------------------------------------
    # the round
    # ------------------------------------------------------------------

    def _run(self) -> None:
        if self.obs.enabled:
            self.obs.thread_cpu.register("round")
        # From here run_round ends in the gap phase: one run_round's end
        # to the next one's start (the wait for work after an idle round
        # and the wait to get the interpreter back); a caller that drives
        # run_round itself sees none.
        self._looping = True
        try:
            if self._compact:
                self._warm_gather()
            # (the need-host surgery's two programs: a pass over no group)
            self._need_host_pass(np.zeros(0, np.int64))
            while not self._stop_ev.is_set():
                self.run_round()
                # A round with nothing to do is a round's worth of the
                # interpreter taken from the front while it parses the
                # next cohort: wait for whoever queues work to say so
                # (one set() a submit_pairs call, i.e. a front's pass), or
                # for the idle tick.
                self._work.clear()
                if self._idle():
                    self._work.wait(IDLE_TICK_S)
        except Exception as e:  # noqa: BLE001 — record, then re-raise
            self.failed = e
            self._stop_ev.set()
            raise
        finally:
            self._looping = False

    def _idle(self) -> bool:
        """Nothing for a round to do but tick: the last round staged
        nothing, confirmed no read and journalled nothing, nothing is
        queued, every group has a leader and every leader has committed
        all it admitted (an entry waiting on a retransmit, a step-down or
        an election is carried there at round speed, not a tick at a
        time)."""
        return (self._quiet and not self._dirty and not self._reads_waiting
                and not self._ripe_waiting and not self._admin_q
                and not self._confs_outstanding and self._settled())

    def run_round(self) -> None:
        """One engine round. Callable directly (tests drive the engine
        synchronously); the background thread just loops it."""
        t_round = time.perf_counter()
        jnp, kernel = self._jnp, self._kernel
        G, P, W, E = (self.cfg.groups, self.cfg.peers, self.cfg.window,
                      self.cfg.max_ents)
        o = self.obs if self.obs.enabled else None
        clock = o.clock if o else None
        r_no = self.round_no
        self._last_admitted = 0
        self._trace_rids.clear()
        if o:
            clock.lap("stage", t_round)
            self._d2h_n = self._d2h_b = 0
            self._h2d_n = self._h2d_b = 0
            self._rec_gather = self._rec_admit = 0.0
            o.flight.mark(r_no, obs_mod.SUBMITTED, t_round)

        # -- -1. tenant lifecycle admin ops (rare; round-boundary surgery)
        if self._admin_q:
            self._process_admin()

        # -- 0. TTL expiry: stage a replicated SYNC into tenants holding a
        # DUE expiration (leader-clock cutoff; deletion applies — and
        # replays — deterministically from the log).
        if self.cfg.sync_interval:
            now = time.time()
            if now - self._last_sync_scan >= self.cfg.sync_interval:
                self._last_sync_scan = now
                self._stage_syncs(now)
                if o:
                    o.h_sync_scan.observe(time.time() - now)

        # -- 0b. entries a deposed leader admitted and the committed log
        # has overwritten go back to their queues (only after an election)
        if self._resolve:
            self._repropose_lost()

        # -- 1. stage proposals at known leaders --------------------------
        self._staged.clear()
        with self._lock:
            if self._dirty:
                # One vectorized pass instead of a per-group leader_slot
                # call (16k np calls/round at bench scale); .tolist() once
                # beats 16k numpy scalar __getitem__s in the loop below.
                lead_rows = (np.where(self.h_mask, self.h_state, 0)
                             == _LEADER)
                has_lead = lead_rows.any(axis=1).tolist()
                lead_slots = lead_rows.argmax(axis=1).tolist()
                # Where a group shows two LEADER rows the first is not
                # the one: the row of the HIGHEST term is (a deposed leader
                # that has not heard of its successor gets nothing).
                two = [g for g in self._dirty if g in self._multi_lead]
                if two:
                    for g, s in zip(two, self._leaders(two)[0].tolist()):
                        lead_slots[g] = s
            B = self.cfg.batch_max
            for g in list(self._dirty):
                dq = self._pending[g]
                if not dq:
                    self._dirty.discard(g)
                    continue
                if not has_lead[g]:
                    continue
                s = lead_slots[g]
                # Pack queued requests into at most E log entries of up to
                # B requests each (group commit): conf changes stay
                # singleton entries (their committed-boundary scan keys on
                # the payload tag), plain requests coalesce.
                ents: List[List[Tuple[int, bytes]]] = []
                while dq and len(ents) < E:
                    if dq[0][1] and dq[0][1][0] == P_CONF:
                        ents.append([dq.popleft()])
                        continue
                    cur: List[Tuple[int, bytes]] = []
                    nbytes = 0
                    while (dq and len(cur) < B
                           and nbytes < self.cfg.batch_bytes and dq[0][1]
                           and dq[0][1][0] == P_REQ):
                        nbytes += len(dq[0][1])
                        cur.append(dq.popleft())
                    if not cur:
                        # Head is neither P_CONF nor P_REQ (empty or junk
                        # tag): consume it or the group jams on count=0
                        # entries forever; fail its waiter immediately
                        # rather than letting the client ride out the
                        # full request timeout.
                        rid, junk = dq.popleft()[:2]
                        log.error("engine: dropping untagged proposal "
                                  "g=%d rid=%d len=%d", g, rid, len(junk))
                        self.wait.trigger(rid, errors.EtcdError(
                            errors.ECODE_RAFT_INTERNAL,
                            cause="untagged proposal dropped"))
                        continue
                    ents.append(cur)
                if not dq:
                    self._dirty.discard(g)
                if ents:    # (none: every queued item was junk)
                    self._staged[g] = (s, ents)
        # One pass builds the staged index arrays; they feed the two
        # scatter writes here AND the admission gather after the step
        # (_staged is round-thread-private and not mutated in between).
        # Batching replaces ~2*G numpy scalar stores at ~0.2 µs each.
        # (prop: the round's one upload, row 0 the entries staged a group,
        # row 1 its leader's slot; None when nothing was staged)
        staged_gs = staged_ss = prop = None
        if self._staged:
            gs_l, ss_l, cnt_l = [], [], []
            waited = o.h_pending_wait.observe if o else None
            t_staged = time.perf_counter() if o else 0.0
            every = o.tracer.every if o else 0
            for g, (s, ents) in self._staged.items():
                gs_l.append(g)
                ss_l.append(s)
                cnt_l.append(len(ents))
                if waited:
                    # Queue wait of each request staged this round (items
                    # enqueued by do()/submit_many carry their time; a
                    # requeued item carries it negated, so each counts
                    # once, and a sampled one keeps its first `staged`:
                    # its queue segment is the interval observed here).
                    for items in ents:
                        for it in items:
                            if len(it) > 3 and it[3] > 0:
                                waited(t_staged - it[3])
                                if every and it[0] % every == 0:
                                    o.tracer.mark(it[0], "staged",
                                                  t=t_staged,
                                                  staged_round=r_no)
            staged_gs = np.asarray(gs_l, np.int64)
            staged_ss = np.asarray(ss_l, np.int64)
            prop = np.zeros((2, G), np.int32)
            prop[0, staged_gs] = cnt_l
            prop[1, staged_gs] = ss_l

        # -- 1b. read plane: snapshot how many parked quorum reads each
        # group carries BEFORE the step is dispatched. A read parking
        # after this point must not adopt this round's confirmation —
        # an applier running under the device step could ack a write
        # whose commit index exceeds the index this round captures, and
        # serving such a late read at the captured index would miss that
        # acked write. The snapshot pins exactly which reads this
        # round's confirmation covers (see tests/test_read_plane.py).
        read_take: Optional[Dict[int, int]] = None
        if self._reads_waiting:
            with self._lock:
                if self._reads_waiting:
                    read_take = {g: len(self._reads[g])
                                 for g in self._read_dirty
                                 if self._reads[g]}

        # -- 1c. fault injection: this round's held follower slots, or its
        # slots cut off from their peers (the map's upkeep and its upload)
        hold = down = None
        if self._lag is not None or self._churn is not None:
            with self.obs.span("etcd.round.fault_map"):
                hold = self._lag_hold() if self._lag is not None else None
                down = (self._churn_down() if self._churn is not None
                        else None)

        if o:
            t_take = t_ph = time.perf_counter()
            clock.lap("dispatch", t_ph)

        # -- 2. the kernel round (fused step + routing: one ASYNC
        # dispatch; jax queues it and returns immediately). The lap hands
        # over ONE upload, the staged array, onto the placement both
        # programs take it in, and none in a round that staged nothing (the
        # boot-time zeros; neither program donates it) ---------------------
        tick = self._ticks[self.round_no % self.cfg.ticks_per_round == 0]
        if prop is None:
            prop_d = self._prop_zero
        else:
            prop_d = self._put(prop)
            if o:
                self._h2d(prop_d)
        if o:
            t_up = time.perf_counter()
        flags_d = anh_d = None
        conf_d = rc_d = None
        if read_take:
            # A ReadIndex round is a full round (proposals, ticks and
            # the forced leader heartbeat all ride the same program),
            # and its step returns the same on-device diff as the
            # compact step's: one record builder serves both.
            st, inbox, conf_d, rc_d, f_d, a_d, stats_d = self._step_fn_r(
                self.st, self.inbox, prop_d, tick, hold, down)
            if self._compact:
                flags_d, anh_d = f_d, a_d
        elif self._compact:
            st, inbox, flags_d, anh_d, stats_d = self._step_fn_c(
                self.st, self.inbox, prop_d, tick, hold, down)
        else:
            st, inbox, stats_d = self._step_fn(
                self.st, self.inbox, prop_d, tick, hold, down)
        self.st = st
        self.inbox = inbox
        if o:
            t_step = time.perf_counter()
        # The compact round's one readback, enqueued right behind the
        # step with no host read in between: gather_rows picks the rows
        # that changed (and the staged leader rows) where the flag map
        # lies and packs them with the attestation and the hops'
        # counts into one buffer, from the six fields it reads. A round
        # after a surgery takes the full readback whatever the device
        # says, and asks for nothing here.
        gather = buf_d = None
        if flags_d is not None and not self._force_full:
            gather = (self._gather_fields(st), flags_d, anh_d, stats_d,
                      prop_d)
            kp = self._gather_bucket(len(self._staged))
            buf_d = self._gather_rows(*gather, kp)
        self._staged_prev = len(self._staged)
        if o:
            t_now = time.perf_counter()
            d_dispatch = t_now - t_ph
            part = o.h_dispatch_part
            part["upload"].observe(t_up - t_ph)
            part["step"].observe(t_step - t_up)
            part["gather"].observe(t_now - t_step)
            t_ph = t_now
            clock.lap("readback", t_now)

        # -- 3. read back round k (blocks until the device finishes; the
        # GIL is released while waiting, so the applier thread makes
        # progress on earlier rounds' committed work here). Compact mode
        # makes ONE blocking read, of gather_rows' packed buffer: the
        # attestation, the changed rows' flags and their values; need_host
        # rounds and rounds changing more rows than the cap take the full
        # readback below. ------------------------------------------------
        rec = None
        need_host = None
        # Which readback builds this round's record: "compact" (the rows
        # gather_rows packed), "over_cap" (the attempt, then the full
        # readback) or "full".
        readback_kind = "full"
        d_readback = d_record = 0.0
        if buf_d is not None:
            buf = np.asarray(buf_d)
            hop_stats = buf[0, self._kernel.HEAD_STATS:][
                :2 * self.cfg.hops].reshape(2, -1)
            if o:
                self._d2h(buf_d)
            if not buf[0, 0]:           # the device attests: no need-host
                if o:
                    t_now = time.perf_counter()
                    d_readback = t_now - t_ph
                    t_ph = t_stepped = t_now
                    clock.lap("record", t_now)
                rec = self._compact_record_admit(buf, kp, gather,
                                                 staged_gs, staged_ss)
                # Over the cap (rec is None) the attempt still counts as
                # record; the full readback below is a second readback lap.
                readback_kind = "compact" if rec is not None else "over_cap"
                if o:
                    t_now = time.perf_counter()
                    d_record = t_now - t_ph
                    t_ph = t_conf = t_now
                    clock.lap("tail" if rec is not None else "readback",
                              t_now)
        if rec is None:
            full = (st.term, st.vote, st.commit, st.state,
                    st.last_index, st.log_term, st.need_host, stats_d)
            (term, vote, commit, state, last, ring, need_host,
             hop_stats) = (np.array(a) for a in self._jax.device_get(full))
            if o:
                t_now = time.perf_counter()
                d_readback += t_now - t_ph
                t_ph = t_stepped = t_now
                self._d2h(*full)
                clock.lap("record", t_now)

            # Violation check FIRST — before this round's WAL append,
            # applies, or acks: a flagged round's commits come from state
            # the kernel just classified as untrustworthy, and must never
            # reach clients.
            if need_host.any():
                from etcd_tpu.ops.state import NH_VIOLATION
                viol = (need_host & NH_VIOLATION) != 0
                if viol.any():
                    self._fail_violation(viol)

            # -- 5. durable round record ----------------------------------
            rec = RoundRecord(round_no=self.round_no)
            chg = (term != self.h_term) | (vote != self.h_vote) | \
                  (commit != self.h_commit)
            gi, pi = np.nonzero(chg)
            rec.hs_g, rec.hs_p = gi.astype(np.uint32), pi.astype(np.uint16)
            rec.hs_term = term[gi, pi].astype(np.uint32)
            rec.hs_vote = vote[gi, pi].astype(np.uint16)
            rec.hs_commit = commit[gi, pi].astype(np.uint32)

            last_chg = last != self.h_last
            gi, pi = np.nonzero(last_chg)
            rec.last_g = gi.astype(np.uint32)
            rec.last_p = pi.astype(np.uint16)
            rec.last_v = last[gi, pi].astype(np.uint32)

            # Ring diff in two stages: a vectorized per-row any-reduction
            # finds the rows whose ring changed (SIMD compare — NOT the
            # 3-axis np.nonzero over (G, P, W) that dominated host cost
            # at 100k groups), then the slot-level diff runs only on
            # those rows. The full compare is required for correctness:
            # an equal-length conflict overwrite can change ring terms in
            # a round where that row's term/vote/commit/last are ALL
            # unchanged (the follower adopted the new leader's term in an
            # earlier round), so a HardState-based row filter would
            # silently drop the overwrite from the WAL and crash replay
            # would resurrect superseded entries.
            act_g, act_p = np.nonzero(np.any(ring != self.h_ring, axis=2))
            if len(act_g):
                sub = ring[act_g, act_p] != self.h_ring[act_g, act_p]
                ai, wi = np.nonzero(sub)
                gi, pi = act_g[ai], act_p[ai]
                lastv = last[gi, pi]
                # ring slot w holds absolute index
                # i = last - ((last - w) mod W)
                absi = lastv - ((lastv - wi) % W)
                keep = absi >= 1
                rec.ring_g = gi[keep].astype(np.uint32)
                rec.ring_p = pi[keep].astype(np.uint16)
                rec.ring_i = absi[keep].astype(np.uint32)
                rec.ring_t = ring[gi[keep], pi[keep],
                                  wi[keep]].astype(np.uint32)

            # Index assignment for admitted proposals: a pre-existing
            # leader admits in order at prev_last+1.. (its last_index can
            # move this round ONLY by admission: it was already leader,
            # so no no-op, and leaders ignore MsgApp).
            if self._staged:
                # Batch-gather the admission scalars: one fancy-indexed
                # pull per array instead of 6 numpy scalar reads per
                # staged group, reusing the index arrays built at staging
                # time.
                gs, ss = staged_gs, staged_ss
                t_gs = term[gs, ss]
                adm_l = np.where((state[gs, ss] == _LEADER)
                                 & (t_gs == self.h_term[gs, ss]),
                                 last[gs, ss] - self.h_last[gs, ss],
                                 0).tolist()
                self._admit_staged(rec, adm_l, t_gs.tolist(),
                                   self.h_last[gs, ss].tolist())

            gs_role = np.nonzero((state != self.h_state).any(axis=1))[0]
            led = self._leaders(gs_role) if len(gs_role) else None
            self.h_term, self.h_vote, self.h_commit = term, vote, commit
            self.h_state, self.h_last, self.h_ring = state, last, ring
            self._force_full = False   # mirrors == device state again
            if led is not None:
                self._leaders_moved(gs_role, led)
            if o:
                t_conf = t_now = time.perf_counter()
                d_record += t_now - t_ph
                clock.lap("tail", t_now)

        # -- 5b. read plane: pop the snapshotted reads of every group
        # whose ReadIndex confirmation landed into the ripe queue at the
        # captured commit index (either record path above leaves the
        # mirrors the confirmation consults equal to this round's device
        # state).
        if conf_d is not None:
            sampled = self._confirm_reads(read_take, np.asarray(conf_d),
                                          np.asarray(rc_d))
            if o:
                self._d2h(conf_d)
                self._d2h(rc_d)
                # A sampled read's `staged` is the pre-dispatch reading of
                # the round that confirmed it, `confirmed` the reading
                # behind that round's readback (phase boundaries both).
                for rid in sampled:
                    o.tracer.mark(rid, "staged", t=t_take,
                                  staged_round=r_no)
                    o.tracer.mark(rid, "confirmed", t=t_conf)

        # -- 6. persist, then apply+ack. WAL fsync strictly precedes the
        # acks of everything this round committed (doc.go:31-39 ordering)
        # — by GATING, not by inline ordering: the record is handed to
        # the writer compartment (which group-commits it with its queue
        # neighbors on its own thread) and the applier workers withhold
        # waiter wakeups until the writer's durability watermark passes
        # this round's ticket. Applies may run ahead of the fsync; acks
        # may not. Membership flips committed this round must be in the
        # SAME durable record as the round that commits them (replay
        # re-applies them) — and conf traffic forces the SYNCHRONOUS
        # path: applying a conf performs device-state surgery that must
        # precede the next dispatch, so the record is appended+fsynced
        # before the inline apply below (append_sync).
        if o:
            o.h_step.observe(d_dispatch + d_readback)
            # record's parts: what _compact_record_admit / _admit_staged
            # clocked, and the rest of the phase as build.
            part = o.h_rec_part
            if readback_kind == "compact":
                part["gather"].observe(self._rec_gather)
            part["admit"].observe(self._rec_admit)
            part["build"].observe(
                d_record - self._rec_gather - self._rec_admit)
            o.c_readback[readback_kind].inc()
            hop_busy, hop_passes = hop_stats
            n_full = int(np.count_nonzero(hop_busy))
            o.c_step_hops["full"].inc(n_full)
            o.c_step_hops["quiet"].inc(len(hop_busy) - n_full)
            o.c_step_passes.inc(int(hop_passes.sum()))
            o.flight.mark(r_no, obs_mod.STEPPED, t_stepped)
            if self._staged:
                o.h_batch.observe(self._last_admitted)
        rec.confs.extend(self._collect_committed_confs())
        sync_round = bool(rec.confs or self._confs_outstanding
                          or not self.cfg.pipeline_applies)
        if not rec.is_empty():
            t0 = time.perf_counter() if o else 0.0
            with self.obs.span("etcd.round.wal_submit"):
                if sync_round:
                    self.wal.append_sync(rec)
                else:
                    self.wal.submit(rec)
            if o:
                o.h_wal_submit.observe(time.perf_counter() - t0)
                o.flight.mark(r_no, obs_mod.WAL_SUBMITTED)
            if self._trace_rids:
                tr = self.obs.tracer
                t_sub = time.perf_counter()
                for rid in self._trace_rids:
                    tr.mark(rid, "wal_submit", t=t_sub,
                            ticket=self.wal.ticket)
                    if sync_round:      # append_sync returned: it is
                        tr.mark(rid, "durable", t=self.wal.durable_at(
                            self.wal.ticket) or t_sub)
            self._recent_recs.append(rec)
        if sync_round:
            self._drain_applies()
            a0 = self._acks.acked
            self._apply_committed(trigger=True)
            if o:
                o.flight.mark(r_no, obs_mod.APPLIED)
                o.flight.mark(r_no, obs_mod.ACKED)
                if self._acks.acked > a0:
                    o.c_acked.inc(self._acks.acked - a0)
        else:
            self._enqueue_apply(self._commit_view())

        # -- 6b. read plane: serve every ripe read whose group has
        # applied past its read index. Sync rounds serve their own reads
        # immediately (the inline apply above advanced the cursor);
        # pipelined rounds serve reads the applier shards ripened while
        # the device step ran — at most one round of extra latency.
        if self._ripe_waiting:
            self._serve_ripe_reads()

        # -- 7. need_host: snapshot-install lagging followers (violations
        # already failed the round before anything was persisted or
        # acked). need_host is None on a compact round — the device
        # already attested any_need_host == False for it.
        if need_host is not None and need_host.any():
            with self.obs.span("etcd.round.need_host"):
                self._service_need_host(need_host)

        # What _idle asks of the round just run, whichever readback built
        # its record (a surgery leaves the next round a diff to journal).
        self._quiet = (rec.is_empty() and not self._staged
                       and not read_take and not self._force_full)

        if o:
            clock.lap("post", time.perf_counter())
            o.c_rounds.inc()
        self.round_no += 1
        if (self.cfg.mask_check_rounds
                and self.round_no % self.cfg.mask_check_rounds == 0):
            self._check_mask()
        ms = (time.perf_counter() - t_round) * 1000.0
        if self._ewma_live:
            self.round_ms_ewma += 0.05 * (ms - self.round_ms_ewma)
        elif self._ewma_armed:
            self._ewma_live = True
            self.round_ms_ewma = ms
        else:
            # The boot rounds are not the serving cadence: the first one
            # pays the step's compile and, staggered, elects every group
            # in the same call. Seed with the round after the first that
            # ended with a leader everywhere.
            self._ewma_armed = self._all_led()
        if self.round_no % self.cfg.checkpoint_rounds == 0:
            t0 = time.perf_counter() if o else 0.0
            self._drain_applies()    # checkpoint state must be consistent
            self._checkpoint()
            if self._resolve:
                # (the GC below drops what the groups have applied past:
                # first take back what was lost there, not applied)
                self._repropose_lost()
            self._gc_payloads()
            if o:
                o.h_checkpoint.observe(time.perf_counter() - t0)
        if o:
            if self._d2h_n:
                o.c_d2h_syncs.inc(self._d2h_n)
                o.c_d2h_bytes.inc(self._d2h_b)
            o.c_h2d_syncs.inc(self._h2d_n)
            o.c_h2d_bytes.inc(self._h2d_b)
            # (opened here, not in _run: the thread can lose the
            # interpreter for tens of ms on its way out of this call)
            clock.lap("gap" if self._looping else None, time.perf_counter())

    def _lag_hold(self):
        """This round's held follower slots (server/lag.py), on the
        device, sharded like the state's (G, P) fields on a mesh."""
        held = self._lag.held(self.round_no, self.h_mask, self.h_state)
        if self.obs.enabled:
            self.obs.c_lag_releases.inc(
                np.count_nonzero(self._lag_held & ~held))
            self.obs.g_lag_held.set(np.count_nonzero(held))
        self._lag_held = held
        return self._dev("state", held)

    def _churn_down(self):
        """This round's down slots (server/lag.py ChurnSchedule), on the
        device, sharded like the state's (G, P) fields on a mesh: the cuts
        that ended come up, and each group whose cut begins loses its
        working leader, if it has one and at least two other peers."""
        r = self.round_no
        down = self._down
        moved = self._down_d is None
        ends = self._churn.ending(r)
        if len(ends) and down[ends].any():
            down[ends] = False
            moved = True
        starts = self._churn.starting(r)
        if len(starts):
            slot, term = self._leaders(starts)
            ok = (term > 0) & (self.h_mask[starts].sum(axis=1) >= 3)
            if ok.any():
                down[starts] = False
                down[starts[ok], slot[ok]] = True
                moved = True
                n = int(np.count_nonzero(ok))
                self.churn_cuts += n
                if self.obs.enabled:
                    self.obs.c_churn_cuts.inc(n)
        if moved:
            # (a copy: jnp.asarray of a live numpy array may share it)
            self._down_d = self._dev("state", down.copy())
            if self.obs.enabled:
                self.obs.g_churn_down.set(np.count_nonzero(down))
        return self._down_d

    def _leaders_moved(self, gs: np.ndarray, before: tuple) -> None:
        """The mirrors of the groups `gs` were just updated and a row in
        each changed role; `before` is what _leaders(gs) gave before the
        update. Which of them show two LEADER rows is kept for staging
        (_multi_lead); a group whose routable leader is a new one (another
        slot or term) is counted, its _lead_since set, and, as its predecessor
        may have admitted entries that the new leader's log does not hold,
        handed to _repropose_lost."""
        slot0, term0 = before
        slot1, term1 = self._leaders(gs)
        many = (self.h_mask[gs]
                & (self.h_state[gs] == _LEADER)).sum(axis=1) > 1
        self._multi_lead.difference_update(gs[~many].tolist())
        self._multi_lead.update(gs[many].tolist())
        new = (term1 > 0) & ((term1 != term0) | (slot1 != slot0))
        if not new.any():
            return
        g_new = gs[new]
        self._lead_since[g_new] = time.perf_counter()
        self._resolve.update(g_new.tolist())
        if self.obs.enabled:
            self.obs.c_leader_changes.inc(len(g_new))

    def _repropose_lost(self) -> None:
        """Propose again what a deposed leader admitted and the committed
        log has overwritten, ONCE THE HOST KNOWS FOR CERTAIN, for the
        groups in _resolve (those that changed leader since).

        An admitted entry (g, i, t) of an older term than the group's
        routable leader's can never commit, by log matching, if
        (a) the group has applied index i and it was not this entry that
            was applied there (the applier pops an entry's payload_reqs
            key before it moves `applied` on, so a key still there at
            i <= applied[g] was passed over: the committed entry at i has
            another term), or
        (b) i lies beyond the group's commit index c and the committed
            entry at c has a later term than t: every log that can still
            win an election holds that entry at c, and only entries of its
            term or later behind it.
        Its requests whose clients still wait go back to the HEAD of the
        group's queue in their order and are staged at the new leader;
        the waiter, the request id and the front's timeout sweep stay as
        they are. Nothing is proposed again on suspicion (a timeout, a
        step-down seen, a missing ack), so no request is applied twice.
        Conf entries keep their own handling (_gc_payloads)."""
        res = self._resolve
        old: Dict[int, list] = {}
        for k in list(self.payload_reqs):       # (appliers pop meanwhile)
            if k[0] in res:
                old.setdefault(k[0], []).append(k)
        res.clear()
        if not old:
            return
        W = self.cfg.window
        gs = np.fromiter(old, np.int64, len(old))
        _, lterm = self._leaders(gs)
        cm = np.where(self.h_mask[gs], self.h_commit[gs], 0)
        sc = cm.argmax(axis=1)
        c = cm[np.arange(len(gs)), sc]
        in_ring = (c >= 1) & (c > self.h_last[gs, sc] - W)
        tc = np.where(in_ring, self.h_ring[gs, sc, c % W], 0)
        requeue = []
        for g, lt, c_g, tc_g in zip(gs.tolist(), lterm.tolist(), c.tolist(),
                                    tc.tolist()):
            keys = sorted((k for k in old[g] if k[2] < lt),
                          key=lambda k: (k[2], k[1]))
            if lt == 0 or not keys:
                if lt == 0:
                    res.add(g)              # no leader yet: look again
                continue
            a_g = int(self.applied[g])      # read BEFORE the pop below
            items = []
            for k in keys:
                _, i, t = k
                if not (i <= a_g or (i > c_g and tc_g > t)):
                    res.add(g)              # undecided: look again
                    continue
                ent = self.payload_reqs.pop(k, None)
                if ent is None:
                    continue                # applied under our feet
                payload = self.payloads.pop(k, None)
                reqs, t0 = ent
                if payload is None:
                    blobs = [bytes([P_REQ]) + r.encode() for r in reqs]
                elif payload[0] == P_MULTI:
                    blobs = [bytes([P_REQ]) + b
                             for b in _unpack_multi(payload)]
                else:
                    blobs = [payload]
                items.extend((r.id, b, r, -t0) for r, b in zip(reqs, blobs)
                             if self.wait.is_registered(r.id))
            if items:
                requeue.append((g, items))
        if not requeue:
            return
        n = 0
        with self._lock:
            for g, items in requeue:
                self._pending[g].extendleft(reversed(items))
                self._dirty.add(g)
                n += len(items)
        self.reproposed += n
        if self.obs.enabled:
            self.obs.c_reproposed.inc(n)

    def _d2h(self, *arrays) -> None:
        """Count one blocking device->host read of `arrays` (called
        under obs.enabled only; flushed to the counters once a round)."""
        self._d2h_n += 1
        for a in arrays:
            self._d2h_b += a.nbytes

    def _h2d(self, *arrays) -> None:
        """Count the upload of each of `arrays`, host->device (called
        under obs.enabled only; flushed to the counters once a round)."""
        self._h2d_n += len(arrays)
        for a in arrays:
            self._h2d_b += a.nbytes

    def _all_led(self) -> bool:
        """Every provisioned group's mirror shows a leader."""
        led = (np.where(self.h_mask, self.h_state, 0) == _LEADER).any(axis=1)
        return bool(led[self.h_mask.any(axis=1)].all())

    def _settled(self) -> bool:
        """Every provisioned group's mirror shows a leader whose log is
        committed to its end: the routable one. What a deposed leader that
        has not heard of its successor still holds uncommitted is lost,
        not pending (_repropose_lost), and keeps no idle member awake."""
        slot, term = self._leaders()
        at = np.arange(len(slot))
        return (bool((term > 0)[self.h_mask.any(axis=1)].all())
                and not (self.h_commit[at, slot]
                         < self.h_last[at, slot])[term > 0].any())

    def _admit_staged(self, rec: RoundRecord, adm_l: list, t_l: list,
                      base_l: list) -> None:
        """Turn this round's staged entries into payload-store entries +
        WAL records (admitted) or requeue them (rejected: the group's
        leader changed or throttled admission). Shared by the full- and
        compact-readback tails; iteration order is self._staged's
        insertion order, which both tails' scalar lists follow."""
        requeue: List[Tuple[int, List[Tuple[int, bytes]]]] = []
        tr = self.obs.tracer
        every = tr.every
        n_admitted = 0
        t_admit = time.perf_counter()
        ann = self.obs.span("etcd.record.admit")
        ann.__enter__()
        # A write older than its group's leader waited for it: in the
        # queue, or in an entry the leader before admitted and lost.
        since = self._lead_since
        waited = (self.obs.h_leaderless_wait.observe
                  if self.obs.enabled else None)
        for (g, (_, ents)), admitted, t, base in zip(
                self._staged.items(), adm_l, t_l, base_l):
            for j, items in enumerate(ents):
                if j < admitted:
                    i = base + 1 + j
                    payload = _pack_entry(items)
                    self.payloads[(g, i, t)] = payload
                    if payload[0] != P_CONF:
                        reqs = [it[2] for it in items]
                        if None not in reqs:
                            it = items[0]
                            t0 = abs(it[3]) if len(it) > 3 else 0.0
                            self.payload_reqs[(g, i, t)] = (reqs, t0)
                            if waited and 0.0 < t0 < since[g]:
                                for it in items:
                                    if len(it) > 3:
                                        waited(t_admit - abs(it[3]))
                    n_admitted += len(items)
                    if every:
                        for it in items:
                            if it[0] % every == 0:
                                tr.mark(it[0], "admitted",
                                        round=rec.round_no)
                                self._trace_rids.append(it[0])
                    rec.entries.append((g, i, t, payload))
                else:
                    # (rid, payload, request, -enqueue time): negated,
                    # its queue wait was observed at this staging.
                    requeue.append(
                        (g, [it[:3] + (-abs(it[3]),) if len(it) > 3 else it
                             for e in ents[j:] for it in e]))
                    break
        self._last_admitted = n_admitted
        if requeue:
            with self._lock:
                for g, rest in requeue:
                    self._pending[g].extendleft(reversed(rest))
                    self._dirty.add(g)
        ann.__exit__(None, None, None)
        self._rec_admit += time.perf_counter() - t_admit

    def _gather_bucket(self, n_staged: int) -> int:
        """The size bucket (a power of two >= 256, one compiled program
        each) asked of gather_rows for the round being dispatched, from
        what the host can see before the step runs: the most rows any of
        the last heartbeat_tick + 1 compact rounds picked, and the groups
        staged now and in the last round (a staged group changes its P
        rows in the round that admits its entries, and its followers'
        when they learn the commit: with the next append or heartbeat).
        A round that outgrows it is read twice
        (etcd_engine_gather_rebuckets_total)."""
        want = max(max(self._gather_ks),
                   self.cfg.peers * (n_staged + self._staged_prev))
        return _bucket(min(want, self._compact_cap))

    def _gather_fields(self, st) -> tuple:
        """What gather_rows reads of the state `st`."""
        return tuple(getattr(st, f) for f in self._kernel.GATHER_FIELDS)

    def _gather_buckets(self) -> List[int]:
        """Every bucket a round of this geometry can ask for."""
        top = _bucket(min(self._compact_cap,
                          self.cfg.groups * self.cfg.peers))
        return [1 << i for i in range(8, top.bit_length())]

    def _warm_gather(self) -> None:
        """Build (compile, or load from the persistent cache) gather_rows
        for every bucket before the first round, on arguments placed as a
        round's are (jit keys its programs on that too), so that no
        serving round ever waits for one."""
        jax, jnp = self._jax, self._jnp
        G, P = self.cfg.groups, self.cfg.peers
        flags_d = jnp.zeros((G, P), jnp.uint8)
        anh_d = jnp.zeros((), bool)
        stats_d = jnp.zeros((2, self.cfg.hops), jnp.int32)
        if self.cfg.mesh is not None:
            from etcd_tpu.parallel.mesh import (flag_sharding,
                                                replicated_sharding)
            rep = replicated_sharding(self.cfg.mesh)
            flags_d = jax.device_put(flags_d, flag_sharding(self.cfg.mesh))
            anh_d = jax.device_put(anh_d, rep)
            stats_d = jax.device_put(stats_d, rep)
        fields = self._gather_fields(self.st)
        for kp in self._gather_buckets():
            self._gather_rows(fields, flags_d, anh_d, stats_d,
                              self._prop_zero, kp).block_until_ready()

    def _compact_record_admit(self, buf: np.ndarray, kp: int, gather,
                              staged_gs, staged_ss
                              ) -> Optional[RoundRecord]:
        """The compact-readback round tail: build the SAME durable round
        record (byte-identical; tests/test_engine_compact.py pins it)
        and run the same admission as the full tail, from gather_rows'
        packed buffer `buf` of only the rows the device flagged as
        changed (and the staged leader rows). `gather` holds the
        arguments it was called with at bucket `kp`: when the round
        picked more rows than kp the call is made again, on the same
        outputs, at the bucket that holds them. Returns None when the
        round changed more rows than the cap — the caller then falls
        back to the full readback (saturation: the bulk transfer is
        amortized by the batch it carries)."""
        kernel = self._kernel
        P, W = self.cfg.peers, self.cfg.window
        K = int(buf[0, 1])
        self._gather_ks.append(K)
        if K > self._compact_cap:
            return None
        rec = RoundRecord(round_no=self.round_no)
        if K == 0:
            return rec
        # What is left of the gather on the host: a second call when the
        # bucket missed, and the one buffer's unpacking.
        t_gather = time.perf_counter()
        with self.obs.span("etcd.record.gather"):
            if K > kp:
                buf_d = self._gather_rows(*gather, _bucket(K))
                buf = np.asarray(buf_d)
                if self.obs.enabled:
                    self._d2h(buf_d)
                    self.obs.c_gather_rebuckets.inc()
            rows = buf[1:K + 1]
            lin = rows[:, kernel.ROW_LIN]
            chg = rows[:, kernel.ROW_FLAGS]
            t_k, v_k, c_k, s_k, l_k = (
                rows[:, c] for c in (kernel.ROW_TERM, kernel.ROW_VOTE,
                                     kernel.ROW_COMMIT, kernel.ROW_STATE,
                                     kernel.ROW_LAST))
            r_k = rows[:, kernel.ROW_RING:]
            gi, pi = np.divmod(lin, P)
        if self.obs.enabled:
            self._rec_gather += time.perf_counter() - t_gather

        # The picked rows ascend in g*P + p, the order np.nonzero walks a
        # flag map in: a bit's rows are a mask over them.
        m = (chg & kernel.CHG_HS) != 0
        rec.hs_g = gi[m].astype(np.uint32)
        rec.hs_p = pi[m].astype(np.uint16)
        rec.hs_term = t_k[m].astype(np.uint32)
        rec.hs_vote = v_k[m].astype(np.uint16)
        rec.hs_commit = c_k[m].astype(np.uint32)

        m = (chg & kernel.CHG_LAST) != 0
        rec.last_g = gi[m].astype(np.uint32)
        rec.last_p = pi[m].astype(np.uint16)
        rec.last_v = l_k[m].astype(np.uint32)

        m = (chg & kernel.CHG_RING) != 0
        if m.any():
            g2, p2 = gi[m], pi[m]
            new_rows = r_k[m]                       # (n2, W)
            sub = new_rows != self.h_ring[g2, p2]
            ai, wi = np.nonzero(sub)
            lastv = l_k[m][ai]
            absi = lastv - ((lastv - wi) % W)
            keep = absi >= 1
            rec.ring_g = g2[ai][keep].astype(np.uint32)
            rec.ring_p = p2[ai][keep].astype(np.uint16)
            rec.ring_i = absi[keep].astype(np.uint32)
            rec.ring_t = new_rows[ai, wi][keep].astype(np.uint32)

        if self._staged:
            pos_s = np.searchsorted(lin, staged_gs * P + staged_ss)
            t_gs = t_k[pos_s]
            adm_l = np.where((s_k[pos_s] == _LEADER)
                             & (t_gs == self.h_term[staged_gs, staged_ss]),
                             l_k[pos_s]
                             - self.h_last[staged_gs, staged_ss],
                             0).tolist()
            self._admit_staged(
                rec, adm_l, t_gs.tolist(),
                self.h_last[staged_gs, staged_ss].tolist())

        # Mirror update LAST (admission reads the pre-round mirrors).
        # Gathered values are authoritative for every union row —
        # writing back an unchanged staged row is a no-op. A group's
        # routable leader can change only where a row changed role.
        m = (chg & kernel.CHG_STATE) != 0
        led = None
        if m.any():
            gs_role = np.unique(gi[m])
            led = self._leaders(gs_role)
        self.h_term[gi, pi] = t_k
        self.h_vote[gi, pi] = v_k
        self.h_commit[gi, pi] = c_k
        self.h_state[gi, pi] = s_k
        self.h_last[gi, pi] = l_k
        self.h_ring[gi, pi] = r_k
        if led is not None:
            self._leaders_moved(gs_role, led)
        return rec

    # ------------------------------------------------------------------
    # apply
    # ------------------------------------------------------------------

    def _group_commit(self) -> np.ndarray:
        c = np.where(self.h_mask, self.h_commit, 0)
        return c.max(axis=1)

    def _committed_span(self, g: int):
        """(slot, lo, hi] apply span for group g using the slot that has
        the highest commit (its ring covers the span: the admission
        throttle keeps last-commit <= W/2, so hi > last - W)."""
        row = np.where(self.h_mask[g], self.h_commit[g], 0)
        s = int(row.argmax())
        return s, int(self.applied[g]), int(row[s])

    def _collect_committed_confs(self) -> List[Tuple[int, int, int]]:
        """Scan newly committed spans for conf payloads WITHOUT applying —
        their mask flips must be in the same durable record as the round
        that commits them."""
        out = []
        if self._confs_outstanding == 0:
            # Common case: no membership change in flight anywhere — skip
            # re-scanning every committed span (the apply loop scans them
            # again right after; this scan only exists to bind mask flips
            # into the committing round's durable record).
            return out
        # The scan spans applied..commit, and `applied` is applier-owned:
        # settle it first (conf rounds are rare; the drain is the price of
        # binding flips into the right record).
        self._drain_applies()
        gc = self._group_commit()
        for g in np.nonzero(gc > self.applied)[0]:
            s, lo, hi = self._committed_span(int(g))
            for i in range(lo + 1, hi + 1):
                t = int(self.h_ring[g, s, i % self.cfg.window])
                payload = self.payloads.get((int(g), i, t))
                if payload and payload[0] == P_CONF:
                    d = json.loads(payload[1:].decode())
                    op = CONF_ADD if d["op"] == "add" else CONF_REMOVE
                    out.append((int(g), d["slot"], op))
        return out

    def _apply_committed(self, trigger: bool, hist=None, view=None,
                         g_lo: int = 0, g_hi: Optional[int] = None,
                         acct: Optional[_AckCounter] = None,
                         sink: Optional[_AckBatch] = None) -> None:
        """Apply every newly committed entry (applied..commit per group)
        to its tenant store and trigger waiters. `view` is an immutable
        (gc, s_vec, ring, last, ticket) snapshot when called from an
        applier worker; None applies against the live mirrors
        (synchronous callers + replay). [g_lo, g_hi) restricts the pass
        to one shard's tenant range (workers touch only their own slice
        of self.applied and their own stores); acct is the ack tally to
        charge — the worker's own, or the engine's synchronous one.
        With `sink` set, waiter wakeups and the ack tally are DEFERRED
        into it instead of fired inline — the worker releases them after
        the view's durability ticket clears the WAL watermark.

        A write is applied one of two ways. A group whose whole span in
        this view is plain-file PUTs with no conditions and no TTL, on a
        native store with no watcher, joins the VIEW BATCH: the pass
        makes one native call over all those tenants' cores
        (_apply_view), so a cohort of writes spread over as many tenants
        costs one call, not one a request. Any other group (a condition,
        a TTL, a directory, DELETE / POST / QGET / SYNC, a conf change,
        a live watcher, a Python Store) is applied request by request
        as it is met (_apply_entries). Tenants share no state, so only
        the order inside a group is observable, and both keep it."""
        W = self.cfg.window
        tr = self.obs.tracer
        every = tr.every
        if acct is None:
            acct = self._acks
        if view is None:
            view = self._commit_view()
        gc, s_vec, h_ring, h_last = view[:4]
        if g_hi is None:
            g_hi = len(gc)
        changed = np.nonzero(gc[g_lo:g_hi] > self.applied[g_lo:g_hi])[0]
        if not len(changed):
            return
        changed += g_lo
        # One new entry a group is the common span: its term for every
        # changed group in one index, not int() by int().
        s_c, hi_c = s_vec[changed], gc[changed]
        rows = zip(changed.tolist(), s_c.tolist(),
                   self.applied[changed].tolist(), hi_c.tolist(),
                   h_last[changed, s_c].tolist(),
                   h_ring[changed, s_c, hi_c % W].tolist())
        payloads, payload_reqs = self.payloads, self.payload_reqs
        stores = self._stores
        is_reg = self.wait.is_registered
        vb = _ViewBatch()
        paths, vals, need, rids = vb.paths, vb.vals, vb.need, vb.rids
        cur_g, cur_i, cur_end = vb.cur_g, vb.cur_i, vb.cur_end
        n_scalar = 0
        for g, s, lo, hi, last_gs, t_hi in rows:
            if hi == lo + 1 and hi > last_gs - W and t_hi:
                span = ((hi, t_hi),)
            else:
                span = self._span_terms(g, s, lo, hi, h_ring, last_gs, hist)
            ents = []           # (index, requests | None, conf payload)
            plain = True
            for i, t in span:
                key = (g, i, t)
                payload = payloads.get(key)
                if payload is None:
                    continue  # leader no-op
                if payload[0] == P_CONF:
                    ents.append((i, None, payload))
                    plain = False
                elif payload[0] in (P_REQ, P_MULTI):
                    # Coalesced entries: each request applies independently
                    # in order, with its own result/error and its own
                    # waiter trigger — semantically identical to one entry
                    # per request. The live path reuses the Requests
                    # decoded at proposal time (payload_reqs sidecar);
                    # replay decodes from the durable bytes.
                    reqs = payload_reqs.pop(key, None)
                    if reqs is not None:
                        reqs = reqs[0]
                    elif payload[0] == P_REQ:
                        reqs = (Request.decode(payload[1:]),)
                    else:
                        reqs = [Request.decode(b)
                                for b in _unpack_multi(payload)]
                    if not trigger and every:
                        # Restart replay: sampled rids ride the durable
                        # Request payloads, so the trace picks them back
                        # up in the new process.
                        for r0 in reqs:
                            if r0.id % every == 0:
                                tr.mark(r0.id, "replayed", g=g)
                    ents.append((i, reqs, None))
                    if plain:
                        for r in reqs:
                            # a file PUT with no condition and no TTL is
                            # what the view batch's native call applies
                            if (r.method != METHOD_PUT or r.dir
                                    or r.refresh or r.prev_exist is not None
                                    or r.prev_index or r.prev_value
                                    or r.expiration is not None):
                                plain = False
                                break
            if not ents:
                self.applied[g] = hi
                continue
            st = stores.get(g) or self.store(g)
            if (plain and type(st) is NativeStore
                    and not st.watcher_hub.count):
                # Joins the view batch, in log order. A waiter-held
                # request's flat position goes in `need`: the native call
                # returns its raw node descriptors and the waiter is woken
                # with a LazyWriteEvent (the Event/JSON churn happens on
                # the HTTP thread that resolves it, not here). The cursor
                # moves only once the call has applied an entry (cur_*).
                n0 = len(paths)
                for i, reqs, _ in ents:
                    for r in reqs:
                        if is_reg(r.id):
                            need.append(len(paths))
                            rids.append(r.id)
                        paths.append(r.path)
                        vals.append(r.val or "")
                    cur_g.append(g)
                    cur_i.append(i)
                    cur_end.append(len(paths))
                cur_i[-1] = hi      # and over the span's trailing no-ops
                vb.stores.append(st)
                vb.counts.append(len(paths) - n0)
            else:
                n_scalar += self._apply_entries(g, ents, trigger, acct, sink)
                self.applied[g] = hi
        if paths:
            self._apply_view(vb, trigger, acct, sink)
        if self.obs.enabled:
            if paths:
                self.obs.c_apply["view"].inc(len(paths))
            if n_scalar:
                self.obs.c_apply["scalar"].inc(n_scalar)

    def _span_terms(self, g: int, s: int, lo: int, hi: int, h_ring,
                    last_gs: int, hist) -> List[Tuple[int, int]]:
        """(index, term) of group g's committed entries lo+1..hi, from
        slot s's ring (the slot with the highest commit: its ring covers
        the span, the admission throttle keeps last-commit <= W/2)."""
        W = self.cfg.window
        ring_row = h_ring[g, s]
        span = []
        for i in range(lo + 1, hi + 1):
            t = 0
            if i > last_gs - W:
                t = int(ring_row[i % W])
            if t == 0 and hist is not None:
                # Restore path: the span slot's ring can hold the 0
                # sentinel INSIDE the window — a slot removed and
                # later re-added had its ring zeroed at the join, so
                # indices below its join point are unresolvable from
                # it even though other slots know them. hist (built
                # from every slot's replayed log history) supplies
                # the committed term; without this fallback those
                # entries would silently apply as leader no-ops and
                # ACKED WRITES WOULD VANISH on restart (soak-found).
                t = hist.get((g, i), 0)
            if t == 0:
                # Live path: unreachable (applies are incremental, so
                # the span never reaches below a re-added slot's join
                # point or the ring window); refusing beats
                # misapplying.
                log.error("engine: no term for committed entry g=%d "
                          "i=%d (slot=%d last=%d)", g, i, s, last_gs)
                continue
            span.append((i, t))
        return span

    def _apply_entries(self, g: int, ents: list, trigger: bool,
                       acct: _AckCounter,
                       sink: Optional[_AckBatch]) -> int:
        """Apply group g's entries request by request, in log order (the
        path of everything the view batch does not take). Returns the
        number of requests applied."""
        tr = self.obs.tracer
        every = tr.every
        n = 0
        for i, reqs, conf in ents:
            if reqs is None:
                d = json.loads(conf[1:].decode())
                self._apply_conf(g, d["op"], d["slot"])
                if trigger:
                    self.wait.trigger(
                        d["id"],
                        [int(x) for x in np.nonzero(self.h_mask[g])[0]])
                reqs = ()
            for r in reqs:
                try:
                    result = self._apply_request(g, r)
                except errors.EtcdError as err:
                    result = err
                n += 1
                if trigger:
                    traced = every and r.id % every == 0
                    if traced:
                        tr.mark(r.id, "applied")
                    if sink is not None:
                        if r.method != METHOD_SYNC:
                            sink.acked += 1
                        sink.items.append((r.id, result))
                    else:
                        if r.method != METHOD_SYNC:
                            acct.acked += 1
                        if traced:      # before its waiter runs
                            tr.mark(r.id, "acked",
                                    acked_round=self.round_no)
                        self.wait.trigger(r.id, result)
            # Advance the cursor PER ENTRY, not at span end: if an
            # apply raises mid-span, a retry (or post-mortem) must
            # resume after the last applied entry, never re-apply it
            # (duplicate watch events / double store mutations).
            self.applied[g] = i
        return n

    def _apply_view(self, vb: "_ViewBatch", trigger: bool,
                    acct: _AckCounter, sink: Optional[_AckBatch]) -> None:
        """The view batch: one native call over many tenants' cores, the
        cursors, then each waiter's LazyWriteEvent (or its per-op
        EtcdError) — Event materialization is deferred to the HTTP
        thread that resolves it. With `sink`, wakeups and the tally are
        deferred for post-watermark release instead. View-batch requests
        are client writes (SYNC never qualifies: its method is not PUT);
        their per-op store errors count as served, same as a scalar
        error result."""
        n = len(vb.paths)
        tr = self.obs.tracer
        every = tr.every
        done, descs, now = set_applied_view(vb.stores, vb.counts, vb.paths,
                                            vb.vals, vb.need or None)
        t_applied = time.perf_counter() if every else 0.0
        # cur_end is where each entry's requests end in the flat lists:
        # the entries that end at or before `done` are applied, whole.
        # One cursor a group: a group with several entries in the view
        # (a hot tenant, replay's deep span) stands at its LAST applied
        # one; the dict keeps the later of two, in log order.
        k = bisect.bisect_right(vb.cur_end, done)
        cur = dict(zip(vb.cur_g[:k], vb.cur_i[:k]))
        self.applied[list(cur)] = list(cur.values())
        if done < n:
            # The native call ran out of memory at flat position `done`.
            # The cursors stand behind exactly what was applied (a group
            # cut inside an entry stands before that entry: it HAS taken
            # part of it); no result of this batch is handed out and the
            # caller HALTs.
            raise MemoryError(
                f"view batch stopped at request {done} of {n}")
        if not trigger:
            return
        if sink is not None:
            sink.acked += n
        else:
            acct.acked += n
        if not descs:
            return
        for (_pos, nd, pd, idx), rid in zip(descs, vb.rids):
            if nd is None:
                code, cause = pd
                res: Any = errors.EtcdError(code, cause=cause, index=idx)
            else:
                res = LazyWriteEvent(nd, pd, idx, now)
            traced = every and rid % every == 0
            if traced:
                tr.mark(rid, "applied", t=t_applied)
            if sink is not None:
                sink.items.append((rid, res))
            else:
                if traced:          # before its waiter runs
                    tr.mark(rid, "acked", acked_round=self.round_no)
                self.wait.trigger(rid, res)

    def _apply_request(self, g: int, r: Request):
        """Deterministic request->store mapping (reference applyRequest
        server.go:766-820), against the group's own tenant store."""
        st = self.store(g)
        exp = r.expiration
        if r.method == METHOD_POST:
            return st.create(r.path, is_dir=r.dir, value=r.val, unique=True,
                             expire_time=exp)
        if r.method == METHOD_PUT:
            if r.refresh:
                return st.update(r.path, None, exp, refresh=True)
            if r.prev_exist is not None:
                if r.prev_exist:
                    if r.prev_index or r.prev_value:
                        return st.compare_and_swap(r.path, r.prev_value,
                                                   r.prev_index, r.val, exp)
                    return st.update(r.path, r.val, exp)
                return st.create(r.path, is_dir=r.dir, value=r.val,
                                 expire_time=exp)
            if r.prev_index or r.prev_value:
                return st.compare_and_swap(r.path, r.prev_value,
                                           r.prev_index, r.val, exp)
            if not r.dir:
                # Unconditional file PUT — the apply loop's dominant op.
                # The native store skips Event materialization entirely
                # unless a watcher is live; a waiter-held id gets the raw
                # descriptors (LazyWriteEvent) and the HTTP thread that
                # consumes the result materializes the Event in do().
                if self.wait.is_registered(r.id):
                    lazy = getattr(st, "set_applied_lazy", None)
                    if lazy is not None:
                        return lazy(r.path, r.val, exp)
                    return st.set_applied(r.path, r.val, exp, True)
                return st.set_applied(r.path, r.val, exp, False)
            return st.set(r.path, is_dir=r.dir, value=r.val, expire_time=exp)
        if r.method == METHOD_DELETE:
            if r.prev_index or r.prev_value:
                return st.compare_and_delete(r.path, r.prev_value,
                                             r.prev_index)
            return st.delete(r.path, is_dir=r.dir, recursive=r.recursive)
        if r.method == METHOD_QGET:
            return st.get(r.path, r.recursive, r.sorted)
        if r.method == METHOD_SYNC:
            st.delete_expired_keys(r.time)
            self._sync_pending.pop(g, None)
            return None
        raise errors.EtcdError(errors.ECODE_INVALID_FORM,
                               cause=f"bad method {r.method}")

    # ------------------------------------------------------------------
    # host surgery: conf changes + snapshot install
    # ------------------------------------------------------------------

    def _check_mask(self) -> None:
        """Liveness watchdog (EngineConfig.mask_check_rounds): the device
        peer_mask must ALWAYS equal the host h_mask — membership flows
        only host -> device through _apply_conf/_restore, in the round
        thread, with h_mask written first. Any divergence is therefore
        device buffer corruption. Observed mode (CPU backend, donated
        multi-hop step; disabling donation makes it vanish): the mask
        buffer comes back holding the step's is-leader intermediate —
        one active slot per group — which silences every cross-slot send
        AND suppresses campaigns, a permanent wedge since the corrupt
        value feeds the next round's donated step. Repair from the host
        copy (a fresh buffer: jnp.asarray of a live numpy array may be
        zero-copy, and the repaired mask enters the donated chain);
        recovery then needs no further help — the next tick's heartbeat
        timeout resumes the leader's paused probes and replication
        catches up."""
        m = np.asarray(self.st.peer_mask)
        if self.obs.enabled:
            self._d2h(self.st.peer_mask)
        if np.array_equal(m, self.h_mask):
            return
        self.mask_repairs += 1
        bad = int((m != self.h_mask).any(axis=1).sum())
        log.warning("device peer_mask diverged from host mask in %d "
                    "group(s) at round %d (repair #%d) — restoring",
                    bad, self.round_no, self.mask_repairs)
        self.st = self.st._replace(
            peer_mask=self._dev("peer_mask", self.h_mask.copy()))

    def _apply_conf(self, g: int, op: str, slot: int,
                    admin: bool = False) -> None:
        """Flip a membership bit at a committed boundary and reset the
        affected progress/vote columns (reference raft.go addNode/
        removeNode + multinode.go:181-218). admin=True flips come from the
        tenant-lifecycle path, which never incremented the outstanding-conf
        counter — decrementing would steal a concurrent real conf change's
        count and disable its committed-conf binding scan."""
        add = (op == "add")
        if not admin:
            with self._lock:   # pairs with conf_change's locked increment
                self._confs_outstanding = max(0, self._confs_outstanding - 1)
        self.h_mask[g, slot] = add
        mask = self._dev("peer_mask", self.h_mask)

        st = self.st
        if add:
            # Fresh empty follower state in the slot.
            def zero_at(name, a):
                arr = np.asarray(a).copy()
                arr[g, slot] = 0
                return self._dev(name, arr)

            ring = np.asarray(st.log_term).copy()
            ring[g, slot] = 0
            nxt = np.asarray(st.next).copy()
            nxt[g, :, slot] = 1        # every potential leader probes from 1
            match = np.asarray(st.match).copy()
            match[g, :, slot] = 0
            prs = np.asarray(st.pr_state).copy()
            prs[g, :, slot] = 0        # PR_PROBE
            paused = np.asarray(st.paused).copy()
            paused[g, :, slot] = False
            votes = np.asarray(st.votes).copy()
            votes[g, :, slot] = 0
            self.st = st._replace(
                peer_mask=mask,
                term=zero_at("term", st.term), vote=zero_at("vote", st.vote),
                commit=zero_at("commit", st.commit),
                lead=zero_at("lead", st.lead),
                state=zero_at("state", st.state),
                elapsed=zero_at("elapsed", st.elapsed),
                last_index=zero_at("last_index", st.last_index),
                log_term=self._dev("log_term", ring),
                next=self._dev("next", nxt),
                match=self._dev("match", match),
                pr_state=self._dev("pr_state", prs),
                paused=self._dev("paused", paused),
                votes=self._dev("votes", votes))
            self.h_ring[g, slot] = 0
            self.h_last[g, slot] = 0
            self.h_term[g, slot] = 0
            self.h_vote[g, slot] = 0
            self.h_commit[g, slot] = 0
            self.h_state[g, slot] = 0
        else:
            # Freeze the removed slot as an inert follower so a stale
            # LEADER row can never win leader_slot() again.
            stat = np.asarray(st.state).copy()
            stat[g, slot] = 0
            lead = np.asarray(st.lead).copy()
            lead[g, slot] = 0
            self.st = st._replace(peer_mask=mask,
                                  state=self._dev("state", stat),
                                  lead=self._dev("lead", lead))
            self.h_state[g, slot] = 0

    def _fail_violation(self, viol: np.ndarray) -> None:
        """NH_VIOLATION is a protocol-violation DETECTOR (an append
        conflicted at/below a committed index — reference log.go
        maybeAppend panics on this). Dump the flagged groups' full device
        state plus the recent WAL rounds for offline diagnosis, then
        refuse to continue: papering over it would let diverged state
        serve reads as if committed."""
        import os
        flagged = [int(g) for g in np.nonzero(viol.any(axis=1))[0]]
        arrays = self._jax.device_get({
            "term": self.st.term, "vote": self.st.vote,
            "commit": self.st.commit, "lead": self.st.lead,
            "state": self.st.state, "last_index": self.st.last_index,
            "log_term": self.st.log_term, "match": self.st.match,
            "next": self.st.next, "pr_state": self.st.pr_state,
            "need_host": self.st.need_host})
        dump = {
            "round": self.round_no,
            "flagged": {str(g): {
                "slots": [int(p) for p in np.nonzero(viol[g])[0]],
                "applied": int(self.applied[g]),
                "mask": np.asarray(self.h_mask[g]).tolist(),
                **{k: np.asarray(v[g]).tolist() for k, v in arrays.items()},
            } for g in flagged},
            "recent_rounds": [{
                "round": r.round_no,
                "hs": [[int(a), int(b), int(c), int(d), int(e)]
                       for a, b, c, d, e in zip(r.hs_g, r.hs_p, r.hs_term,
                                                r.hs_vote, r.hs_commit)],
                "ring": [[int(a), int(b), int(c), int(d)]
                         for a, b, c, d in zip(r.ring_g, r.ring_p,
                                               r.ring_i, r.ring_t)],
                "entries": [[g, i, t, len(p)] for g, i, t, p in r.entries],
                "confs": list(r.confs),
            } for r in self._recent_recs],
        }
        ddir = os.path.join(self.cfg.data_dir, "diagnostics")
        os.makedirs(ddir, exist_ok=True)
        path = os.path.join(ddir, f"violation-{self.round_no:016x}.json")
        with open(path, "w") as f:
            json.dump(dump, f)
        log.critical("engine: CONSENSUS SAFETY VIOLATION in groups %s "
                     "(conflict at/below commit); state dumped to %s",
                     flagged, path)
        # Flight-recorder auto-dump: the last <ring> rounds' stage
        # timeline, beside the state dump.
        self.obs.flight.dump(self.cfg.data_dir,
                             f"violation-{self.round_no:016x}")
        raise EngineViolation(
            f"conflict at/below commit in groups {flagged}; dump: {path}")

    def _service_need_host(self, need_host: np.ndarray) -> None:
        """Consume need_host flags: for each flagged group with a live
        leader, snapshot-install every active follower whose needed entries
        fell below the leader's ring window (the host side of MsgSnap,
        reference raft.go:246-260 + etcdserver snapshot catch-up §3.5).
        A follower the lag injection holds in this round is left alone, and
        so is a slot the churn has cut off (an install is a message too);
        the leader is the group's routable one, of the highest term.

        Only the flagged groups' rows cross between device and host,
        NEED_HOST_GROUPS groups a pass: the progress fields the host keeps
        no mirror of are picked on the device (kernel.pick_groups, one
        blocking read), the rows of the mirrored fields come from the
        mirrors (a need-host round took the full readback, so they ARE the
        device's), and the result is scattered back under the fields'
        shardings (kernel.put_groups), which also clears need_host."""
        flagged = np.nonzero(need_host.any(axis=1))[0]
        if not len(flagged):
            return
        parts = [0.0, 0.0, 0.0]         # obs.NEED_HOST_PARTS' seconds
        installs = 0
        t0 = time.perf_counter()
        for lo in range(0, len(flagged), NEED_HOST_GROUPS):
            n, t1, t2 = self._need_host_pass(flagged[lo:lo + NEED_HOST_GROUPS])
            t3 = time.perf_counter()
            installs += n
            for k, d in enumerate((t1 - t0, t2 - t1, t3 - t2)):
                parts[k] += d
            t0 = t3
        if installs:
            # Mirrors stay pre-surgery: the next round must therefore run
            # the FULL readback so its diff journals the install's
            # term/commit/ring/last changes, making it durable — a compact
            # (device-vs-device) diff cannot see surgery that happened
            # between rounds.
            self._force_full = True
        self.snap_installs += installs
        if self.obs.enabled:
            o = self.obs
            o.c_snap_installs.inc(installs)
            for name, d in zip(obs_mod.NEED_HOST_PARTS, parts):
                o.h_need_host_part[name].observe(d)
            o.h_need_host.observe(sum(parts))

    def _need_host_pass(self, gs: np.ndarray) -> Tuple[int, float, float]:
        """The surgery over the groups `gs` (at most NEED_HOST_GROUPS; none:
        the programs are built and the state stays as it is). Returns the
        installs made and the clock readings behind the blocking read and
        behind the host's work."""
        K, kernel, st = NEED_HOST_GROUPS, self._kernel, self.st
        idx = np.full(K, self.cfg.groups, np.int32)     # padding: past G
        idx[:len(gs)] = gs
        idx_d = self._jnp.asarray(idx)
        picked = self._pick_groups(
            tuple(getattr(st, f) for f in kernel.NEED_HOST_READ), idx_d)
        rows = dict(zip(kernel.NEED_HOST_READ,
                        (np.array(a) for a in self._jax.device_get(picked))))
        t1 = time.perf_counter()
        for f, mirror in _NEED_HOST_MIRRORS:
            a = getattr(self, mirror)
            rows[f] = np.zeros((K,) + a.shape[1:], a.dtype)
            rows[f][:len(gs)] = a[gs]
        installs = self._install_rows(gs, rows)
        t2 = time.perf_counter()
        out = tuple(rows[f] for f in kernel.NEED_HOST_WRITE)
        new, nh = self._put_groups(
            tuple(getattr(st, f) for f in kernel.NEED_HOST_WRITE),
            st.need_host, idx_d, out)
        self.st = st._replace(need_host=nh,
                              **dict(zip(kernel.NEED_HOST_WRITE, new)))
        if self.obs.enabled:
            self._d2h(*picked)
            self._h2d(idx_d, *out)
        return installs, t1, t2

    def _install_rows(self, gs: np.ndarray, rows: Dict[str, np.ndarray]
                      ) -> int:
        """The host's part of the surgery: `rows[field][j]` is group
        gs[j]'s row of the field, changed in place; returns the installs
        made."""
        W = self.cfg.window
        term, vote, commit = rows["term"], rows["vote"], rows["commit"]
        lastv, ring, stat = rows["last_index"], rows["log_term"], rows["state"]
        nxt, match, prs = rows["next"], rows["match"], rows["pr_state"]
        paused, lead, elapsed = rows["paused"], rows["lead"], rows["elapsed"]
        installs = 0
        for j, g in enumerate(gs.tolist()):
            s = self.leader_slot(g)
            if s < 0 or self._down[g, s]:
                continue
            c = int(commit[j, s])
            for f in np.nonzero(self.h_mask[g])[0]:
                f = int(f)
                if f == s or self._lag_held[g, f] or self._down[g, f]:
                    continue
                # Lagging = the kernel's need_snap condition: entries from
                # next are no longer resolvable from the leader's ring
                # (next <= last - W; see kernel ents_ok/sendable).
                if nxt[j, s, f] > lastv[j, s] - W:
                    continue  # still reachable by appends
                if term[j, f] > term[j, s]:
                    continue  # follower is ahead in term; let raft sort it
                log.debug("engine: snapshot-install g=%d slot=%d from "
                          "leader=%d commit=%d", g, f, s, c)
                if term[j, f] < term[j, s]:
                    vote[j, f] = 0
                term[j, f] = term[j, s]
                # Copy the leader's ring, but zero slots holding leader
                # entries ABOVE the install point: on the follower those
                # positions alias indices c-W..c and would otherwise carry
                # wrong terms (the device never reads them below commit,
                # but the WAL ring-diff would record the junk).
                row = ring[j, s].copy()
                l_s = int(lastv[j, s])
                for w in range(W):
                    if l_s - ((l_s - w) % W) > c:
                        row[w] = 0
                ring[j, f] = row
                lastv[j, f] = c
                commit[j, f] = c
                stat[j, f] = 0
                lead[j, f] = s + 1
                elapsed[j, f] = 0
                match[j, s, f] = c
                nxt[j, s, f] = c + 1
                prs[j, s, f] = 1       # PR_REPLICATE
                paused[j, s, f] = False
                installs += 1
        return installs

    # ------------------------------------------------------------------
    # checkpoint
    # ------------------------------------------------------------------

    def _checkpoint(self) -> None:
        import base64 as _b64
        # (a front thread creates a tenant's store at the tenant's first
        # request, under the lock: a checkpoint that walks the live dict
        # meanwhile dies of "dictionary changed size during iteration" and
        # takes the engine thread with it)
        with self._lock:
            stores = list(self._stores.items())
        state = {
            "round": self.round_no - 1,
            "term": np_b64(self.h_term), "vote": np_b64(self.h_vote),
            "commit": np_b64(self.h_commit), "last": np_b64(self.h_last),
            "ring": np_b64(self.h_ring), "mask": np_b64(self.h_mask),
            "applied": np_b64(self.applied),
            "stores": {str(g): s.save().decode() for g, s in stores},
            "payloads": [
                (g, i, t, _b64.b64encode(p).decode())
                for (g, i, t), p in self.payloads.items()
                if i > self.applied[g]],
        }
        self.wal.save_checkpoint(self.round_no - 1, state)
        if self.obs.enabled:
            self.obs.c_checkpoint_stores.inc(len(stores))

    def _gc_payloads(self) -> None:
        dead = [k for k in self.payloads if k[1] <= self.applied[k[0]]]
        for k in dead:
            del self.payloads[k]
            self.payload_reqs.pop(k, None)
        # Reconcile the conf counter: a conf entry superseded by leader
        # turnover never applies (so never decrements) and would pin the
        # committed-conf scan on forever. Recompute from ground truth —
        # un-applied admitted conf payloads PLUS confs still queued
        # (enqueued but unadmitted ones aren't in the payload store yet).
        with self._lock:
            self._confs_outstanding = sum(
                1 for (g, i, t), p in self.payloads.items()
                if p and p[0] == P_CONF and i > self.applied[g]) + sum(
                1 for dq in self._pending
                for it in dq if it[1] and it[1][0] == P_CONF)
