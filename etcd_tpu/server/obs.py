"""Pipeline observability plane: per-compartment metrics, a round
flight recorder, and sampled end-to-end request traces.

This module gives each stage of the compartment pipeline (round loop
-> WAL writer shards -> applier shards -> ack gate) the live
queue+latency view "Scaling Replicated State Machines with
Compartmentalization" (PAPERS.md) assumes — the reference ships the
same shape as etcdserver/wal/snap/rafthttp metrics.go behind /metrics.

Three planes, all built to stay off the round loop's critical path:

  * Prometheus series (module-level, in metrics.REGISTRY): histograms
    for round-loop phases, kernel step time, batch occupancy, per-shard
    WAL fsync latency / group-commit size, per-applier-shard apply
    batches and the ack-gate wait, plus queue-depth and watermark-lag
    gauges and the pool router's per-shard request counts. Exposed by
    the engine HTTP layer at /metrics (etcdhttp/tenants.py) and the
    pool router (scripts/pool_serve.py).

  * FlightRecorder: a fixed ring of per-round stage timestamps
    (submitted -> stepped -> wal-submitted -> durable -> applied ->
    acked). mark() is three list stores — near-zero steady state — and
    the ring dumps as Chrome trace-event JSON (chrome://tracing /
    Perfetto) via SIGUSR2, GET /debug/flight, or automatically when a
    compartment fail-stops.

  * Tracer: one request in N (1 in 16 unless ETCD_TPU_TRACE_EVERY
    says otherwise) is followed by request id through the HTTP front,
    the staging queue, the round that takes it, the WAL submit, apply,
    the durability gate, the front's wake and the reply. When the reply
    is handed to the socket the span's consecutive stamps are folded
    into etcd_request_segment_seconds{kind, segment} (the segments tile
    the request) and etcd_request_rounds{kind}. The rid rides the
    durable Request payload, so a SIGKILL'd engine's replay re-marks
    surviving sampled rids as "replayed".

On top of those, the accounting a host-bound member needs (PR 24): the
round loop's seven disjoint phases tile the loop (RoundClock: wall,
thread CPU and a jax.profiler.TraceAnnotation per phase, so the device
trace's idle gaps carry the program's own stage names), `record` is
split where its work happens, blocking device->host reads are counted,
a write's wait in the staging queue and the HTTP front's span and self
time are histograms, and CPU per thread class is read at scrape time
(ThreadCpu).

ETCD_TPU_OBS=off disables every engine-side observation (the A/B
switch the instrumentation-overhead gate measures against); the series
still exist, they just stay flat.
"""
from __future__ import annotations

import contextlib
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from etcd_tpu.utils import metrics

log = logging.getLogger("etcd_tpu.obs")


def obs_enabled() -> bool:
    """The instrumentation master switch (default on). The off side is
    the round-7 baseline the overhead A/B compares against."""
    return os.environ.get("ETCD_TPU_OBS", "on").lower() not in (
        "off", "0", "false", "no")


# -- Prometheus series -------------------------------------------------------
# Module-level so every engine in the process shares one set (the
# registry is idempotent-by-name anyway). Sub-ms phases need finer
# buckets than fsyncs; request-count histograms use count buckets.

_COUNT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096,
                  16384, 65536)

# The seven disjoint phases tile the round loop: their _sum deltas add up
# to the wall window. wal_submit lies inside tail and is not one of them.
ROUND_PHASES = ("stage", "dispatch", "readback", "record", "tail", "post",
                "gap")
RECORD_PARTS = ("gather", "build", "admit")
DISPATCH_PARTS = ("upload", "step", "gather")
FRONT_KINDS = ("write", "qread", "other")

round_phase = metrics.LabeledHistogram(
    "etcd_engine_round_phase_seconds",
    "Wall time of one round-loop phase: stage/dispatch/readback/record/"
    "tail/post (tail's end to the end of run_round)/gap (one run_round's "
    "end to the next one's start) are disjoint and tile the loop; "
    "wal_submit lies inside tail.", ("phase",))
round_phase_cpu = metrics.LabeledCounter(
    "etcd_engine_round_phase_cpu_seconds_total",
    "CPU time of the round thread (time.thread_time) per disjoint "
    "round-loop phase. Wall minus CPU of a phase is what the thread spent "
    "not running: blocked on the device, on a lock, or waiting for the "
    "interpreter.", ("phase",))
record_part = metrics.LabeledHistogram(
    "etcd_engine_record_part_seconds",
    "Wall time of the record phase's parts per round: gather (what is "
    "left of gather_rows on the host: unpacking its one buffer, and the "
    "second call and read of a round that outgrew its bucket), admit "
    "(_admit_staged) and build (the rest); a full-readback round has "
    "build and admit only.", ("part",))
dispatch_part = metrics.LabeledHistogram(
    "etcd_engine_dispatch_part_seconds",
    "Wall time of the dispatch phase's three hand-overs per round: upload "
    "(the round's staged proposals to the device, and picking the tick), "
    "step (the call that enqueues the step program) and gather (choosing "
    "the bucket and the call that enqueues gather_rows; a round that asks "
    "for no gather has the lap's last few lines there). They tile the "
    "phase: gather + step + upload = dispatch.", ("part",))
d2h_syncs = metrics.Counter(
    "etcd_engine_d2h_syncs_total",
    "Blocking device->host reads on the round thread: gather_rows' "
    "packed buffer (one a compact round, a second when the bucket "
    "missed), the full readback (one device_get), the read step's "
    "conf/read-index arrays, the need-host surgery's, the mask check.")
d2h_bytes = metrics.Counter(
    "etcd_engine_d2h_bytes_total",
    "Bytes those device->host reads brought back (the arrays' nbytes).")
h2d_syncs = metrics.Counter(
    "etcd_engine_h2d_syncs_total",
    "Host->device uploads the round thread makes, one an array: the "
    "round's staged proposals (count, slot and the tick: three a round), "
    "the fault maps (--engine-lag-share: one a round; "
    "--engine-churn-down-rounds: one in a round in which a cut began or "
    "ended), and what host surgery writes back (the need-host surgery's "
    "rows or arrays, a conf change's, a tenant reset's, a mask repair).")
h2d_bytes = metrics.Counter(
    "etcd_engine_h2d_bytes_total",
    "Bytes those host->device uploads handed over (the arrays' nbytes).")
READBACK_KINDS = ("compact", "full", "over_cap")
readback_rounds = metrics.LabeledCounter(
    "etcd_engine_readback_rounds_total",
    "Rounds by the readback that built their record: compact (the rows "
    "gather_rows picked and packed on the device), over_cap (it counted "
    "more rows than compact_cap, so the full readback followed it) or "
    "full (need-host and post-surgery rounds, and every round with "
    "compact readback off).", ("kind",))
gather_rebuckets = metrics.Counter(
    "etcd_engine_gather_rebuckets_total",
    "Compact rounds that picked more rows than the size bucket "
    "gather_rows was called with, so the call and its blocking read were "
    "made a second time at the bucket that holds them.")
STEP_PATHS = ("quiet", "full")
step_hops = metrics.LabeledCounter(
    "etcd_engine_step_hops_total",
    "Hops of the device step by the message phase that ran, which the "
    "step chooses there: quiet (no group busy: one pass over all groups) "
    "or full (some group electing, changing term or waiting for the "
    "host: sequential passes over all groups, as many as the busiest "
    "receiver holds messages that need one; one a peer slot on a mesh).",
    ("path",))
step_passes = metrics.Counter(
    "etcd_engine_step_passes_total",
    "Sequential message passes, summed over the hops of the device step "
    "(a quiet hop makes none; a full hop as many as its busiest receiver "
    "needs by rank, or one a peer slot by sender).")
_NEED_HOST_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                      1.0, 2.5, 5.0)
need_host_seconds = metrics.Histogram(
    "etcd_engine_need_host_seconds",
    "Wall time of the need-host surgery on the round thread, one "
    "observation a serviced round (it lies at the end of tail): the "
    "flagged groups' rows of the progress fields picked on the device and "
    "read back, the leader's ring row copied into each lagging "
    "follower's on the host, twelve fields' rows scattered back.",
    buckets=_NEED_HOST_BUCKETS)
NEED_HOST_PARTS = ("read", "surgery", "write")
need_host_part = metrics.LabeledHistogram(
    "etcd_engine_need_host_part_seconds",
    "The need-host surgery's wall time by part, one observation of each a "
    "serviced round: read (its blocking device->host reads), surgery (the "
    "host's work on what it read) and write (handing the result back to "
    "the device, on the fields' pinned shardings on a mesh). The three "
    "tile etcd_engine_need_host_seconds: their sums add up to its sum.",
    ("part",), buckets=_NEED_HOST_BUCKETS)
snapshot_installs = metrics.Counter(
    "etcd_engine_snapshot_installs_total",
    "Followers the host snapshot-installed: their entries had fallen out "
    "of the leader's ring, so the need-host surgery set them to the "
    "leader's commit; the next round's full readback journals it.")
lag_releases = metrics.Counter(
    "etcd_engine_lag_releases_total",
    "Lagging-follower injection (--engine-lag-share): follower slots held "
    "in one round and no longer in the next (a hold ran out, or the "
    "group's leader changed).")
lag_held_slots = metrics.Gauge(
    "etcd_engine_lag_held_slots",
    "Lagging-follower injection: follower slots held in the current "
    "round (at most one a group; 0 with the injection off).")
leader_changes = metrics.Counter(
    "etcd_engine_leader_changes_total",
    "Groups whose routable leader (the active LEADER row of the highest "
    "term, where the round stages a group's writes) changed slot or term, "
    "counted where a round's readback updates the host's mirrors.")
leaderless_wait = metrics.Histogram(
    "etcd_engine_leaderless_wait_seconds",
    "Age of a write when it is staged at a leader its group did not have "
    "when the write arrived: it waited in the staging queue, or in an "
    "entry its old leader admitted and lost, for this one. Observed at "
    "that staging, once per request and leader.",
    buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
             10.0))
reproposed_requests = metrics.Counter(
    "etcd_engine_reproposed_requests_total",
    "Requests put back at the head of their group's queue because the "
    "entry that carried them was admitted by a deposed leader and the "
    "committed log holds another entry at its index, or has passed it in "
    "a later term: it can never commit, so they are proposed again.")
churn_down_slots = metrics.Gauge(
    "etcd_engine_churn_down_slots",
    "Leader-election churn (--engine-churn-down-rounds): slots cut off "
    "from their peers in the current round (at most one a group; 0 with "
    "the injection off).")
churn_cuts = metrics.Counter(
    "etcd_engine_churn_cuts_total",
    "Leader-election churn: cuts begun, i.e. leaders taken off their "
    "group's network for churn_down_rounds rounds.")
pending_wait = metrics.Histogram(
    "etcd_engine_pending_wait_seconds",
    "Time a request sat in the engine's staging queue: do()/submit_many "
    "enqueue to the round that staged it (observed once per request).")
checkpoint_seconds = metrics.Histogram(
    "etcd_engine_checkpoint_seconds",
    "Full checkpoint on the round thread: applier drain, checkpoint "
    "write and payload GC.",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
             2.5, 5.0, 10.0, 30.0, 60.0))
checkpoint_stores = metrics.Counter(
    "etcd_engine_checkpoint_stores_total",
    "Tenant stores serialised by full checkpoints (added once a "
    "checkpoint): the denominator of etcd_engine_checkpoint_seconds.")
sync_scan_seconds = metrics.Histogram(
    "etcd_engine_sync_scan_seconds",
    "One TTL scan on the round thread (_stage_syncs: next_expiration() on "
    "every tenant's store, every sync_interval); a round that scans "
    "nothing observes nothing.",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 0.5, 1.0, 2.5))
jax_compiles = metrics.Counter(
    "etcd_jax_compiles_total",
    "Programs XLA built for this process, compiled or loaded from the "
    "persistent compile cache (jax.monitoring "
    "backend_compile_duration events).")
jax_compile_seconds = metrics.Counter(
    "etcd_jax_compile_seconds_total",
    "Seconds those builds took.")
http_request = metrics.LabeledHistogram(
    "etcd_http_request_seconds",
    "HTTP front: parsed request line to reply handed to the socket, by "
    "kind (write = went through the engine's propose path, qread = "
    "through the read plane, other = the rest); streams and watches left "
    "out.", ("kind",))
http_front_self = metrics.LabeledHistogram(
    "etcd_http_front_self_seconds",
    "HTTP front self time: etcd_http_request_seconds minus the request's "
    "wait for its ack (submit to the completion drained by the event "
    "loop; on a handler thread, the time blocked inside the engine).",
    ("kind",))
FRONT_PATHS = ("loop", "thread")
http_front_served = metrics.LabeledCounter(
    "etcd_http_front_served_total",
    "Requests by where the front ran their handler: loop (the event "
    "loop itself: split-phase keys requests, replies that need no "
    "handler) or thread (what may block: watches, streams, upgrades, "
    "admin routes, TLS connections, servers with only a blocking do).",
    ("path",))
http_front_wakes = metrics.Counter(
    "etcd_http_front_wakes_total",
    "Passes of the front's event loop that drained at least one "
    "completion from its sink (one signal per ack batch).")
http_front_completions = metrics.Counter(
    "etcd_http_front_completions_total",
    "Completions the front's event loop drained from its sink.")
kernel_step = metrics.Histogram(
    "etcd_engine_kernel_step_seconds",
    "Host wall time of the round's dispatch + readback phases (enqueue "
    "the step, then block on its first result); the device step itself "
    "is shorter, see the device trace.")
round_batch = metrics.Histogram(
    "etcd_engine_round_batch_requests",
    "Client requests admitted into one round's log entries (batch "
    "occupancy).", buckets=_COUNT_BUCKETS)
rounds_total = metrics.Counter(
    "etcd_engine_rounds_total", "Engine rounds completed.")
acked_total = metrics.Counter(
    "etcd_engine_acked_requests_total",
    "Client requests acked by live rounds.")

wal_fsync = metrics.LabeledHistogram(
    "etcd_wal_writer_fsync_seconds",
    "WAL writer shard group-commit duration (append batch + one fsync, "
    "measured in the writer thread).", ("shard",))
wal_commit_rounds = metrics.LabeledHistogram(
    "etcd_wal_writer_group_commit_rounds",
    "Round records covered by one WAL writer group commit.",
    ("shard",), buckets=_COUNT_BUCKETS)
wal_queue_depth = metrics.LabeledGauge(
    "etcd_wal_writer_queue_depth",
    "WAL writer shard queue depth observed at submit.", ("shard",))
wal_watermark_lag = metrics.Gauge(
    "etcd_wal_writer_watermark_lag_tickets",
    "Submitted tickets not yet covered by the durability watermark "
    "(min over shards).")

applier_queue_depth = metrics.LabeledGauge(
    "etcd_applier_queue_depth",
    "Applier shard commit-view queue depth observed at enqueue.",
    ("shard",))
applier_batch = metrics.LabeledHistogram(
    "etcd_applier_apply_batch_requests",
    "Client requests applied+acked by one applier-shard pass.",
    ("shard",), buckets=_COUNT_BUCKETS)
APPLY_PATHS = ("view", "scalar")
apply_requests = metrics.LabeledCounter(
    "etcd_engine_apply_requests_total",
    "Requests applied, by how: view (a plain PUT of a group whose whole "
    "span in the commit view is plain PUTs, on a native store with no "
    "watcher: the pass applies all of them in one native call over the "
    "tenants' cores) or scalar (everything else, request by request).",
    ("path",))
ack_gate_wait = metrics.Histogram(
    "etcd_ack_gate_wait_seconds",
    "Time an applier shard waited at the durability gate "
    "(wal.wait_durable) before releasing a pass's acks.")

pool_router_requests = metrics.LabeledCounter(
    "etcd_pool_router_requests_total",
    "Requests the pool router relayed, by owning shard (refused/unknown "
    "route under shard=\"none\").", ("shard",))

# The zero-append read plane (engine._quorum_read): quorum reads leave
# the etcd_server_proposal_* families entirely — they append nothing —
# and meter here instead.
read_index_confirms = metrics.Histogram(
    "etcd_read_index_confirmations_per_round",
    "Groups whose ReadIndex quorum confirmation succeeded in one read "
    "round.", buckets=_COUNT_BUCKETS)
read_index_parked = metrics.Gauge(
    "etcd_read_index_parked_reads",
    "Quorum reads parked on the read plane: awaiting a leadership "
    "confirmation or the apply cursor reaching their read index.")
read_index_durations = metrics.Summary(
    "etcd_read_index_durations_milliseconds",
    "The latency distributions of quorum reads served by the ReadIndex "
    "plane (submit to serve).")
read_index_served = metrics.Counter(
    "etcd_read_index_reads_total",
    "Quorum reads served by the ReadIndex plane (zero log entries, zero "
    "WAL bytes).")
read_index_failed = metrics.Counter(
    "etcd_read_index_failed_total",
    "Quorum reads that timed out before confirmation + apply catch-up.")
read_index_lease = metrics.Counter(
    "etcd_read_index_lease_reads_total",
    "Quorum reads that skipped the confirmation round under a leader "
    "lease (EngineConfig.read_lease_ms).")

# The coalescing ingress tier (server/ingress.py): a stateless front
# process that buffers shallow per-tenant writes inside an adaptive
# window and ships each flush upstream as ONE /tenants/{t}/batch
# request. These families meter the manufactured batch depth (the whole
# point of the tier), why each window closed, how many batches are in
# flight upstream, and the watch fan-out hub. Module-level like the rest
# so the ingress process just imports and observes.
ingress_batch = metrics.Histogram(
    "etcd_ingress_coalesce_batch_requests",
    "Client writes coalesced into one upstream batch flush (the depth "
    "the ingress manufactured from shallow clients).",
    buckets=_COUNT_BUCKETS)
ingress_flush_reason = metrics.LabeledCounter(
    "etcd_ingress_flush_reason_total",
    "Why a coalescing window closed: count (flush_max_requests hit), "
    "bytes (flush_max_bytes hit), or drain (upstream inflight slot "
    "freed with a non-empty buffer).", ("reason",))
ingress_inflight = metrics.Gauge(
    "etcd_ingress_upstream_inflight_batches",
    "Coalesced batches currently in flight to the upstream engine.")
ingress_acked = metrics.Counter(
    "etcd_ingress_acked_requests_total",
    "Client writes acked by the ingress AFTER the upstream batch ack "
    "(never before — an ingress crash cannot lose an acked write).")
ingress_errors = metrics.Counter(
    "etcd_ingress_upstream_errors_total",
    "Client writes failed back because their upstream flush errored "
    "(connection loss, non-200 batch response).")
ingress_ack_ms = metrics.Summary(
    "etcd_ingress_ack_milliseconds",
    "Client-observed write ack latency through the ingress (enqueue "
    "into the coalescing window -> upstream-acked fan-back).")
ingress_hub_watchers = metrics.Gauge(
    "etcd_ingress_hub_watchers",
    "Downstream watchers currently multiplexed onto upstream watch "
    "streams by the fan-out hub.")
ingress_hub_streams = metrics.Gauge(
    "etcd_ingress_hub_streams",
    "Upstream watch streams the hub holds open (one per live "
    "(tenant, prefix, recursive) key).")
ingress_hub_deliveries = metrics.Counter(
    "etcd_ingress_hub_deliveries_total",
    "Events fanned out to downstream watchers by the hub (one upstream "
    "event delivered to N watchers counts N).")
ingress_lease_reads = metrics.Counter(
    "etcd_ingress_lease_reads_total",
    "Quorum GETs the ingress downgraded to plain local GETs under its "
    "read lease (a quorum-confirmed upstream ack within read_lease_ms).")
ingress_slow_clients = metrics.Counter(
    "etcd_ingress_slow_clients_total",
    "Downstream connections dropped because their buffered response "
    "backlog exceeded the per-connection cap (a stalled watcher on a "
    "busy key must not grow ingress memory without bound).")

# The pipelined binary upstream channel (server/batchframe.py): one
# persistent frame connection per lane, up to flush_window flushes in
# flight, demuxed by flush id. These families meter the channel's
# lifecycle (reconnects with capped backoff, JSON-path fallbacks when
# the upstream doesn't speak frames) and its frame traffic.
ingress_upstream_reconnects = metrics.Counter(
    "etcd_ingress_upstream_reconnects_total",
    "Upstream channel (re-)establishment attempts after a failure or a "
    "severed channel; paced by capped exponential backoff so a flapping "
    "engine never spins a lane flusher hot.")
ingress_upstream_fallbacks = metrics.Counter(
    "etcd_ingress_upstream_fallbacks_total",
    "Lanes that fell back from the binary batchframe channel to the "
    "JSON /batch path because the upstream refused the 101 handshake "
    "(e.g. a router that only rewrites /tenants/{t}/batch).")
ingress_upstream_frames = metrics.LabeledCounter(
    "etcd_ingress_upstream_frames_total",
    "Binary frames on the upstream channel by direction (sent = request "
    "frames / one per flush; recv = response frames).", ("direction",))
ingress_upstream_severed = metrics.Counter(
    "etcd_ingress_upstream_severed_flushes_total",
    "In-flight flushes failed back with 503 because their channel died "
    "before their response frame arrived (exactly the registered "
    "flush ids — never a retry, a dead flush MAY have committed).")

# The native (ingresscore.c) hot loop. The *_total counters meter the
# scan/format hot loop regardless of codec; etcd_ingress_native_enabled
# says which implementation is serving (1 = C extension, 0 = the pure-
# Python reference fallback).
ingress_native_enabled = metrics.Gauge(
    "etcd_ingress_native_enabled",
    "1 when the ingresscore C extension serves the HTTP scan/format hot "
    "loop, 0 when the pure-Python fallback does.")
ingress_native_scanned = metrics.Counter(
    "etcd_ingress_native_scanned_requests_total",
    "Client HTTP requests emitted by the read-buffer scanner (one "
    "GIL-releasing C pass per readable event when native is enabled).")
ingress_native_formatted = metrics.Counter(
    "etcd_ingress_native_formatted_responses_total",
    "Client HTTP responses materialized by the batch response formatter "
    "(whole-flush fan-backs format in one call when native is enabled).")


# -- flight recorder ---------------------------------------------------------

# Stage indices into a ring row (row[0] is the round number; stage k's
# timestamp lives at row[1+k]).
SUBMITTED, STEPPED, WAL_SUBMITTED, DURABLE, APPLIED, ACKED = range(6)
STAGE_NAMES = ("submitted", "stepped", "wal_submitted", "durable",
               "applied", "acked")


class FlightRecorder:
    """Fixed ring of per-round stage timestamps.

    mark() is the hot path: slot lookup + two or three list stores, no
    locks, no allocation. Rounds map to slots by round_no % capacity;
    the round loop (the only SUBMITTED writer) resets a slot when it
    reuses it, and late markers from writer/applier threads verify the
    slot still holds their round before writing — a wrapped slot drops
    the stale mark instead of corrupting the new round's row. Lost
    marks under that race are bounded to rounds a full ring apart.
    """

    def __init__(self, capacity: int = 0) -> None:
        cap = capacity or int(os.environ.get("ETCD_TPU_FLIGHT_CAP",
                                             "4096"))
        self.capacity = max(16, cap)
        # row = [round_no, t_submitted, ..., t_acked]; -1 = unset.
        self._ring: List[list] = [[-1] + [0.0] * 6
                                  for _ in range(self.capacity)]
        self.enabled = obs_enabled()
        self.dumps = 0

    def mark(self, round_no: int, stage: int,
             t: Optional[float] = None) -> None:
        if not self.enabled or round_no < 0:
            return
        row = self._ring[round_no % self.capacity]
        if stage == SUBMITTED:
            # The round loop claims the slot: one list rebind keeps the
            # reset a single atomic store (late markers for the evicted
            # round then miss the identity check below and drop out).
            self._ring[round_no % self.capacity] = \
                [round_no, t if t is not None else time.perf_counter(),
                 0.0, 0.0, 0.0, 0.0, 0.0]
            return
        if row[0] != round_no:
            return                      # slot wrapped; drop the late mark
        row[1 + stage] = t if t is not None else time.perf_counter()

    def snapshot(self) -> List[list]:
        """Rows holding at least a SUBMITTED mark, in round order."""
        rows = [list(r) for r in self._ring if r[0] >= 0]
        rows.sort(key=lambda r: r[0])
        return rows

    def to_trace_events(self) -> dict:
        """Chrome trace-event JSON (load in chrome://tracing/Perfetto).

        Each round becomes one tid; every present stage timestamp is an
        instant event, and each consecutive present stage pair becomes a
        complete ("X") span, so the per-round waterfall reads directly.
        """
        rows = self.snapshot()
        events = []
        t0 = min((r[1] for r in rows), default=0.0)

        def us(t):
            return (t - t0) * 1e6

        for row in rows:
            rnd = row[0]
            stamps = [(k, row[1 + k]) for k in range(6)
                      if row[1 + k] > 0.0]
            for k, t in stamps:
                events.append({"name": STAGE_NAMES[k], "ph": "i",
                               "ts": us(t), "pid": 1, "tid": rnd,
                               "s": "t", "args": {"round": rnd}})
            for (ka, ta), (kb, tb) in zip(stamps, stamps[1:]):
                events.append({
                    "name": f"{STAGE_NAMES[ka]}->{STAGE_NAMES[kb]}",
                    "ph": "X", "ts": us(ta), "dur": max(us(tb) - us(ta),
                                                        0.01),
                    "pid": 1, "tid": rnd, "args": {"round": rnd}})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def dump(self, data_dir: str, reason: str) -> Optional[str]:
        """Write the ring as trace-event JSON under <data_dir>/
        diagnostics; never raises (dumping is diagnostics, not a
        failure path of its own)."""
        try:
            ddir = os.path.join(data_dir, "diagnostics")
            os.makedirs(ddir, exist_ok=True)
            self.dumps += 1
            path = os.path.join(
                ddir, f"flight-{reason}-{self.dumps:04d}.trace.json")
            with open(path, "w") as f:
                json.dump(self.to_trace_events(), f)
            log.warning("flight recorder dumped to %s (%s)", path, reason)
            return path
        except Exception:  # noqa: BLE001 — diagnostics must not cascade
            log.exception("flight recorder dump failed (%s)", reason)
            return None


# -- sampled request traces ----------------------------------------------------

TRACE_STAGES = ("front_in", "submit", "staged", "admitted", "wal_submit",
                "confirmed", "durable", "applied", "acked", "woke",
                "replied", "replayed")
# One request id in this many is followed, unless ETCD_TPU_TRACE_EVERY
# says otherwise (0: none).
TRACE_EVERY_DEFAULT = 16

# The stamps that bound a request's segments, in order, and the segment
# each consecutive pair makes: disjoint, and summing to replied - front_in.
# `admitted` and `durable` are side stamps (/debug/traces shows them).
SEGMENT_STAMPS = {
    "write": ("front_in", "submit", "staged", "wal_submit", "applied",
              "acked", "woke", "replied"),
    "qread": ("front_in", "submit", "staged", "confirmed", "acked", "woke",
              "replied"),
}
SEGMENT_NAMES = {
    "write": ("parse", "queue", "round", "apply", "gate", "wake", "reply"),
    "qread": ("parse", "queue", "round", "apply", "wake", "reply"),
}
_ROUND_BUCKETS = (1, 2, 3, 4, 6, 8, 12, 16, 32)

request_segment = metrics.LabeledHistogram(
    "etcd_request_segment_seconds",
    "One segment of a sampled request's life (1 in 16 request ids, "
    "ETCD_TPU_TRACE_EVERY), observed when its reply is handed to the "
    "socket; a kind's segments tile etcd_http_request_seconds. parse: "
    "head parsed to submit_pairs; queue: to the round that staged it (a "
    "read: the round that confirmed it); round: to the WAL submit (a "
    "read: to the confirmation read back); apply: to applied (a read: "
    "the wait for the apply cursor and the serve); gate: the rest of the "
    "applier pass and the wait for the fsync, to the ack released "
    "(writes); wake: to the front's loop draining it; reply: to the "
    "reply handed to the socket.",
    ("kind", "segment"))
request_rounds = metrics.LabeledHistogram(
    "etcd_request_rounds",
    "Engine rounds a sampled request took: the round whose commit view "
    "(a read: whose serve) released it minus the round that staged it, "
    "plus one.", ("kind",), buckets=_ROUND_BUCKETS)
spans_dropped = metrics.Counter(
    "etcd_request_spans_dropped_total",
    "Sampled requests whose span was not folded into "
    "etcd_request_segment_seconds: a stamp is missing (refused, timed "
    "out, answered under a read lease, replayed after a restart, not a "
    "write or quorum read) or the request never reached a reply the "
    "front accounts for (engine.do without the front, a batch) and was "
    "pushed out of the in-flight table.")
# Every label set exists from import on: a window's first scrape already
# exports it, at zero.
_SEGMENT_HISTS = {kind: [request_segment.labels(kind, seg) for seg in names]
                  for kind, names in SEGMENT_NAMES.items()}
_ROUNDS_HISTS = {kind: request_rounds.labels(kind) for kind in SEGMENT_NAMES}


class Tracer:
    """Deterministic 1-in-N request sampling by request id.

    rid % every == 0 selects a request where the engine takes it in
    (`submit`, which opens its span); the same predicate re-selects it at
    every later stage — including a restarted process's WAL replay,
    because the rid rides the durable Request payload — so no sampling
    decision needs to travel. A call site on a per-request path tests the
    predicate itself (one modulo an unsampled request) and calls mark()
    for a sampled rid only. finish() ends the span where the front hands
    the reply to the socket: the span leaves the in-flight table for the
    bounded list /debug/traces serves and is folded into the segment
    histograms, once. Off (every=0, or ETCD_TPU_OBS=off) every call is one
    predicate test.
    """

    MAX_LIVE = 1024     # sampled requests in flight
    MAX_SPANS = 4096    # ended spans kept for /debug/traces

    def __init__(self, every: Optional[int] = None) -> None:
        if every is None:
            every = (int(os.environ.get("ETCD_TPU_TRACE_EVERY",
                                        TRACE_EVERY_DEFAULT))
                     if obs_enabled() else 0)
        self.every = max(0, every)
        self._lock = threading.Lock()
        self._live: Dict[int, dict] = {}
        self._done: deque = deque(maxlen=self.MAX_SPANS)

    def sampled(self, rid: int) -> bool:
        return bool(self.every) and rid % self.every == 0

    def mark(self, rid: int, stage: str, t: Optional[float] = None,
             **extra) -> None:
        """Record one stage timestamp (now, or the perf_counter reading
        `t` taken earlier) for a sampled rid. `submit` opens the span; a
        mark for a rid with no open span (its request timed out, or was
        pushed out) is dropped. Cold path by construction (1 in N), and
        lock-free on an open span: a stamp is one dict store, and the
        threads that stamp one request do so one after the other (only
        opening and ending a span change the table, under the lock, both
        on the front's loop for a request it serves), so the round and
        applier threads never wait here for each other or for the loop."""
        if not self.sampled(rid):
            return
        if t is None:
            t = time.perf_counter()
        span = self._live.get(rid)
        if span is None:
            if stage == "submit":
                span = self._open(rid)
            elif stage == "replayed":
                # nothing follows a replay: straight to the list
                with self._lock:
                    self._done.append(
                        {"rid": rid, "stages": {stage: t}, **extra})
                spans_dropped.inc()
                return
            else:
                return
        span["stages"][stage] = t
        if extra:
            span.update(extra)

    def _open(self, rid: int) -> dict:
        with self._lock:
            if len(self._live) >= self.MAX_LIVE:
                # The oldest in flight will not see a reply any more.
                self._done.append(self._live.pop(next(iter(self._live))))
                spans_dropped.inc()
            span = self._live[rid] = {"rid": rid, "stages": {}}
        return span

    def drop(self, rid: int) -> None:
        """A sampled request was refused before its span opened."""
        if self.sampled(rid):
            spans_dropped.inc()

    def finish(self, rid: int, kind: str,
               t: Optional[float] = None) -> None:
        """The reply of `rid`, a request of the front's `kind`, was handed
        to the socket (at `t`): stamp `replied`, take the span out of the
        in-flight table and fold it. One call per finished span, on the
        thread that replied."""
        if not self.sampled(rid):
            return
        if t is None:
            t = time.perf_counter()
        with self._lock:
            span = self._live.pop(rid, None)
            if span is not None:
                span["stages"]["replied"] = t
                span["kind"] = kind
                self._fold(span, kind)
                self._done.append(span)

    @staticmethod
    def _fold(span: dict, kind: str) -> None:
        stages = span["stages"]
        stamps = SEGMENT_STAMPS.get(kind, ())
        if (not stamps or not all(s in stages for s in stamps)
                or "staged_round" not in span or "acked_round" not in span):
            spans_dropped.inc()
            return
        prev = stages[stamps[0]]
        for hist, stamp in zip(_SEGMENT_HISTS[kind], stamps[1:]):
            t = stages[stamp]
            hist.observe(t - prev)
            prev = t
        span["rounds"] = span["acked_round"] - span["staged_round"] + 1
        _ROUNDS_HISTS[kind].observe(span["rounds"])

    def live(self) -> int:
        """Sampled requests in flight."""
        return len(self._live)

    def spans(self) -> List[dict]:
        """Ended spans (the newest MAX_SPANS) and those in flight."""
        with self._lock:
            return list(self._done) + [dict(s, stages=dict(s["stages"]))
                                       for s in self._live.values()]

    def dump(self) -> dict:
        """Spans with per-stage deltas (seconds from the earliest stage
        seen: front_in, or submit without the front; replayed spans have
        neither)."""
        out = []
        for s in sorted(self.spans(), key=lambda s: s["rid"]):
            stages = s["stages"]
            base = min(stages.values())
            out.append({**{k: v for k, v in s.items() if k != "stages"},
                        "stages": {k: round(v - base, 6)
                                   for k, v in sorted(
                                       stages.items(),
                                       key=lambda kv: kv[1])}})
        return {"every": self.every, "spans": out}


# -- the HTTP front's hand-off ------------------------------------------------


class _FrontLocal(threading.local):
    """What one worker thread's current request (the front's thread
    path; a request served by the event loop carries these on its LoopOp
    instead) learned inside the engine, read back by etcdhttp/web.py
    when the response is written:
    `blocked` seconds the thread waited for its ack (do()'s and
    _quorum_read's own clocks, handed over instead of clocking twice),
    the request's `kind`, the request's start `t_in` (perf_counter) and,
    for a sampled rid, `trace` = (tracer, rid). Class attributes are the
    defaults a thread that never served HTTP reads."""

    blocked = 0.0
    kind = "other"
    t_in = 0.0
    trace = None


front = _FrontLocal()


# -- the round loop's clock ---------------------------------------------------


class RoundClock:
    """Wall time, thread CPU time and a profiler annotation for each of
    the round loop's disjoint phases. The round thread calls lap() at
    every phase boundary with the perf_counter reading it took there
    anyway: the phase that ends is observed into
    etcd_engine_round_phase_seconds / _cpu_seconds_total and its
    `etcd.round.<phase>` TraceAnnotation closes; the next one opens at
    the same instant, so the phases tile the loop and no annotation ever
    encloses a whole round (the benchmark's gap labeller gives an idle
    gap to the host event that overlaps it most)."""

    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation
        self._annotate = TraceAnnotation
        self._wall = {p: round_phase.labels(p) for p in ROUND_PHASES}
        self._cpu = {p: round_phase_cpu.labels(p) for p in ROUND_PHASES}
        self._cur: Optional[str] = None
        self._ann = None
        self._t = self._c = 0.0

    def lap(self, nxt: Optional[str], t: float) -> None:
        """End the running phase at `t` (if one runs) and start `nxt`
        there (None: stop the clock)."""
        c = time.thread_time()
        cur = self._cur
        if cur is not None:
            self._wall[cur].observe(t - self._t)
            self._cpu[cur].inc(c - self._c)
            self._ann.__exit__(None, None, None)
        self._cur, self._t, self._c = nxt, t, c
        if nxt is not None:
            self._ann = self._annotate("etcd.round." + nxt)
            self._ann.__enter__()

    def span(self, name: str):
        """A nested annotation (etcd.round.wal_submit, etcd.record.*)."""
        return self._annotate(name)


# -- CPU by thread class ------------------------------------------------------


class ThreadCpu:
    """CPU clocks of an engine's long-lived threads by class (round,
    wal, applier, and the HTTP front's event loop). Each thread registers
    itself once at its start (pthread_getcpuclockid of its own, live id);
    read() is called at scrape time only. Where the platform has no such
    clock the class reads nothing and /metrics leaves the series out."""

    def __init__(self) -> None:
        # class -> [(clock id, thread)]
        self._clocks: Dict[str, List[tuple]] = {}
        self._lock = threading.Lock()

    def register(self, cls: str) -> None:
        try:
            clk = time.pthread_getcpuclockid(threading.get_ident())
            time.clock_gettime(clk)
        except (AttributeError, OSError):
            return
        with self._lock:
            self._clocks.setdefault(cls, []).append(
                (clk, threading.current_thread()))

    def read(self) -> Dict[str, float]:
        """{class: CPU seconds of its live threads}. A thread that has
        exited is skipped (its clock id may name another thread by then),
        so its CPU falls to the remainder."""
        with self._lock:
            clocks = {k: list(v) for k, v in self._clocks.items()}
        out = {}
        for cls, entries in clocks.items():
            tot = 0.0
            for clk, th in entries:
                if th.is_alive():
                    try:
                        tot += time.clock_gettime(clk)
                    except OSError:
                        pass
            out[cls] = tot
        return out


def cpu_exposition(thread_cpu: ThreadCpu) -> List[str]:
    """/metrics lines for process_cpu_seconds_total and
    etcd_thread_cpu_seconds_total{thread}: `front` is the process minus
    round, wal and applier (the HTTP front's event loop, its worker
    threads, which come and go; the JAX runtime's own threads fall under
    it too); `loop` is the event loop's own clock, a part of `front`, so
    `front` minus `loop` is the workers and the runtime."""
    proc = time.process_time()
    lines = [
        "# HELP process_cpu_seconds_total Total user and system CPU time "
        "spent in seconds.",
        "# TYPE process_cpu_seconds_total counter",
        f"process_cpu_seconds_total {proc}",
    ]
    named = thread_cpu.read()
    if named:
        lines += [
            "# HELP etcd_thread_cpu_seconds_total CPU time by thread "
            "class: round (the round loop), wal (writer shards), applier "
            "(applier shards), front (the process minus those: the HTTP "
            "front's loop and workers, and the runtime's own), loop (the "
            "front's event loop alone, counted in front too).",
            "# TYPE etcd_thread_cpu_seconds_total counter"]
        for cls, v in sorted(named.items()):
            lines.append(
                f'etcd_thread_cpu_seconds_total{{thread="{cls}"}} {v}')
        front_s = max(0.0, proc - sum(v for cls, v in named.items()
                                      if cls != "loop"))
        lines.append(
            f'etcd_thread_cpu_seconds_total{{thread="front"}} {front_s}')
    return lines


# -- compile events -----------------------------------------------------------

_compile_listener_installed = False


def install_compile_listener() -> None:
    """Count XLA program builds (once per process; a callback on compile
    only, nothing on the round's path)."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    _compile_listener_installed = True
    from jax import monitoring

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            jax_compiles.inc()
            jax_compile_seconds.inc(duration)

    monitoring.register_event_duration_secs_listener(on_duration)


class EngineObs:
    """One engine's bound observability plane: pre-resolved metric
    children for its shard geometry (hot paths index lists instead of
    formatting label keys), the flight recorder, and the tracer.
    `enabled` False (ETCD_TPU_OBS=off) makes the engine skip every
    observation — the series stay registered but flat."""

    _NO_SPAN = contextlib.nullcontext()

    def span(self, name: str):
        """Context manager: a profiler annotation `name` nested in the
        running phase's, nothing when the plane is off."""
        return self.clock.span(name) if self.clock else self._NO_SPAN

    def __init__(self, wal_shards: int, applier_shards: int) -> None:
        self.enabled = obs_enabled()
        self.flight = FlightRecorder()
        self.tracer = Tracer()
        self.h_wal_submit = round_phase.labels("wal_submit")
        for p in ROUND_PHASES:      # the series exist, flat, from the start
            round_phase.labels(p)
            round_phase_cpu.labels(p).inc(0.0)
        # The disjoint phases are observed through the clock (wall, CPU,
        # annotation); it needs jax.profiler, so only a live plane has one.
        self.clock = RoundClock() if self.enabled else None
        self.thread_cpu = ThreadCpu()
        self.h_rec_part = {p: record_part.labels(p) for p in RECORD_PARTS}
        self.h_dispatch_part = {p: dispatch_part.labels(p)
                                for p in DISPATCH_PARTS}
        self.c_readback = {k: readback_rounds.labels(k)
                           for k in READBACK_KINDS}
        for c in self.c_readback.values():
            c.inc(0.0)              # flat from the start, like the phases
        self.c_gather_rebuckets = gather_rebuckets
        self.c_step_hops = {k: step_hops.labels(k) for k in STEP_PATHS}
        for c in self.c_step_hops.values():
            c.inc(0.0)
        self.c_step_passes = step_passes
        self.c_d2h_syncs = d2h_syncs
        self.c_d2h_bytes = d2h_bytes
        self.c_h2d_syncs = h2d_syncs
        self.c_h2d_bytes = h2d_bytes
        self.h_pending_wait = pending_wait
        self.h_checkpoint = checkpoint_seconds
        self.c_checkpoint_stores = checkpoint_stores
        self.h_sync_scan = sync_scan_seconds
        self.h_need_host = need_host_seconds
        self.h_need_host_part = {p: need_host_part.labels(p)
                                 for p in NEED_HOST_PARTS}
        self.c_snap_installs = snapshot_installs
        self.c_lag_releases = lag_releases
        self.g_lag_held = lag_held_slots
        self.c_leader_changes = leader_changes
        self.h_leaderless_wait = leaderless_wait
        self.c_reproposed = reproposed_requests
        self.g_churn_down = churn_down_slots
        self.c_churn_cuts = churn_cuts
        for k in FRONT_KINDS:
            http_request.labels(k)
            http_front_self.labels(k)
        for p in FRONT_PATHS:
            http_front_served.labels(p).inc(0.0)
        if self.enabled:
            install_compile_listener()
        self.h_step = kernel_step
        self.h_batch = round_batch
        self.h_wal_fsync = [wal_fsync.labels(k)
                            for k in range(wal_shards)]
        self.h_wal_commit = [wal_commit_rounds.labels(k)
                             for k in range(wal_shards)]
        self.g_wal_queue = [wal_queue_depth.labels(k)
                            for k in range(wal_shards)]
        self.g_wal_lag = wal_watermark_lag
        self.g_appl_queue = [applier_queue_depth.labels(k)
                             for k in range(applier_shards)]
        self.h_appl_batch = [applier_batch.labels(k)
                             for k in range(applier_shards)]
        self.c_apply = {k: apply_requests.labels(k) for k in APPLY_PATHS}
        for c in self.c_apply.values():
            c.inc(0.0)
        self.h_ack_wait = ack_gate_wait
        self.c_rounds = rounds_total
        self.c_acked = acked_total
        self.h_read_confirms = read_index_confirms
        self.g_read_parked = read_index_parked
        self.s_read_dur = read_index_durations
        self.c_reads_served = read_index_served
        self.c_reads_failed = read_index_failed
        self.c_reads_lease = read_index_lease
