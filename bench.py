"""Headline benchmark: aggregate Raft commits/sec across G groups on one chip.

Reproduces BASELINE.json config 4's shape (default 100k groups x 5 peers)
with the batched consensus kernel:
every round is ONE XLA program stepping all G x P instances (tick + message
delivery + proposals + quorum commit + send assembly), with message routing a
device-side transpose.

Baseline for vs_baseline: the reference's best published write throughput,
4,157 writes/sec (256B values, 256 clients, leader-only — BASELINE.md,
Documentation/benchmarks/etcd-2-1-0-benchmarks.md:46). One committed entry
here == one write there (payloads ride the host log store; the device commits
index metadata, which is the consensus bottleneck being measured).

Latency is MEASURED, not estimated: per-round history of the leader's
last_index (admission time) and commit (commit time) gives per-proposal
propose->commit latency; p50/p99 are computed over sampled groups.

Robustness contract with the driver: result lines are CUMULATIVE and
STREAMED — after every completed scenario a full JSON line (containing all
scenarios measured so far) reaches stdout immediately, so consumers should
take the LAST matching line; a kill at any moment after the first scenario
still leaves a valid result. The measurement runs in ONE child process (the
parent never imports JAX, so the child owns the chip); the parent kills a
child that overruns the budget. The bench measures the TPU: with no TPU
visible the child exits non-zero and no result is printed. A CPU run exists
only when asked for by name (BENCH_PLATFORM=cpu, with BENCH_GROUPS /
BENCH_ROUNDS sized by the caller) and labels every result "platform": "cpu".

Scenario matrix (BASELINE.json configs 3-5):
  uniform — every group's leader admits max_ents/round (configs 1-2 shape)
  zipf    — Zipf(1.1)-skewed per-group admission rates (config 3: hot
            tenants get orders of magnitude more writes than the tail)
  lag     — 5%% of groups have one fully partitioned follower (config 4:
            Progress.Paused flow control engages)
  churn   — every ~40 rounds the leaders of 10%% of groups are partitioned
            for 15 rounds, forcing re-elections mid-load (config 5)
  engine  — the FULL serving path: MultiEngine rounds with the real
            engine WAL (fsync on), payload store, apply-to-store and ack
            machinery — end-to-end acked writes/s, the apples-to-apples
            line against the reference's 4,157 writes/s (which also pays
            fsync + apply per write)
  qread   — the round-9 read plane: quorum reads through the zero-append
            batched-ReadIndex path, A/B-interleaved against the same
            reads driven down the propose path (METHOD_QGET), plus a
            mixed read/write phase; the read-only leg measures the
            zero-append claim as wal-byte / log-length deltas (both 0)
  watch_storm — 100k+ stream watchers fed from the event-history ring
            under concurrent writes: delivery throughput + p99 staleness
  expiry_wave — every tenant's TTL keys expire at the same instant; the
            sync scan stages SYNCs that sweep the TTL heaps through
            consensus: expired keys/s + the round-loop stall the wave adds
The primary metric is the uniform run; the other scenarios run in the
remaining budget and report under "scenarios".

Env knobs: BENCH_GROUPS, BENCH_PEERS (5), BENCH_ROUNDS, BENCH_WARM_ROUNDS,
BENCH_BUDGET_S (480), BENCH_SCENARIO (all|uniform|zipf|lag|churn|qread|
watch_storm|expiry_wave), BENCH_PLATFORM, BENCH_QREAD_GROUPS,
BENCH_WATCHERS, BENCH_WATCH_KEYS, BENCH_EXPIRY_GROUPS, BENCH_TTL_KEYS.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE_WRITES_PER_SEC = 4157.0


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _metrics_snapshot():
    """Flat registry snapshot, bucket series dropped for size (the
    _count/_sum pair already summarizes each histogram). Lazy import:
    the parent must never pull the engine stack."""
    from etcd_tpu.utils.metrics import REGISTRY
    return {k: v for k, v in REGISTRY.snapshot().items()
            if not k.split("{", 1)[0].endswith("_bucket")}


def _metrics_delta(before, after):
    """What the registry saw during one scenario: monotone series
    (_total/_count/_sum) as after-minus-before movement, gauges as
    their final value (a depth-gauge 'delta' means nothing). Series
    born mid-scenario count from zero. This is the cross-check column:
    the BENCH numbers and /metrics must tell the same story — e.g.
    etcd_engine_acked_requests_total's movement here must equal the
    scenario's own acked count (tests/test_observability.py asserts
    the same invariant in-process)."""
    out = {}
    for k, v in sorted(after.items()):
        base = k.split("{", 1)[0]
        if base.endswith(("_total", "_count", "_sum")):
            d = v - before.get(k, 0.0)
            if d:
                out[k] = round(d, 6)
        else:
            out[k] = round(v, 6)
    return out


# ---------------------------------------------------------------------------
# Child: the actual measurement
# ---------------------------------------------------------------------------

def child_main() -> int:
    budget = float(os.environ.get("BENCH_BUDGET_S", 480.0))
    deadline = time.time() + budget * 0.9
    platform = os.environ.get("BENCH_PLATFORM", "auto")
    scenario = os.environ.get("BENCH_SCENARIO", "all")

    if platform == "cpu":
        from etcd_tpu.utils.platform import force_cpu
        force_cpu(1)
    from etcd_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    devs = jax.devices()      # no chip -> JAX raises -> non-zero exit
    on_tpu = devs[0].platform == "tpu"
    log(f"devices: {devs} (tpu={on_tpu})")
    if not on_tpu and platform != "cpu":
        log(f"bench: no TPU visible (found {devs[0].platform}); this "
            "benchmark measures the chip. BENCH_PLATFORM=cpu asks for a "
            "CPU run by name.")
        return 3

    from etcd_tpu.ops import kernel
    from etcd_tpu.ops.state import LEADER, KernelConfig, init_state

    G = int(os.environ.get("BENCH_GROUPS", 100_000))
    P = int(os.environ.get("BENCH_PEERS", 5))
    P0 = P   # frozen for the metric name (churn rebinds P to 7)
    rounds = int(os.environ.get("BENCH_ROUNDS", 300))
    warm = int(os.environ.get("BENCH_WARM_ROUNDS", 20))

    cfg = KernelConfig(groups=G, peers=P, window=16, max_ents=4,
                       election_tick=10, heartbeat_tick=3)
    st = init_state(cfg, stagger=True)
    inbox = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    zero = jnp.zeros(G, jnp.int32)

    # --- Phase 1: staggered elections converge in 3 rounds ----------------
    t0 = time.time()
    for r in range(8):
        st, inbox = kernel.step_routed_auto(cfg, st, inbox, zero, zero,
                                            jnp.asarray(True))
        state = np.asarray(st.state)
        if (np.sum(state == LEADER, axis=1) >= 1).all():
            break
    state = np.asarray(st.state)
    if not (np.sum(state == LEADER, axis=1) >= 1).all():
        raise RuntimeError("staggered elections did not converge in 8 rounds")
    log(f"elections converged in {r + 1} rounds ({time.time() - t0:.1f}s "
        f"incl compile)")

    full = jnp.full(G, cfg.max_ents, jnp.int32)
    rng = np.random.default_rng(0)

    @jax.jit
    def extract(st, slots):
        g = jnp.arange(st.term.shape[0])
        # (fixed-slot last/commit for the latency estimator on stable
        # groups; max-over-peers commit is the leader-change-proof count)
        return (st.last_index[g, slots], st.commit[g, slots],
                st.commit.max(axis=1))

    def current_slots(st):
        state = np.asarray(st.state)
        return (state == LEADER).argmax(axis=1).astype(np.int32)

    def lag_mask(slots_np):
        """Fully partition one non-leader slot in 5% of groups (config 4);
        flow control engages at effective_flow_window un-acked entries."""
        lag_groups = rng.choice(G, size=max(1, G // 20), replace=False)
        mask_to = np.ones((G, P, 1, 1), np.int32)
        mask_from = np.ones((G, 1, P, 1), np.int32)
        lag_slot = (slots_np[lag_groups] + 1) % P
        mask_to[lag_groups, lag_slot] = 0
        mask_from[lag_groups, 0, lag_slot] = 0
        return jnp.asarray(mask_to * mask_from), len(lag_groups)

    def churn_mask(slots_np):
        """Partition the LEADER of 10% of groups (config 5): those groups
        must re-elect among the remaining peers while the rest keep
        committing."""
        churned = rng.choice(G, size=max(1, G // 10), replace=False)
        mask_to = np.ones((G, P, 1, 1), np.int32)
        mask_from = np.ones((G, 1, P, 1), np.int32)
        mask_to[churned, slots_np[churned]] = 0
        mask_from[churned, 0, slots_np[churned]] = 0
        return jnp.asarray(mask_to * mask_from), churned

    def zipf_rates():
        """Per-group client-write arrival rates, Zipf(1.1)-skewed, scaled
        so the AGGREGATE offered load equals the uniform scenario's
        (G * max_ents writes/round): same total load, skewed placement —
        the hottest tenant alone receives ~18% of all writes."""
        w = 1.0 / np.arange(1, G + 1, dtype=np.float64) ** 1.1
        rng.shuffle(w)
        return w * (G * cfg.max_ents) / w.sum()

    def measure_zipf(st, inbox, sc_deadline, max_rounds):
        """Config 3 (hot tenants) through the engine's write-batching
        admission model: queued client writes coalesce into log entries of
        up to B writes each (engine.py group commit, EngineConfig.batch_max),
        at most max_ents entries per group per round. Rounds are SYNCED
        (per-round last_index/commit readback) so entry admission — and
        therefore which writes each committed entry carries — is exact,
        not assumed. The metric is committed client WRITES/s; entry
        commits are reported alongside."""
        # Writes-per-entry cap mirrors the engine's BYTE-capped group
        # commit (EngineConfig.batch_bytes = 1MB, the reference's
        # maxSizePerMsg): 256B values + JSON envelope ~= 300B/write.
        B = min(4096, (1 << 20) // 300)
        slots_np = current_slots(st)
        slots = jnp.asarray(slots_np)
        zr = zipf_rates()
        queue = np.zeros(G)
        EB = cfg.max_ents * B

        def staged(queue):
            a_w = np.minimum(np.floor(queue), EB)
            pc = np.ceil(a_w / B).astype(np.int32)
            return a_w, pc

        # Warmup (queue evolves; nothing counted).
        li, ci, _ = extract(st, slots)
        li_prev = np.asarray(li)
        for r in range(warm):
            queue += zr
            a_w, pc = staged(queue)
            st, inbox = kernel.step_routed_auto(
                cfg, st, inbox, jnp.asarray(pc), slots, jnp.asarray(True))
            li, ci, _ = extract(st, slots)
            li_np = np.asarray(li)
            adm_w = np.minimum(a_w, (li_np - li_prev) * B)
            queue -= adm_w
            li_prev = li_np
            if time.time() > sc_deadline:
                break
        li0 = li_prev.copy()

        li_hist, ci_hist, aw_hist = [], [], []
        t_hist = [time.time()]
        n = 0
        while n < min(max_rounds, 400):
            queue += zr
            a_w, pc = staged(queue)
            st, inbox = kernel.step_routed_auto(
                cfg, st, inbox, jnp.asarray(pc), slots, jnp.asarray(True))
            li, ci, _ = extract(st, slots)
            li_np = np.asarray(li)
            adm_w = np.minimum(a_w, (li_np - li_prev) * B)
            queue -= adm_w
            li_hist.append(li_np)
            ci_hist.append(np.asarray(ci))
            aw_hist.append(adm_w)
            li_prev = li_np
            t_hist.append(time.time())
            n += 1
            if n >= 10 and time.time() > sc_deadline:
                break
        elapsed = t_hist[-1] - t_hist[0]
        # DRAIN the measurement boundary: entries admitted in the window
        # but not yet committed at its close were counted as offered yet
        # never as committed — the "measurement-boundary commit lag" that
        # held the captured share 4.7 points under the structural ceiling
        # (VERDICT r4 weak #6). A few proposal-free rounds let the tail
        # commit; the offered clock stays stopped.
        for _ in range(6):
            st, inbox = kernel.step_routed_auto(
                cfg, st, inbox, jnp.zeros(G, jnp.int32), slots,
                jnp.asarray(True))
        _, ci_drained, _ = extract(st, slots)
        li_h = np.stack(li_hist)                      # (n, G)
        ci_h = np.stack(ci_hist)                      # (n, G)
        aw_h = np.stack(aw_hist)                      # (n, G)
        # Commits credited up to the END of the measured admissions (the
        # drain commits nothing new, it only finishes in-flight entries).
        ci_f = np.minimum(np.asarray(ci_drained), li_h[-1])
        li_base = np.concatenate([li0[None], li_h[:-1]])  # prev li per round

        # Committed writes: rounds whose admitted entries all sit at or
        # below the final commit count fully; the boundary round counts
        # B-packed writes of its committed prefix of entries.
        com_e = np.minimum(li_h, ci_f[None, :]) - np.minimum(li_base,
                                                             ci_f[None, :])
        com_w = np.minimum(aw_h, com_e * B)
        committed_writes = int(com_w.sum())
        committed_entries = int((np.minimum(li_h[-1], ci_f) - li0).sum())
        wps = committed_writes / elapsed
        round_ms = 1000.0 * elapsed / n

        # Write-weighted propose->commit latency over sampled groups.
        t_arr = np.asarray(t_hist)
        lrng = np.random.default_rng(1)
        sample = lrng.choice(G, size=min(G, 1024), replace=False)
        lats, weights = [], []
        for g in sample:
            li_g = li_h[:, g]
            # Latency needs a commit TIMESTAMP, so only commits observed
            # inside the measured window qualify (drain-phase commits
            # count for the admission share, not for latency).
            first, last = li0[g] + 1, min(ci_h[-1, g], li_g[-1])
            if last < first:
                continue
            idx = np.arange(first, last + 1)
            r_adm = np.searchsorted(li_g, idx, side="left")
            r_com = np.searchsorted(ci_h[:, g], idx, side="left")
            lats.append(t_arr[r_com + 1] - t_arr[r_adm])
            j = idx - li_base[r_adm, g] - 1           # entry # within round
            w = np.minimum(B, aw_h[r_adm, g] - j * B).clip(min=0)
            weights.append(w)
        if lats:
            lat = np.concatenate(lats)
            w = np.concatenate(weights).astype(np.int64)
            lat = np.repeat(lat, np.maximum(w, 0))
            p50 = round(1000.0 * float(np.percentile(lat, 50)), 3)
            p99 = round(1000.0 * float(np.percentile(lat, 99)), 3)
        else:
            p50 = p99 = None
        offered = float(zr.sum()) * n
        log(f"[zipf] G={G} P={P}: {committed_writes} committed writes "
            f"({committed_entries} entries) in {elapsed:.2f}s / {n} synced "
            f"rounds ({round_ms:.2f} ms/round) -> {wps:,.0f} writes/s "
            f"({100 * committed_writes / max(offered, 1):.0f}% of offered); "
            f"latency p50 {p50} p99 {p99} ms (write-weighted)")
        # NOTE: zipf runs fully SYNCED (per-round readback for exact write
        # accounting) — only *_synced keys are reported; its throughput is
        # therefore conservative vs the pipelined scenarios.
        # Structural admission ceiling, computed IN the artifact so the
        # claim is self-verifying (VERDICT r4 next-step #9): per-group
        # capacity is max_ents entries x B byte-capped writes per round;
        # tenants offered more than that can never commit the excess —
        # by design (per-group backpressure, reference raft/node.go:279).
        ceiling = float(np.minimum(zr, EB).sum() / zr.sum())
        share = committed_writes / max(offered, 1)
        if share < 0.95 * ceiling:
            log(f"ZIPF ADMISSION GAP: share {share:.3f} is more than 5% "
                f"under the structural ceiling {ceiling:.3f} — engine "
                f"admission is leaving capacity on the table")
        res = {"commits_per_sec": round(wps, 1),
               "entry_commits_per_sec": round(committed_entries / elapsed, 1),
               "write_batching": B,
               "offered_writes_per_round": int(zr.sum()),
               "committed_share_of_offered": round(share, 4),
               "admission_ceiling": round(ceiling, 4),
               "share_of_ceiling": round(share / ceiling, 4),
               "p50_commit_latency_ms": p50,
               "p99_commit_latency_ms": p99,
               "round_ms_synced": round(round_ms, 3),
               "rounds_synced": n,
               "hottest_rate_share": round(float(zr.max() / zr.sum()), 4)}
        return res, st, inbox

    def measure(scenario, st, inbox, sc_deadline, max_rounds):
        slots_np = current_slots(st)
        slots = jnp.asarray(slots_np)
        drop = None
        extra = {}
        churn_period, churn_len, churned = 40, 15, None
        if scenario == "lag":
            drop, extra["lagged_groups"] = lag_mask(slots_np)

        def one_round(r, st, inbox, slots, drop):
            st, inbox = kernel.step_routed_auto(cfg, st, inbox, full, slots,
                                                jnp.asarray(True))
            if drop is not None:
                inbox = inbox * drop
            return st, inbox

        # Warmup + per-round cost estimate under THIS scenario.
        for r in range(warm):
            st, inbox = one_round(r, st, inbox, slots, drop)
            if time.time() > sc_deadline:
                break
        jax.block_until_ready(st.commit)
        t_est = time.time()
        for r in range(3):
            st, inbox = one_round(r, st, inbox, slots, drop)
        jax.block_until_ready(st.commit)
        est = (time.time() - t_est) / 3
        n = max(10, min(max_rounds,
                        int((sc_deadline - time.time() - 1.0)
                            / max(est, 1e-4))))

        # --- Throughput phase: PIPELINED rounds (no per-round host sync —
        # dispatch streams ahead, exactly how a serving engine overlaps
        # readback with the next round; per-round sync would bill the
        # host<->device round-trip latency to every round). Churn
        # partitions are injected here too (the sync at each churn
        # boundary is the scenario's own cost). Takes ~60% of the scenario
        # budget; the synced latency phase gets the rest.
        n_t = max(min(n, int(0.6 * (sc_deadline - time.time())
                             / max(est, 1e-4))), 20)
        _, _, cm0_t = extract(st, slots)
        jax.block_until_ready(cm0_t)
        t0 = time.time()
        for r in range(n_t):
            if scenario == "churn":
                ph = r % churn_period
                if ph == 0:
                    drop, _ = churn_mask(current_slots(st))
                elif ph == churn_len:
                    drop = None
            st, inbox = one_round(r, st, inbox, slots, drop)
        jax.block_until_ready(st.commit)
        t_elapsed = time.time() - t0
        _, _, cm1_t = extract(st, slots)
        commits_t = int((np.asarray(cm1_t) - np.asarray(cm0_t)).sum())
        cps = commits_t / t_elapsed
        pipelined_round_ms = 1000.0 * t_elapsed / n_t

        # --- Latency phase: per-round synced history for the
        # propose->commit estimator (bounded; sync costs dominate it).
        n = min(n, 60)
        slots_np = current_slots(st)
        slots = jnp.asarray(slots_np)
        stable = np.ones(G, bool)   # groups whose leader never churned
        li0, ci0, cm0 = extract(st, slots)
        jax.block_until_ready(cm0)
        li_hist, ci_hist = [], []
        t_hist = np.zeros(n + 1)
        t_hist[0] = time.time()
        done = 0
        for r in range(n):
            if scenario == "churn":
                ph = r % churn_period
                if ph == 0:
                    drop, churned = churn_mask(current_slots(st))
                    stable[churned] = False
                elif ph == churn_len:
                    drop = None   # heal; churned groups re-elect
            st, inbox = one_round(r, st, inbox, slots, drop)
            li, ci, cm = extract(st, slots)
            li_hist.append(li)
            ci_hist.append(ci)
            jax.block_until_ready(cm)
            t_hist[r + 1] = time.time()
            done = r + 1
            # Each synced round pays the full host<->device round trip,
            # which est (mostly unsynced) did not price in — stop at the
            # deadline instead of overrunning the whole scenario matrix.
            if done >= 10 and time.time() > sc_deadline:
                break
        n = done
        t_hist = t_hist[:n + 1]
        elapsed = t_hist[n] - t_hist[0]

        li_h = np.asarray(jnp.stack(li_hist))   # (n, G)
        ci_h = np.asarray(jnp.stack(ci_hist))
        li0, ci0 = np.asarray(li0), np.asarray(ci0)
        round_ms = 1000.0 * elapsed / n

        # Measured propose->commit latency over sampled STABLE groups:
        # entry i admitted in the first round with last_index >= i, commit
        # visible at t[rc+1]; uncommitted tail censored.
        lrng = np.random.default_rng(1)
        pool = np.nonzero(stable)[0]
        sample = lrng.choice(pool, size=min(len(pool), 1024), replace=False)
        lats = []
        for g in sample:
            li, ci = li_h[:, g], ci_h[:, g]
            first, last = li0[g] + 1, ci[-1]
            if last < first:
                continue
            idx = np.arange(first, last + 1)
            r_adm = np.searchsorted(li, idx, side="left")
            r_com = np.searchsorted(ci, idx, side="left")
            lats.append(t_hist[r_com + 1] - t_hist[r_adm])
        if lats:
            lat = np.concatenate(lats)
            p50 = round(1000.0 * float(np.percentile(lat, 50)), 3)
            p99 = round(1000.0 * float(np.percentile(lat, 99)), 3)
            nlat = int(lat.size)
        else:
            p50 = p99 = None
            nlat = 0
        if scenario == "churn":
            extra["churned_groups"] = int((~stable).sum())
            extra["groups_with_leader_at_end"] = int(
                (np.asarray(st.state) == LEADER).any(axis=1).sum())
            # LIVENESS FLOOR: heal every partition and give churned
            # groups 8 election ticks' worth of rounds — the randomized
            # timeout draws up to 2x election_tick per attempt and a
            # split vote costs another attempt, so 8x covers >=2 full
            # attempts for every group. A shortfall past that is an
            # election-starvation regression, not timing noise; flag it
            # loudly in the artifact.
            drop = None
            heal_rounds = 8 * cfg.election_tick
            for _ in range(heal_rounds):
                st, inbox = one_round(0, st, inbox, slots, drop)
            healed = int((np.asarray(st.state) == LEADER)
                         .any(axis=1).sum())
            extra["groups_with_leader_after_heal"] = healed
            extra["liveness_floor_ok"] = bool(healed == G)
            if healed != G:
                log(f"LIVENESS FLOOR VIOLATION: {G - healed} groups "
                    f"still leaderless {heal_rounds} rounds "
                    f"after churn healed")

        log(f"[{scenario}] G={G} P={P}: {commits_t} commits in "
            f"{t_elapsed:.2f}s / {n_t} pipelined rounds "
            f"({pipelined_round_ms:.2f} ms/round) -> {cps:,.0f} commits/s; "
            f"synced-loop latency p50 {p50} p99 {p99} ms over {nlat} "
            f"proposals ({n} rounds at {round_ms:.2f} ms, stable groups: "
            f"{int(stable.sum())})")
        res = {"commits_per_sec": round(cps, 1),
               "round_ms_pipelined": round(pipelined_round_ms, 3),
               "rounds_pipelined": n_t,
               "p50_commit_latency_ms": p50,
               "p99_commit_latency_ms": p99,
               "round_ms_synced": round(round_ms, 3),
               "rounds_synced": n, **extra}
        return res, st, inbox

    def measure_engine(sc_deadline, G_e=None, sat_frac=0.55,
                       label="engine"):
        """End-to-end serving-path throughput: acked writes/s through the
        MultiEngine (kernel round + WAL fsync + payload store + apply +
        wait-trigger), offered load = max_ents per group per round.

        Two callers: the `engine` scenario runs the full north-star
        tenant count (100k on TPU — the serving path exercised at the
        same G the kernel scenarios claim), and the `latency` scenario
        runs the per-chip shard shape (G=12,500 = 100k/8 chips) with
        most of its budget on the paced 50%-load phase — the <10 ms p99
        ack-latency target is stated at that shape."""
        import queue as _q
        import tempfile

        from etcd_tpu.server.engine import EngineConfig, MultiEngine
        from etcd_tpu.server.request import Request

        # Peers pinned from env, NOT the child-scope P (the churn scenario
        # rebinds that to 7 for BASELINE config 5).
        P = int(os.environ.get("BENCH_PEERS", 5))
        if G_e is None:
            # The serving path runs the FULL north-star tenant count on
            # TPU (no 16k cap — VERDICT r4 weak #3); CPU keeps a host-
            # sized count (the single core saturates on apply far below
            # the kernel's batch axis).
            G_e = int(os.environ.get("BENCH_ENGINE_GROUPS",
                                     min(G, 100_000 if on_tpu else 2048)))
        E = 4
        # Applier pool width (engine.EngineConfig.applier_shards): the
        # post-commit apply/ack path partitioned by tenant range across
        # K worker threads. Default 2: the measured sweet spot of the
        # K in {1,2,4} sweep (docs/perf.md) — 1.96x deep-queue over the
        # single applier even on a 1-core box (appliers overlap the
        # round loop's WAL fsync stalls), while K=4 only adds scheduling
        # overhead until there are cores to back it. Set 1 for the
        # single-applier baseline.
        K_appl = int(os.environ.get("BENCH_APPLIER_SHARDS", 2))
        # WAL-writer compartment (EngineConfig.wal_shards /
        # pipeline_wal): group-commit fsyncs happen on writer threads,
        # off the round loop; S>1 shards the log into per-tenant-range
        # streams with parallel fsyncs. Default 2: the measured sweet
        # spot of the round-7 S in {1,2,4} sweep (docs/perf.md) —
        # 1.14-1.18x deep-queue over the round-6 inline writer in
        # same-box interleaved controls even on a 1-core box (halved
        # per-stream fsyncs release the ack watermark sooner), while
        # S=1 pipelined actually LOSES to inline there
        # (the writer thread's GIL time stretches the round loop with
        # no parallel-fsync payback). BENCH_WAL_PIPELINE=0 restores the
        # round-6 inline append+fsync for A/B baselines.
        S_wal = int(os.environ.get("BENCH_WAL_SHARDS", 2))
        wal_pipe = os.environ.get("BENCH_WAL_PIPELINE", "1") != "0"
        with tempfile.TemporaryDirectory() as tmp:
            eng = MultiEngine(EngineConfig(
                groups=G_e, peers=P, data_dir=tmp, window=16, max_ents=E,
                heartbeat_tick=3, fsync=True, stagger=True,
                applier_shards=K_appl, wal_shards=S_wal,
                pipeline_wal=wal_pipe,
                checkpoint_rounds=1 << 30))
            def all_led():
                # Vectorized: leader_slot() per group is an O(G) Python
                # loop that costs ~1s per check at G=100k.
                return bool((np.where(eng.h_mask, eng.h_state, 0) == 2)
                            .any(axis=1).all())

            for _ in range(12):
                eng.run_round()
                if all_led():
                    break
            assert all_led(), "engine elections did not converge"

            payload = Request(method="PUT", path="/bench/k",
                              val="x" * 64)

            class _Sample:
                """Wait-registry waiter that timestamps the ack as it
                fires (a collector thread reading queues would add its own
                scheduling delay to the tail percentiles)."""
                __slots__ = ("t0", "t1")

                def __init__(self):
                    self.t0 = time.time()
                    self.t1 = None

                def put(self, value):
                    self.t1 = time.time()

            samples = []

            def sample_rid(rid):
                if rid in eng.wait._waiters:
                    return   # already sampled (undrained queue head)
                s = _Sample()
                eng.wait._waiters[rid] = s
                samples.append(s)

            # Offered load rides a pre-encoded request pool: the bench
            # measures the ENGINE's serving capacity (WAL + payload store
            # + apply + ack), not the generator's Request-construct+encode
            # cost (~6 µs/req — comparable to the whole native apply path,
            # and a cost the HTTP frontend pays on its own threads in real
            # serving). Pool entries are real requests; only
            # latency-sampled ones need fresh ids (their ack is observed
            # through the wait registry).
            pool = []
            for i in range(4096):
                rid = eng.reqid.next()
                rq = Request(**{**payload.__dict__, "id": rid})
                pool.append((rid, b"\x00" + rq.encode(), rq))
            pool_i = 0

            def fresh_sampled():
                rid = eng.reqid.next()
                rq = Request(**{**payload.__dict__, "id": rid})
                sample_rid(rid)
                return (rid, b"\x00" + rq.encode(), rq)

            def offer(r, depth=E, sample=True):
                """Top pending queues up to `depth` per group; optionally
                sample one fresh-id waiter's ack latency per round."""
                nonlocal pool_i
                item = fresh_sampled() if sample else None
                with eng._lock:
                    for g in range(G_e):
                        dq = eng._pending[g]
                        while len(dq) < depth:
                            dq.append(pool[pool_i & 4095])
                            pool_i += 1
                        eng._dirty.add(g)
                    if item is not None:
                        eng._pending[r % G_e].append(item)
                        eng._dirty.add(r % G_e)

            for r in range(5):   # warm the serving loop
                offer(r)
                eng.run_round()

            # -- Phase A: SATURATED throughput (queues topped every
            # round; latency samples here measure full-backlog queueing).
            sat_end = time.time() + sat_frac * max(
                sc_deadline - time.time(), 20.0)
            a0 = eng.acked_requests
            t0 = time.time()
            r = 0
            while time.time() < sat_end - 1.0 or r < 10:
                offer(r)
                eng.run_round()
                r += 1
                if r >= 100000:
                    break
            elapsed = time.time() - t0
            acked = eng.acked_requests - a0

            def drain():
                """Queues empty + applier settled: the next phase starts
                from a quiescent engine."""
                for _ in range(200):
                    eng.run_round()
                    with eng._lock:
                        if not any(eng._pending[g] for g in range(G_e)):
                            break
                eng._drain_applies()

            drain()
            sat_samples, samples = samples, []
            aps = acked / elapsed

            # -- Phase A2 (engine scenario only): DEEP-QUEUE throughput.
            # Depth E (above) models E in-flight requests per tenant —
            # conservative next to the reference benchmark's hundreds of
            # concurrent clients (Documentation/benchmarks: up to 1,000
            # clients on ONE keyspace). At depth 64 the group commit
            # packs ~16x larger entries and the per-entry host costs
            # amortize; this phase reports what a busy tenant's pipeline
            # actually sustains. Skipped when the scenario is out of
            # budget, for the latency scenario (its budget belongs to
            # the paced phase B), and at very large G_e (topping 100k
            # queues to depth 64 is ~6M single-core Python appends per
            # round — the phase would measure the generator, not the
            # engine).
            DEEP = 64
            deep_aps = rd = None
            deep_samples = []
            if (label.split("/", 1)[0] == "engine"
                    and G_e * DEEP <= 2_000_000
                    and time.time() < sc_deadline - 5.0):
                deep_end = time.time() + 0.3 * (sc_deadline - time.time())
                d0 = eng.acked_requests
                t_d = time.time()
                rd = 0
                while time.time() < deep_end - 0.5 or rd < 5:
                    # One fresh-id waiter per round rides the depth-64
                    # backlog: deep_queue_p50/p99 report what a request
                    # actually waits behind a saturated pipeline (the
                    # throughput-vs-latency price of queue depth).
                    offer(rd, depth=DEEP)
                    eng.run_round()
                    rd += 1
                    if rd >= 100000:
                        break
                deep_elapsed = time.time() - t_d
                deep_acked = eng.acked_requests - d0
                drain()
                deep_aps = deep_acked / deep_elapsed
                deep_samples, samples = samples, []

            # -- Phase B: latency AT LOAD — offered load paced to ~50% of
            # the measured saturated capacity (the standard way to report
            # serving latency; at saturation the number is just the
            # backpressure cap). Every 8th request is latency-sampled.
            rate = 0.5 * aps
            b_end = max(sc_deadline - 1.0, time.time() + 5.0)
            injected = 0
            sample_every = 8
            t_b = time.time()
            rb = 0
            while time.time() < b_end:
                want = int(rate * (time.time() - t_b)) - injected
                if want > 0:
                    with eng._lock:
                        for k in range(want):
                            g = (injected + k) % G_e
                            if (injected + k) % sample_every == 0:
                                item = fresh_sampled()
                            else:
                                item = pool[(injected + k) & 4095]
                            eng._pending[g].append(item)
                            eng._dirty.add(g)
                    injected += want
                eng.run_round()
                rb += 1
            t_b_end = time.time()   # before drain/stop/teardown skew it
            for _ in range(6):
                eng.run_round()
            eng._drain_applies()
            # Per-shard apply share BEFORE stop tears the workers down:
            # phase_s has one "apply" key at K=1, "apply[k]" per worker
            # otherwise (each written by exactly one thread).
            apply_s = {k: v for k, v in eng.phase_s.items()
                       if k == "apply" or k.startswith("apply[")}
            n_shards = len(eng._appliers)
            # Writer-compartment profile BEFORE stop closes the streams:
            # per-group-commit fsync latency (measured IN the writer
            # thread — satellite fix: the round loop only pays for the
            # submit hand-off), batch size, and the submit-side queue
            # depth.
            wal_stats = eng.wal.stats()
            eng.stop()
        # Discard phase-B warmup (first 20% of the window): the paced rate
        # needs a few rounds to reach steady state.
        cut = t_b + 0.2 * (t_b_end - t_b)
        b_lats = [s.t1 - s.t0 for s in samples
                  if s.t1 is not None and s.t0 >= cut]
        s_lats = [s.t1 - s.t0 for s in sat_samples if s.t1 is not None]
        p50 = (round(1000 * float(np.percentile(b_lats, 50)), 3)
               if b_lats else None)
        p99 = (round(1000 * float(np.percentile(b_lats, 99)), 3)
               if b_lats else None)
        sp50 = (round(1000 * float(np.percentile(s_lats, 50)), 3)
                if s_lats else None)
        sp99 = (round(1000 * float(np.percentile(s_lats, 99)), 3)
                if s_lats else None)
        d_lats = [s.t1 - s.t0 for s in deep_samples if s.t1 is not None]
        dp50 = (round(1000 * float(np.percentile(d_lats, 50)), 3)
                if d_lats else None)
        dp99 = (round(1000 * float(np.percentile(d_lats, 99)), 3)
                if d_lats else None)
        # Per-shard apply share: each worker's fraction of the pool's
        # total apply seconds — flags range-imbalance (a hot shard shows
        # up here long before it throttles the round loop).
        tot_apply = sum(apply_s.values())
        shard_share = ({k: round(v / tot_apply, 3)
                        for k, v in sorted(apply_s.items())}
                       if tot_apply > 0 else {})
        deep_txt = (f"deep-queue (depth {DEEP}) {deep_aps:,.0f} writes/s "
                    f"over {rd} rounds (p50 {dp50} p99 {dp99} ms); "
                    if deep_aps is not None else "")
        log(f"[{label}] G={G_e} P={P} applier_shards={n_shards} "
            f"wal_shards={wal_stats['wal_shards']}"
            f"{'' if wal_pipe else ' (wal pipeline OFF)'}: "
            f"{acked} acked writes in "
            f"{elapsed:.2f}s / {r} rounds -> {aps:,.0f} writes/s "
            f"(fsync on, depth {E}); {deep_txt}ack latency at "
            f"50% load p50 {p50} p99 {p99} ms over {len(b_lats)} samples "
            f"({rb} paced rounds); saturated p50 {sp50} p99 {sp99} ms; "
            f"apply share {shard_share}; wal fsync p50 "
            f"{wal_stats['wal_fsync_p50_ms']} p99 "
            f"{wal_stats['wal_fsync_p99_ms']} ms/commit, group-commit "
            f"mean {wal_stats['wal_group_commit_mean']} max "
            f"{wal_stats['wal_group_commit_max']} rounds, queue depth "
            f"p50 {wal_stats['wal_queue_depth_p50']} max "
            f"{wal_stats['wal_queue_depth_max']}")
        deep_keys = ({"deep_queue_acked_writes_per_sec": round(deep_aps, 1),
                      "deep_queue_depth": DEEP,
                      "deep_queue_rounds": rd,
                      "deep_queue_p50_ms": dp50,
                      "deep_queue_p99_ms": dp99}
                     if deep_aps is not None else {})
        return {"acked_writes_per_sec": round(aps, 1),
                "applier_shards": n_shards,
                "apply_share_per_shard": shard_share,
                "commits_per_sec": round(aps, 1),
                **deep_keys,
                **wal_stats,
                "wal_pipeline": wal_pipe,
                "groups": G_e,
                "rounds_pipelined": r,
                "round_ms_pipelined": round(1000 * elapsed / max(r, 1), 3),
                "p50_commit_latency_ms": p50,
                "p99_commit_latency_ms": p99,
                "latency_load_fraction": 0.5,
                "saturated_p50_ms": sp50,
                "saturated_p99_ms": sp99,
                "fsync": True}

    def measure_obs_ab(sc_deadline, pairs):
        """Instrumentation-overhead A/B (BENCH_OBS_AB=N pairs): the
        engine scenario run 2N times on this same box with the
        observability plane alternately DISABLED (ETCD_TPU_OBS=off —
        the round-7 baseline side: no histograms, dead flight ring,
        tracer off) and enabled, interleaved off/on/off/on so slow
        drift (thermal, page cache, background load) cancels instead of
        landing on one side. Reports the mean deep-queue throughput
        cost as obs_overhead_pct on the ON leg's result (budget:
        <= 3%, gated by _regression_gate)."""
        legs = []
        n = 2 * pairs
        t0 = time.time()
        span = max(sc_deadline - t0, 1.0)
        prev_env = os.environ.get("ETCD_TPU_OBS")
        try:
            for i in range(n):
                mode = "off" if i % 2 == 0 else "on"
                os.environ["ETCD_TPU_OBS"] = mode
                legs.append((mode, measure_engine(
                    min(t0 + span * (i + 1) / n, sc_deadline),
                    label=f"engine/obs-{mode}")))
        finally:
            if prev_env is None:
                os.environ.pop("ETCD_TPU_OBS", None)
            else:
                os.environ["ETCD_TPU_OBS"] = prev_env
        col = "deep_queue_acked_writes_per_sec"
        offs = [r[col] for m, r in legs if m == "off" and r.get(col)]
        ons = [r[col] for m, r in legs if m == "on" and r.get(col)]
        out = dict(next((r for m, r in reversed(legs) if m == "on"),
                        legs[-1][1]))
        if offs and ons:
            off_m = sum(offs) / len(offs)
            on_m = sum(ons) / len(ons)
            out["obs_overhead_pct"] = round(100 * (1 - on_m / off_m), 2)
            out["obs_ab"] = {"pairs": pairs, "deep_queue_off": offs,
                             "deep_queue_on": ons}
            log(f"[engine/obs-ab] deep-queue off {off_m:,.0f} vs on "
                f"{on_m:,.0f} writes/s -> overhead "
                f"{out['obs_overhead_pct']}% ({pairs} interleaved pairs)")
        return out

    def measure_qread(sc_deadline):
        """Round-9 read plane A/B: quorum reads through the zero-append
        batched-ReadIndex path vs the SAME reads driven down the propose
        path (METHOD_QGET — a log entry per read, the pre-round-9
        behavior), interleaved qget/qread/qget/qread on this same box so
        slow drift cancels. The leading qread leg runs READ-ONLY against
        a QUIESCED WAL and reports the zero-append claim as measured
        columns: the WAL byte delta and log-length delta across the leg
        (both exactly 0 — tests/test_read_plane.py asserts the same
        invariant in-process). A trailing mixed phase drives writes and
        quorum reads together at the engine-scenario queue depth."""
        import tempfile

        from etcd_tpu.server.engine import EngineConfig, MultiEngine
        from etcd_tpu.server.request import Request

        P = int(os.environ.get("BENCH_PEERS", 5))
        G_q = int(os.environ.get("BENCH_QREAD_GROUPS",
                                 min(G, 8192 if on_tpu else 1024)))
        DEPTH = 64
        with tempfile.TemporaryDirectory() as tmp:
            eng = MultiEngine(EngineConfig(
                groups=G_q, peers=P, data_dir=tmp, window=16, max_ents=4,
                heartbeat_tick=3, fsync=True, stagger=True,
                checkpoint_rounds=1 << 30))

            def all_led():
                return bool((np.where(eng.h_mask, eng.h_state, 0) == 2)
                            .any(axis=1).all())

            for _ in range(12):
                eng.run_round()
                if all_led():
                    break
            assert all_led(), "engine elections did not converge"

            # Seed the key every read hits, one acked PUT per group.
            put = Request(method="PUT", path="/bench/k", val="x" * 64)
            with eng._lock:
                for g in range(G_q):
                    rq = Request(**{**put.__dict__,
                                    "id": eng.reqid.next()})
                    eng._pending[g].append(
                        (rq.id, b"\x00" + rq.encode(), rq))
                    eng._dirty.add(g)
            for _ in range(400):
                eng.run_round()
                with eng._lock:
                    if not any(eng._pending[g] for g in range(G_q)):
                        break
            eng._drain_applies()

            def wal_bytes():
                n = 0
                for root, _dirs, files in os.walk(tmp):
                    for f in files:
                        try:
                            n += os.path.getsize(os.path.join(root, f))
                        except OSError:
                            pass
                return n

            def log_len():
                return int(np.where(eng.h_mask, eng.h_last, 0)
                           .max(axis=1).sum())

            # QUIESCE: commit-index convergence keeps appending
            # hardstate diffs for a few rounds after the last ack — the
            # zero-append baseline must be taken on a WAL that has
            # stopped moving.
            stable, wb = 0, wal_bytes()
            for _ in range(400):
                eng.run_round()
                nb = wal_bytes()
                stable = stable + 1 if nb == wb else 0
                wb = nb
                if stable >= 20:
                    break

            class _Sample:
                __slots__ = ("t0", "t1")

                def __init__(self):
                    self.t0 = time.time()
                    self.t1 = None

                def put(self, value):
                    self.t1 = time.time()

            rsamples = []
            gq = Request(method="GET", path="/bench/k", quorum=True)
            rpool = []
            for _ in range(1024):
                rq = Request(**{**gq.__dict__, "id": eng.reqid.next()})
                rpool.append((rq.id, rq))
            qpool = []
            for _ in range(1024):
                rq = Request(**{**gq.__dict__, "method": "QGET",
                                "id": eng.reqid.next()})
                qpool.append((rq.id, b"\x00" + rq.encode(), rq))
            wpool = []
            for _ in range(1024):
                rq = Request(**{**put.__dict__, "id": eng.reqid.next()})
                wpool.append((rq.id, b"\x00" + rq.encode(), rq))
            rp_i = qp_i = wp_i = 0

            def offer_reads(depth, sample=True):
                """Top the parked-read queues to `depth` per group; the
                pooled items ride unregistered ids (wait.trigger no-ops),
                one fresh-id waiter per round samples latency."""
                nonlocal rp_i
                item = None
                if sample:
                    rq = Request(**{**gq.__dict__,
                                    "id": eng.reqid.next()})
                    s = _Sample()
                    eng.wait._waiters[rq.id] = s
                    rsamples.append(s)
                    item = (rq.id, rq)
                added = 0
                with eng._lock:
                    for g in range(G_q):
                        dq = eng._reads[g]
                        while len(dq) < depth:
                            dq.append(rpool[rp_i & 1023])
                            rp_i += 1
                            added += 1
                        eng._read_dirty.add(g)
                    if item is not None:
                        eng._reads[0].append(item)
                        eng._read_dirty.add(0)
                        added += 1
                    eng._reads_waiting += added
                return added

            def offer_writes(pool, depth):
                nonlocal qp_i, wp_i
                with eng._lock:
                    for g in range(G_q):
                        dq = eng._pending[g]
                        while len(dq) < depth:
                            if pool is qpool:
                                dq.append(pool[qp_i & 1023])
                                qp_i += 1
                            else:
                                dq.append(pool[wp_i & 1023])
                                wp_i += 1
                        eng._dirty.add(g)

            def drain_reads():
                for _ in range(400):
                    eng.run_round()
                    with eng._lock:
                        if (eng._reads_waiting == 0
                                and eng._ripe_waiting == 0):
                            return

            def drain_writes():
                for _ in range(400):
                    eng.run_round()
                    with eng._lock:
                        if not any(eng._pending[g] for g in range(G_q)):
                            break
                eng._drain_applies()

            def leg_qread(end_t):
                injected = 0
                t0 = time.time()
                r = 0
                while time.time() < end_t or r < 10:
                    injected += offer_reads(DEPTH)
                    eng.run_round()
                    r += 1
                    if r >= 100000:
                        break
                with eng._lock:
                    backlog = eng._reads_waiting + eng._ripe_waiting
                elapsed = time.time() - t0
                drain_reads()
                return (injected - backlog) / elapsed

            def leg_qget(end_t):
                a0 = eng.acked_requests
                t0 = time.time()
                r = 0
                while time.time() < end_t or r < 10:
                    offer_writes(qpool, DEPTH)
                    eng.run_round()
                    r += 1
                    if r >= 100000:
                        break
                elapsed = time.time() - t0
                acked = eng.acked_requests - a0
                drain_writes()
                return acked / elapsed

            # Warm the read plane BEFORE anything is timed or
            # snapshotted: the first read round pays the read-step
            # variant's XLA compile (~seconds), which would land on the
            # first latency sample and the first leg's clock.
            offer_reads(4, sample=False)
            drain_reads()

            # Leg schedule: zero-append qread first (the WAL is
            # quiesced NOW), then the interleaved ratio legs, then the
            # mixed phase.
            span = max(sc_deadline - time.time(), 15.0)
            t_base = time.time()
            wb0, ll0 = wal_bytes(), log_len()
            qread_legs = [leg_qread(t_base + 0.20 * span)]
            wb1, ll1 = wal_bytes(), log_len()
            qget_legs = [leg_qget(t_base + 0.36 * span)]
            qread_legs.append(leg_qread(t_base + 0.52 * span))
            qget_legs.append(leg_qget(t_base + 0.68 * span))
            qread_legs.append(leg_qread(t_base + 0.84 * span))

            # Mixed read/write phase at the same total depth.
            a0 = eng.acked_requests
            injected = 0
            t0 = time.time()
            r = 0
            m_end = max(sc_deadline - 1.0, time.time() + 3.0)
            while time.time() < m_end or r < 10:
                injected += offer_reads(DEPTH // 2, sample=False)
                offer_writes(wpool, DEPTH // 2)
                eng.run_round()
                r += 1
                if r >= 100000:
                    break
            with eng._lock:
                backlog = eng._reads_waiting + eng._ripe_waiting
            m_elapsed = time.time() - t0
            m_reads = (injected - backlog) / m_elapsed
            m_writes = (eng.acked_requests - a0) / m_elapsed
            drain_reads()
            drain_writes()
            eng.stop()

        lats = [s.t1 - s.t0 for s in rsamples if s.t1 is not None]
        p50 = (round(1000 * float(np.percentile(lats, 50)), 3)
               if lats else None)
        p99 = (round(1000 * float(np.percentile(lats, 99)), 3)
               if lats else None)
        rps = sum(qread_legs) / len(qread_legs)
        qps = sum(qget_legs) / len(qget_legs)
        ratio = round(rps / qps, 2) if qps > 0 else None
        log(f"[qread] G={G_q} P={P} depth {DEPTH}: quorum reads "
            f"{rps:,.0f}/s vs propose-path QGET {qps:,.0f}/s -> "
            f"{ratio}x ({len(qread_legs)}+{len(qget_legs)} interleaved "
            f"legs); read latency p50 {p50} p99 {p99} ms over "
            f"{len(lats)} samples; read-only leg wal delta {wb1 - wb0} "
            f"bytes / {ll1 - ll0} entries; mixed {m_reads:,.0f} reads/s "
            f"+ {m_writes:,.0f} writes/s")
        if wb1 != wb0 or ll1 != ll0:
            log(f"ZERO-APPEND VIOLATION: read-only quorum-read leg "
                f"moved the WAL ({wb1 - wb0} bytes, {ll1 - ll0} log "
                f"entries) — the read plane is appending")
        return {"commits_per_sec": round(rps, 1),
                "qread_reads_per_sec": round(rps, 1),
                "qget_reads_per_sec": round(qps, 1),
                "qread_vs_qget": ratio,
                "qread_p50_ms": p50,
                "qread_p99_ms": p99,
                "p50_commit_latency_ms": p50,
                "p99_commit_latency_ms": p99,
                "qread_wal_bytes_delta": int(wb1 - wb0),
                "qread_log_delta": int(ll1 - ll0),
                "mixed_reads_per_sec": round(m_reads, 1),
                "mixed_acked_writes_per_sec": round(m_writes, 1),
                "depth": DEPTH,
                "groups": G_q,
                "fsync": True}

    def measure_watch_storm(sc_deadline):
        """Watch fan-out under write load, at the store plane the
        engine's appliers drive: W stream watchers spread over K keys
        (the event-history ring records every mutation either way), one
        writer mutating the keys round-robin with the write timestamp
        as the value, consumer threads draining the watcher queues.
        Reported: deliveries/s summed over all watchers and delivery
        staleness (write timestamp -> consumer dequeue) p50/p99."""
        import queue as _q
        import threading as _th

        from etcd_tpu.store import HAVE_NATIVE_STORE, new_store

        W = int(os.environ.get("BENCH_WATCHERS",
                               100_000 if on_tpu else 25_000))
        K = int(os.environ.get("BENCH_WATCH_KEYS", 256))
        st_ = new_store(history_capacity=8192)
        watchers = [st_.watch(f"/storm/k{i % K}", recursive=False,
                              stream=True, since_index=0)
                    for i in range(W)]
        end_t = max(time.time() + 5.0, sc_deadline - 2.0)
        stop = _th.Event()
        writes = [0]

        def writer():
            i = 0
            while not stop.is_set() and time.time() < end_t:
                st_.set_applied(f"/storm/k{i % K}", repr(time.time()),
                                None, False)
                i += 1
            writes[0] = i

        n_cons = 2
        delivered = [0] * n_cons
        stale = [[] for _ in range(n_cons)]

        def consumer(ci):
            part = watchers[ci::n_cons]
            got = 0
            samp = stale[ci]
            while True:
                moved = 0
                for w in part:
                    # Bounded drain per watcher per pass: a hot watcher
                    # must not starve the rest of the partition.
                    for _k in range(32):
                        try:
                            e = w._q.get_nowait()
                        except _q.Empty:
                            break
                        got += 1
                        moved += 1
                        if got % 64 == 0 and e is not None and e.node:
                            try:
                                samp.append(time.time()
                                            - float(e.node.value))
                            except (TypeError, ValueError):
                                pass
                # Publish progress every pass and stop AT the window
                # edge: the backlog still queued is exactly what the
                # storm could not deliver in time — draining it after
                # the clock stops would overstate throughput.
                delivered[ci] = got
                if stop.is_set() and moved == 0:
                    break
                if time.time() > end_t + 5.0:
                    break

        threads = [_th.Thread(target=writer, daemon=True)]
        threads += [_th.Thread(target=consumer, args=(ci,), daemon=True)
                    for ci in range(n_cons)]
        t0 = time.time()
        for t in threads:
            t.start()
        while time.time() < end_t:
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(timeout=15.0)
        elapsed = time.time() - t0
        dps = sum(delivered) / elapsed
        wps = writes[0] / elapsed
        samp = [s for lst in stale for s in lst]
        p50 = (round(1000 * float(np.percentile(samp, 50)), 3)
               if samp else None)
        p99 = (round(1000 * float(np.percentile(samp, 99)), 3)
               if samp else None)
        log(f"[watch_storm] {W} stream watchers over {K} keys "
            f"(native={HAVE_NATIVE_STORE}): {sum(delivered)} deliveries "
            f"in {elapsed:.2f}s -> {dps:,.0f}/s ({wps:,.0f} writes/s, "
            f"fan-out ~{W // K}/write); staleness p50 {p50} p99 {p99} "
            f"ms over {len(samp)} samples")
        return {"commits_per_sec": round(dps, 1),
                "deliveries_per_sec": round(dps, 1),
                "writes_per_sec": round(wps, 1),
                "staleness_p50_ms": p50,
                "staleness_p99_ms": p99,
                "p50_commit_latency_ms": p50,
                "p99_commit_latency_ms": p99,
                "watchers": W,
                "keys": K,
                "native_store": HAVE_NATIVE_STORE}

    def measure_expiry_wave(sc_deadline):
        """Mass-TTL expiry through the engine: every tenant holds
        BENCH_TTL_KEYS keys expiring at the SAME instant; the host's
        sync scan (EngineConfig.sync_interval) stages one SYNC per due
        tenant, each SYNC commits through consensus and its apply
        sweeps the tenant's TTL heap (store delete_expired_keys).
        Reported: expired keys/s over the wave and the round-loop
        stall the wave adds (wave-round p99 vs quiesced-baseline p50)
        — the wave must ride the normal round cadence, not freeze
        it."""
        import tempfile

        from etcd_tpu.server.engine import EngineConfig, MultiEngine
        from etcd_tpu.server.request import Request

        P = int(os.environ.get("BENCH_PEERS", 5))
        G_x = int(os.environ.get("BENCH_EXPIRY_GROUPS",
                                 min(G, 4096 if on_tpu else 512)))
        NK = int(os.environ.get("BENCH_TTL_KEYS", 16))
        with tempfile.TemporaryDirectory() as tmp:
            eng = MultiEngine(EngineConfig(
                groups=G_x, peers=P, data_dir=tmp, window=16, max_ents=4,
                heartbeat_tick=3, fsync=True, stagger=True,
                sync_interval=0.05, checkpoint_rounds=1 << 30))

            def all_led():
                return bool((np.where(eng.h_mask, eng.h_state, 0) == 2)
                            .any(axis=1).all())

            for _ in range(12):
                eng.run_round()
                if all_led():
                    break
            assert all_led(), "engine elections did not converge"

            # Load NK TTL keys per tenant, all due at exp_at.
            exp_at = time.time() + max(
                3.0, min(8.0, 0.3 * (sc_deadline - time.time())))
            with eng._lock:
                for g in range(G_x):
                    for i in range(NK):
                        rq = Request(method="PUT", path=f"/ttl/k{i}",
                                     val="v", expiration=exp_at,
                                     id=eng.reqid.next())
                        eng._pending[g].append(
                            (rq.id, b"\x00" + rq.encode(), rq))
                    eng._dirty.add(g)
            for _ in range(2000):
                eng.run_round()
                with eng._lock:
                    if not any(eng._pending[g] for g in range(G_x)):
                        break
            eng._drain_applies()
            loaded = G_x * NK

            # Baseline cadence on the idle engine until the wave is due.
            base_ms = []
            while time.time() < exp_at - 0.2 and len(base_ms) < 4000:
                t_r = time.perf_counter()
                eng.run_round()
                base_ms.append(1000 * (time.perf_counter() - t_r))
            while time.time() < exp_at:
                time.sleep(0.005)

            # The wave: rounds until every tenant's TTL heap is empty.
            wave_ms = []
            t_w = time.time()
            r = 0
            left = G_x
            while time.time() < sc_deadline and r < 20000:
                t_r = time.perf_counter()
                eng.run_round()
                wave_ms.append(1000 * (time.perf_counter() - t_r))
                r += 1
                if r % 10 == 0:
                    left = sum(1 for g in range(G_x)
                               if eng.store(g).next_expiration()
                               is not None)
                    if left == 0:
                        break
            wave_elapsed = time.time() - t_w
            eng._drain_applies()
            if left:
                left = sum(1 for g in range(G_x)
                           if eng.store(g).next_expiration() is not None)
            eng.stop()
        # delete_expired_keys sweeps a tenant's due keys atomically, so
        # the expired count is exact even on a deadline-truncated wave.
        expired = loaded - left * NK
        eps = expired / wave_elapsed if wave_elapsed > 0 else 0.0
        base_p50 = (round(float(np.percentile(base_ms, 50)), 3)
                    if base_ms else None)
        wave_p99 = (round(float(np.percentile(wave_ms, 99)), 3)
                    if wave_ms else None)
        log(f"[expiry_wave] G={G_x} x {NK} TTL keys: {expired} expired "
            f"in {wave_elapsed:.2f}s / {r} rounds -> {eps:,.0f} keys/s; "
            f"round p99 during wave {wave_p99} ms vs idle baseline p50 "
            f"{base_p50} ms ({left} tenants unswept)")
        return {"commits_per_sec": round(eps, 1),
                "expired_keys_per_sec": round(eps, 1),
                "ttl_keys": loaded,
                "unswept_tenants": int(left),
                "round_stall_ms": wave_p99,
                "baseline_round_p50_ms": base_p50,
                "p50_commit_latency_ms": base_p50,
                "p99_commit_latency_ms": wave_p99,
                "groups": G_x,
                "fsync": True}

    def measure_shallow_clients(sc_deadline):
        """The ingress tier under its reason-to-exist load: CONNS
        concurrent DEPTH-1 clients — each waits for its ack before its
        next write, the worst shape for a batching engine — measured on
        the same box against the same engine subprocess (fsync ON).
        Round 11 interleaves the A/B that matters now: the PIPELINED
        binary-channel ingress (flush_window frames in flight, native
        hot loop) vs a round-10-configured ingress (--upstream-mode
        json: one JSON POST at a time), json/frame/json/frame, plus one
        direct-to-engine leg for continuity with the round-10 ratio
        (the direct path collapses under 10k depth-1 conns; a collapsed
        leg records a NULL ratio, never a division artifact). The LAST
        leg SIGKILLs the pipelined ingress mid-leg and restarts it —
        every write acked to a client must still be readable from the
        engine afterwards (values are per-client monotone seqs, so
        stored seq >= last acked seq per key is exact). Ends with the
        hub fan-out phase: W stream watchers of ONE key through the
        ingress ride a single upstream stream."""
        import selectors as _selmod
        import socket as _sock
        import subprocess as _sp
        import tempfile
        import urllib.request as _url

        from etcd_tpu.tools.functional_tester import _free_ports

        CONNS = int(os.environ.get("BENCH_SHALLOW_CONNS", 10_000))
        T = int(os.environ.get("BENCH_SHALLOW_TENANTS", 8))
        W_HUB = int(os.environ.get("BENCH_HUB_WATCHERS", 2_000))
        if on_tpu:
            # The engine member below is a subprocess that inherits this
            # process's platform, and this process holds the chip: one
            # chip belongs to one process. Until the scenario is driven
            # from a JAX-free parent it cannot run on a TPU host, and it
            # must not quietly measure a CPU engine under a TPU label.
            raise RuntimeError(
                "shallow_clients: the bench child holds the chip, so the "
                "engine subprocess cannot; run it with BENCH_PLATFORM=cpu "
                "or drive the served path with chip_smoke.py")
        repo = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ, PYTHONPATH=repo)
        env.pop("XLA_FLAGS", None)
        eport, iport, jport = _free_ports(3)
        ebase = f"http://127.0.0.1:{eport}"
        tmp = tempfile.mkdtemp(prefix="bench-shallow-")
        procs = []

        def boot_engine():
            p = _sp.Popen(
                [sys.executable, "-m", "etcd_tpu",
                 "--engine-groups", str(T), "--engine-peers", "3",
                 "--data-dir", tmp,
                 "--listen-client-urls", ebase],
                env=env, stdout=_sp.DEVNULL, stderr=_sp.DEVNULL)
            procs.append(p)
            dl = time.time() + 180
            while time.time() < dl:
                try:
                    with _url.urlopen(f"{ebase}/engine/status",
                                      timeout=2) as r:
                        stt = json.loads(r.read())
                    if stt.get("groups_with_leader") == stt.get("groups"):
                        return p
                except Exception:  # noqa: BLE001 — still booting
                    time.sleep(0.3)
            raise RuntimeError("shallow_clients: engine never led")

        def boot_ingress(port=iport, mode="frame"):
            # The json arm is a FAITHFUL round-10 replica — JSON
            # single-POST upstream AND the pure-Python hot loop (round
            # 10 predates ingresscore.c) — so ingress_pipelined_vs_r10
            # measures the whole round-11 delta, not just the
            # transport. The frame arm runs the full round-11 config.
            cmd = [sys.executable, "-m", "etcd_tpu.server.ingress",
                   "--upstream", ebase, "--port", str(port),
                   "--upstream-mode", mode]
            if mode == "json":
                cmd.append("--no-native")
            p = _sp.Popen(cmd, env=env, stdout=_sp.PIPE,
                          stderr=_sp.DEVNULL)
            p.stdout.readline()            # its ready line
            procs.append(p)
            return p

        # -- the depth-1 client harness (event-driven; the bench child
        # must itself hold CONNS sockets without a thread per client) --
        # Every leg writes its OWN key namespace (/l{leg}s{cid}) with
        # per-leg seqs: direct-leg writes that timed out client-side
        # stay in the engine's queue and commit minutes later under
        # 10k-thread thrash — on shared keys they would overwrite seqs
        # a LATER ingress leg acked and read as false "losses".
        cur = {}    # run_leg installs {"prefix", "next", "acked", ...}

        class _C:
            __slots__ = ("sock", "cid", "buf", "need", "status", "out",
                         "seq", "t0", "dead")

            def __init__(self, cid):
                self.cid = cid
                self.buf = bytearray()
                self.out = b""
                self.need = -1
                self.seq = -1
                self.dead = False

        def _connect(port, n, tag):
            conns = []
            refused = 0
            while len(conns) < n:
                burst = min(96, n - len(conns))
                for _ in range(burst):
                    c = _C(len(conns))
                    s = _sock.socket()
                    s.settimeout(10.0)
                    try:
                        s.connect(("127.0.0.1", port))
                    except OSError:
                        refused += 1
                        if refused > 200:
                            raise
                        time.sleep(0.1)
                        continue
                    s.setsockopt(_sock.IPPROTO_TCP, _sock.TCP_NODELAY, 1)
                    s.setblocking(False)
                    c.sock = s
                    conns.append(c)
                # Pace the storm: the direct leg's thread-per-conn front
                # accepts + spawns at finite speed; overrunning its
                # backlog just burns the window in SYN retries.
                time.sleep(0.02)
            log(f"[shallow_clients] {len(conns)} conns up ({tag})")
            return conns

        def _send_next(c, selx):
            c.seq = cur["next"][c.cid]
            cur["next"][c.cid] += 1
            body = f"value={c.cid}:{c.seq}"
            c.out += (
                f"PUT /tenants/{c.cid % T}/v2/keys/{cur['prefix']}"
                f"s{c.cid} HTTP/1.1\r\n"
                f"Host: b\r\nContent-Type: application/"
                f"x-www-form-urlencoded\r\n"
                f"Content-Length: {len(body)}\r\n\r\n{body}").encode()
            c.t0 = time.perf_counter()
            _flush_out(c, selx)

        def _flush_out(c, selx):
            try:
                while c.out:
                    n = c.sock.send(c.out)
                    c.out = c.out[n:]
            except (BlockingIOError, InterruptedError):
                pass
            except OSError:
                c.dead = True
                return
            try:
                selx.modify(c.sock, _selmod.EVENT_READ
                            | (_selmod.EVENT_WRITE if c.out else 0), c)
            except (KeyError, ValueError):
                pass

        def _feed(c):
            """Consume ONE complete response (depth-1: never more)."""
            if c.need < 0:
                i = c.buf.find(b"\r\n\r\n")
                if i < 0:
                    return None
                head = bytes(c.buf[:i]).lower()
                c.status = int(c.buf[9:12])
                j = head.find(b"content-length:")
                clen = 0
                if j >= 0:
                    e = head.find(b"\r\n", j)
                    clen = int(head[j + 15:e if e >= 0 else len(head)])
                c.need = i + 4 + clen
            if len(c.buf) < c.need:
                return None
            del c.buf[:c.need]
            c.need = -1
            return c.status

        def run_leg(leg, port, leg_s, lat, kill_proc=None):
            """One measured leg. The MEASURE clock starts after the
            connect storm completes — at 10k conns the direct leg's
            thread-per-connection front takes minutes just to accept
            the population, and counting that against the write window
            would compare connect storms, not write paths. Both modes
            get identical post-connect windows. Returns the leg's
            acked/errors/elapsed plus its acked-seq table and the seqs
            that were in flight when a connection died (the kill leg's
            audit needs both)."""
            cur.clear()
            cur.update(prefix=f"l{leg}", next=[0] * CONNS,
                       acked=[-1] * CONNS, dead_inflight={})
            conns = _connect(port, CONNS,
                             {eport: "direct", iport: "frame-ingress",
                              jport: "json-ingress"}.get(port, "?"))
            selx = _selmod.DefaultSelector()
            for c in conns:
                selx.register(c.sock, _selmod.EVENT_READ, c)
                _send_next(c, selx)
            t_meas = time.time()
            leg_end = t_meas + leg_s
            kill_at = t_meas + leg_s / 2.0 if kill_proc is not None \
                else None
            acked = errors = 0
            killed = False
            dead_pool = []
            while time.time() < leg_end:
                if (kill_at is not None and not killed
                        and time.time() >= kill_at):
                    kill_proc.kill()       # SIGKILL, mid-leg
                    kill_proc.wait()
                    killed = True
                    boot_ingress(port, "frame")
                    log("[shallow_clients] ingress SIGKILLed mid-leg "
                        "and restarted")
                for key, mask in selx.select(0.2):
                    c = key.data
                    if mask & _selmod.EVENT_READ:
                        try:
                            data = c.sock.recv(65536)
                        except (BlockingIOError, InterruptedError):
                            data = None
                        except OSError:
                            data = b""
                        if data == b"":
                            c.dead = True
                        elif data:
                            c.buf += data
                            stc = _feed(c)
                            if stc is not None:
                                if 200 <= stc < 300:
                                    acked += 1
                                    cur["acked"][c.cid] = c.seq
                                    if acked % 16 == 0:
                                        lat.append(time.perf_counter()
                                                   - c.t0)
                                else:
                                    errors += 1
                                _send_next(c, selx)
                    if not c.dead and (mask & _selmod.EVENT_WRITE):
                        _flush_out(c, selx)
                    if c.dead:
                        # An in-flight write on a dying conn was never
                        # acked — it must NOT count (and the read-back
                        # below would catch us if we lied). Its seq IS
                        # recorded: an unacked write that was inside
                        # the dead ingress may still commit (the batch
                        # POST had already left), and linearizability
                        # lets that pending op take effect any time
                        # after invocation — even after newer acked
                        # writes. The audit exempts exactly that seq.
                        if c.seq > cur["acked"][c.cid]:
                            cur["dead_inflight"].setdefault(
                                c.cid, set()).add(c.seq)
                        try:
                            selx.unregister(c.sock)
                        except (KeyError, ValueError):
                            pass
                        c.sock.close()
                        dead_pool.append(c)
                # Resurrect killed-ingress casualties in small batches.
                if dead_pool and killed:
                    batch, dead_pool[:] = dead_pool[:256], dead_pool[256:]
                    for c in batch:
                        s = _sock.socket()
                        s.settimeout(2.0)
                        try:
                            s.connect(("127.0.0.1", port))
                        except OSError:
                            dead_pool.append(c)
                            continue
                        s.setsockopt(_sock.IPPROTO_TCP,
                                     _sock.TCP_NODELAY, 1)
                        s.setblocking(False)
                        c.sock, c.dead = s, False
                        c.buf.clear()
                        c.out, c.need = b"", -1
                        selx.register(s, _selmod.EVENT_READ, c)
                        _send_next(c, selx)
            for c in conns:
                if not c.dead:
                    try:
                        selx.unregister(c.sock)
                    except (KeyError, ValueError):
                        pass
                    c.sock.close()
            selx.close()
            return (acked, errors, time.time() - t_meas,
                    cur["acked"], cur["dead_inflight"])

        boot_engine()
        frame_proc = boot_ingress(iport, "frame")
        boot_ingress(jport, "json")    # the round-10 comparison side
        # Warm both paths (first quorum round + route caches) before
        # the clock starts.
        for t in range(T):
            with _url.urlopen(_url.Request(
                    f"{ebase}/tenants/{t}/v2/keys/warm", method="PUT",
                    data=b"value=w",
                    headers={"Content-Type":
                             "application/x-www-form-urlencoded"}),
                    timeout=30) as r:
                r.read()

        def _drain_engine(max_s):
            """Barrier between legs: wait until the engine has no
            pending proposals. A leg's client-side timeouts leave
            writes queued in the engine that commit LATER — unfenced,
            they steal the next leg's capacity and poison the
            interleave."""
            dl = time.time() + max_s
            while time.time() < dl:
                try:
                    with _url.urlopen(f"{ebase}/metrics",
                                      timeout=10) as r:
                        m = r.read().decode()
                    pend = next(
                        (float(ln.rsplit(" ", 1)[1])
                         for ln in m.splitlines()
                         if ln.startswith(
                             "etcd_server_pending_proposal_total")),
                        0.0)
                    if pend == 0.0:
                        return
                except Exception:  # noqa: BLE001 — engine busy
                    pass
                time.sleep(1.0)
            log("[shallow_clients] drain barrier timed out "
                f"after {max_s:.0f}s — next leg may share capacity")

        # One direct leg (ratio continuity with round 10 — it collapses
        # under 10k depth-1 conns), then the round-11 interleaved A/B:
        # json/frame/json/frame (round-10-configured ingress vs the
        # pipelined binary channel), plus a dedicated KILL leg. Each
        # leg's MEASURE window (post-connect) is an equal share of what
        # remains of the scenario budget, overridable via
        # BENCH_SHALLOW_LEG_S — the connect storms themselves (minutes
        # at 10k conns on the direct leg) ride outside the measured
        # windows, so a tight budget shrinks the windows rather than
        # zeroing a leg. The kill leg is excluded from the A/B rates:
        # half its window is a 10k-reconnect storm by design, so its
        # "throughput" would measure reconnects; it exists to prove
        # zero lost acked writes across the SIGKILL.
        span = max(20.0, (sc_deadline - time.time()) - 25.0)
        leg_s = float(os.environ.get("BENCH_SHALLOW_LEG_S", "0")) \
            or max(15.0, span / 6.0)
        d_acked = d_err = j_acked = j_err = i_acked = i_err = 0
        d_time = j_time = i_time = 0.0
        d_lat, j_lat, i_lat = [], [], []
        ingress_audits = []        # (leg, acked_tbl, dead_inflight)
        for leg, mode in enumerate(
                ("direct", "json", "frame", "json", "frame")):
            if mode == "direct":
                a, e, dt, _, _ = run_leg(leg, eport, leg_s, d_lat)
                d_acked += a
                d_err += e
                d_time += dt
            elif mode == "json":
                a, e, dt, atbl, dinf = run_leg(leg, jport, leg_s, j_lat)
                j_acked += a
                j_err += e
                j_time += dt
                ingress_audits.append((leg, atbl, dinf))
            else:
                a, e, dt, atbl, dinf = run_leg(leg, iport, leg_s, i_lat)
                i_acked += a
                i_err += e
                i_time += dt
                ingress_audits.append((leg, atbl, dinf))
            log(f"[shallow_clients] leg {leg} {mode}: {a} acked "
                f"({e} errors) in {dt:.1f}s measured")
            _drain_engine(120.0)
        kl = 5
        a, e, dt, atbl, dinf = run_leg(kl, iport, leg_s, [],
                                       kill_proc=frame_proc)
        frame_proc = procs[-1]
        ingress_audits.append((kl, atbl, dinf))
        log(f"[shallow_clients] kill leg: {a} acked ({e} errors) in "
            f"{dt:.1f}s measured (excluded from rates)")
        _drain_engine(120.0)

        # Zero-lost-acked-writes audit, per ingress leg: read every
        # key back from the ENGINE (not the ingress) and compare
        # against the last seq each client saw acked. Depth-1 +
        # per-leg keys + per-key monotone seqs make `stored >= acked`
        # exact — with ONE exemption: a write that was IN FLIGHT when
        # its connection died unacked may commit after newer acked
        # writes (its batch had already left the dead ingress;
        # linearizability places an unacked op anywhere after its
        # invocation), so `stored == that seq` is a legal final state,
        # never counted as a loss.
        lost = 0
        stored = {}
        for t in range(T):
            with _url.urlopen(
                    f"{ebase}/tenants/{t}/v2/keys/?recursive=true",
                    timeout=60) as r:
                for nd in json.loads(r.read())["node"].get("nodes", []):
                    stored[(t, nd["key"])] = nd.get("value", "")
        for leg, atbl, dinf in ingress_audits:
            for cid in range(CONNS):
                if atbl[cid] < 0:
                    continue
                v = stored.get((cid % T, f"/l{leg}s{cid}"), "")
                got = int(v.split(":")[1]) if ":" in v else -1
                if got < atbl[cid] and got not in dinf.get(cid, ()):
                    lost += 1
        assert lost == 0, (f"{lost} acked writes missing after ingress "
                           f"SIGKILL — the ack-after-upstream-ack "
                           f"contract is broken")

        # Hub fan-out phase: W stream watchers of one key through the
        # ingress; ONE upstream stream serves them all.
        hub_deliveries = 0
        hub_events = 8
        hw_conns = []
        selx = _selmod.DefaultSelector()
        for i in range(W_HUB):
            s = _sock.socket()
            s.settimeout(10.0)
            s.connect(("127.0.0.1", iport))
            s.sendall(b"GET /tenants/0/v2/keys/hub?wait=true&stream="
                      b"true HTTP/1.1\r\nHost: b\r\n\r\n")
            s.setblocking(False)
            hw_conns.append(s)
            selx.register(s, _selmod.EVENT_READ, bytearray())
            if i % 96 == 95:
                time.sleep(0.01)
        time.sleep(1.0)                    # all subscribed
        t_hub = time.time()
        for i in range(hub_events):
            with _url.urlopen(_url.Request(
                    f"http://127.0.0.1:{iport}/tenants/0/v2/keys/hub",
                    method="PUT", data=f"value=h{i}".encode(),
                    headers={"Content-Type":
                             "application/x-www-form-urlencoded"}),
                    timeout=30) as r:
                r.read()
        hub_end = time.time() + 20.0
        want = W_HUB * hub_events
        while hub_deliveries < want and time.time() < hub_end:
            for key, _m in selx.select(0.5):
                try:
                    data = key.fileobj.recv(65536)
                except OSError:
                    data = b""
                if data:
                    key.data.extend(data)
                    n = key.data.count(b'"action"')
                    if n:
                        hub_deliveries += n
                        key.data.clear()
        hub_elapsed = time.time() - t_hub
        # Scrape WHILE the watchers are attached: the claim is W live
        # watchers over N upstream streams, not the post-close state.
        with _url.urlopen(f"http://127.0.0.1:{iport}/metrics",
                          timeout=10) as r:
            mtx = r.read().decode()
        hub_streams = next(
            (float(ln.split()[-1]) for ln in mtx.splitlines()
             if ln.startswith("etcd_ingress_hub_streams")), -1.0)
        for s in hw_conns:
            s.close()
        selx.close()

        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except _sp.TimeoutExpired:
                p.kill()

        d_rate = d_acked / d_time if d_time else 0.0
        j_rate = j_acked / j_time if j_time else 0.0
        i_rate = i_acked / i_time if i_time else 0.0
        # A collapsed direct leg (thread-per-conn front thrashing under
        # 10k depth-1 conns: a handful of acks in minutes) makes the
        # ratio a division artifact, not a measurement — record NULL
        # and say so, never a six-figure "advantage".
        collapsed = d_rate < 1.0
        ratio = None if collapsed else round(i_rate / d_rate, 2)
        r10_ratio = round(i_rate / j_rate, 2) if j_rate else None
        dp99 = (round(1000 * float(np.percentile(d_lat, 99)), 3)
                if d_lat else None)
        jp99 = (round(1000 * float(np.percentile(j_lat, 99)), 3)
                if j_lat else None)
        ip50 = (round(1000 * float(np.percentile(i_lat, 50)), 3)
                if i_lat else None)
        ip99 = (round(1000 * float(np.percentile(i_lat, 99)), 3)
                if i_lat else None)
        hub_rate = hub_deliveries / hub_elapsed if hub_elapsed else 0.0
        d_txt = ("direct: collapsed "
                 f"({d_acked} acks in {d_time:.0f}s)" if collapsed
                 else f"direct {d_rate:,.0f} acked/s -> {ratio}x")
        log(f"[shallow_clients] {CONNS} depth-1 conns, {T} tenants, "
            f"fsync on: pipelined ingress {i_rate:,.0f} acked/s vs "
            f"round-10 json ingress {j_rate:,.0f} acked/s -> "
            f"{r10_ratio}x (target >= 5x); {d_txt}; pipelined ack p50 "
            f"{ip50} p99 {ip99} ms (json p99 {jp99}, direct p99 "
            f"{dp99}); {lost} lost acked writes across SIGKILL; hub "
            f"{W_HUB} watchers x {hub_events} events -> "
            f"{hub_deliveries} deliveries ({hub_rate:,.0f}/s) over "
            f"{hub_streams:.0f} upstream stream(s)")
        return {"commits_per_sec": round(i_rate, 1),
                "direct_acked_per_sec": round(d_rate, 1),
                "direct_collapsed": collapsed,
                "ingress_acked_per_sec": round(i_rate, 1),
                "ingress_json_acked_per_sec": round(j_rate, 1),
                "ingress_vs_direct": ratio,
                "ingress_pipelined_vs_r10": r10_ratio,
                "ingress_ack_p50_ms": ip50,
                "ingress_ack_p99_ms": ip99,
                "ingress_json_ack_p99_ms": jp99,
                "direct_ack_p99_ms": dp99,
                "p50_commit_latency_ms": ip50,
                "p99_commit_latency_ms": ip99,
                "flush_window": 4,
                "hub_fanout": W_HUB,
                "hub_deliveries": int(hub_deliveries),
                "hub_deliveries_per_sec": round(hub_rate, 1),
                "hub_upstream_streams": int(hub_streams),
                "direct_errors": int(d_err),
                "ingress_json_errors": int(j_err),
                "ingress_errors": int(i_err),
                "lost_acked_writes": int(lost),
                "ingress_sigkilled": True,
                "conns": CONNS,
                "tenants": T,
                "fsync": True}

    sel = scenario
    # churn LAST: it boots a second kernel geometry (7 peers, BASELINE
    # config 5) whose compile can eat a cold-cache TPU budget — the
    # serving-path engine/latency scenarios must never be starved by it
    # (results stream cumulatively, so whatever completes is recorded).
    # Weighted budget: the serving scenarios (engine at the full
    # north-star G, latency at the per-chip shard shape) carry the
    # round's headline claims and get real time; zipf/lag are
    # comparatively quick synced loops.
    _WEIGHTS = {"uniform": 0.20, "zipf": 0.05, "lag": 0.05,
                "engine": 0.17, "latency": 0.15, "churn": 0.08,
                "qread": 0.09, "watch_storm": 0.06, "expiry_wave": 0.06,
                "shallow_clients": 0.09}
    # Serving scenarios directly after the primary: a time-boxed TPU run
    # must land the north-star engine/latency numbers before the quick
    # synced loops, and churn stays last (its
    # 7-peer geometry is a second cold compile). The round-9 read/watch/
    # expiry scenarios ride between them: qread reuses the engine
    # scenario's compiled geometry family, watch_storm/expiry_wave are
    # host-dominated.
    order = (["uniform", "engine", "latency", "qread",
              "shallow_clients", "watch_storm", "expiry_wave", "zipf",
              "lag", "churn"]
             if sel == "all" else [sel])
    results = {}
    if sel == "all" and on_tpu:
        # See measure_shallow_clients: its engine subprocess cannot share
        # this process's chip. Selected by name it fails; in a full run
        # it is left out, loudly, so the scenarios after it still land.
        log("shallow_clients NOT RUN: its engine subprocess needs the "
            "chip this process holds")
        order.remove("shallow_clients")
        results["shallow_clients"] = {
            "skipped": "engine-subprocess-needs-the-chip-this-process-holds"}
    if (sel == "all" and not on_tpu
            and "BENCH_LAT_GROUPS" not in os.environ):
        # On CPU the latency scenario collapses into the engine scenario
        # (same G=2048, same paced 50%-load phase B) — re-measuring it
        # burned ~22% of a CPU bench run for a duplicate number. Skip it
        # with a marker and let the other scenarios inherit its share;
        # BENCH_LAT_GROUPS (or selecting `latency` directly) still runs
        # it, and TPU runs keep the 12,500 per-chip shard shape.
        order.remove("latency")
        results["latency"] = {
            "skipped": "cpu-duplicate-of-engine-shape",
            "note": "engine scenario at the same G already reports the "
                    "50%-load p50/p99; set BENCH_LAT_GROUPS or run "
                    "`latency` directly to force a distinct shape"}
    remaining = deadline - time.time()
    shares = ([_WEIGHTS[sc] for sc in order] if len(order) > 1
              else [1.0])
    # Reallocate a dropped scenario's share instead of idling it.
    shares = [s / sum(shares) for s in shares]

    def emit(results):
        """Print the CUMULATIVE result line after every scenario: if a
        later scenario overruns and the parent kills us, the completed
        measurements already reached stdout (the parent keeps the LAST
        line)."""
        primary = results[order[0]]
        out = {
            "metric": f"aggregate_commits_per_sec_{G}_groups_{P0}_peers",
            "value": primary["commits_per_sec"],
            "unit": "commits/s",
            "vs_baseline": round(primary["commits_per_sec"]
                                 / BASELINE_WRITES_PER_SEC, 2),
            "p50_commit_latency_ms": primary["p50_commit_latency_ms"],
            "p99_commit_latency_ms": primary["p99_commit_latency_ms"],
            "round_ms": primary.get("round_ms_pipelined",
                                    primary.get("round_ms_synced")),
            "rounds": primary.get("rounds_pipelined",
                                  primary.get("rounds_synced")),
            "platform": devs[0].platform,
            "scenario": order[0],
            "scenarios": {k: v for k, v in results.items()
                          if k != order[0]},
        }
        # The primary scenario's dict is otherwise reduced to the
        # headline columns; the observability columns must reach the
        # artifact even when engine leads the run (BENCH_SCENARIO=engine
        # BENCH_OBS_AB=N is exactly that shape).
        for extra in ("obs_overhead_pct", "obs_ab", "metrics_delta"):
            if extra in primary:
                out[extra] = primary[extra]
        print(json.dumps(out), flush=True)

    for i, (sc, share) in enumerate(zip(order, shares)):
        if i > 0 and time.time() > deadline - 5.0:
            log(f"budget exhausted; skipping scenarios {order[i:]}")
            break
        sc_deadline = min(time.time() + remaining * share, deadline)
        snap0 = _metrics_snapshot()
        if sc == "engine":
            ab_pairs = int(os.environ.get("BENCH_OBS_AB", "0"))
            if ab_pairs:
                results[sc] = measure_obs_ab(sc_deadline, ab_pairs)
            else:
                results[sc] = measure_engine(sc_deadline)
        elif sc == "latency":
            # The per-chip shard shape: 100k north-star groups / 8 chips.
            # Most of the budget goes to the paced 50%-load phase — this
            # scenario exists to measure the <10 ms p99 ack target where
            # it is stated, not to maximize throughput.
            # 12,500 is a TPU shape; the single CPU core saturates on
            # apply far below it (same reasoning as the engine cap).
            G_lat = int(os.environ.get("BENCH_LAT_GROUPS",
                                       12_500 if on_tpu else 2048))
            results[sc] = measure_engine(sc_deadline, G_e=G_lat,
                                         sat_frac=0.35, label=sc)
            results[sc]["target_p99_ms"] = 10.0
        elif sc == "qread":
            results[sc] = measure_qread(sc_deadline)
        elif sc == "shallow_clients":
            results[sc] = measure_shallow_clients(sc_deadline)
        elif sc == "watch_storm":
            results[sc] = measure_watch_storm(sc_deadline)
        elif sc == "expiry_wave":
            results[sc] = measure_expiry_wave(sc_deadline)
        elif sc == "zipf":
            res, st, inbox = measure_zipf(st, inbox, sc_deadline, rounds)
            results[sc] = res
        elif sc == "churn":
            # BASELINE config 5 runs churn at SEVEN peers (100k x 7):
            # rebind the child-scope geometry the measure() closures read
            # (late binding) and boot a fresh 7-peer state.
            P = int(os.environ.get("BENCH_CHURN_PEERS", 7))
            cfg = KernelConfig(groups=G, peers=P, window=16, max_ents=4,
                               election_tick=10, heartbeat_tick=3)
            st7 = init_state(cfg, stagger=True)
            in7 = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
            for _ in range(8):
                st7, in7 = kernel.step_routed_auto(cfg, st7, in7, zero,
                                                   zero, jnp.asarray(True))
                if ((np.asarray(st7.state) == LEADER).sum(axis=1)
                        >= 1).all():
                    break
            full = jnp.full(G, cfg.max_ents, jnp.int32)
            res, st7, in7 = measure(sc, st7, in7, sc_deadline, rounds)
            res["peers"] = P
            results[sc] = res
        else:
            res, st, inbox = measure(sc, st, inbox, sc_deadline, rounds)
            results[sc] = res
        results[sc].setdefault("platform", devs[0].platform)
        results[sc]["metrics_delta"] = _metrics_delta(
            snap0, _metrics_snapshot())
        emit(results)
    return 0


# ---------------------------------------------------------------------------
# Parent: stays off JAX, streams the child's JSON lines, enforces the budget
# ---------------------------------------------------------------------------

def _run_child(extra_env: dict, timeout_s: float):
    """Run one measurement child, STREAMING its cumulative JSON lines to our
    stdout the moment they appear: if an external timeout kills this whole
    process mid-run, every scenario measured so far has already been
    printed (consumers take the last line). Returns the last line seen."""
    env = dict(os.environ)
    env.update(extra_env)
    env["BENCH_CHILD"] = "1"
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env, stdout=subprocess.PIPE, stderr=None)
    best = None
    deadline = time.time() + timeout_s

    def feed(raw: bytes):
        """Forward a candidate result line iff it is WHOLE, valid JSON —
        a kill can leave a truncated tail that must never become the
        'last matching line' a consumer parses."""
        nonlocal best
        line = raw.decode(errors="replace").strip()
        if not (line.startswith("{") and '"metric"' in line):
            return
        try:
            json.loads(line)
        except ValueError:
            return
        best = line
        print(line, flush=True)

    import selectors
    sel = selectors.DefaultSelector()
    sel.register(p.stdout, selectors.EVENT_READ)
    buf = b""
    try:
        while True:
            if p.poll() is not None:
                buf += p.stdout.read() or b""
                break
            if time.time() > deadline:
                log(f"bench child timed out after {timeout_s:.0f}s")
                p.kill()
                p.wait()
                buf += p.stdout.read() or b""  # drain what it got out
                break
            if sel.select(timeout=0.5):
                chunk = os.read(p.stdout.fileno(), 65536)
                if not chunk:
                    p.wait()
                    break
                buf += chunk
            while b"\n" in buf:
                raw, buf = buf.split(b"\n", 1)
                feed(raw)
    finally:
        sel.close()
    for raw in buf.splitlines():
        feed(raw)
    if best is None:
        log(f"bench child exited rc={p.returncode} without a JSON line")
    return best, p.returncode


def _regression_gate(line: str, artifact_dir=None) -> None:
    """Diff the final result against the previous round's driver artifact
    (BENCH_r{N}.json) and flag >20% same-workload drops LOUDLY — the r04
    artifact shipped a churn number measured at a silently redefined
    geometry (P=7 vs r03's P=5) plus a contention-skewed uniform number,
    and nothing called it out. Comparisons are gated on matching platform
    AND matching geometry (metric name carries groups/peers; churn
    carries its own 'peers'; engine its own 'groups') so a legitimate
    workload change reads as 'not comparable', never as a regression.
    On a flagged drop the LAST emitted line carries 'perf_regressions',
    so the marker lands in the artifact of record."""
    import glob as _g
    import re as _re
    try:
        cur = json.loads(line)
    except ValueError:
        return
    root = artifact_dir or os.path.dirname(os.path.abspath(__file__))
    arts = sorted(
        _g.glob(os.path.join(root, "BENCH_r*.json")),
        key=lambda p: int(_re.search(r"r(\d+)",
                                     os.path.basename(p)).group(1)))
    prev = None
    for p in reversed(arts):
        try:
            with open(p) as f:
                cand = json.load(f).get("parsed")
            if cand and cand.get("value"):
                prev, prev_name = cand, os.path.basename(p)
                break
        except (ValueError, OSError):
            continue
    if prev is None:
        return
    flags = []

    def cmp(name, new, old, new_geom, old_geom, lower_better=False):
        if not new or not old:
            return
        if new_geom != old_geom:
            log(f"perf-gate: {name} not comparable to {prev_name} "
                f"({new_geom} vs {old_geom})")
            return
        # The same >20% rule both ways: throughput dropping below 0.8x,
        # or a lower-better column (latency) rising above 1/0.8 = 1.25x.
        worse = (new > old / 0.8) if lower_better else (new < 0.8 * old)
        if worse:
            pct = (new / old - 1) if lower_better else (1 - new / old)
            flags.append({"scenario": name, "now": new, "prev": old,
                          "prev_artifact": prev_name,
                          "drop_pct": round(100 * pct, 1)})

    plat = cur.get("platform")
    prev_plat = prev.get("platform")
    # The primary's metric string doesn't encode WHICH scenario led the
    # run (a BENCH_SCENARIO=engine run reuses it) — gate on the scenario
    # name too, or a subset run gets compared against uniform.
    cmp("primary", cur.get("value"), prev.get("value"),
        (cur.get("metric"), cur.get("scenario"), plat),
        (prev.get("metric"), prev.get("scenario"), prev_plat))
    for sc, v in (cur.get("scenarios") or {}).items():
        o = (prev.get("scenarios") or {}).get(sc)
        if not o:
            continue
        geom_keys = {"churn": "peers", "engine": "groups",
                     "latency": "groups", "qread": "groups",
                     "expiry_wave": "groups",
                     "watch_storm": "watchers",
                     "shallow_clients": "conns"}.get(sc)
        # Geometry tuple: the scenario's own shape key where it has one,
        # the platform (older artifacts carry no per-scenario platform
        # key — fall back to the artifact-level platform on BOTH sides,
        # or every scenario reads "not comparable" and the gate silently
        # no-ops), AND the primary metric string — zipf/lag inherit the
        # top-level G/P, so a BENCH_GROUPS change must degate them too.
        ng = (v.get(geom_keys) if geom_keys else None,
              v.get("platform", plat), cur.get("metric"))
        og = (o.get(geom_keys) if geom_keys else None,
              o.get("platform", prev_plat), prev.get("metric"))
        cmp(sc, v.get("commits_per_sec"), o.get("commits_per_sec"),
            ng, og)
        # Round-7 columns, gated only when BOTH artifacts carry them
        # (older rounds predate the writer compartment). Deep-queue
        # throughput is the headline the WAL pipeline moves; fsync
        # percentiles gate the other direction (a >20% latency RISE per
        # group commit). The compartment's geometry is part of the
        # tuple: wal_shards=4 vs 1 is a different workload, not a
        # regression. Queue depth and batch size are load-dependent
        # shapes, reported but not gated.
        wg_n = ng + (v.get("applier_shards"), v.get("wal_shards"))
        wg_o = og + (o.get("applier_shards"), o.get("wal_shards"))
        cmp(f"{sc}.deep_queue",
            v.get("deep_queue_acked_writes_per_sec"),
            o.get("deep_queue_acked_writes_per_sec"), wg_n, wg_o)
        for col in ("wal_fsync_p50_ms", "wal_fsync_p99_ms"):
            cmp(f"{sc}.{col}", v.get(col), o.get(col), wg_n, wg_o,
                lower_better=True)
        # Round-9 read/watch/expiry columns, gated only when BOTH
        # artifacts carry them (older rounds predate the read plane).
        # Throughputs already ride the generic commits_per_sec mirror
        # above; here the LOWER-is-better tails (read latency, watch
        # staleness, expiry round-stall) gate a >20% RISE, and the
        # read-plane advantage ratio gates a >20% fall — a qread that
        # drifts back toward the propose path's cost is a regression
        # even if absolute reads/s held up.
        for col, lb in (("qread_vs_qget", False),
                        ("qread_p99_ms", True),
                        ("staleness_p99_ms", True),
                        ("round_stall_ms", True),
                        # Round-10 ingress-tier columns: the coalescing
                        # advantage ratio gates a >20% fall (an ingress
                        # drifting back toward direct shallow cost is a
                        # regression even if absolute acked/s held) and
                        # the client-observed ack tail a >25% rise.
                        ("ingress_vs_direct", False),
                        # Round-11 column: the pipelined channel's
                        # advantage over a round-10-configured (JSON
                        # single-POST) ingress in the same interleaved
                        # run gates a >20% fall; the ack tail
                        # (ingress_ack_p99_ms above) keeps gating a
                        # rise — pipelining must buy throughput without
                        # giving the client-observed tail back.
                        ("ingress_pipelined_vs_r10", False),
                        ("ingress_ack_p99_ms", True)):
            cmp(f"{sc}.{col}", v.get(col), o.get(col), ng, og,
                lower_better=lb)
        # Instrumentation-overhead budget: the observability plane may
        # cost at most 3% of deep-queue throughput in its own
        # interleaved A/B (absolute budget, not vs the prior artifact —
        # the A/B already carries its own baseline side).
        ov = v.get("obs_overhead_pct")
        if ov is not None and ov > 3.0:
            flags.append({"scenario": f"{sc}.obs_overhead_pct",
                          "now": ov, "prev": 3.0,
                          "prev_artifact": "obs-overhead-budget",
                          "drop_pct": round(ov, 1)})
    # The overhead budget also applies when engine LED the run and its
    # columns ride the top level (see emit's passthrough).
    ov0 = cur.get("obs_overhead_pct")
    if ov0 is not None and ov0 > 3.0:
        flags.append({"scenario": f"{cur.get('scenario')}.obs_overhead_pct",
                      "now": ov0, "prev": 3.0,
                      "prev_artifact": "obs-overhead-budget",
                      "drop_pct": round(ov0, 1)})
    if flags:
        for fl in flags:
            log(f"PERF REGRESSION vs {fl['prev_artifact']}: "
                f"{fl['scenario']} {fl['now']:,} vs {fl['prev']:,} "
                f"(-{fl['drop_pct']}%)")
        cur["perf_regressions"] = flags
        print(json.dumps(cur), flush=True)


def _warn_orphans() -> None:
    """A leaked `python -m etcd_tpu` member (e.g. a timeout-killed test
    run's subprocess) time-slices this box's ONE core and silently skews
    every number measured here — exactly what produced a 2x phantom
    slowdown mid-round-5. Warn loudly; kill them first with
    BENCH_KILL_ORPHANS=1 (safe on a dedicated bench box)."""
    try:
        import subprocess as _sp
        out = _sp.run(["ps", "-eo", "pid,args"], capture_output=True,
                      text=True, timeout=10).stdout
        orphans = [ln.split(None, 1) for ln in out.splitlines()
                   if "-m etcd_tpu" in ln or "multihost_engine" in ln]
        orphans = [(int(p), a) for p, a in orphans
                   if int(p) != os.getpid()]
        if not orphans:
            return
        if os.environ.get("BENCH_KILL_ORPHANS") == "1":
            import signal as _sig
            for pid, _ in orphans:
                try:
                    os.kill(pid, _sig.SIGKILL)
                except OSError:
                    pass
            log(f"killed {len(orphans)} orphan engine process(es) "
                f"before measuring")
        else:
            log(f"WARNING: {len(orphans)} stray engine process(es) are "
                f"sharing this core — numbers below are contended "
                f"(pids {[p for p, _ in orphans]}; "
                f"BENCH_KILL_ORPHANS=1 removes them)")
    except Exception:  # noqa: BLE001 — diagnostics must not break bench
        pass


def main() -> int:
    if os.environ.get("BENCH_CHILD") == "1":
        return child_main()
    _warn_orphans()

    # Best-effort native build (~2s, idempotent): the engine scenario is
    # 2.6x faster on the C store core, and a freshly cleaned tree has no
    # .so — without this the serving number silently regresses to the
    # Python-store fallback. Checked by filename (importing etcd_tpu here
    # would pull jax into the parent, which must stay off the chip).
    try:
        import glob
        root = os.path.dirname(os.path.abspath(__file__))
        if not glob.glob(os.path.join(root, "etcd_tpu", "native",
                                      "storecore*.so")):
            r = subprocess.run([os.path.join(root, "build")],
                               capture_output=True, timeout=120)
            log(f"native build rc={r.returncode}"
                + ("" if r.returncode == 0 else
                   f": {r.stderr.decode(errors='replace')[-300:]}"))
    except Exception as e:  # noqa: BLE001 — fallbacks exist for everything
        log(f"native build skipped: {e}")

    budget = float(os.environ.get("BENCH_BUDGET_S", 480.0))
    # One child, one attempt, on the platform JAX finds (or the CPU when
    # BENCH_PLATFORM=cpu names it). A child that streamed ANY scenario
    # line produced a result; one that produced none — no chip, a crash,
    # a hang past the budget — is a failed run and exits non-zero.
    line, rc = _run_child({"BENCH_BUDGET_S": str(budget)},
                          timeout_s=budget + 15)
    if line is None:
        log("bench: no result (child rc=%s)" % rc)
        return rc or 1
    try:
        _regression_gate(line)
    except Exception as e:  # noqa: BLE001 — the gate must never
        log(f"perf-gate skipped: {e}")   # invalidate a measurement
    return 0


if __name__ == "__main__":
    sys.exit(main())
