#!/usr/bin/env python3
"""One run of one cell of the benchmark: the served engine, from the client's
side, on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data found by name: benchmark/workloads/
<name>.json -> benchmark/configs/<config>.json (the member's command line,
its guarantees) and benchmark/traffic/<mix>.json (read by the one generator,
lib/loadgen.py); with --trace 1 every benchmark/layer_metrics/*.json whose
`cells` fit is read by one of three readers (prom_delta, client,
device_trace). Adding a cell, a mix, a configuration or a layer metric is
adding files (README.md).

This process never imports JAX: the member (lib/member.py ->
etcd_tpu.etcdmain.main) owns the chip. A run that did not happen on a TPU
never reports `correct: true` and exits non-zero. `--rehearsal-groups N` is
for the CPU rehearsal only: it overrides the configuration's G, forces
`correct: false` and a non-zero exit.

The last line of standard output is the result: {"correct", "attempted",
"failed", "metrics", "device"[, "breakdown"], "compared"}; `compared` holds
every number compared with its limit, and the last lines of standard error
say the same. Earlier lines of standard output are one JSON object each:
phases, sample counts, the numbers compared.
"""
from __future__ import annotations

_T_START = __import__("time").monotonic()      # setup_s starts here

import argparse
import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(BENCH, "lib"))

import checker  # noqa: E402
import loadgen  # noqa: E402
import prom  # noqa: E402
import roofline  # noqa: E402
from harness import (ROOT, BenchFailure, Deadline, Member,  # noqa: E402
                     build_native, cache_dir, check, cli_value,
                     device_of, http, timed_out, with_groups)

TRACE_SECONDS = 3.0
READ_BACK_TRIES = 3
DL_BOOT, DL_READ_VARIANT, DL_EXIT = 900, 480, 60
DL_TOTAL = 1150            # a cold first run may take 1200 s


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def load_json(*parts) -> dict:
    path = os.path.join(BENCH, *parts)
    check(os.path.exists(path), f"no such file: {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str):
    cell = load_json("workloads", workload + ".json")
    cfg = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    loadgen.validate_mix(mix)
    return cell, cfg, mix


def percentile(sorted_vals: list, p: float) -> float:
    """Nearest rank, exact: all samples are kept."""
    return sorted_vals[max(0, math.ceil(p * len(sorted_vals)) - 1)]


# ---------------------------------------------------------------------------
# Set-up pieces
# ---------------------------------------------------------------------------

def first_quorum_read(m: Member) -> dict:
    """The read variant loads (or compiles) at the first ?quorum=true read:
    pay it here, in every cell, so that it is neither in the window nor in
    the check. One write first, so that there is something to read."""
    dl = Deadline("read-variant", DL_READ_VARIANT)
    url = m.base + "/tenants/0/v2/keys/bench/ready"
    resent = 0
    while True:
        code, body = http("PUT", url, {"value": "1"}, min(60.0, dl.left()))
        if not timed_out(code, body):
            break
        resent += 1
    check(code in (200, 201), f"read-variant: PUT answered {code} {body}")
    while True:
        code, body = http("GET", url + "?quorum=true", None,
                          min(60.0, dl.left()))
        if not timed_out(code, body):
            break
        resent += 1
    check(code == 200 and body["node"]["value"] == "1",
          f"read-variant: quorum read answered {code} {body}")
    return {"seconds": dl.elapsed(), "resent_after_timeout": resent}


def boot(m: Member, chips: int, require_tpu: bool) -> dict:
    dl = Deadline("boot", DL_BOOT)
    m.spawn()
    st = m.wait_status(dl, lambda s: True)
    if require_tpu:
        check(st["platform"] == "tpu",
              f"the member runs on {st['platform']!r}, not on a TPU")
        check(st["device_count"] == chips,
              f"{st['device_count']} devices visible, the cell asks {chips}")
    st = m.wait_status(dl, lambda s: s["groups_with_leader"] == s["groups"])
    check(st["groups"] == m.groups, f"member serves {st['groups']} groups")
    rows = st["device_rows"]
    check(sum(rows.values()) == m.groups and len(rows) == st["device_count"],
          f"state rows per device {rows}: want {m.groups} over "
          f"{st['device_count']} device(s)")
    return st


# ---------------------------------------------------------------------------
# The answers
# ---------------------------------------------------------------------------

def discard_wal_tail(data_dir: str) -> dict:
    """The control: break the durability guarantee underneath the member.
    Every engine WAL stream loses the second half of its newest segment, as
    if the writes acknowledged late in the run had never been fsynced. The
    engine boots from a torn tail by design; the read-back must then miss
    acknowledged writes."""
    cut = {}
    streams: dict = {}
    for path in glob.glob(os.path.join(data_dir, "engine", "**", "*.wal"),
                          recursive=True):
        streams.setdefault(os.path.dirname(path), []).append(path)
    for segs in streams.values():
        newest = max(segs)
        size = os.path.getsize(newest)
        os.truncate(newest, size // 2)
        cut[os.path.relpath(newest, data_dir)] = [size, size // 2]
    return cut


def read_back(m: Member, ref: checker.Reference, keys: list, groups: int):
    """Quorum-read `keys` and one neighbour-tenant probe; returns
    (mismatches, leaked, n_read). A read that got no answer (the member can
    stall for seconds, e.g. at its full checkpoint) is sent again; one that
    stays unanswered counts as a mismatch."""
    todo = list(keys)
    probe = checker.isolation_probe(ref, keys, groups)
    if probe is not None:
        todo.append(probe)
    n_read, answers = len(todo), {}
    for _ in range(READ_BACK_TRIES):
        reqs = [(loadgen.get_bytes(t, k), (t, k)) for t, k in todo]
        for tag, status, body in loadgen.drive(m.port, reqs):
            if status in (200, 404):
                try:
                    answers[tuple(tag)] = loadgen.node_value(status, body)
                except (ValueError, KeyError):
                    pass
        todo = [k for k in todo if k not in answers]
        if not todo:
            break
    bad = checker.compare_reads(ref, answers)
    leaked = 1 if probe is not None and any(
        (t, k) == probe for t, k, _ in bad) else 0
    return len(bad) - leaked + len(todo), leaked, n_read


# ---------------------------------------------------------------------------
# Per-layer readers
# ---------------------------------------------------------------------------

def metric_applies(cells, workload: str, mix: dict) -> bool:
    if cells == "all":
        return True
    if cells == "writes":
        return mix["write_share"] > 0
    if cells == "quorum_reads":
        return mix["write_share"] < 1 and mix.get("read") == "quorum"
    return workload in cells


def read_layer_metrics(workload, mix, ctx) -> dict:
    """Every layer metric whose file says it applies to this cell; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for path in sorted(glob.glob(os.path.join(BENCH, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if not metric_applies(spec["cells"], workload, mix):
            continue
        src = spec["source"]
        reader = src["reader"]
        if reader == "prom_delta":
            v = prom.prom_delta(ctx["prom0"], ctx["prom1"], src,
                                ctx["window_s"])
        elif reader == "client":
            v = ctx["client"].get(src["stat"])
        elif reader == "device_trace":
            v = ctx["trace"].get(src["reduction"])
            if "module_pattern" in src:      # a reduction kept per program
                v = (v or {}).get(src["module_pattern"])
        else:
            raise BenchFailure(f"{path}: unknown reader {reader!r}")
        if v is not None:
            out[spec["name"]] = {"value": v, "unit": spec["unit"]}
    return out


def client_stats(ops: list, think: list, late: list, t1: float) -> dict:
    """What the `client` reader reads: statistics of the generator's own
    records (think and late sorted, seconds). A closed loop has no due time
    and an open one no think time: those read nothing."""
    return {
        "think_p50_us": percentile(think, 0.5) * 1e6 if think else None,
        "late_p99_ms": percentile(late, 0.99) * 1e3 if late else None,
        # sent (or due) and unanswered when the window ended: in flight,
        # waiting for a connection, or never answered
        "backlog_end": sum(1 for o in ops if o[2] is None or o[2] > t1)}


def module_patterns(workload: str, mix: dict) -> list:
    """The programs this cell's layer metrics time, each named by a pattern
    in the metric's own file."""
    found = set()
    for path in glob.glob(os.path.join(BENCH, "layer_metrics", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if (metric_applies(spec["cells"], workload, mix)
                and "module_pattern" in spec["source"]):
            found.add(spec["source"]["module_pattern"])
    return sorted(found)


def reduce_trace(work: str, ctl: str, patterns: list, least_s: float,
                 rounds) -> dict:
    """Run lib/trace_reduce.py on what the member's profiler wrote, then
    derive the metrics that need the peaks and the traced window. least_s:
    the least time one step can take on this device (lib/roofline.py)."""
    out_path = os.path.join(work, "trace_reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(BENCH, "lib", "trace_reduce.py"),
           os.path.join(ctl, "trace"), out_path, str(rounds), *patterns]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=300)
    check(r.returncode == 0, f"trace_reduce failed: {r.stderr[-2000:]}")
    with open(out_path) as f:
        red = json.load(f)
    with open(os.path.join(ctl, "trace.done")) as f:
        red["window_s"] = json.load(f)["window_s"]
    if red["busy_s"] > 0:
        red["device_idle_pct"] = 100.0 * (1 - red["busy_s"] / red["window_s"])
    if red["source"] == "tpu":
        # (a rehearsal's CPU trace has no peak to be held against)
        red["module_roofline_pct"] = {
            pat: 100.0 * least_s / (ms / 1e3)
            for pat, ms in red["module_mean_ms"].items()}
    return red


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             groups_override: int | None = None, require_tpu: bool = True,
             front=None, control: bool = False) -> dict:
    """The whole run; returns the result line as a dict. `front` (tests
    only) maps the member's port to the port the load is offered at.
    `control` (--control): SIGKILL after the window, discard the WAL's tail,
    restart, read back: a run that must come out not correct."""
    cell, cfg, mix = load_cell(workload)
    Deadline.run_end = _T_START + DL_TOTAL
    built_s = build_native()
    cli = with_groups(cfg["cli"], groups_override)
    groups = cli_value(cli, "--engine-groups")
    work = os.path.join(ROOT, ".bench_work", f"{workload}-{seed}-{os.getpid()}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    ctl = os.path.join(work, "ctl") if trace else None
    if ctl:
        os.makedirs(ctl)
    emit(phase="start", workload=workload, seed=seed, seconds=seconds,
         trace=trace, groups=groups, cli=cli, build_s=built_s,
         compile_cache_dir=cache_dir())

    m = Member(work, cli, ctl)
    gen = None
    try:
        # ---- set-up ------------------------------------------------------
        st = boot(m, cfg["chips"], require_tpu)
        boot_s = time.monotonic() - _T_START
        port = front(m.port) if front else m.port
        gen = loadgen.Generator(work, seed, mix, groups, port)
        gen.open()
        qr = first_quorum_read(m)
        t = time.monotonic()
        preloaded = gen.preload() if mix.get("preload") else 0
        preload_s = time.monotonic() - t
        t0 = time.monotonic() + 0.05
        gen.run(t0, t0 + mix["warmup_seconds"], record=False, run_id=1)
        round0 = m.status(30.0)["round"]
        emit(phase="setup", boot_s=boot_s, first_quorum_read=qr,
             preloaded=preloaded, preload_s=preload_s,
             warmup_s=mix["warmup_seconds"])

        # ---- the window --------------------------------------------------
        prom0 = prom.parse(m.metrics_text())
        t0 = time.monotonic() + 0.05
        t1 = t0 + seconds
        setup_s = t0 - _T_START
        traced_rounds = None
        with ThreadPoolExecutor(1) as pool:
            window = pool.submit(gen.run, t0, t1, True)
            if trace:
                # The last TRACE_SECONDS of the window, from the member's
                # own process; stop_trace (slow) falls after the window.
                time.sleep(max(0.0, t1 - TRACE_SECONDS - 0.3
                               - time.monotonic()))
                r0 = prom.total(prom.parse(m.metrics_text()),
                                "etcd_engine_rounds_total")
                open(os.path.join(ctl, "trace.start"), "w").close()
                _wait_file(os.path.join(ctl, "trace.started"), 60)
            time.sleep(max(0.0, t1 - time.monotonic()))
            prom1 = prom.parse(m.metrics_text())
            if trace:
                open(os.path.join(ctl, "trace.stop"), "w").close()
                traced_rounds = prom.total(
                    prom1, "etcd_engine_rounds_total") - r0
            window.result()
        if trace:
            _wait_file(os.path.join(ctl, "trace.done"), 240)
        rec = gen.dump()

        # ---- the answers -------------------------------------------------
        ref = rec["reference"]
        if mix.get("preload"):
            for tnt in range(groups):
                v = checker.value_for(seed, "pre", tnt, mix["value_bytes"])
                ref.model[(tnt, "/pre/k0")] = [v, []]
        keys = checker.sample_keys(ref, seed, mix["readback_keys"])
        numbers = {"stale_quorum_reads_in_window": rec["stale_reads"]}
        bad, leaked, n_read = read_back(m, ref, keys, groups)
        numbers["readback_mismatches"] = bad
        numbers["cross_tenant_leaks"] = leaked
        st = m.status(60.0)
        window_rounds = [round0, round0 + int(
            prom.total(prom1, "etcd_engine_rounds_total")
            - prom.total(prom0, "etcd_engine_rounds_total"))]
        numbers["mask_repairs"] = st["mask_repairs"]
        numbers["groups_without_leader"] = st["groups"] - st["groups_with_leader"]
        device = device_of(st)
        least_s = (roofline.step_min_seconds(
            max(st["device_rows"].values()), st["peers"],
            cli_value(cli, "--engine-window"), device["kind"])
            if device["platform"] == "tpu" else None)
        if trace or control:
            # An acknowledged write survives SIGKILL: engine WAL replay.
            rc = m.reap(signal.SIGKILL, Deadline("sigkill", DL_EXIT))
            check(rc == -signal.SIGKILL, f"SIGKILL gave rc={rc}")
            if control:
                emit(phase="control", wal_bytes_cut=discard_wal_tail(
                    m.data_dir))
            boot(m, cfg["chips"], require_tpu)
            first_quorum_read(m)
            bad, leaked, n2 = read_back(m, ref, keys, groups)
            numbers["readback_mismatches_after_sigkill"] = bad
            numbers["cross_tenant_leaks_after_sigkill"] = leaked
            n_read += n2
        rc = m.reap(signal.SIGTERM, Deadline("sigterm", DL_EXIT))
        numbers["sigterm_exit_code"] = rc
        gen.close()
        gen = None

        # ---- the numbers -------------------------------------------------
        ops = rec["ops"]
        attempted = len(ops)
        failed = sum(1 for o in ops if not o[3])
        in_window = sum(1 for o in ops if o[3] and o[2] <= t1)
        timeout_ms = loadgen.CLIENT_TIMEOUT_S * 1e3

        def lat_ms(kind=None):
            return sorted(((o[2] - o[1]) * 1e3 if o[3] else timeout_ms)
                          for o in ops if kind is None or o[0] == kind)

        all_ms, w_ms, r_ms = lat_ms(), lat_ms(loadgen.W), lat_ms(loadgen.R)
        check(attempted > 0, "the window saw no operation")
        e2e = {"acked_ops_per_s": {"value": in_window / seconds,
                                   "unit": "ops/s"},
               "ack_p50_ms": {"value": percentile(all_ms, 0.5), "unit": "ms"},
               "setup_s": {"value": setup_s, "unit": "s"}}
        if w_ms:
            e2e["write_ack_p99_ms"] = {"value": percentile(w_ms, 0.99),
                                       "unit": "ms"}
        if r_ms:
            e2e["qread_p99_ms"] = {"value": percentile(r_ms, 0.99),
                                   "unit": "ms"}
        think, late = sorted(rec["think"]), sorted(rec["late"])
        is_open = mix["loop"] == "open"
        client = client_stats(ops, think, late, t1)
        slices = [0] * max(1, math.ceil(seconds / 5.0))
        for o in ops:
            if o[3] and o[2] <= t1:
                slices[min(len(slices) - 1, int((o[2] - t0) / 5.0))] += 1
        emit(phase="samples", attempted=attempted, failed=failed,
             acked_per_5s_slice=slices, window_rounds=window_rounds,
             acked_in_window=in_window, writes=len(w_ms), reads=len(r_ms),
             read_back=n_read, think_samples=len(think),
             think_p99_us=percentile(think, 0.99) * 1e6 if think else None,
             gen_max_loop_gap_ms=rec["max_loop_gap_s"] * 1e3,
             ack_max_ms=all_ms[-1],
             **({"rate": mix["rate"],
                 "backlog_end": client["backlog_end"],
                 "pool_dry": rec["pool_dry"], "given_up": rec["given_up"],
                 "late_p50_ms": percentile(late, 0.5) * 1e3 if late else None,
                 "late_max_ms": late[-1] * 1e3 if late else None}
                if is_open else {}))
        layers = read_layer_metrics(workload, mix, {
            "prom0": prom0, "prom1": prom1, "window_s": seconds,
            "client": client, "trace": {}})
        emit(phase="layers_from_counters", metrics={
            k: v["value"] for k, v in layers.items()})
        correct, lines = checker.verdict(numbers)
        for line in lines:
            emit(**line)

        result = {"correct": correct, "attempted": attempted,
                  "failed": failed, "metrics": e2e, "device": device}
        if trace:
            red = reduce_trace(work, ctl, module_patterns(workload, mix),
                               least_s, traced_rounds)
            emit(phase="trace", source=red["source"],
                 xplane_bytes=red["xplane_bytes"], op_events=red["op_events"],
                 module_events=red["module_events"],
                 traced_rounds=traced_rounds,
                 modules=red["modules"], layout=red["layout"])
            if require_tpu:
                check(red["source"] == "tpu" and red["busy_s"] > 0,
                      "the trace holds no operation that ran on the device")
            result["metrics"] = read_layer_metrics(workload, mix, {
                "prom0": prom0, "prom1": prom1, "window_s": seconds,
                "client": client, "trace": red})
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
            emit(phase="end_to_end_of_traced_run", metrics=e2e)
        result["compared"] = {ln["check"]: {"value": ln["value"],
                                            "limit": ln["limit"]}
                              for ln in lines}
        return result
    except BaseException:
        tail = m.log_tail()
        if tail:
            print(f"--- member log tail ---\n{tail}", file=sys.stderr)
        raise
    finally:
        if gen is not None:
            gen.close()
        m.destroy()
        shutil.rmtree(work, ignore_errors=True)


def _wait_file(path: str, seconds: float) -> None:
    end = time.monotonic() + seconds
    while not os.path.exists(path):
        check(time.monotonic() < end, f"{os.path.basename(path)} did not "
                                      f"appear within {seconds}s")
        time.sleep(0.01)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal-groups", type=int, default=None,
                    help="CPU rehearsal only: overrides the configuration's "
                         "G and forces correct=false")
    ap.add_argument("--control", action="store_true",
                    help="the output check's control: lose the WAL's tail "
                         "under the member after the window; the run must "
                         "end correct=false")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "etcd_tpu")):
        print("benchmark/run.py: no etcd_tpu/ beside benchmark/: nothing to "
              "measure here", file=sys.stderr)
        return 2
    rehearsal = args.rehearsal_groups is not None
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), args.rehearsal_groups,
                          require_tpu=not rehearsal, control=args.control)
    except BenchFailure as e:
        print(f"benchmark/run.py: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    if rehearsal or result["device"]["platform"] != "tpu":
        result["correct"] = False
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] and not rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
