#!/usr/bin/env python3
"""From a profiler trace (.xplane.pb) to numbers.

Run as a subprocess AFTER the member has exited (the chip belongs to one
process; this one imports jax only for jax.profiler.ProfileData and is
started with JAX_PLATFORMS=cpu so that it touches no accelerator):

    python benchmark/lib/trace_reduce.py <trace dir> <out.json> <rounds>
                                         [<module pattern> ...]

The arithmetic (interval union, gaps, labelling, top-by-name) works on
(name, start_ns, end_ns) tuples, so it is tested without a trace.

What is read from a TPU trace: planes named /device:TPU:<n>; on each, the
line "XLA Ops" (one event per executed HLO op: busy time is the union of
these) and the line "XLA Modules" (one event per executed program). A layer
metric that times a program names it by a pattern in its own data file
(`module_pattern`); a pattern that matches no executed program in a TPU
trace is an error, never another quantity under the same name.
Host planes (/host:*) give the TraceMe events of PjRt (execute, transfers)
that label the device's idle gaps. A CPU trace has no device plane; for the
rehearsal and the tests the events that carry an `hlo_op` stat stand in for
device ops (source "cpu-hlo", never reported from a chip run), and, there
being no line of programs, busy time over rounds stands in for every pattern.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
LABELLED_GAPS = 2000         # the longest gaps get a host label
TOP = 10


# ---------------------------------------------------------------------------
# Arithmetic on intervals
# ---------------------------------------------------------------------------

def union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def gaps_between(merged):
    """The idle intervals between merged busy intervals."""
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:])]


def short_name(name: str) -> str:
    """A TPU trace names an op by its whole HLO text, `%cond.62 = (s32[...`:
    keep what stands before the ` = `."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def top_by_name(events, n: int = TOP):
    """[[name, total seconds]] of (name, start_ns, end_ns) events."""
    tot: dict = {}
    for name, s, e in events:
        name = short_name(name)
        tot[name] = tot.get(name, 0) + (e - s)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def label_gaps(gaps, host_events, n: int = TOP,
               labelled: int = LABELLED_GAPS):
    """[[label, idle seconds]]: each of the `labelled` longest gaps takes the
    name of the host event that overlaps it most (the shortest such event on
    a tie, i.e. the innermost), else host:unattributed; shorter gaps are
    summed under gaps:short."""
    import numpy as np      # with jax in this subprocess; not the runner

    by_len = sorted(gaps, key=lambda g: g[0] - g[1])
    names = [h[0] for h in host_events]
    starts = np.array([h[1] for h in host_events], dtype=np.int64)
    ends = np.array([h[2] for h in host_events], dtype=np.int64)
    tot: dict = {}
    for a, b in by_len[:labelled]:
        label = "host:unattributed"
        if names:
            ov = np.minimum(b, ends) - np.maximum(a, starts)
            cand = np.flatnonzero(ov > 0)
            if len(cand):
                pick = cand[np.lexsort(((ends - starts)[cand], -ov[cand]))[0]]
                label = "host:" + names[pick]
        tot[label] = tot.get(label, 0) + (b - a)
    rest = sum(b - a for a, b in by_len[labelled:])
    if rest:
        tot["gaps:short"] = rest
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


class NoSuchModule(LookupError):
    pass


def module_mean_ms(modules, pattern: str):
    """(mean duration in ms, executions) of the programs whose name matches
    `pattern`; per device: a mesh runs one program on every chip at once."""
    rx = re.compile(pattern)
    took = [e - s for name, s, e in modules if rx.search(name)]
    if not took:
        raise NoSuchModule(
            f"no executed program matches {pattern!r}; the trace holds "
            f"{sorted({short_name(m[0]) for m in modules})[:20]}")
    return sum(took) / len(took) / 1e6, len(took)


def reduce_events(device_planes, host_events, rounds=None, source="tpu",
                  patterns=()):
    """device_planes: [{"ops": [(name, s, e)], "modules": [(name, s, e)]}]
    per device. Returns the reductions the layer-metric files name;
    `module_mean_ms` and `module_events` are keyed by pattern."""
    busy, spans, all_ops, all_mods = [], [], [], []
    idle_gaps = []
    for d in device_planes:
        merged = union((s, e) for _, s, e in d["ops"])
        busy.append(length(merged))
        if merged:
            spans.append(merged[-1][1] - merged[0][0])
        idle_gaps += gaps_between(merged)
        all_ops += d["ops"]
        all_mods += d["modules"]
    n = max(1, len(device_planes))
    out = {
        "source": source,
        "devices": len(device_planes),
        "busy_s": sum(busy) / n / 1e9,
        "device_span_s": (max(spans) / 1e9) if spans else 0.0,
        "op_events": len(all_ops),
        "modules": top_by_name(all_mods),
        "device_ops": top_by_name(all_ops),
        "idle_gaps": label_gaps(idle_gaps, host_events),
    }
    means, events = {}, {}
    for pattern in patterns:
        if source == "tpu":
            means[pattern], events[pattern] = module_mean_ms(all_mods,
                                                             pattern)
        elif rounds and busy:            # the rehearsal's stand-in
            means[pattern] = sum(busy) / n / rounds / 1e6
    out["module_mean_ms"], out["module_events"] = means, events
    return out


# ---------------------------------------------------------------------------
# Reading the file (needs jax)
# ---------------------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str):
    """(device_planes, host_events, source, layout)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_planes, host_events, layout = [], [], {}
    cpu_hlo = []
    planes = list(data.planes)
    on_chip = any(p.name.startswith("/device:") for p in planes)
    for plane in planes:
        is_dev = plane.name.startswith("/device:")
        is_host = plane.name.startswith("/host:")
        lines = {}
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events]
            lines[line.name] = evs
            layout.setdefault(plane.name, {})[line.name] = len(evs)
            if is_host:
                host_events += [ev for ev in evs if ev[2] > ev[1]
                                and not ev[0].startswith("ThreadpoolListener")]
        if is_dev and lines.get(OPS_LINE):
            device_planes.append({"ops": lines[OPS_LINE],
                                  "modules": lines.get(MODULES_LINE, [])})
        if is_host and not on_chip:
            for line in plane.lines:
                for e in line.events:
                    if any(k == "hlo_op" for k, _ in e.stats):
                        cpu_hlo.append((e.name, int(e.start_ns),
                                        int(e.start_ns + e.duration_ns)))
    if device_planes:
        return device_planes, host_events, "tpu", layout
    if cpu_hlo:
        hlo = set(cpu_hlo)
        host_events = [ev for ev in host_events if ev not in hlo]
        return [{"ops": cpu_hlo, "modules": []}], host_events, "cpu-hlo", layout
    return [], host_events, "none", layout


def main(argv) -> int:
    trace_dir, out_path, rounds = argv[1], argv[2], float(argv[3])
    path = find_xplane(trace_dir)
    device_planes, host_events, source, layout = read_xplane(path)
    try:
        out = reduce_events(device_planes, host_events, rounds, source,
                            argv[4:])
    except NoSuchModule as e:
        print(f"trace_reduce: {e}", file=sys.stderr)
        return 1
    out["xplane_bytes"] = os.path.getsize(path)
    out["layout"] = layout
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
