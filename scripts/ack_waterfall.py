#!/usr/bin/env python3
"""One benchmark cell, and where its slowest acks spent their time.

Runs `benchmark/run.py` as it is (same arguments, same result line) and,
between the window's end and the read-back, takes the member's
`GET /debug/traces`: the sampled request spans of the window's last seconds
(server/obs.py Tracer keeps the newest 4,096 finished spans). Prints one
more line, `{"phase": "waterfall", ...}`: per kind the spans folded, each
segment's mean over them, and for the slowest 1 % the mean of each segment
and the segment that took most of their time (what `write_ack_p99_ms` /
`qread_p99_ms` is made of: the benchmark's `prom_delta` reads means only);
and `tiling`, from the window's two `/metrics` scrapes: per kind the sum of
the segments' means beside the mean of `etcd_http_request_seconds`, which
they tile (equal to the sample: 1 request id in 16 against all).
The spans go to <out>/waterfall-<workload>-<seed>.json.

    python3 scripts/ack_waterfall.py --workload share12k5.put256-c256 \
        --seed 7 --seconds 30 --trace 1 [--out chiprun_out]
"""
import argparse
import json
import math
import os
import sys
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "benchmark"),
          os.path.join(ROOT, "benchmark", "lib")):
    sys.path.insert(0, p)
sys.path.insert(0, ROOT)

import run                                              # noqa: E402
from etcd_tpu.server.obs import SEGMENT_NAMES, SEGMENT_STAMPS  # noqa: E402


def segments(span: dict) -> dict:
    """{segment: ms, "rounds": n} of one folded span of /debug/traces."""
    at = [span["stages"][s] for s in SEGMENT_STAMPS[span["kind"]]]
    row = {name: (b - a) * 1e3 for name, a, b in
           zip(SEGMENT_NAMES[span["kind"]], at, at[1:])}
    row["rounds"] = span["rounds"]
    return row


def mean(rows: list) -> dict:
    return {k: sum(r[k] for r in rows) / len(rows) for k in rows[0]}


def summary(spans: list) -> dict:
    out = {}
    for kind, names in SEGMENT_NAMES.items():
        rows = sorted((segments(s) for s in spans
                       if s.get("kind") == kind and "rounds" in s),
                      key=lambda r: sum(r[n] for n in names))
        if rows:
            slow = mean(rows[-math.ceil(len(rows) / 100):])
            out[kind] = {"spans": len(rows), "mean_ms": mean(rows),
                         "slowest_1pct": {"mean_ms": slow, "most_in": max(
                             names, key=slow.get)}}
    return out


def tiling(before: dict, after: dict) -> dict:
    """Per kind, ms: the segments' means summed | the front's mean span."""
    def mean_ms(series, **labels):
        src = {"num": {"series": series + "_sum", "labels": labels},
               "den": {"series": series + "_count", "labels": labels},
               "scale": 1000}
        return run.prom.prom_delta(before, after, src, 0.0)
    out = {}
    for kind, names in SEGMENT_NAMES.items():
        segs = [mean_ms("etcd_request_segment_seconds", kind=kind,
                        segment=n) for n in names]
        if None not in segs:
            out[kind] = {"segments_ms": sum(segs), "request_ms": mean_ms(
                "etcd_http_request_seconds", kind=kind)}
    return out


def main(argv: list) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    opts, argv = ap.parse_known_args(argv)
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--seed")
    cell, _ = ap.parse_known_args(argv)
    taken = {}
    read_back = run.read_back

    def traces_then_read_back(m, *args):
        if not taken:
            with urllib.request.urlopen(m.base + "/debug/traces",
                                        timeout=60) as r:
                taken.update(json.load(r))
        return read_back(m, *args)

    run.read_back = traces_then_read_back
    scrapes = []
    parse = run.prom.parse
    run.prom.parse = lambda text: scrapes.append(parse(text)) or scrapes[-1]
    rc = run.main(argv)
    if taken:
        os.makedirs(opts.out, exist_ok=True)
        path = os.path.join(opts.out, "waterfall-%s-%s.json" % (
            cell.workload, cell.seed))
        with open(path, "w") as f:
            json.dump(taken, f)
        print(json.dumps({"phase": "waterfall", "every": taken["every"],
                          "kept": len(taken["spans"]), "file": path,
                          "tiling": tiling(scrapes[0], scrapes[-1]),
                          **summary(taken["spans"])}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
