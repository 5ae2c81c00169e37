"""Reading the member's /metrics (Prometheus text) as deltas over a window.

Only counters and histogram `_sum`/`_count` series are read: the histogram
buckets are too coarse for percentiles.
"""
from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict:
    """{(series, ((label, value), ...)): float}, labels sorted."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line)
        if m is None:
            continue
        name, labels, value = m.groups()
        try:
            v = float(value)
        except ValueError:
            continue
        out[(name, tuple(sorted(_LABEL.findall(labels or ""))))] = v
    return out


def total(scrape: dict, series: str, labels: dict | None = None):
    """Sum of `series` over every label set that contains `labels` (all
    shards of a sharded compartment); None if the series is not there."""
    want = set((labels or {}).items())
    vals = [v for (name, lab), v in scrape.items()
            if name == series and want <= set(lab)]
    return sum(vals) if vals else None


def delta(before: dict, after: dict, term: dict, window_s: float):
    """One term of a prom_delta source: {"window_seconds": true} or
    {"series": ..., "labels": {...}}."""
    if term.get("window_seconds"):
        return window_s
    a = total(after, term["series"], term.get("labels"))
    b = total(before, term["series"], term.get("labels"))
    if a is None or b is None:
        return None
    return a - b


def prom_delta(before: dict, after: dict, source: dict, window_s: float):
    """num delta / den delta * scale; None where there is nothing to read
    (a series missing, or nothing counted in the window)."""
    num = delta(before, after, source["num"], window_s)
    if num is None:
        return None
    if "den" in source:
        den = delta(before, after, source["den"], window_s)
        if not den:
            return None
        num /= den
    return num * source.get("scale", 1)
