"""The pipeline observability plane (server/obs.py, utils/metrics.py):

- /metrics exposes every compartment's histograms and gauges (round
  loop, WAL writer shards, applier shards, ack gate) and stays
  un-torn and monotone under concurrent deep-queue writes — verified
  at the HTTP level through the same parser etcd_top uses.
- The registry's acked-requests counter moves by EXACTLY the number of
  writes the engine reports acked (a scrape-to-scrape delta is what
  the benchmark's per-layer metrics are made of).
- The flight recorder ring wraps without mixing rounds, drops late
  marks for evicted rounds, and its SIGUSR2 dump is valid Chrome
  trace-event JSON carrying all six pipeline stages.
- Sampled trace ids ride the durable WAL payloads: a SIGKILL'd engine's
  acked writes come back as `replayed` trace spans in the restarted
  process.
- The accounting of PR 24: the seven disjoint round phases tile the
  loop, record's parts add up to record, device->host reads repeat
  exactly, the HTTP front's span and self time, the staging-queue wait,
  CPU by thread class, the three front marks of a sampled rid, the
  profiler annotations on a CPU trace — and all of it flat under
  ETCD_TPU_OBS=off.
- The sampled request trace of PR 41: a finished span's consecutive
  stamps fold into etcd_request_segment_seconds{kind, segment}, which
  tile etcd_http_request_seconds, and etcd_request_rounds{kind}; `durable`
  is the WAL writer's own stamp; 1 request id in 16 by default; what lacks
  a stamp is counted, not folded; nothing stays in the in-flight table.
"""
import importlib.util
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from etcd_tpu.server import engine as engine_mod              # noqa: E402
from etcd_tpu.server import obs as obs_mod                     # noqa: E402
from etcd_tpu.utils import metrics                             # noqa: E402

G, P = 6, 3


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_for_obs_test", os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- unit: histogram + exposition escaping -----------------------------------


def test_histogram_buckets_cumulative_and_consistent():
    reg = metrics.Registry()
    h = metrics.Histogram("t_hist_seconds", "t", buckets=(0.01, 0.1, 1.0),
                          registry=reg)
    for v in (0.005, 0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    rows = {(n, tuple(sorted(lab.items()))): v
            for n, lab, v in h.samples()}
    assert rows[("t_hist_seconds_bucket", (("le", "0.01"),))] == 2
    assert rows[("t_hist_seconds_bucket", (("le", "0.1"),))] == 3
    assert rows[("t_hist_seconds_bucket", (("le", "1.0"),))] == 4
    assert rows[("t_hist_seconds_bucket", (("le", "+Inf"),))] == 5
    assert rows[("t_hist_seconds_count", ())] == 5
    assert abs(rows[("t_hist_seconds_sum", ())] - 5.56) < 1e-9
    # The labeled variant keeps per-child series under one family.
    lh = metrics.LabeledHistogram("t_lab_seconds", "t", ("shard",),
                                  buckets=(1.0,), registry=reg)
    lh.labels("0").observe(0.5)
    lh.labels("1").observe(2.0)
    text = reg.expose()
    assert 't_lab_seconds_bucket{le="1.0",shard="0"} 1' in text
    assert 't_lab_seconds_bucket{le="+Inf",shard="1"} 1' in text
    assert text.count("# TYPE t_lab_seconds histogram") == 1


def test_expose_escapes_label_values_roundtrip():
    """Satellite fix: backslash, double-quote, and newline in a label
    value must be escaped per the text exposition format — and round-
    trip back through a conforming parser (etcd_top's)."""
    reg = metrics.Registry()
    c = metrics.LabeledCounter("t_esc_total", 'help with "quotes"\nand\\',
                               ("path",), registry=reg)
    evil = 'a\\b"c\nd'
    c.labels(evil).inc(3)
    text = reg.expose()
    assert 'path="a\\\\b\\"c\\nd"' in text
    # HELP escapes backslash + newline (no quote escaping there).
    assert '# HELP t_esc_total help with "quotes"\\nand\\\\' in text
    parsed = _load_script("etcd_top").parse_metrics(text)
    assert parsed[("t_esc_total", (("path", evil),))] == 3.0


def test_etcd_top_quantiles_and_render():
    top = _load_script("etcd_top")
    prev = {("h_bucket", (("le", "0.1"),)): 0.0,
            ("h_bucket", (("le", "+Inf"),)): 0.0,
            ("h_count", ()): 0.0, ("h_sum", ()): 0.0,
            ("etcd_engine_rounds_total", ()): 10.0}
    cur = {("h_bucket", (("le", "0.1"),)): 90.0,
           ("h_bucket", (("le", "+Inf"),)): 100.0,
           ("h_count", ()): 100.0, ("h_sum", ()): 5.0,
           ("etcd_engine_rounds_total", ()): 30.0}
    buckets, total, dsum = top.hist_delta(prev, cur, "h")
    assert total == 100.0 and dsum == 5.0
    assert top.quantile(buckets, total, 0.5) == 0.1
    assert top.quantile(buckets, total, 0.99) == float("inf")
    assert top.counter_rate(prev, cur, "etcd_engine_rounds_total",
                            2.0) == 10.0
    frame = top.render(prev, cur, 2.0)
    assert any("rounds/s" in ln for ln in frame)


# -- unit: flight recorder ----------------------------------------------------


def test_flight_ring_wraparound_drops_late_marks():
    fl = obs_mod.FlightRecorder(capacity=16)
    base = 1000.0
    for rnd in range(40):
        for st in range(6):
            fl.mark(rnd, st, base + rnd + st * 0.01)
    rows = fl.snapshot()
    live = sorted(r[0] for r in rows if r[0] >= 0)
    assert live == list(range(24, 40))            # last 16 rounds only
    # A late mark for an evicted round must be DROPPED, not written
    # into whatever round now owns the slot.
    fl.mark(3, obs_mod.ACKED, 9999.0)
    row19 = next(r for r in fl.snapshot() if r[0] == 3 + 16 * 2)
    assert 9999.0 not in row19
    # Every surviving row is internally one round: stages ascend.
    for r in fl.snapshot():
        stamps = [r[1 + k] for k in range(6) if r[1 + k] > 0]
        assert stamps == sorted(stamps)


def test_flight_dump_is_chrome_trace_json(tmp_path):
    fl = obs_mod.FlightRecorder(capacity=32)
    for rnd in range(8):
        for st in range(6):
            fl.mark(rnd, st, 5.0 + rnd * 0.1 + st * 0.001)
    path = fl.dump(str(tmp_path), "golden")
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs if e["ph"] == "i"}
    assert names == set(obs_mod.STAGE_NAMES)
    spans = {e["name"] for e in evs if e["ph"] == "X"}
    assert f"{obs_mod.STAGE_NAMES[0]}->{obs_mod.STAGE_NAMES[1]}" in spans
    for e in evs:
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] > 0


def test_obs_disabled_master_switch(monkeypatch):
    monkeypatch.setenv("ETCD_TPU_OBS", "off")
    eo = obs_mod.EngineObs(wal_shards=2, applier_shards=2)
    assert not eo.enabled and not eo.flight.enabled
    fl = obs_mod.FlightRecorder(capacity=16)
    fl.mark(1, obs_mod.SUBMITTED, 1.0)
    assert all(r[0] == -1 for r in fl.snapshot())



# -- PR 24 units: run before the module's live engine exists, so the
# process-global series move only by what each test does ---------------------


def _reg():
    """The live registry as {(series, sorted labels): value} (etcd_top's
    parser, as the HTTP-level tests use)."""
    return _load_script("etcd_top").parse_metrics(metrics.REGISTRY.expose())


def _val(scrape, series, **labels):
    want = set(labels.items())
    vals = [v for (name, lab), v in scrape.items()
            if name == series and want <= set(lab)]
    return sum(vals) if vals else None


def _delta(a, b, series, **labels):
    return _val(b, series, **labels) - _val(a, series, **labels)


def _small_engine(tmp_path, **kw):
    from etcd_tpu.server.engine import EngineConfig, MultiEngine
    cfg = dict(groups=4, peers=3, data_dir=str(tmp_path), window=16,
               max_ents=4, heartbeat_tick=3, fsync=False,
               checkpoint_rounds=1 << 30, request_timeout=60.0)
    cfg.update(kw)
    return MultiEngine(EngineConfig(**cfg))


def _elect(eng, rounds=400):
    for _ in range(rounds):
        eng.run_round()
        if all(eng.leader_slot(g) >= 0 for g in range(eng.cfg.groups)):
            return
    raise AssertionError("no leaders")


def test_thread_cpu_reads_another_threads_clock():
    tc = obs_mod.ThreadCpu()
    go, done = threading.Event(), threading.Event()

    def busy():
        tc.register("round")
        go.wait(10)
        end = time.thread_time() + 0.2
        while time.thread_time() < end:
            pass
        done.wait(10)

    th = threading.Thread(target=busy)
    th.start()
    try:
        if not tc.read():
            pytest.skip("no per-thread CPU clock on this platform")
        before = tc.read()["round"]
        go.set()
        deadline = time.time() + 20
        while tc.read()["round"] - before < 0.19 and time.time() < deadline:
            time.sleep(0.01)
        assert tc.read()["round"] - before >= 0.19
        lines = obs_mod.cpu_exposition(tc)
        assert any(ln.startswith("process_cpu_seconds_total ")
                   for ln in lines)
        assert any('thread="front"' in ln for ln in lines)
    finally:
        go.set()
        done.set()
        th.join(10)
    assert not th.is_alive()
    # An exited thread is skipped, not read through a stale clock id.
    assert tc.read() == {"round": 0.0}


def test_tracer_mark_takes_an_earlier_reading():
    tr = obs_mod.Tracer(every=1)
    tr.mark(7, "submit")
    tr.mark(7, "front_in", t=time.perf_counter() - 1.0)
    stages = tr.dump()["spans"][0]["stages"]
    assert list(stages) == ["front_in", "submit"]
    assert 0.9 < stages["submit"] < 1.5
    assert {"front_in", "staged", "confirmed", "woke",
            "replied"} <= set(obs_mod.TRACE_STAGES)


def test_d2h_syncs_per_round_repeat_exactly(tmp_path):
    """An idle compact round reads gather_rows' packed buffer at the
    smallest bucket (the attestation and no row), nothing else:
    syncs/round is the same integer in two windows of one idle engine,
    and bytes/round the same number."""
    eng = _small_engine(tmp_path, mask_check_rounds=0)
    try:
        _elect(eng)
        for _ in range(40):          # let the election's traffic settle
            eng.run_round()
        per_round = []
        for _ in range(2):
            a = _reg()
            for _ in range(20):
                eng.run_round()
            b = _reg()
            assert _delta(a, b, "etcd_engine_rounds_total") == 20
            per_round.append(
                (_delta(a, b, "etcd_engine_d2h_syncs_total") / 20,
                 _delta(a, b, "etcd_engine_d2h_bytes_total") / 20))
        assert per_round[0] == per_round[1]
        assert per_round[0][0] == 1
        # a header row and 256 rows of 7 + W int32
        assert per_round[0][1] == 257 * (7 + eng.cfg.window) * 4
        # run_round driven by hand: no gap, the loop's own phase.
        assert _delta(a, b, "etcd_engine_round_phase_seconds_count",
                      phase="gap") == 0
        assert _delta(a, b, "etcd_engine_round_phase_seconds_count",
                      phase="post") == 20
    finally:
        eng.stop()


@pytest.mark.parametrize("sync_interval,scans", [(3600.0, 1), (0.0, 0)])
def test_one_observation_a_scan_and_none_in_a_round_without_one(
        tmp_path, monkeypatch, sync_interval, scans):
    """etcd_engine_sync_scan_seconds is observed inside run_round's scan
    branch: once in the round whose clock says a scan is due, never in a
    round that scans nothing, and not at all with the scan switched off."""
    eng = _small_engine(tmp_path, sync_interval=sync_interval)
    calls = []
    stage_syncs = eng._stage_syncs
    monkeypatch.setattr(eng, "_stage_syncs",
                        lambda now: (calls.append(now), stage_syncs(now)))
    try:
        _elect(eng)                  # (the first round of all scanned)
        a = _reg()
        n = len(calls)
        for _ in range(10):
            eng.run_round()
        b = _reg()
        assert len(calls) == n
        assert _delta(a, b, "etcd_engine_sync_scan_seconds_count") == 0
        assert _delta(a, b, "etcd_engine_sync_scan_seconds_sum") == 0
        eng._last_sync_scan = 0.0    # the clock says: due
        for _ in range(6):
            eng.run_round()
        c = _reg()
        assert len(calls) == n + scans
        assert _delta(b, c, "etcd_engine_sync_scan_seconds_count") == scans
        assert (_delta(b, c, "etcd_engine_sync_scan_seconds_sum") > 0) == (
            scans > 0)
    finally:
        eng.stop()


def test_checkpoint_stores_total_is_the_stores_a_checkpoint_wrote(tmp_path):
    """etcd_engine_checkpoint_stores_total grows once a checkpoint, by the
    stores that checkpoint serialised: etcd_engine_checkpoint_seconds'
    denominator."""
    eng = _small_engine(tmp_path)
    try:
        _elect(eng)
        for g in (0, 2, 3):          # three of the four tenants have a store
            eng.store(g)
        a = _reg()
        for rounds in (1 << 30, 1, 1 << 30):    # one checkpoint, in between
            eng.cfg.checkpoint_rounds = rounds
            eng.run_round()
        b = _reg()
        assert _delta(a, b, "etcd_engine_checkpoint_seconds_count") == 1
        _, state = eng.wal.load_checkpoint()
        assert sorted(state["stores"]) == ["0", "2", "3"]
        assert _delta(a, b, "etcd_engine_checkpoint_stores_total") == 3
        eng.store(1)
        eng.cfg.checkpoint_rounds = 1
        eng.run_round()
        c = _reg()
        assert _delta(b, c, "etcd_engine_checkpoint_seconds_count") == 1
        assert _delta(b, c, "etcd_engine_checkpoint_stores_total") == 4
    finally:
        eng.stop()


def test_round_ms_ewma_is_seeded_after_the_elections(tmp_path):
    """/engine/status round_ms_ewma: 0 through the boot rounds (the
    first pays the step's compile and, staggered, elects every group in
    the same call), seeded by the first round that starts with a leader
    everywhere."""
    eng = _small_engine(tmp_path)
    try:
        eng.run_round()              # the compile round
        assert eng._all_led() and eng.round_ms_ewma == 0.0
        eng.run_round()
        seed = eng.round_ms_ewma
        assert 0.0 < seed < 5000.0
        eng.run_round()
        assert eng.round_ms_ewma != seed     # live: smoothed from here on
    finally:
        eng.stop()


def test_profiler_trace_carries_the_round_phases(tmp_path):
    """A few rounds under jax.profiler.trace: the host plane holds the
    program's own stages (read with the benchmark's reader), and no
    annotation encloses a whole round — the gap labeller would give
    every idle gap to it."""
    import jax
    spec = importlib.util.spec_from_file_location(
        "trace_reduce_for_obs_test",
        os.path.join(REPO, "benchmark", "lib", "trace_reduce.py"))
    trace_reduce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_reduce)
    from etcd_tpu.server.request import METHOD_PUT, Request

    eng = _small_engine(tmp_path / "data")
    try:
        _elect(eng)
        out = str(tmp_path / "trace")
        acks = []
        with jax.profiler.trace(out):
            for i in range(3):
                th = threading.Thread(target=lambda i=i: acks.append(
                    eng.do(i % 4, Request(method=METHOD_PUT,
                                          path=f"/tr/k{i}", val="v"))))
                th.start()
                deadline = time.time() + 60
                while th.is_alive() and time.time() < deadline:
                    eng.run_round()
                th.join(1)
                assert not th.is_alive()
        assert len(acks) == 3
    finally:
        eng.stop()
    _, host, _, _ = trace_reduce.read_xplane(trace_reduce.find_xplane(out))
    ours = [h for h in host if h[0].startswith("etcd.")]
    names = {h[0] for h in ours}
    assert {"etcd.round." + p for p in
            ("stage", "dispatch", "readback", "record", "wal_submit",
             "tail", "post")} <= names
    assert {"etcd.record.gather", "etcd.record.admit"} <= names
    assert "etcd.round.gap" not in names       # run_round driven by hand
    stages = sorted(h for h in ours if h[0] == "etcd.round.stage")
    posts = sorted(h for h in ours if h[0] == "etcd.round.post")
    assert len(stages) >= 3 and len(posts) >= 3
    for name, s, e in ours:
        for st in stages:
            nxt = next((p for p in posts if p[1] >= st[1]), None)
            if nxt is not None and name not in ("etcd.round.stage",
                                                "etcd.round.post"):
                assert not (s <= st[1] and e >= nxt[2]), (
                    f"{name} encloses a whole round")


_OBS_OFF_CHILD = r"""
import json, os, sys, threading, time, urllib.request
os.environ["ETCD_TPU_OBS"] = "off"
os.environ["ETCD_TPU_TRACE_EVERY"] = "1"    # the master switch wins
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from etcd_tpu.etcdhttp.tenants import EngineHttp
from etcd_tpu.server.engine import EngineConfig, MultiEngine

eng = MultiEngine(EngineConfig(
    groups=4, peers=3, data_dir=sys.argv[1], window=16, max_ents=4,
    heartbeat_tick=3, fsync=False, checkpoint_rounds=16,
    request_timeout=60.0))
eng.start()
assert eng.wait_leaders(180), eng.failed
front = EngineHttp(eng, port=0)
front.start()
base = front.url.rstrip("/")

def http(method, url, body=None):
    req = urllib.request.Request(url, method=method,
                                 data=body.encode() if body else None)
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.read().decode()

before = http("GET", base + "/metrics")
for i in range(8):
    http("PUT", f"{base}/tenants/{i % 4}/v2/keys/off/k{i}", f"value=v{i}")
    http("GET", f"{base}/tenants/{i % 4}/v2/keys/off/k{i}?quorum=true")
deadline = time.time() + 30     # a fast member is done in ~32 rounds: let
while eng.round_no <= 32 and time.time() < deadline:    # idle ones tick on
    time.sleep(0.05)
after = http("GET", base + "/metrics")
print(json.dumps({"before": before, "after": after,
                  "rounds": eng.round_no,
                  "traces": json.loads(http("GET", base + "/debug/traces"))}))
front.stop()
eng.stop()
"""

NEW_SERIES = (
    "etcd_engine_round_phase_cpu_seconds_total",
    "etcd_engine_record_part_seconds_sum",
    "etcd_engine_record_part_seconds_count",
    "etcd_engine_d2h_syncs_total",
    "etcd_engine_d2h_bytes_total",
    "etcd_engine_pending_wait_seconds_sum",
    "etcd_engine_pending_wait_seconds_count",
    "etcd_engine_checkpoint_seconds_sum",
    "etcd_engine_checkpoint_seconds_count",
    "etcd_jax_compiles_total",
    "etcd_jax_compile_seconds_total",
    "etcd_http_request_seconds_sum",
    "etcd_http_request_seconds_count",
    "etcd_http_front_self_seconds_sum",
    "etcd_http_front_self_seconds_count",
    # PR 25: the event-loop front's own counters
    "etcd_http_front_served_total",
    "etcd_http_front_wakes_total",
    "etcd_http_front_completions_total",
    # PR 41: the sampled request trace's folds
    "etcd_request_segment_seconds_sum",
    "etcd_request_segment_seconds_count",
    "etcd_request_rounds_sum",
    "etcd_request_rounds_count",
    "etcd_request_spans_dropped_total",
    # PR 43: what a round uploads, and the need-host surgery's parts
    "etcd_engine_h2d_syncs_total",
    "etcd_engine_h2d_bytes_total",
    "etcd_engine_need_host_part_seconds_sum",
    "etcd_engine_need_host_part_seconds_count",
    # PR 44: the dispatch lap's three hand-overs
    "etcd_engine_dispatch_part_seconds_sum",
    "etcd_engine_dispatch_part_seconds_count",
    # PR 46: the TTL scan's time and the checkpoint's denominator (the
    # child scans twice a second and checkpoints every 16 rounds)
    "etcd_engine_sync_scan_seconds_sum",
    "etcd_engine_sync_scan_seconds_count",
    "etcd_engine_checkpoint_stores_total",
)


def test_new_series_stay_flat_with_obs_off(tmp_path):
    """ETCD_TPU_OBS=off: writes, quorum reads and checkpoints go by, and
    every series this plane added is still there and has not moved; the
    scrape-time CPU series are left out."""
    r = subprocess.run(
        [sys.executable, "-c", _OBS_OFF_CHILD, str(tmp_path / "off")],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["rounds"] > 32          # checkpoints at 16 rounds happened
    assert doc["traces"] == {"every": 0, "spans": []}
    parse = _load_script("etcd_top").parse_metrics
    before, after = parse(doc["before"]), parse(doc["after"])
    for series in NEW_SERIES:
        assert _val(after, series) is not None, series
        assert _delta(before, after, series) == 0, series
    for phase in ("post", "gap"):
        assert _delta(before, after, "etcd_engine_round_phase_seconds_count",
                      phase=phase) == 0
    names = {k[0] for k in after}
    assert "process_cpu_seconds_total" not in names
    assert "etcd_thread_cpu_seconds_total" not in names
    assert "process_open_fds" in names


# -- engine-level: /metrics over HTTP under concurrent load ------------------


@pytest.fixture(scope="module")
def eng_http():
    prev = os.environ.get("ETCD_TPU_TRACE_EVERY")
    os.environ["ETCD_TPU_TRACE_EVERY"] = "2"
    from etcd_tpu.etcdhttp.tenants import EngineHttp
    from etcd_tpu.server.engine import EngineConfig, MultiEngine
    tmp = tempfile.mkdtemp(prefix="obs-test-")
    eng = MultiEngine(EngineConfig(
        groups=G, peers=P, data_dir=tmp, window=16, max_ents=4,
        heartbeat_tick=3, fsync=False, checkpoint_rounds=1 << 30,
        applier_shards=2, wal_shards=2, request_timeout=60.0))
    eng.start()
    assert eng.wait_leaders(180), f"no leaders: {eng.failed}"
    front = EngineHttp(eng, port=0)
    front.start()
    try:
        yield eng, front.url.rstrip("/")
    finally:
        front.stop()
        eng.stop()
        if prev is None:
            os.environ.pop("ETCD_TPU_TRACE_EVERY", None)
        else:
            os.environ["ETCD_TPU_TRACE_EVERY"] = prev


def _http(method, url, body=None):
    req = urllib.request.Request(
        url, method=method, data=body.encode() if body else None)
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.read().decode()


def test_metrics_http_all_compartments_under_load(eng_http):
    """The acceptance surface: all four compartments' series on
    /metrics, scraped CONCURRENTLY with deep-queue writes — every
    scrape parses, histograms are internally consistent (+Inf bucket
    == _count), and counters never move backwards between scrapes."""
    eng, base = eng_http
    top = _load_script("etcd_top")
    stop = threading.Event()
    errs = []

    def writer(tid):
        i = 0
        try:
            while not stop.is_set():
                _http("PUT",
                      f"{base}/tenants/{(tid + i) % G}/v2/keys/"
                      f"obs/w{tid}-{i}", f"value=v{i}")
                i += 1
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    writers = [threading.Thread(target=writer, args=(t,))
               for t in range(4)]
    for t in writers:
        t.start()
    try:
        scrapes = []
        deadline = time.time() + 12
        while time.time() < deadline and len(scrapes) < 6:
            scrapes.append(top.parse_metrics(_http("GET",
                                                   base + "/metrics")))
            time.sleep(0.4)
    finally:
        stop.set()
        for t in writers:
            t.join()
    assert not errs, errs
    assert len(scrapes) >= 3

    last = scrapes[-1]
    names = {k[0] for k in last}
    # Round-loop compartment.
    assert "etcd_engine_round_phase_seconds_bucket" in names
    assert "etcd_engine_kernel_step_seconds_bucket" in names
    assert "etcd_engine_round_batch_requests_bucket" in names
    phases = {dict(k[1]).get("phase") for k in last
              if k[0] == "etcd_engine_round_phase_seconds_bucket"}
    assert {"stage", "dispatch", "readback", "record", "wal_submit",
            "tail"} <= phases
    # WAL-writer compartment: per-shard fsync + queue depth + lag.
    shards = {dict(k[1]).get("shard") for k in last
              if k[0] == "etcd_wal_writer_fsync_seconds_bucket"}
    # Superset, not equality: labeled children live in the process-global
    # registry, so earlier test modules' engines (other shard counts) may
    # have left extra labels behind.
    assert {"0", "1"} <= shards
    assert "etcd_wal_writer_queue_depth" in names
    assert "etcd_wal_writer_watermark_lag_tickets" in names
    assert "etcd_wal_writer_group_commit_rounds_bucket" in names
    # Applier compartment + ack gate.
    assert {"0", "1"} <= {dict(k[1]).get("shard") for k in last
                          if k[0] == "etcd_applier_queue_depth"}
    assert "etcd_applier_apply_batch_requests_bucket" in names
    assert "etcd_ack_gate_wait_seconds_bucket" in names
    # Reference proposal metrics (satellite wiring).
    assert "etcd_server_proposal_durations_milliseconds_count" in names
    assert "etcd_server_pending_proposal_total" in names
    assert last[("etcd_server_proposal_durations_milliseconds_count",
                 ())] > 0

    # No torn exposition: within one scrape, +Inf == _count per family.
    for fam in ("etcd_engine_kernel_step_seconds",
                "etcd_ack_gate_wait_seconds"):
        inf = sum(v for k, v in last.items()
                  if k[0] == fam + "_bucket"
                  and dict(k[1]).get("le") == "+Inf")
        assert inf == last[(fam + "_count", ())]
    # Monotone counters across consecutive scrapes.
    for a, b in zip(scrapes, scrapes[1:]):
        for key in ("etcd_engine_rounds_total",
                    "etcd_engine_acked_requests_total",
                    "etcd_server_proposal_durations_milliseconds_count"):
            assert b[(key, ())] >= a[(key, ())]


def test_acked_counter_differential(eng_http):
    """Registry movement == engine-reported acks: what a scrape-to-scrape
    delta of /metrics says is what the engine acknowledged."""
    eng, base = eng_http
    snap0 = _reg()
    a0 = eng.acked_requests
    N = 12
    for i in range(N):
        _http("PUT", f"{base}/tenants/{i % G}/v2/keys/diff/k{i}",
              f"value=v{i}")
    moved = _delta(snap0, _reg(), "etcd_engine_acked_requests_total")
    assert moved == N == eng.acked_requests - a0


def test_flight_and_traces_http(eng_http):
    eng, base = eng_http
    for i in range(2 * G):
        _http("PUT", f"{base}/tenants/{i % G}/v2/keys/fl/k{i}",
              f"value=v{i}")
    doc = json.loads(_http("GET", base + "/debug/flight"))
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "i"}
    assert names == set(obs_mod.STAGE_NAMES)
    tr = json.loads(_http("GET", base + "/debug/traces"))
    assert tr["every"] == 2 and tr["spans"]
    stages = set()
    for s in tr["spans"]:
        stages |= set(s["stages"])
    assert {"submit", "admitted", "wal_submit", "durable", "applied",
            "acked"} <= stages


def test_sigusr2_dumps_flight_ring(eng_http):
    eng, base = eng_http
    diag = os.path.join(eng.cfg.data_dir, "diagnostics")
    before = set(os.listdir(diag)) if os.path.isdir(diag) else set()
    os.kill(os.getpid(), signal.SIGUSR2)
    deadline = time.time() + 15
    new = set()
    while time.time() < deadline and not new:
        now = set(os.listdir(diag)) if os.path.isdir(diag) else set()
        new = {f for f in now - before if "sigusr2" in f}
        time.sleep(0.1)
    assert new, "SIGUSR2 produced no flight dump"
    with open(os.path.join(diag, sorted(new)[-1])) as f:
        doc = json.load(f)
    assert {e["name"] for e in doc["traceEvents"]
            if e["ph"] == "i"} == set(obs_mod.STAGE_NAMES)



# -- PR 24 on the live engine: a whole round, a whole request, the CPU -------


def _load(base, n=24, reads=True):
    """n writes (and as many quorum reads) over HTTP; returns when the
    handler threads have observed all of them (the front observes after
    the response is written, so the client can be ahead of it)."""
    a = _reg()
    for i in range(n):
        _http("PUT", f"{base}/tenants/{i % G}/v2/keys/acct/k{i}",
              f"value=v{i}")
        if reads:
            _http("GET", f"{base}/tenants/{i % G}/v2/keys/acct/k{i}"
                         "?quorum=true")
    deadline = time.time() + 20
    while time.time() < deadline:
        b = _reg()
        if (_delta(a, b, "etcd_http_request_seconds_count",
                   kind="write") >= n
                and (not reads or _delta(
                    a, b, "etcd_http_request_seconds_count",
                    kind="qread") >= n)):
            return a, b
        time.sleep(0.02)
    raise AssertionError("the front did not observe every request")


@pytest.mark.parametrize("series,labels", [
    ("etcd_engine_round_phase_seconds_count", {"phase": "post"}),
    ("etcd_engine_round_phase_seconds_count", {"phase": "gap"}),
    *[("etcd_engine_round_phase_cpu_seconds_total", {"phase": p})
      for p in obs_mod.ROUND_PHASES],
    *[("etcd_engine_record_part_seconds_count", {"part": p})
      for p in obs_mod.RECORD_PARTS],
    *[("etcd_engine_dispatch_part_seconds_count", {"part": p})
      for p in obs_mod.DISPATCH_PARTS],
    ("etcd_engine_d2h_syncs_total", {}),
    ("etcd_engine_d2h_bytes_total", {}),
    ("etcd_engine_h2d_syncs_total", {}),
    ("etcd_engine_h2d_bytes_total", {}),
    ("etcd_engine_pending_wait_seconds_count", {}),
    *[("etcd_http_request_seconds_count", {"kind": k})
      for k in obs_mod.FRONT_KINDS],
    *[("etcd_http_front_self_seconds_sum", {"kind": k})
      for k in obs_mod.FRONT_KINDS],
    ("process_cpu_seconds_total", {}),
])
def test_new_series_move_under_load(eng_http, series, labels):
    """Every series this plane added is on /metrics (the HTTP
    exposition, not only the registry) and moves under load."""
    eng, base = eng_http
    parse = _load_script("etcd_top").parse_metrics
    a = parse(_http("GET", base + "/metrics"))
    _load(base, n=6)
    b = parse(_http("GET", base + "/metrics"))
    assert _val(a, series, **labels) is not None, (series, labels)
    assert _delta(a, b, series, **labels) > 0, (series, labels)


@pytest.mark.parametrize("series,labels", [
    ("etcd_engine_checkpoint_seconds_count", {}),
    ("etcd_engine_checkpoint_stores_total", {}),
    ("etcd_engine_sync_scan_seconds_count", {}),
    ("etcd_engine_gather_rebuckets_total", {}),
    *[("etcd_engine_need_host_part_seconds_count", {"part": p})
      for p in obs_mod.NEED_HOST_PARTS],
    ("etcd_jax_compiles_total", {}), ("etcd_jax_compile_seconds_total", {})])
def test_rare_event_series_are_exposed(eng_http, series, labels):
    """Checkpoints, bucket misses, need-host surgeries and compiles need
    not happen in a window (a member without fault injection may never
    see a surgery); their series are there all the same, at 0 from the
    start (the compile counters have counted this process's step
    variants)."""
    eng, base = eng_http
    scrape = _load_script("etcd_top").parse_metrics(
        _http("GET", base + "/metrics"))
    assert _val(scrape, series, **labels) is not None
    if "jax" in series:
        assert _val(scrape, series) > 0


def test_round_phases_tile_the_loop(eng_http):
    """With the engine thread running, the seven disjoint phases' sums
    add up to the wall window (2 % and an idle wait), under load and
    idle alike; CPU per phase never exceeds its wall."""
    eng, base = eng_http
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            _http("PUT", f"{base}/tenants/{i % G}/v2/keys/tile/k{i % 7}",
                  f"value=v{i}")
            i += 1

    th = threading.Thread(target=writer)
    th.start()
    try:
        for _ in range(2):
            t0 = time.perf_counter()
            a = _reg()
            ta = time.perf_counter()
            time.sleep(1.5)
            t1 = time.perf_counter()
            b = _reg()
            tb = time.perf_counter()
            # each registry walk takes a moment of its own: the window
            # the deltas cover lies between these two; a lap is counted
            # whole when it ends, so a scrape can cut up to one idle
            # wait of the engine thread off either end
            tick = engine_mod.IDLE_TICK_S
            lo, hi = (t1 - ta) * 0.98 - tick, (tb - t0) * 1.02 + tick
            wall = {p: _delta(a, b, "etcd_engine_round_phase_seconds_sum",
                              phase=p) for p in obs_mod.ROUND_PHASES}
            cpu = {p: _delta(a, b,
                             "etcd_engine_round_phase_cpu_seconds_total",
                             phase=p) for p in obs_mod.ROUND_PHASES}
            assert lo <= sum(wall.values()) <= hi, (wall, lo, hi)
            assert all(v > 0 for v in wall.values()), wall
            for p in obs_mod.ROUND_PHASES:
                assert cpu[p] <= wall[p] * 1.02 + 1e-3, (p, cpu, wall)
            # wal_submit lies inside tail
            assert _delta(a, b, "etcd_engine_round_phase_seconds_sum",
                          phase="wal_submit") <= wall["tail"]
            stop.set()               # second window: idle
            th.join(30)
    finally:
        stop.set()
        th.join(30)
    assert not th.is_alive()


def test_record_parts_add_up_to_record(eng_http):
    eng, base = eng_http
    a, b = _load(base, n=24, reads=False)
    parts = {p: _delta(a, b, "etcd_engine_record_part_seconds_sum", part=p)
             for p in obs_mod.RECORD_PARTS}
    record = _delta(a, b, "etcd_engine_round_phase_seconds_sum",
                    phase="record")
    # the two scrapes walk the registry while rounds run: one round's
    # record of slack on top of the 2 %
    rounds = _delta(a, b, "etcd_engine_rounds_total")
    assert abs(sum(parts.values()) - record) <= (0.02 * record
                                                 + 2 * record / rounds)
    assert parts["gather"] > 0 and parts["admit"] > 0
    assert parts["build"] > 0


def _dispatch_snapshot():
    """(the three parts' sums, dispatch's sum, the parts' counts, rounds,
    uploads, bytes) read from the registry between two rounds: read until
    two readings 5 ms apart agree (an idle member runs a round of a
    millisecond or two every 50 ms)."""
    def read():
        parts = [obs_mod.dispatch_part.labels(p)
                 for p in obs_mod.DISPATCH_PARTS]
        return (tuple(h.sum for h in parts),
                obs_mod.round_phase.labels("dispatch").sum,
                tuple(h.count for h in parts), obs_mod.rounds_total.value,
                obs_mod.h2d_syncs.value, obs_mod.h2d_bytes.value)
    deadline = time.time() + 20
    while time.time() < deadline:
        one = read()
        time.sleep(0.005)
        if read() == one:
            return one
    raise AssertionError("the member never stood between two rounds")


def test_dispatch_parts_add_up_to_dispatch(eng_http):
    """upload + step + gather tile the dispatch phase round by round (two
    more clock readings a round), and a round uploads at most its one
    staged array: rounds that staged nothing upload nothing."""
    eng, base = eng_http
    a = _dispatch_snapshot()
    _load(base, n=24, reads=False)
    b = _dispatch_snapshot()
    parts = [y - x for x, y in zip(a[0], b[0])]
    dispatch, rounds = b[1] - a[1], b[3] - a[3]
    assert all(v > 0 for v in parts), parts
    assert abs(sum(parts) - dispatch) <= 1e-6 * rounds
    assert [y - x for x, y in zip(a[2], b[2])] == [rounds] * 3
    uploads = b[4] - a[4]
    assert 0 < uploads <= rounds
    assert b[5] - a[5] == uploads * 2 * 4 * eng.cfg.groups


def test_front_self_time_is_the_span_minus_the_engine_wait(eng_http):
    eng, base = eng_http
    a, b = _load(base, n=24)
    for kind in obs_mod.FRONT_KINDS:
        span = _delta(a, b, "etcd_http_request_seconds_sum", kind=kind)
        self_s = _delta(a, b, "etcd_http_front_self_seconds_sum", kind=kind)
        assert 0 <= self_s <= span, kind
        assert (_delta(a, b, "etcd_http_request_seconds_count", kind=kind)
                == _delta(a, b, "etcd_http_front_self_seconds_count",
                          kind=kind))
    blocked = (_delta(a, b, "etcd_http_request_seconds_sum", kind="write")
               - _delta(a, b, "etcd_http_front_self_seconds_sum",
                        kind="write"))
    proposed = _delta(
        a, b, "etcd_server_proposal_durations_milliseconds_sum") / 1e3
    assert blocked == pytest.approx(proposed, rel=0.05)
    waited = (_delta(a, b, "etcd_http_request_seconds_sum", kind="qread")
              - _delta(a, b, "etcd_http_front_self_seconds_sum",
                       kind="qread"))
    read = _delta(a, b, "etcd_read_index_durations_milliseconds_sum") / 1e3
    assert waited == pytest.approx(read, rel=0.05)
    # A coalesced batch is one request whose thread waits in
    # collect_many: a write, its wait handed over the same way.
    a = _reg()
    body = json.dumps({"reqs": [{"method": "PUT", "path": f"/b/k{i}",
                                 "value": "v"} for i in range(3)]})
    assert len(json.loads(_http("POST", base + "/tenants/0/batch",
                                body))["results"]) == 3
    deadline = time.time() + 20
    while time.time() < deadline:
        b = _reg()
        if _delta(a, b, "etcd_http_request_seconds_count", kind="write"):
            break
        time.sleep(0.02)
    assert _delta(a, b, "etcd_http_request_seconds_count", kind="write") == 1
    blocked = (_delta(a, b, "etcd_http_request_seconds_sum", kind="write")
               - _delta(a, b, "etcd_http_front_self_seconds_sum",
                        kind="write"))
    # (the batch's window is observed as three proposals of a third each)
    assert blocked == pytest.approx(_delta(
        a, b, "etcd_server_proposal_durations_milliseconds_sum") / 1e3,
        rel=0.05)
    # /metrics, /engine/status, /debug/* never reach the engine: other,
    # all of it self time.
    a = _reg()
    for path in ("/metrics", "/engine/status", "/debug/traces"):
        _http("GET", base + path)
    deadline = time.time() + 20
    while time.time() < deadline:
        b = _reg()
        if _delta(a, b, "etcd_http_request_seconds_count",
                  kind="other") >= 3:
            break
        time.sleep(0.02)
    assert _delta(a, b, "etcd_http_request_seconds_count", kind="other") == 3
    assert (_delta(a, b, "etcd_http_request_seconds_sum", kind="other")
            == pytest.approx(_delta(a, b, "etcd_http_front_self_seconds_sum",
                                    kind="other")))
    assert _delta(a, b, "etcd_http_request_seconds_count", kind="write") == 0


def test_loop_served_requests_count_once_and_the_layer_metrics_read_them(
        eng_http):
    """PR 25: keys writes and quorum reads are served by the front's event
    loop. Each is one observation of the span and of the self time (self
    <= span), one `served{path=loop}`, one completion; a batch of acks is
    at most one wake per completion; and the benchmark's two layer-metric
    files read exactly these series off two /metrics scrapes."""
    import importlib.util
    eng, base = eng_http
    spec = importlib.util.spec_from_file_location(
        "bench_prom", os.path.join(REPO, "benchmark", "lib", "prom.py"))
    prom = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(prom)
    text0 = _http("GET", base + "/metrics")        # itself on a thread
    for series in ("etcd_http_front_served_total{path=\"loop\"}",
                   "etcd_http_front_served_total{path=\"thread\"}",
                   "etcd_http_front_wakes_total",
                   "etcd_http_front_completions_total"):
        assert series in text0, series
    deadline = time.time() + 20        # until that scrape is counted
    while (_val(_reg(), "etcd_http_front_served_total", path="thread")
           <= _val(prom.parse(text0), "etcd_http_front_served_total",
                   path="thread") and time.time() < deadline):
        time.sleep(0.02)
    a, b = _load(base, n=24)
    assert _delta(a, b, "etcd_http_front_served_total", path="loop") == 48
    assert _delta(a, b, "etcd_http_front_served_total", path="thread") == 0
    assert _delta(a, b, "etcd_http_front_completions_total") == 48
    assert 1 <= _delta(a, b, "etcd_http_front_wakes_total") <= 48
    for kind in ("write", "qread"):
        assert _delta(a, b, "etcd_http_request_seconds_count",
                      kind=kind) == 24
        assert _delta(a, b, "etcd_http_front_self_seconds_count",
                      kind=kind) == 24
        assert (0 < _delta(a, b, "etcd_http_front_self_seconds_sum",
                           kind=kind)
                <= _delta(a, b, "etcd_http_request_seconds_sum", kind=kind))
    before, after = prom.parse(text0), prom.parse(
        _http("GET", base + "/metrics"))
    got = {}
    for name in ("front_loop_share", "front_acks_per_wake"):
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            got[name] = prom.prom_delta(before, after,
                                        json.load(f)["source"], 1.0)
    # 48 on the loop and the first scrape on a thread
    assert got["front_loop_share"] == pytest.approx(48 / 49)
    assert 1 <= got["front_acks_per_wake"] <= 48


def test_pending_wait_counts_every_acked_write(eng_http):
    eng, base = eng_http
    a, b = _load(base, n=24, reads=False)
    acked = _delta(a, b, "etcd_engine_acked_requests_total")
    assert acked == 24
    assert _delta(a, b, "etcd_engine_pending_wait_seconds_count") >= acked
    waited = _delta(a, b, "etcd_engine_pending_wait_seconds_sum")
    # a write waits for the round that stages it, inside its whole ack
    assert 0 < waited < _delta(
        a, b, "etcd_server_proposal_durations_milliseconds_sum") / 1e3


def test_thread_cpu_series_name_the_classes_and_sum_to_the_process(eng_http):
    eng, base = eng_http
    parse = _load_script("etcd_top").parse_metrics
    _load(base, n=12)
    scrape = parse(_http("GET", base + "/metrics"))
    threads = {dict(k[1])["thread"]: v for k, v in scrape.items()
               if k[0] == "etcd_thread_cpu_seconds_total"}
    if not threads:
        pytest.skip("no per-thread CPU clock on this platform")
    assert set(threads) == {"round", "wal", "applier", "front", "loop"}
    # `loop` (the front's event loop) is a part of `front`, not a class
    # beside it: the other four still sum to the process
    loop = threads.pop("loop")
    assert 0 < loop <= threads["front"]
    proc = scrape[("process_cpu_seconds_total", ())]
    assert sum(threads.values()) == pytest.approx(proc, rel=0.05)
    assert threads["round"] > 0 and threads["applier"] > 0
    assert threads["round"] < proc


def test_sampled_rid_carries_the_front_marks_in_order(eng_http):
    """/debug/traces: front_in (the handler's start) ... acked -> woke
    (the handler thread runs again) -> replied (response written)."""
    eng, base = eng_http
    _load(base, n=2 * G)
    deadline = time.time() + 20
    full = []
    while time.time() < deadline and not full:
        tr = json.loads(_http("GET", base + "/debug/traces"))
        full = [s["stages"] for s in tr["spans"]
                if {"front_in", "admitted", "woke", "replied"}
                <= set(s["stages"])]
        time.sleep(0.05)
    assert full, "no sampled write carries the front's marks"
    for stages in full:
        order = list(stages)         # sorted by time in the dump
        assert order[0] == "front_in" and order[-1] == "replied"
        for x, y in (("front_in", "submit"), ("submit", "admitted"),
                     ("admitted", "acked"), ("acked", "woke"),
                     ("woke", "replied")):
            assert order.index(x) < order.index(y), order
    # a sampled quorum read has them too (no admission: nothing proposed)
    reads = [s["stages"] for s in tr["spans"]
             if "replied" in s["stages"] and "admitted" not in s["stages"]]
    assert reads and all(list(s)[0] == "front_in" for s in reads)


# -- PR 41: a finished span folds into the segment histograms ----------------

SEG = "etcd_request_segment_seconds"
ROUNDS = "etcd_request_rounds"
DROPPED = "etcd_request_spans_dropped_total"


@pytest.fixture(scope="module")
def eng_all():
    """A member that samples every request id, behind the event-loop
    front (the tracer reads ETCD_TPU_TRACE_EVERY when it is built)."""
    prev = os.environ.get("ETCD_TPU_TRACE_EVERY")
    os.environ["ETCD_TPU_TRACE_EVERY"] = "1"
    from etcd_tpu.etcdhttp.tenants import EngineHttp
    from etcd_tpu.server.engine import EngineConfig, MultiEngine
    try:
        eng = MultiEngine(EngineConfig(
            groups=G, peers=P, data_dir=tempfile.mkdtemp(prefix="seg-test-"),
            window=16, max_ents=4, heartbeat_tick=3, fsync=False,
            checkpoint_rounds=1 << 30, applier_shards=2, wal_shards=2,
            request_timeout=60.0))
    finally:
        if prev is None:
            os.environ.pop("ETCD_TPU_TRACE_EVERY", None)
        else:
            os.environ["ETCD_TPU_TRACE_EVERY"] = prev
    eng.start()
    assert eng.wait_leaders(180), f"no leaders: {eng.failed}"
    front = EngineHttp(eng, port=0)
    front.start()
    try:
        yield eng, front.url.rstrip("/")
    finally:
        front.stop()
        eng.stop()


def _folded(a, b, kind):
    return _delta(a, b, ROUNDS + "_count", kind=kind)


def test_segments_tile_the_request_and_leave_nothing_in_flight(eng_all):
    """Every request sampled: per kind, each segment is observed once a
    request, the segments' sums add up to the front's span of the same
    requests (etcd_http_request_seconds), a round count is folded for
    each, nothing is dropped, and the in-flight table is empty once every
    request has its reply."""
    eng, base = eng_all
    assert eng.obs.tracer.every == 1
    n = 24
    a, b = _load(base, n=n)
    for kind in ("write", "qread"):
        names = obs_mod.SEGMENT_NAMES[kind]
        assert {_delta(a, b, SEG + "_count", kind=kind, segment=seg)
                for seg in names} == {n}, kind
        tiled = sum(_delta(a, b, SEG + "_sum", kind=kind, segment=seg)
                    for seg in names)
        assert tiled == pytest.approx(
            _delta(a, b, "etcd_http_request_seconds_sum", kind=kind),
            rel=0.01), kind
        assert _folded(a, b, kind) == n
        assert _delta(a, b, ROUNDS + "_sum", kind=kind) >= n
    # a read has no gate: nothing is fsynced for it
    assert _val(b, SEG + "_count", kind="qread", segment="gate") is None
    assert _delta(a, b, DROPPED) == 0
    assert eng.obs.tracer.live() == 0
    # the queue segment is the staging wait, here of every write
    assert _delta(a, b, SEG + "_sum", kind="write", segment="queue") == \
        pytest.approx(_delta(a, b, "etcd_engine_pending_wait_seconds_sum"))


def _finished(base, kind, seen, want, timeout=30.0):
    """Spans of `kind` that /debug/traces shows finished and `seen` (rids)
    does not hold, once there are `want` of them."""
    deadline = time.time() + timeout
    while True:
        spans = [s for s in json.loads(_http(
            "GET", base + "/debug/traces"))["spans"]
            if s.get("kind") == kind and s["rid"] not in seen]
        if len(spans) >= want or time.time() > deadline:
            assert len(spans) >= want, (kind, len(spans), want)
            return spans
        time.sleep(0.05)


def _cut_leader_off(eng, g):
    """Nothing reaches tenant g's leader (it still sends: its followers
    keep hearing it, so nobody stands for election, and nothing it admits
    commits). The mask is (G, to, from, 1)."""
    import jax.numpy as jnp
    import numpy as np
    cut = np.ones((eng.cfg.groups, eng.cfg.peers, eng.cfg.peers, 1),
                  np.int32)
    cut[g, eng.leader_slot(g)] = 0
    eng.drop_mask = jnp.asarray(cut)


def _puts(base, g, n, tag):
    out = []

    def put(i):
        out.append(_http("PUT", f"{base}/tenants/{g}/v2/keys/{tag}/k{i}",
                         f"value=v{i}"))
    ths = [threading.Thread(target=put, args=(i,)) for i in range(n)]
    for t in ths:
        t.start()
    return ths, out


def _traffic_write(eng, base):
    for i in range(6):
        _http("PUT", f"{base}/tenants/{i % G}/v2/keys/seg/w{i}", "value=v")
    return "write", 6, lambda spans: None


def _traffic_qread(eng, base):
    _http("PUT", f"{base}/tenants/2/v2/keys/seg/r", "value=v")
    for _ in range(6):
        _http("GET", f"{base}/tenants/2/v2/keys/seg/r?quorum=true")
    return "qread", 6, lambda spans: None


def _traffic_requeued(eng, base):
    """One entry a write (batch_max 1), four entries a round (max_ents):
    with tenant 0's leader cut off its uncommitted tail reaches half the
    window (8 of 16), admission stops, and what the next rounds stage
    goes back to the queue until the cut heals."""
    a = _reg()
    eng.cfg.batch_max = 1
    _cut_leader_off(eng, 0)
    try:
        ths, out = _puts(base, 0, 12, "seg-rq")
        deadline = time.time() + 30
        while (_delta(a, _reg(), "etcd_engine_pending_wait_seconds_count")
               < 12 and time.time() < deadline):
            time.sleep(0.02)
        r0 = eng.round_no
        while eng.round_no < r0 + 4 and time.time() < deadline:
            time.sleep(0.02)            # a few rounds that requeue
    finally:
        eng.drop_mask = None
        eng.cfg.batch_max = 4096
    for t in ths:
        t.join(60)
    assert len(out) == 12

    def check(spans):
        late = [s for s in spans if s["round"] > s["staged_round"]]
        assert late, "no write was staged in one round and admitted later"
        # staged once: the first time, where its queue wait was observed
        assert all(s["rounds"] >= s["round"] - s["staged_round"] + 1
                   for s in late)
    return "write", 12, check


def _traffic_sync_round(eng, base):
    """A conf change of tenant 1 that cannot commit (its leader hears
    nobody) stays outstanding: every round takes the synchronous path, so
    tenant 0's writes are applied and acked on the round thread."""
    follower = (eng.leader_slot(1) + 1) % P
    _cut_leader_off(eng, 1)
    conf = threading.Thread(target=_http, args=(
        "POST", f"{base}/tenants/1/conf",
        json.dumps({"op": "remove", "slot": follower})))
    try:
        conf.start()
        deadline = time.time() + 30
        while not eng._confs_outstanding and time.time() < deadline:
            time.sleep(0.01)
        assert eng._confs_outstanding == 1
        for i in range(6):
            _http("PUT", f"{base}/tenants/0/v2/keys/seg/s{i}", "value=v")
        assert eng._confs_outstanding == 1
    finally:
        eng.drop_mask = None
    conf.join(60)
    assert not conf.is_alive()
    _http("POST", f"{base}/tenants/1/conf",
          json.dumps({"op": "add", "slot": follower}))
    return "write", 6, lambda spans: None


@pytest.mark.parametrize("traffic", [
    _traffic_write, _traffic_qread, _traffic_requeued, _traffic_sync_round],
    ids=lambda f: f.__name__[len("_traffic_"):])
def test_every_folded_segment_is_positive_and_takes_a_round(eng_all, traffic):
    """The stamps that bound a kind's segments are in order in every
    finished span, whichever path acked it, and it took at least a round."""
    eng, base = eng_all
    seen = {s["rid"] for s in eng.obs.tracer.spans()}
    a = _reg()
    kind, n, check = traffic(eng, base)
    spans = _finished(base, kind, seen, n)
    stamps = obs_mod.SEGMENT_STAMPS[kind]
    for s in spans:
        at = [s["stages"][k] for k in stamps]
        assert at == sorted(at) and at[0] == 0.0, s
        assert s["rounds"] >= 1, s
    check(spans)
    b = _reg()
    assert _delta(a, b, DROPPED) == 0
    for seg in obs_mod.SEGMENT_NAMES[kind]:
        assert _delta(a, b, SEG + "_sum", kind=kind, segment=seg) >= 0
    assert eng.obs.tracer.live() == 0


def test_durable_is_the_wal_writers_stamp_inside_the_gate(eng_all,
                                                          monkeypatch):
    """Every stream's fsync takes 30 ms more: a write is applied ahead of
    it and waits at the gate, `applied -> acked` spans the fsync, and
    `durable`, the writer's own clock at the fsync's end, lies inside."""
    eng, base = eng_all
    for sh in eng.wal.shards:
        def slow(sync=sh.wal.sync):
            time.sleep(0.03)
            return sync()
        monkeypatch.setattr(sh.wal, "sync", slow)
    seen = {s["rid"] for s in eng.obs.tracer.spans()}
    a = _reg()
    for i in range(4):
        _http("PUT", f"{base}/tenants/{i}/v2/keys/seg/d{i}", "value=v")
    for s in _finished(base, "write", seen, 4):
        st = s["stages"]
        assert st["applied"] <= st["durable"] <= st["acked"], s
        assert st["acked"] - st["applied"] >= 0.02, s
        assert st["wal_submit"] <= st["durable"], s
    b = _reg()
    assert _delta(a, b, SEG + "_sum", kind="write", segment="gate") >= 4 * 0.02


def _served(tmp_path, **kw):
    from etcd_tpu.etcdhttp.tenants import EngineHttp
    eng = _small_engine(tmp_path, **kw)
    eng.start()
    assert eng.wait_leaders(180), f"no leaders: {eng.failed}"
    front = EngineHttp(eng, port=0)
    front.start()
    return eng, front


@pytest.mark.parametrize("env,every", [(None, 16), ("0", 0), ("4", 4)])
def test_one_request_id_in_16_is_sampled_unless_the_variable_says_otherwise(
        env, every, tmp_path, monkeypatch):
    if env is None:
        monkeypatch.delenv("ETCD_TPU_TRACE_EVERY", raising=False)
    else:
        monkeypatch.setenv("ETCD_TPU_TRACE_EVERY", env)
    assert obs_mod.TRACE_EVERY_DEFAULT == 16
    eng, front = _served(tmp_path)
    try:
        assert eng.obs.tracer.every == every
        base = front.url.rstrip("/")
        n = 40
        id0 = eng.reqid.next()
        a = _reg()
        for i in range(n):
            _http("PUT", f"{base}/tenants/{i % 4}/v2/keys/dflt/k{i}",
                  "value=v")
            _http("GET", f"{base}/tenants/{i % 4}/v2/keys/dflt/k{i}"
                         "?quorum=true")
        deadline = time.time() + 20
        while time.time() < deadline:
            b = _reg()
            if _delta(a, b, "etcd_http_request_seconds_count",
                      kind="qread") >= n:
                break
            time.sleep(0.02)
        id1 = eng.reqid.next()
        assert id1 - id0 == 2 * n + 1       # the requests took every id
        want = [rid for rid in range(id0 + 1, id1)
                if every and rid % every == 0]
        assert len(want) == (2 * n // every if every else 0)
        assert _folded(a, b, "write") + _folded(a, b, "qread") == len(want)
        assert _delta(a, b, DROPPED) == 0
        doc = json.loads(_http("GET", base + "/debug/traces"))
        assert doc["every"] == every
        assert sorted(s["rid"] for s in doc["spans"]) == want
        assert eng.obs.tracer.live() == 0
    finally:
        front.stop()
        eng.stop()


def test_a_timed_out_and_a_refused_request_fold_nothing(tmp_path,
                                                        monkeypatch):
    """A span that lacks a stamp is counted, not folded: a write whose
    entry cannot commit is answered by the front's sweep, and a request
    the engine will not register is refused at once. What the round and
    the applier mark for the timed-out write afterwards opens no span."""
    import urllib.error
    monkeypatch.setenv("ETCD_TPU_TRACE_EVERY", "1")
    eng, front = _served(tmp_path, request_timeout=0.5)
    try:
        base = front.url.rstrip("/")
        _http("PUT", f"{base}/tenants/0/v2/keys/to/warm", "value=v")
        a = _reg()
        _cut_leader_off(eng, 0)
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                _http("PUT", f"{base}/tenants/0/v2/keys/to/late", "value=v")
            assert "timed out" in err.value.read().decode()
        finally:
            eng.drop_mask = None
        with monkeypatch.context() as m:
            def refuse(wid, sink=None):
                raise ValueError("refused for the test")
            m.setattr(eng.wait, "register", refuse)
            with pytest.raises(urllib.error.HTTPError) as err:
                _http("PUT", f"{base}/tenants/1/v2/keys/to/no", "value=v")
            assert "refused for the test" in err.value.read().decode()
        # healed, the late write commits and is applied: marks of a rid
        # whose span has ended
        deadline = time.time() + 30
        while not eng._idle() and time.time() < deadline:
            time.sleep(0.02)
        assert eng._idle()
        deadline = time.time() + 20
        while time.time() < deadline:
            b = _reg()
            if _delta(a, b, "etcd_http_request_seconds_count",
                      kind="write") >= 2:
                break
            time.sleep(0.02)
        assert _delta(a, b, DROPPED) == 2
        assert _folded(a, b, "write") == 0
        for seg in obs_mod.SEGMENT_NAMES["write"]:
            assert _delta(a, b, SEG + "_count", kind="write",
                          segment=seg) == 0
        assert eng.obs.tracer.live() == 0
        late = [s for s in eng.obs.tracer.spans()
                if s.get("kind") == "write" and "rounds" not in s]
        assert len(late) == 1 and "acked" not in late[0]["stages"]
    finally:
        front.stop()
        eng.stop()


def test_the_in_flight_table_is_bounded_without_a_scan():
    """A request that never reaches a reply the front accounts for
    (engine.do without the front) is pushed out by the oldest-first bound
    and counted; a late mark never reopens it."""
    tr = obs_mod.Tracer(every=1)
    a = _reg()
    for rid in range(1, tr.MAX_LIVE + 4):
        tr.mark(rid, "submit")
    assert tr.live() == tr.MAX_LIVE
    assert _delta(a, _reg(), DROPPED) == 3
    tr.mark(1, "acked")                         # pushed out: dropped
    assert tr.live() == tr.MAX_LIVE
    assert [s["rid"] for s in tr.spans()[:3]] == [1, 2, 3]
    tr.mark(10_000, "applied")                  # never opened
    assert tr.live() == tr.MAX_LIVE
    tr.finish(4, "other")                       # not a write or a read
    assert tr.live() == tr.MAX_LIVE - 1
    assert _delta(a, _reg(), DROPPED) == 4


# -- trace ids survive SIGKILL + WAL replay ----------------------------------

_TRACE_CRASH_CHILD = r"""
import os, sys, tempfile
os.environ["ETCD_TPU_TRACE_EVERY"] = "1"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from etcd_tpu.server.engine import EngineConfig, MultiEngine
from etcd_tpu.server.request import Request, METHOD_PUT

d, ackpath = sys.argv[1], sys.argv[2]
eng = MultiEngine(EngineConfig(
    groups=4, peers=3, data_dir=d, window=16, max_ents=4,
    heartbeat_tick=3, fsync=True, checkpoint_rounds=1 << 30,
    applier_shards=2, wal_shards=2, request_timeout=60.0))
eng.start()
assert eng.wait_leaders(180), eng.failed
ack = open(ackpath, "a")
print("READY", flush=True)
rid = 10_000
while True:
    r = Request(id=rid, method=METHOD_PUT,
                path=f"/crash/k{rid}", val="v")
    eng.do(rid % 4, r)            # returns only after durable ack
    ack.write("%d\n" % rid)
    ack.flush()
    rid += 2
"""


def test_trace_ids_survive_sigkill_and_replay(tmp_path):
    """Sampled rids ride the durable Request payloads: SIGKILL the
    engine mid-stream, restart on the same data dir with tracing on,
    and every acked rid must reappear as a `replayed` trace span."""
    d = tmp_path / "crash"
    ackpath = tmp_path / "acked.log"
    ackpath.write_text("")
    proc = subprocess.Popen(
        [sys.executable, "-c", _TRACE_CRASH_CHILD, str(d), str(ackpath)],
        stdout=subprocess.PIPE, cwd=REPO)
    try:
        assert proc.stdout.readline().strip() == b"READY"
        deadline = time.time() + 120
        while time.time() < deadline:
            if len(ackpath.read_text().splitlines()) >= 6:
                break
            time.sleep(0.01)
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    acked = [int(x) for x in ackpath.read_text().splitlines() if x]
    assert len(acked) >= 6, "child never got going"

    from etcd_tpu.server.engine import EngineConfig, MultiEngine
    prev = os.environ.get("ETCD_TPU_TRACE_EVERY")
    os.environ["ETCD_TPU_TRACE_EVERY"] = "1"
    try:
        eng = MultiEngine(EngineConfig(
            groups=4, peers=3, data_dir=str(d), window=16, max_ents=4,
            heartbeat_tick=3, fsync=False, checkpoint_rounds=1 << 30,
            applier_shards=2, wal_shards=2))
        spans = {s["rid"]: s["stages"] for s in eng.obs.tracer.spans()}
        eng.stop()
    finally:
        if prev is None:
            os.environ.pop("ETCD_TPU_TRACE_EVERY", None)
        else:
            os.environ["ETCD_TPU_TRACE_EVERY"] = prev
    for rid in acked:
        assert rid in spans, f"acked rid {rid} lost from replay trace"
        assert "replayed" in spans[rid], spans[rid]
