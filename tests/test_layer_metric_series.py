"""The program exports what the benchmark reads.

Every per-layer metric whose file under benchmark/layer_metrics/ has the
`prom_delta` reader is a quotient of deltas of series on the member's
/metrics. A series (or a label value) that the program stops exporting
does not fail a benchmark run: the metric reads `null`. This file holds
the contract from the program's side: one small CPU member serves writes
and quorum reads through the HTTP front across a checkpoint, and each
metric file's `num` and `den` terms must be there in the scrape that
follows, read with the benchmark's own reader. The metric files are read,
never changed.
"""
import glob
import importlib.util
import json
import os
import tempfile
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
G, P = 8, 3


def _prom_delta_files():
    out = []
    for path in sorted(glob.glob(os.path.join(
            REPO, "benchmark", "layer_metrics", "*.json"))):
        with open(path) as f:
            doc = json.load(f)
        if doc["source"].get("reader") == "prom_delta":
            out.append(pytest.param(doc, id=doc["name"]))
    return out


def _http(method, url, body=None):
    req = urllib.request.Request(
        url, method=method, data=body.encode() if body else None)
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.read().decode()


@pytest.fixture(scope="module")
def prom():
    spec = importlib.util.spec_from_file_location(
        "bench_prom_for_series_test",
        os.path.join(REPO, "benchmark", "lib", "prom.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def scrapes(prom):
    """(before, after): /metrics of one member around a little traffic,
    with a checkpoint between the two."""
    from etcd_tpu.etcdhttp.tenants import EngineHttp
    from etcd_tpu.server.engine import EngineConfig, MultiEngine
    # Every request id sampled: at the default of 1 in 16 this little
    # traffic would fold no span of one kind or the other, and the
    # request-segment files would have nothing counted to read.
    prev = os.environ.get("ETCD_TPU_TRACE_EVERY")
    os.environ["ETCD_TPU_TRACE_EVERY"] = "1"
    try:
        eng = MultiEngine(EngineConfig(
            groups=G, peers=P,
            data_dir=tempfile.mkdtemp(prefix="series-test-"),
            window=16, max_ents=4, heartbeat_tick=3, fsync=False,
            checkpoint_rounds=64, request_timeout=60.0))
    finally:
        if prev is None:
            os.environ.pop("ETCD_TPU_TRACE_EVERY", None)
        else:
            os.environ["ETCD_TPU_TRACE_EVERY"] = prev
    eng.start()
    assert eng.wait_leaders(180), f"no leaders: {eng.failed}"
    front = EngineHttp(eng, port=0)
    front.start()
    base = front.url.rstrip("/")
    try:
        before = prom.parse(_http("GET", base + "/metrics"))
        for i in range(2 * G):
            _http("PUT", f"{base}/tenants/{i % G}/v2/keys/s/k{i}",
                  f"value=v{i}")
            _http("GET", f"{base}/tenants/{i % G}/v2/keys/s/k{i}"
                         "?quorum=true")
        ckpt = ("etcd_engine_checkpoint_seconds_count", ())
        deadline = time.time() + 60
        after = prom.parse(_http("GET", base + "/metrics"))
        while after[ckpt] == before[ckpt] and time.time() < deadline:
            time.sleep(0.05)
            after = prom.parse(_http("GET", base + "/metrics"))
        yield before, after
    finally:
        front.stop()
        eng.stop()


@pytest.mark.parametrize("doc", _prom_delta_files())
def test_member_exports_the_series_of(doc, scrapes, prom):
    before, after = scrapes
    src = doc["source"]
    for term in (src["num"], src.get("den")):
        if term is None or term.get("window_seconds"):
            continue
        if (term["series"] == "etcd_thread_cpu_seconds_total"
                and not hasattr(time, "pthread_getcpuclockid")):
            pytest.skip("no per-thread CPU clock on this platform")
        assert prom.total(after, term["series"],
                          term.get("labels")) is not None, term
    # and the reader makes a number of them: this window counted rounds,
    # writes, quorum reads, fsyncs and a checkpoint
    got = prom.prom_delta(before, after, src, 1.0)
    assert got is not None and got >= 0, got
