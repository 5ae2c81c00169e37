"""BENCHMARK.json and the data files agree: every file named exists, every
name and unit uses only the allowed characters, every cell, mix and layer
metric is found by name, and what the generator does not implement is
refused, not ignored."""
import glob
import json
import os
import re

import loadgen
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bm():
    return load(os.path.join(ROOT, "BENCHMARK.json"))


def line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_names_units(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["benchmark"]
    assert bm["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bm[group]]
        assert len(names) == len(set(names)), group
        for e in bm[group]:
            assert NAME.match(e["name"]), e["name"]
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bm["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and line_ok(m["layer"])
    assert len(json.dumps(bm)) < 64 * 1024


def test_configs_and_cells_exist(bm):
    cfgs = {c["name"]: c for c in bm["configs"]}
    used = set()
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line_ok(c["source"]) and line_ok(c["why"])
        doc = load(os.path.join(ROOT, c["file"]))
        assert doc["name"] == c["name"] and doc["source"] == c["source"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        assert set(doc["guarantees"]) == {"durability", "reads", "isolation"}
        assert all(NAME.match(k) for k in c["reduced"])
        for flag in ("--engine-groups", "--engine-peers", "--engine-window"):
            assert int(doc["cli"][doc["cli"].index(flag) + 1]) > 0
    pairs = set()
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert line_ok(w["why"]) and NAME.match(w["traffic"])
        cell = load(os.path.join(BENCH, "workloads", w["name"] + ".json"))
        assert (cell["config"], cell["traffic"]) == (w["config"], w["traffic"])
        assert w["chips"] == load(os.path.join(
            ROOT, cfgs[w["config"]]["file"]))["chips"]
        mix = load(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        loadgen.validate_mix(mix)
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    assert len(pairs) == len(bm["workloads"]) and used == set(cfgs)


def test_layer_metric_files_match_the_manifest(bm):
    import run

    entries = {m["name"]: m for m in bm["per_layer"]}
    files = {os.path.basename(p)[:-5]: load(p) for p in
             glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))}
    assert set(files) == set(entries)
    mixes = {w["name"]: load(os.path.join(BENCH, "traffic",
                                          w["traffic"] + ".json"))
             for w in bm["workloads"]}
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    for name, spec in files.items():
        ent = entries[name]
        for k in ("name", "layer", "unit", "better", "moves"):
            assert spec[k] == ent[k], (name, k)
        assert spec["source"]["reader"] in ("prom_delta", "client",
                                            "device_trace")
        applies = sorted(w for w, mix in mixes.items()
                         if run.metric_applies(spec["cells"], w, mix))
        moved = e2e[ent["moves"]]
        reports_moved = sorted(moved.get("workloads", list(mixes)))
        assert applies == sorted(ent.get("workloads", reports_moved)), name
        # every cell a metric is read in reports the metric it should move
        assert set(applies) <= set(reports_moved), name


def test_every_file_under_paths_is_named_from_allowed_characters():
    tracked = [os.path.relpath(os.path.join(d, f), ROOT)
               for d, _, fs in os.walk(BENCH) for f in fs
               if "__pycache__" not in d]
    assert tracked
    for p in tracked:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p), p


BASE = {"loop": "closed", "clients": 4, "gen_procs": 1, "write_share": 1.0,
        "value_bytes": 16, "keys_per_client": 2,
        "tenant_dist": {"kind": "uniform"}}


@pytest.mark.parametrize("change,word", [
    ({"loop": "open"}, "rate"),
    ({"loop": "open", "arrival": "poisson"}, "rate"),
    ({"loop": "open", "rate": 0, "arrival": "poisson"}, "rate"),
    ({"loop": "open", "rate": 1000}, "arrival"),
    ({"loop": "open", "rate": 1000, "arrival": "uniform"}, "arrival"),
    ({"loop": "open", "rate": 1000, "arrival": "poisson",
      "start_spread_ms": 160}, "start_spread_ms"),
    ({"rate": 1000}, "rate"),
    ({"arrival": "poisson"}, "arrival"),
    ({"loop": "paced"}, "loop"),
    ({"via": "ingress"}, "via"),
    ({"tenant_dist": {"kind": "zipf"}}, "theta"),
    ({"tenant_dist": {"kind": "zipf", "theta": 0}}, "theta"),
    ({"tenant_dist": {"kind": "zipf", "s": 0.99}}, "theta"),
    ({"tenant_dist": {"kind": "zipf", "theta": 0.99, "shift_every_s": 10}},
     "theta"),
    ({"tenant_dist": {"kind": "uniform", "theta": 0.99}}, "uniform"),
    ({"tenant_dist": {"kind": "hotspot"}}, "uniform"),
    ({"window_phase": {"modulo": 2048, "offset": 64}}, "window_phase"),
    ({"write_share": 0.5}, "quorum"),
    ({"write_share": 0, "read": "quorum"}, "preload"),
    ({"write_share": 0, "read": "plain", "preload": {"keys_per_tenant": 1}},
     "quorum"),
    ({"clients": 0}, "clients"),
    ({"start_spread_ms": -1}, "start_spread_ms"),
    ({"watchers": 10}, "watchers"),
])
def test_unimplemented_mix_keys_are_refused(change, word):
    loadgen.validate_mix(BASE)
    with pytest.raises(loadgen.MixError, match=word):
        loadgen.validate_mix({**BASE, **change})


@pytest.mark.parametrize("change", [
    {"loop": "open", "rate": 3900, "arrival": "poisson"},
    {"loop": "open", "rate": 0.5, "arrival": "poisson", "clients": 1024},
    {"tenant_dist": {"kind": "zipf", "theta": 0.99}},
    {"tenant_dist": {"kind": "zipf", "theta": 1.1}},
    {"loop": "open", "rate": 3900, "arrival": "poisson",
     "tenant_dist": {"kind": "zipf", "theta": 0.99}},
    {"start_spread_ms": 160},
])
def test_open_loop_and_zipf_mixes_are_accepted(change):
    loadgen.validate_mix({**BASE, **change})
