"""The cell lag12k5.put256-zipf (PR 35) as the runner finds it: the
configuration is cell 1's plus the three lag flags and cuts nothing but the
groups, the mix is cell 1's with the tenant choice skewed, the cell takes
the write metrics and the three metrics of the need-host path, which read
nothing (and raise nothing) from a program without those series; and the
whole run on a CPU member at G=8 with the flags on: installs happen in the
window, the run is correct, SIGKILL and restart included."""
import json
import os

import prom
import pytest
import run
from harness import cli_value

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "lag12k5.put256-zipf"
NEW = ("snap_installs_per_round", "need_host_ms", "lag_releases_per_round")


def layer_metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_configuration_is_cell_1s_with_the_lag_flags():
    cell, cfg, mix = run.load_cell(CELL)
    assert (cell["config"], cell["traffic"]) == ("mt100k-p5-lag5",
                                                 "put256-c256-z099")
    share = run.load_json("configs", "mt100k-p5-chipshare.json")
    n = len(share["cli"])
    assert cfg["cli"][:n] == share["cli"]
    assert cfg["cli"][n:] == ["--engine-lag-share", "0.05",
                              "--engine-lag-hold-rounds", "256",
                              "--engine-lag-seed", "35"]
    assert cfg["chips"] == 1 and cli_value(cfg["cli"],
                                           "--engine-groups") == 12_500
    assert sorted(cfg["reduced"]) == ["groups"]
    assert cfg["reduced"]["groups"] == share["reduced"]["groups"]
    assert set(cfg["guarantees"]) == set(share["guarantees"])
    for k, text in share["guarantees"].items():
        assert cfg["guarantees"][k].startswith(text), k
    for k in ("value_bytes", "max_ents", "hops", "fsync",
              "checkpoint_rounds"):
        assert cfg["assumed"][k] == share["assumed"][k], k
    assert len(cfg["source"]) <= 200
    assert "configs[3]" in cfg["source"] and "configs[2]" in cfg["source"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    (entry,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1 and bm["workloads"][-1] is entry
    (c,) = [c for c in bm["configs"] if c["name"] == "mt100k-p5-lag5"]
    assert c["reduced"] == ["groups"] and bm["configs"][-1] is c
    # the configurations that still leave the injection out say so
    for name in ("mt100k-p5-chipshare", "mt100k-p5-mesh4"):
        assert "lagging_followers" in run.load_json(
            "configs", name + ".json")["reduced"]


def test_the_mix_is_cell_1s_with_the_tenant_choice_skewed():
    _, _, mix = run.load_cell(CELL)
    _, _, cell1 = run.load_cell("share12k5.put256-c256")
    assert mix["tenant_dist"] == {"kind": "zipf", "theta": 0.99}
    for k in cell1:
        if k not in ("why", "tenant_dist"):
            assert mix[k] == cell1[k], k
    assert set(mix) == set(cell1)


def test_the_cell_reads_the_write_metrics_and_the_need_host_path():
    _, _, mix = run.load_cell(CELL)
    for name in ("ops_per_round", "record_admit_ms", "wal_fsync_mean_ms",
                 "wal_rounds_per_fsync", "ack_gate_wait_ms",
                 "pending_wait_ms", "wal_submit_ms", "step_device_ms",
                 "step_roofline", "d2h_kb_per_round", "readback_ms",
                 "record_ms", "compact_round_share") + NEW:
        assert run.metric_applies(layer_metric(name)["cells"], CELL, mix), name
    for name in ("qreads_per_round", "qread_engine_ms", "gen_think_us",
                 "gather_device_ms"):
        assert not run.metric_applies(layer_metric(name)["cells"], CELL, mix)
    # the step that takes the hold is still named step_routed_*
    assert run.module_patterns(CELL, mix) == ["step_routed"]
    _, _, cell1 = run.load_cell("share12k5.put256-c256")
    for name in NEW:
        spec = layer_metric(name)
        assert spec["cells"] == [CELL]
        assert not run.metric_applies(spec["cells"], "share12k5.put256-c256",
                                      cell1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    writes = ["share12k5.put256-c256", "mt1k.put256-c256",
              "mesh50k.put256-c256", CELL]
    listed = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]
              if m.get("workloads") == writes]
    assert listed == ["write_ack_p99_ms", "ops_per_round",
                      "wal_fsync_mean_ms", "wal_rounds_per_fsync",
                      "ack_gate_wait_ms", "wal_submit_ms", "record_admit_ms",
                      "pending_wait_ms"]
    assert [m["name"] for m in bm["per_layer"][-3:]] == list(NEW)


def scrape(rounds, installs=None, need_host_sum=None, releases=None):
    text = f"etcd_engine_rounds_total {rounds}\n"
    if installs is not None:
        text += (f"etcd_engine_snapshot_installs_total {installs}\n"
                 f"etcd_engine_need_host_seconds_sum {need_host_sum}\n"
                 f"etcd_engine_need_host_seconds_count {installs}\n"
                 f"etcd_engine_lag_releases_total {releases}\n")
    return prom.parse(text)


def test_the_three_metrics_from_two_scrapes_and_nothing_from_the_parent():
    src = {n: layer_metric(n)["source"] for n in NEW}
    before, after = scrape(100, 4, 0.5, 30), scrape(1100, 74, 2.5, 1030)
    assert prom.prom_delta(before, after, src["snap_installs_per_round"],
                           30.0) == 70 / 1000
    assert prom.prom_delta(before, after, src["need_host_ms"], 30.0) == 2.0
    assert prom.prom_delta(before, after, src["lag_releases_per_round"],
                           30.0) == 1.0
    # a program with none of the series (the parent): nothing, no raise
    old0, old1 = scrape(100), scrape(1100)
    for n in NEW:
        assert prom.prom_delta(old0, old1, src[n], 30.0) is None


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS", "").lower() != "cpu",
                    reason="boots a member: run with JAX_PLATFORMS=cpu")
def test_run_at_tiny_g_installs_in_the_window_and_is_correct(capfd):
    result = run.run_cell(CELL, seed=2**31 + 35, seconds=10.0, trace=True,
                          groups_override=8, require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 500
    assert set(result["compared"]) >= {"readback_mismatches_after_sigkill",
                                       "cross_tenant_leaks_after_sigkill"}
    assert all(c == {"value": 0, "limit": 0}
               for c in result["compared"].values()), result["compared"]
    lines = {}
    for line in capfd.readouterr().out.splitlines():
        doc = json.loads(line)
        if "phase" in doc:
            lines[doc["phase"]] = doc
    assert lines["start"]["cli"][-6:] == [
        "--engine-lag-share", "0.05", "--engine-lag-hold-rounds", "256",
        "--engine-lag-seed", "35"]
    counters = lines["layers_from_counters"]["metrics"]
    rounds = lines["samples"]["window_rounds"]
    n_rounds = rounds[1] - rounds[0]
    assert counters["snap_installs_per_round"] * n_rounds >= 1
    assert counters["lag_releases_per_round"] * n_rounds >= 1
    assert counters["need_host_ms"] > 0
    assert counters["compact_round_share"] < 1
    e2e = lines["end_to_end_of_traced_run"]["metrics"]
    assert set(e2e) == {"acked_ops_per_s", "ack_p50_ms", "write_ack_p99_ms",
                        "setup_s"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        want = {m["name"] for m in json.load(f)["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= want
    # (lib/peaks.json has no peak for a CPU, so no share of a roofline)
    assert set(result["metrics"]) == want - {"step_roofline"}
