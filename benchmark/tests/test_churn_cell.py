"""The cells churn12k5.put256-zipf and mt1k.put64-c1 (PR 38) as the runner
finds them: the configuration mt100k-p7-churn is BASELINE.json configs[4] cut
in nothing but the groups (seven peers, the three churn flags, the mix of the
lag cell), its guarantees are no weaker than the accepted configurations',
the cell takes the write metrics and five metrics of its own, which read
nothing (and raise nothing) from a program without those series; the light
cell is the upstream grid's row as benchmark/README.md writes it out; and the
whole run of the churn cell on a CPU member at G=8: elections, a lost write
proposed again and an install inside the window, SIGKILL and restart, every
number compared at its limit. Entries are looked up by name; nothing here
says where they stand or what else a list holds."""
import json
import os

import prom
import pytest
import run
from harness import cli_value

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "churn12k5.put256-zipf"
LIGHT = "mt1k.put64-c1"
NEW = ("churn_elections_per_round", "churn_leaderless_wait_ms",
       "churn_reproposed_per_round", "churn_snap_installs_per_round",
       "churn_need_host_ms")
FLAGS = ["--engine-churn-down-rounds", "128",
         "--engine-churn-period-rounds", "512", "--engine-churn-seed", "38"]


def layer_metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_is_configs_4_cut_in_the_groups_alone():
    cell, cfg, mix = run.load_cell(CELL)
    assert (cell["config"], cell["traffic"]) == ("mt100k-p7-churn",
                                                 "put256-c256-z099")
    assert cfg["cli"] == ["--engine-groups", "12500", "--engine-peers", "7",
                          "--engine-window", "32"] + FLAGS
    assert cfg["chips"] == 1 and cli_value(cfg["cli"],
                                           "--engine-peers") == 7
    assert sorted(cfg["reduced"]) == ["groups"]
    share = run.load_json("configs", "mt100k-p5-chipshare.json")
    assert cfg["reduced"]["groups"] == share["reduced"]["groups"]
    # no guarantee weaker than the accepted configurations': each starts
    # with theirs, word for word, and says what a cut-off leader adds
    assert set(cfg["guarantees"]) == set(share["guarantees"])
    for k, text in share["guarantees"].items():
        assert cfg["guarantees"][k].startswith(text), k
    assert "exactly once" in cfg["guarantees"]["durability"]
    assert "quorum 4" in cfg["guarantees"]["durability"]
    for k in ("value_bytes", "max_ents", "hops", "fsync",
              "checkpoint_rounds"):
        assert cfg["assumed"][k] == share["assumed"][k], k
    assert cfg["assumed"]["churn_seed"] == 38
    assert len(cfg["source"]) <= 200 and "configs[4]" in cfg["source"]
    bm = manifest()
    (entry,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1
    (c,) = [c for c in bm["configs"] if c["name"] == "mt100k-p7-churn"]
    assert c["reduced"] == ["groups"] and c["source"] == cfg["source"]


def test_the_mix_is_the_lag_cells():
    _, _, mix = run.load_cell(CELL)
    _, _, lag = run.load_cell("lag12k5.put256-zipf")
    assert mix == lag and mix["tenant_dist"] == {"kind": "zipf",
                                                 "theta": 0.99}


def test_the_light_cell_is_the_upstream_grids_row():
    cell, cfg, mix = run.load_cell(LIGHT)
    assert (cell["config"], cell["traffic"]) == ("mt1k-p3", "put64-c1")
    assert cli_value(cfg["cli"], "--engine-groups") == 1_000
    for k, v in {"loop": "closed", "clients": 1, "gen_procs": 1,
                 "write_share": 1.0, "value_bytes": 64,
                 "tenant_dist": {"kind": "uniform"}, "keys_per_client": 16,
                 "readback_keys": 2000, "warmup_seconds": 3}.items():
        assert mix[k] == v, k
    (entry,) = [w for w in manifest()["workloads"] if w["name"] == LIGHT]
    assert entry["chips"] == 1


def test_the_cells_read_the_write_metrics_and_the_churn_cell_its_own():
    bm = manifest()
    listed = {m["name"]: m for m in bm["end_to_end"] + bm["per_layer"]}
    for cell in (CELL, LIGHT):
        _, _, mix = run.load_cell(cell)
        for name in ("ops_per_round", "record_admit_ms", "wal_fsync_mean_ms",
                     "wal_rounds_per_fsync", "ack_gate_wait_ms",
                     "pending_wait_ms", "wal_submit_ms", "step_device_ms",
                     "step_roofline", "d2h_kb_per_round", "readback_ms",
                     "record_ms", "compact_round_share", "gen_think_us",
                     "gather_rebucket_share", "gather_device_ms",
                     "device_idle_pct"):
            assert run.metric_applies(layer_metric(name)["cells"], cell,
                                      mix), (cell, name)
        for name in ("qreads_per_round", "qread_engine_ms",
                     "snap_installs_per_round", "need_host_ms",
                     "lag_releases_per_round"):
            assert not run.metric_applies(layer_metric(name)["cells"], cell,
                                          mix), (cell, name)
        for name in ("write_ack_p99_ms", "ops_per_round",
                     "wal_fsync_mean_ms", "wal_rounds_per_fsync",
                     "ack_gate_wait_ms", "wal_submit_ms", "record_admit_ms",
                     "pending_wait_ms", "gen_think_us", "gather_device_ms"):
            assert cell in listed[name]["workloads"], (cell, name)
        assert cell not in listed["qread_p99_ms"]["workloads"]
        # the steps that take the down map are still named step_routed_*
        assert run.module_patterns(cell, mix) == ["gather_rows",
                                                  "step_routed"]
    _, _, mix = run.load_cell(CELL)
    _, _, light = run.load_cell(LIGHT)
    for name in NEW:
        spec = layer_metric(name)
        assert spec["cells"] == [CELL] and spec["source"]["reader"] == \
            "prom_delta"
        assert run.metric_applies(spec["cells"], CELL, mix)
        assert not run.metric_applies(spec["cells"], LIGHT, light)
        assert listed[name]["workloads"] == [CELL], name
        assert listed[name] in bm["per_layer"]
        assert listed[name]["source"] == "program_counter"
    # the two that read the need-host path's series read what PR 35's read
    for mine, theirs in (("churn_snap_installs_per_round",
                          "snap_installs_per_round"),
                         ("churn_need_host_ms", "need_host_ms")):
        assert layer_metric(mine)["source"] == layer_metric(theirs)["source"]
        assert layer_metric(mine)["layer"] == layer_metric(theirs)["layer"]


def scrape(rounds, changes=None, wait_sum=0.0, reproposed=0, installs=0,
           need_host_sum=0.0):
    text = f"etcd_engine_rounds_total {rounds}\n"
    if changes is not None:
        text += (f"etcd_engine_leader_changes_total {changes}\n"
                 f"etcd_engine_leaderless_wait_seconds_sum {wait_sum}\n"
                 f"etcd_engine_leaderless_wait_seconds_count {reproposed}\n"
                 f"etcd_engine_reproposed_requests_total {reproposed}\n"
                 f"etcd_engine_snapshot_installs_total {installs}\n"
                 f"etcd_engine_need_host_seconds_sum {need_host_sum}\n")
    return prom.parse(text)


def test_the_five_metrics_from_two_scrapes_and_nothing_from_the_parent():
    src = {n: layer_metric(n)["source"] for n in NEW}
    before = scrape(100, 12_500, 1.0, 40, 4, 0.5)
    after = scrape(1100, 36_500, 51.0, 3040, 54, 2.5)
    got = {n: prom.prom_delta(before, after, src[n], 30.0) for n in NEW}
    assert got == {"churn_elections_per_round": 24.0,
                   "churn_leaderless_wait_ms": 50.0,
                   "churn_reproposed_per_round": 3.0,
                   "churn_snap_installs_per_round": 0.05,
                   "churn_need_host_ms": 2.0}
    # a program without the new series (the parent: it has the need-host
    # path's, and no churn to read them under): nothing, no raise
    old0, old1 = scrape(100), scrape(1100)
    for n in NEW:
        assert prom.prom_delta(old0, old1, src[n], 30.0) is None


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS", "").lower() != "cpu",
                    reason="boots a member: run with JAX_PLATFORMS=cpu")
def test_run_at_tiny_g_elects_reproposes_installs_and_is_correct(capfd):
    result = run.run_cell(CELL, seed=2**31 + 38, seconds=20.0, trace=True,
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
    assert lines["start"]["cli"][-6:] == FLAGS
    counters = lines["layers_from_counters"]["metrics"]
    rounds = lines["samples"]["window_rounds"]
    n_rounds = rounds[1] - rounds[0]
    assert n_rounds > 512                   # every group's leader was cut
    assert counters["churn_elections_per_round"] * n_rounds >= 8
    assert counters["churn_reproposed_per_round"] * n_rounds >= 1
    assert counters["churn_leaderless_wait_ms"] > 0
    assert counters["churn_snap_installs_per_round"] * n_rounds >= 1
    assert counters["churn_need_host_ms"] > 0
    assert counters["compact_round_share"] < 1
    e2e = lines["end_to_end_of_traced_run"]["metrics"]
    assert set(e2e) == {"acked_ops_per_s", "ack_p50_ms", "write_ack_p99_ms",
                        "setup_s"}
    want = {m["name"] for m in manifest()["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= want
    # (lib/peaks.json has no peak for a CPU, so no share of a roofline)
    assert set(result["metrics"]) == want - {"step_roofline"}
