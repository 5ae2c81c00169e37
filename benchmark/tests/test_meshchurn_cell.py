"""The cell meshchurn50k.put256-zipf (PR 43) as the runner finds it: the
configuration mt100k-p7-churn-mesh4 is mt100k-p7-churn (BASELINE.json
configs[4]) on mt100k-p5-mesh4's layout: a four-chip mesh deployment whose
command line is the one-chip churn cell's plus the mesh flag at four times
the groups, with that cell's guarantees and assumptions word for word and
its mix; it takes the write metrics, the step's and the gather's device
metrics, and eight metrics this PR brought, each of which reads its series
from two scrapes and nothing (no raise) from a program without them; and the
whole run on a CPU member at G = 8 over a 4x1 mesh of CPU devices:
elections, an install across the shards, SIGKILL inside a cut and restart on
the mesh, every number compared at its limit. Entries are looked up by name;
nothing here says where they stand or what else a list holds."""
import json
import os

import prom
import pytest
import run
from harness import cli_value

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "meshchurn50k.put256-zipf"
CONFIG = "mt100k-p7-churn-mesh4"
ONE_CHIP = "churn12k5.put256-zipf"
MESH = "--engine-mesh-peers-axis"
OWN = ("meshchurn_elections_per_round", "meshchurn_leaderless_wait_ms",
       "meshchurn_reproposed_per_round", "meshchurn_snap_installs_per_round",
       "meshchurn_need_host_ms", "meshchurn_need_host_read_ms",
       "meshchurn_need_host_write_ms")
NEW = OWN + ("h2d_kb_per_round",)
PART = "etcd_engine_need_host_part_seconds_sum"


def layer_metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_churn_cells_on_the_mesh_cells_layout():
    cell, cfg, mix = run.load_cell(CELL)
    assert (cell["config"], cell["traffic"]) == (CONFIG, "put256-c256-z099")
    _, one, one_mix = run.load_cell(ONE_CHIP)
    _, quiet, _ = run.load_cell("mesh50k.put256-c256")
    assert mix == one_mix
    # the one-chip churn cell's command line, the mesh flag put in, at four
    # times the groups: 12,500 rows a device on both
    cli = list(cfg["cli"])
    at = cli.index(MESH)
    assert cli[at:at + 2] == [MESH, "1"]
    del cli[at:at + 2]
    groups = cli_value(cfg["cli"], "--engine-groups")
    assert groups == 50_000 == 4 * cli_value(one["cli"], "--engine-groups")
    cli[cli.index("--engine-groups") + 1] = "12500"
    assert cli == one["cli"]
    assert cfg["chips"] == 4 == quiet["chips"]
    assert cli_value(quiet["cli"], MESH) == 1
    assert cli_value(quiet["cli"], "--engine-groups") == groups
    # no guarantee and no assumption but the one-chip churn cell's
    assert cfg["guarantees"] == one["guarantees"]
    assert cfg["assumed"] == one["assumed"]
    assert sorted(cfg["reduced"]) == ["groups"]
    assert cfg["reduced"]["groups"] == quiet["reduced"]["groups"]
    assert len(cfg["source"]) <= 200 and "configs[4]" in cfg["source"]
    bm = manifest()
    (entry,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 4
    (c,) = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert c["reduced"] == ["groups"] and c["source"] == cfg["source"]
    # four-chip cells are at most half of all, rounded down
    assert (sum(w["chips"] == 4 for w in bm["workloads"])
            <= len(bm["workloads"]) // 2)


def test_the_cell_reads_the_write_metrics_the_device_metrics_and_its_own():
    bm = manifest()
    listed = {m["name"]: m for m in bm["end_to_end"] + bm["per_layer"]}
    _, _, mix = run.load_cell(CELL)
    for name in ("ops_per_round", "record_admit_ms", "wal_fsync_mean_ms",
                 "wal_rounds_per_fsync", "ack_gate_wait_ms",
                 "pending_wait_ms", "wal_submit_ms", "step_device_ms",
                 "step_roofline", "step_passes_per_hop", "d2h_kb_per_round",
                 "readback_ms", "record_ms", "compact_round_share",
                 "gen_think_us", "gather_device_ms", "device_idle_pct",
                 "stage_ms", "apply_view_share", "wack_round_ms", *NEW):
        assert run.metric_applies(layer_metric(name)["cells"], CELL,
                                  mix), name
    for name in ("qreads_per_round", "qread_engine_ms", "need_host_ms",
                 "snap_installs_per_round", "lag_releases_per_round",
                 "churn_elections_per_round", "churn_need_host_ms"):
        assert not run.metric_applies(layer_metric(name)["cells"], CELL,
                                      mix), name
    for name in ("write_ack_p99_ms", "ops_per_round", "wal_fsync_mean_ms",
                 "wal_rounds_per_fsync", "ack_gate_wait_ms", "wal_submit_ms",
                 "record_admit_ms", "pending_wait_ms", "gen_think_us",
                 "gather_device_ms", "apply_view_share", "wack_round_ms"):
        assert CELL in listed[name]["workloads"], name
    assert CELL not in listed["qread_p99_ms"]["workloads"]
    # the traced run must see both programs execute, as the quiet mesh
    # cell's does
    assert run.module_patterns(CELL, mix) == ["gather_rows", "step_routed"]
    _, _, one_mix = run.load_cell(ONE_CHIP)
    for name in OWN:
        spec = layer_metric(name)
        assert spec["cells"] == [CELL]
        assert spec["source"]["reader"] == "prom_delta"
        assert not run.metric_applies(spec["cells"], ONE_CHIP, one_mix)
        assert listed[name]["workloads"] == [CELL], name
        assert listed[name] in bm["per_layer"]
    # what a round uploads is read in every cell, as what it reads back is
    h2d, d2h = layer_metric("h2d_kb_per_round"), layer_metric(
        "d2h_kb_per_round")
    assert h2d["cells"] == d2h["cells"] == "all"
    assert "workloads" not in listed["h2d_kb_per_round"]
    assert h2d["source"]["den"] == d2h["source"]["den"]
    assert h2d["source"]["scale"] == d2h["source"]["scale"]
    # the five of the leader-change and need-host paths read what the
    # one-chip churn cell's five read, under the same layers
    for name in OWN[:5]:
        theirs = layer_metric(name.replace("meshchurn_", "churn_"))
        mine = layer_metric(name)
        for k in ("source", "layer", "unit", "better", "moves"):
            assert mine[k] == theirs[k], (name, k)
    for name in OWN[5:]:
        assert layer_metric(name)["layer"] == layer_metric(
            "meshchurn_need_host_ms")["layer"]
        assert layer_metric(name)["moves"] == "write_ack_p99_ms"


def scrape(rounds, changes=None, wait_sum=0.0, reproposed=0, installs=0,
           need_host=(0.0, 0.0, 0.0), h2d=None):
    text = f"etcd_engine_rounds_total {rounds}\n"
    if changes is not None:
        text += (f"etcd_engine_leader_changes_total {changes}\n"
                 f"etcd_engine_leaderless_wait_seconds_sum {wait_sum}\n"
                 f"etcd_engine_leaderless_wait_seconds_count {reproposed}\n"
                 f"etcd_engine_reproposed_requests_total {reproposed}\n"
                 f"etcd_engine_snapshot_installs_total {installs}\n"
                 f"etcd_engine_need_host_seconds_sum {sum(need_host)}\n")
    if h2d is not None:
        for part, v in zip(("read", "surgery", "write"), need_host):
            text += f'{PART}{{part="{part}"}} {v}\n'
        text += f"etcd_engine_h2d_bytes_total {h2d}\n"
    return prom.parse(text)


def test_the_eight_metrics_from_two_scrapes_and_nothing_from_the_parent():
    src = {n: layer_metric(n)["source"] for n in NEW}
    before = scrape(100, 50_000, 1.0, 40, 4, (0.25, 0.125, 0.125), 1_000_000)
    after = scrape(1100, 148_000, 51.0, 3040, 104, (1.25, 0.625, 1.125),
                   401_000_000)
    got = {n: prom.prom_delta(before, after, src[n], 30.0) for n in NEW}
    assert got == {"meshchurn_elections_per_round": 98.0,
                   "meshchurn_leaderless_wait_ms": 50.0,
                   "meshchurn_reproposed_per_round": 3.0,
                   "meshchurn_snap_installs_per_round": 0.1,
                   "meshchurn_need_host_ms": 2.5,
                   "meshchurn_need_host_read_ms": 1.0,
                   "meshchurn_need_host_write_ms": 1.0,
                   "h2d_kb_per_round": 400.0}
    # the parent exports the leader-change and need-host series and neither
    # the surgery's parts nor the uploads: those three read nothing there,
    # and nothing raises
    old0 = scrape(100, 50_000, 1.0, 40, 4, (0.25, 0.125, 0.125))
    old1 = scrape(1100, 148_000, 51.0, 3040, 104, (1.25, 0.625, 1.125))
    for n in NEW:
        v = prom.prom_delta(old0, old1, src[n], 30.0)
        assert (v is None) == (n in NEW[5:]), n
    # a program with none of them: nothing at all
    for n in NEW:
        assert prom.prom_delta(scrape(100), scrape(1100), src[n],
                               30.0) is None


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS", "").lower() != "cpu",
                    reason="boots a member: run with JAX_PLATFORMS=cpu")
def test_run_at_tiny_g_on_a_cpu_mesh_elects_installs_and_is_correct(
        capfd, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    result = run.run_cell(CELL, seed=2**31 + 43, seconds=20.0, trace=True,
                          groups_override=8, require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 500
    assert result["device"]["count"] == 4
    assert set(result["compared"]) >= {"readback_mismatches_after_sigkill",
                                       "cross_tenant_leaks_after_sigkill"}
    assert all(c == {"value": 0, "limit": 0}
               for c in result["compared"].values()), result["compared"]
    lines = {}
    for line in capfd.readouterr().out.splitlines():
        doc = json.loads(line)
        if "phase" in doc:
            lines[doc["phase"]] = doc
    assert MESH in lines["start"]["cli"]
    counters = lines["layers_from_counters"]["metrics"]
    rounds = lines["samples"]["window_rounds"]
    n_rounds = rounds[1] - rounds[0]
    assert n_rounds > 512                   # every group's leader was cut
    assert counters["meshchurn_elections_per_round"] * n_rounds >= 8
    assert counters["meshchurn_snap_installs_per_round"] * n_rounds >= 1
    assert counters["meshchurn_need_host_ms"] > 0
    assert 0 < (counters["meshchurn_need_host_read_ms"]
                + counters["meshchurn_need_host_write_ms"]) \
        < counters["meshchurn_need_host_ms"]
    assert counters["h2d_kb_per_round"] > 0
    assert counters["step_passes_per_hop"] > 0
    e2e = lines["end_to_end_of_traced_run"]["metrics"]
    assert set(e2e) == {"acked_ops_per_s", "ack_p50_ms", "write_ack_p99_ms",
                        "setup_s"}
    want = {m["name"] for m in manifest()["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= want
    # (lib/peaks.json has no peak for a CPU, so no share of a roofline)
    assert set(result["metrics"]) == want - {"step_roofline"}
