"""The cell one100k.put256-c256 (PR 46) as the runner finds it: the
configuration mt100k-p5-onechip is cell 1's with `--engine-groups` 100000 and
nothing else different in `cli`, `guarantees` or `assumed`, and it cuts
nothing of the source but the lag injection (not the groups); the mix is
cell 1's own file; the device step's least traffic at the cell's (G, P, W)
is eight times cell 1's; the cell takes the write metrics and the device's,
and the two metrics this PR brought read their series from two scrapes and
nothing (no raise) from a program without them; and the whole run on a CPU
member at G = 8, SIGKILL and restart included, every number compared at its
limit. Entries are looked up by name; nothing here says where they stand or
what else a list holds."""
import json
import os

import prom
import pytest
import roofline
import run
from harness import cli_value

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "one100k.put256-c256"
CONFIG = "mt100k-p5-onechip"
CELL_1 = "share12k5.put256-c256"
CKPT_CELL = "share12k5.ycsb-a"      # its warm-up puts round 2,048 in the window
NEW = ("sync_scan_ms", "checkpoint_us_per_store")


def layer_metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_is_cell_1s_at_the_sources_own_groups():
    cell, cfg, _ = run.load_cell(CELL)
    assert (cell["config"], cell["traffic"]) == (CONFIG, "put256-c256")
    _, share, _ = run.load_cell(CELL_1)
    assert cli_value(cfg["cli"], "--engine-groups") == 100_000 == 8 * (
        cli_value(share["cli"], "--engine-groups"))
    cli = list(cfg["cli"])
    cli[cli.index("--engine-groups") + 1] = "12500"
    assert cli == share["cli"]
    assert cfg["guarantees"] == share["guarantees"]
    assert cfg["assumed"] == share["assumed"]
    assert cfg["chips"] == 1 == share["chips"]
    # the source's own G: only the lag injection is left out, and the
    # configuration that holds it says where
    assert sorted(cfg["reduced"]) == ["lagging_followers"]
    assert "mt100k-p5-lag5" in cfg["reduced"]["lagging_followers"]
    assert "groups" in share["reduced"]
    assert len(cfg["source"]) <= 200
    for word in ("north_star", "configs[3]", "configs[1]", "256 clients"):
        assert word in cfg["source"], word
    assert "ONE member" in cfg["deployment"]
    bm = manifest()
    (entry,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 1
    (c,) = [c for c in bm["configs"] if c["name"] == CONFIG]
    assert c["reduced"] == ["lagging_followers"]
    assert c["source"] == cfg["source"]


def test_the_mix_is_cell_1s_own_file():
    cell, _, mix = run.load_cell(CELL)
    cell1, _, mix1 = run.load_cell(CELL_1)
    assert cell["traffic"] == cell1["traffic"] and mix == mix1


def test_the_steps_least_traffic_is_eight_times_cell_1s():
    _, cfg, _ = run.load_cell(CELL)
    _, share, _ = run.load_cell(CELL_1)

    def least(cli):
        return roofline.step_min_bytes(
            cli_value(cli, "--engine-groups"), cli_value(cli, "--engine-peers"),
            cli_value(cli, "--engine-window"), roofline.MAX_ENTS)

    assert least(cfg["cli"]) == 8 * least(share["cli"])
    # state and inbox, once: 295 MB where cell 1 holds 37
    assert least(cfg["cli"]) // 2 == 295_000_000
    assert roofline.step_min_seconds(100_000, 5, 32, "TPU v5 lite") == (
        pytest.approx(8 * roofline.step_min_seconds(12_500, 5, 32,
                                                    "TPU v5 lite")))


def test_the_cell_reads_the_write_metrics_the_devices_and_the_scan():
    bm = manifest()
    listed = {m["name"]: m for m in bm["end_to_end"] + bm["per_layer"]}
    _, _, mix = run.load_cell(CELL)
    for name in ("ops_per_round", "record_admit_ms", "wal_fsync_mean_ms",
                 "wal_rounds_per_fsync", "ack_gate_wait_ms",
                 "pending_wait_ms", "wal_submit_ms", "step_device_ms",
                 "step_roofline", "device_idle_pct", "gather_device_ms",
                 "d2h_kb_per_round", "h2d_kb_per_round", "readback_ms",
                 "stage_ms", "checkpoint_s", "gen_think_us", "wack_round_ms",
                 "apply_view_share", "sync_scan_ms"):
        assert run.metric_applies(layer_metric(name)["cells"], CELL,
                                  mix), name
    for name in ("qreads_per_round", "qread_engine_ms", "need_host_ms",
                 "churn_elections_per_round", "meshchurn_need_host_ms",
                 "checkpoint_us_per_store"):
        assert not run.metric_applies(layer_metric(name)["cells"], CELL,
                                      mix), name
    # on every list cell 1 is on: the same mix on the same kind of member
    on_cell_1s = [m for m in listed.values()
                  if CELL_1 in m.get("workloads", [])]
    assert len(on_cell_1s) >= 20
    for m in on_cell_1s:
        assert CELL in m["workloads"], m["name"]
    assert CELL not in listed["qread_p99_ms"]["workloads"]
    assert run.module_patterns(CELL, mix) == ["gather_rows", "step_routed"]


def test_the_two_new_metrics_are_found_by_name_and_say_where_they_read():
    bm = manifest()
    listed = {m["name"]: m for m in bm["per_layer"]}
    (p99,) = [m for m in bm["end_to_end"] if m["name"] == "write_ack_p99_ms"]
    scan, ckpt = (layer_metric(n) for n in NEW)
    # the scan runs twice a second in every member; it moves the writes'
    # tail, which the read cell does not report
    assert scan["cells"] == "writes" and scan["moves"] == "write_ack_p99_ms"
    assert listed["sync_scan_ms"]["workloads"] == p99["workloads"]
    # a window without a checkpoint has nothing to divide: listed only for
    # the cell whose mix places round 2,048 inside every window (PR 37)
    assert ckpt["cells"] == [CKPT_CELL] == listed[
        "checkpoint_us_per_store"]["workloads"]
    _, _, ckpt_mix = run.load_cell(CKPT_CELL)
    assert ckpt_mix["warmup_seconds"] == 15
    assert ckpt["source"]["num"] == layer_metric("checkpoint_s")["source"][
        "num"]
    for spec in (scan, ckpt):
        assert spec["layer"] == layer_metric("checkpoint_s")["layer"]
        assert spec["source"]["reader"] == "prom_delta"
        assert listed[spec["name"]] in bm["per_layer"]


def scrape(rounds, scans=None, scan_sum=0.0, ckpt_sum=0.0, stores=None):
    text = (f"etcd_engine_rounds_total {rounds}\n"
            f"etcd_engine_checkpoint_seconds_sum {ckpt_sum}\n")
    if scans is not None:
        text += (f"etcd_engine_sync_scan_seconds_sum {scan_sum}\n"
                 f"etcd_engine_sync_scan_seconds_count {scans}\n")
    if stores is not None:
        text += f"etcd_engine_checkpoint_stores_total {stores}\n"
    return prom.parse(text)


def test_the_two_metrics_from_two_scrapes_and_nothing_from_the_parent():
    src = {n: layer_metric(n)["source"] for n in NEW}
    before = scrape(100, 10, 0.5, 0.0, 0)
    after = scrape(800, 70, 3.0, 9.0, 100_000)
    assert prom.prom_delta(before, after, src["sync_scan_ms"],
                           30.0) == pytest.approx(2500 / 60)
    assert prom.prom_delta(before, after, src["checkpoint_us_per_store"],
                           30.0) == pytest.approx(90.0)
    # a window without a checkpoint: nothing to divide, the metric left out
    quiet = scrape(800, 70, 3.0, 0.0, 0)
    assert prom.prom_delta(before, quiet, src["checkpoint_us_per_store"],
                           30.0) is None
    # a program with neither series (the parent, which does export the
    # checkpoint's seconds): nothing, and nothing raises
    old0, old1 = scrape(100, ckpt_sum=1.0), scrape(800, ckpt_sum=2.0)
    for n in NEW:
        assert prom.prom_delta(old0, old1, src[n], 30.0) is None


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS", "").lower() != "cpu",
                    reason="boots a member: run with JAX_PLATFORMS=cpu")
def test_run_at_tiny_g_is_correct_and_reads_the_scan(capfd):
    result = run.run_cell(CELL, seed=2**31 + 46, seconds=8.0, trace=True,
                          groups_override=8, require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 500
    assert result["device"]["count"] == 1
    assert set(result["compared"]) >= {"readback_mismatches",
                                       "cross_tenant_leaks",
                                       "readback_mismatches_after_sigkill",
                                       "cross_tenant_leaks_after_sigkill"}
    assert all(c == {"value": 0, "limit": 0}
               for c in result["compared"].values()), result["compared"]
    lines = {}
    for line in capfd.readouterr().out.splitlines():
        doc = json.loads(line)
        if "phase" in doc:
            lines[doc["phase"]] = doc
    assert lines["start"]["cli"][:6] == ["--engine-groups", "8",
                                         "--engine-peers", "5",
                                         "--engine-window", "32"]
    counters = lines["layers_from_counters"]["metrics"]
    assert 0 < counters["sync_scan_ms"] < 50        # G = 8: microseconds
    assert "checkpoint_us_per_store" not in counters
    assert counters["apply_view_share"] == 1.0
    e2e = lines["end_to_end_of_traced_run"]["metrics"]
    assert set(e2e) == {"acked_ops_per_s", "ack_p50_ms", "write_ack_p99_ms",
                        "setup_s"}
    want = {m["name"] for m in manifest()["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert "sync_scan_ms" in want and "checkpoint_us_per_store" not in want
    # (lib/peaks.json has no peak for a CPU, so no share of a roofline)
    assert set(result["metrics"]) == want - {"step_roofline"}
