"""The four-chip cell mesh50k.put256-c256 and the two readback metrics of
PR 26, as the runner finds them: the configuration loads as a mesh
deployment on four chips, the cell takes the write metrics, and
compact_round_share reads the share of compact rounds from two scrapes and
nothing from a program that has no such series (the parent's)."""
import json
import os

import prom
import run
from harness import cli_value

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "mesh50k.put256-c256"
SERIES = "etcd_engine_readback_rounds_total"


def layer_metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_cell_is_a_four_chip_mesh_deployment():
    cell, cfg, mix = run.load_cell(CELL)
    assert (cell["config"], cell["traffic"]) == ("mt100k-p5-mesh4",
                                                 "put256-c256")
    assert cfg["chips"] == 4
    assert cli_value(cfg["cli"], "--engine-groups") == 50_000
    assert cli_value(cfg["cli"], "--engine-mesh-peers-axis") == 1
    assert sorted(cfg["reduced"]) == ["groups", "lagging_followers"]
    share = run.load_json("configs", "mt100k-p5-chipshare.json")
    # the same guarantees and the same defaults as the one-chip share of
    # the same deployment; 12,500 rows a device on both
    assert cfg["guarantees"] == share["guarantees"]
    assert cfg["assumed"] == share["assumed"]
    assert (cli_value(cfg["cli"], "--engine-groups") // cfg["chips"]
            == cli_value(share["cli"], "--engine-groups"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    (entry,) = [w for w in bm["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 4
    assert sum(w["chips"] == 4 for w in bm["workloads"]) == 1


def test_the_cell_reads_the_write_metrics_and_the_compact_share():
    _, _, mix = run.load_cell(CELL)
    for name in ("ops_per_round", "record_admit_ms", "wal_fsync_mean_ms",
                 "ack_gate_wait_ms", "pending_wait_ms", "step_device_ms",
                 "step_roofline", "d2h_kb_per_round", "compact_round_share"):
        assert run.metric_applies(layer_metric(name)["cells"], CELL, mix), name
    for name in ("qreads_per_round", "qread_engine_ms"):
        assert not run.metric_applies(layer_metric(name)["cells"], CELL, mix)
    # The parent runs no gather on a mesh, and a pattern that matches no
    # program fails a traced TPU run: the gather's device time is read
    # where both sides run one.
    gather = layer_metric("gather_device_ms")
    assert gather["source"]["module_pattern"] == "gather_rows"
    assert not run.metric_applies(gather["cells"], CELL, mix)
    assert "gather_rows" not in run.module_patterns(CELL, mix)
    assert "gather_rows" in run.module_patterns("share12k5.put256-c256", mix)


def scrape(compact, full, over_cap, rounds):
    return prom.parse(
        f'{SERIES}{{kind="compact"}} {compact}\n'
        f'{SERIES}{{kind="full"}} {full}\n'
        f'{SERIES}{{kind="over_cap"}} {over_cap}\n'
        f"etcd_engine_rounds_total {rounds}\n")


def test_compact_round_share_from_two_scrapes():
    src = layer_metric("compact_round_share")["source"]
    before, after = scrape(10, 40, 0, 50), scrape(910, 60, 20, 1050)
    assert prom.prom_delta(before, after, src, 30.0) == 900 / 940
    # a read cell: every round is a read round, so every round is full
    assert prom.prom_delta(scrape(10, 40, 0, 50), scrape(10, 840, 0, 850),
                           src, 30.0) == 0.0
    # the parent: no such series, nothing read, nothing raised
    old = prom.parse("etcd_engine_rounds_total 50\n")
    assert prom.prom_delta(old, old, src, 30.0) is None
