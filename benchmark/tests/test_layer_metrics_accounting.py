"""The thirteen accounting metrics of PR 24, each through its own
layer_metrics file, on two /metrics texts recorded from a CPU member that
has the new series (G=8, P=3, two WAL shards, bucket lines dropped; 10 s,
3,336 rounds, 24 writes and 5 quorum reads apart, one checkpoint inside).
On the scrapes recorded before the series existed (the parent's program)
every one of them reads nothing and raises nothing."""
import glob
import json
import os

import prom
import pytest
import run

DATA = os.path.join(os.path.dirname(__file__), "data")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW_S = 10.0
ROUNDS = 3480.0 - 144.0
REQUESTS = (18 - 17) + (7 - 2) + (26 - 2)

# name -> value worked out by hand from the two texts
WANT = {
    "gap_ms": (4.156929568000351 - 0.17071179799950187) / ROUNDS * 1e3,
    "round_cpu_ms": (
        (0.1407356579999127 - 0.0057047460000037905)
        + (15.76218644599998 - 12.987762003999999)
        + (0.5078760450000823 - 0.022747242999996864)
        + (0.3067111559999347 - 0.07052357699999945)
        + (0.7360185140000599 - 0.028895301999995127)
        + (0.049401267999989784 - 0.0022995130000023067)
        + (0.275098487000041 - 0.011379585000002912)) / ROUNDS * 1e3,
    "record_cpu_ms":
        (0.3067111559999347 - 0.07052357699999945) / ROUNDS * 1e3,
    "record_gather_ms":
        (0.09642843000051471 - 0.07909570199990412) / ROUNDS * 1e3,
    "record_admit_ms":
        (0.00038922199996704876 - 5.468600011226954e-05) / ROUNDS * 1e3,
    "d2h_syncs_per_round": (7237.0 - 322.0) / ROUNDS,
    "d2h_kb_per_round": (1477905.0 - 200414.0) / ROUNDS / 1e3,
    "pending_wait_ms":
        (2.5725730700005442 - 2.5259127880001415) / (26 - 2) * 1e3,
    "front_self_ms": (
        (0.030481182000130502 - 0.02618685200013715)
        + (0.0037633310005276144 - 0.0013773470002433896)
        + (0.03506125799913207 - 0.014766131999749632)) / REQUESTS * 1e3,
    "front_cpu_us_per_op":
        (26.273566315999997 - 24.18675933) / REQUESTS * 1e6,
    "member_cpu_cores": (44.538903045 - 37.403014048) / WINDOW_S,
    "checkpoint_s": 0.002365905000033308,
    "compiles_in_window": 0.0,
}
WRITES_ONLY = {"record_admit_ms", "pending_wait_ms"}


def scrapes(*names):
    out = []
    for name in names:
        with open(os.path.join(DATA, name)) as f:
            out.append(prom.parse(f.read()))
    return out


def specs():
    out = {}
    for path in glob.glob(os.path.join(BENCH, "layer_metrics", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if spec["name"] in WANT:
            out[spec["name"]] = spec
    return out


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_new_metric_reads_its_series(name):
    before, after = scrapes("metrics_acct_before.txt",
                            "metrics_acct_after.txt")
    spec = specs()[name]
    assert spec["source"]["reader"] == "prom_delta"
    got = prom.prom_delta(before, after, spec["source"], WINDOW_S)
    assert got == pytest.approx(WANT[name], rel=1e-9, abs=1e-12)


def test_the_runner_reports_them_where_their_cells_apply():
    before, after = scrapes("metrics_acct_before.txt",
                            "metrics_acct_after.txt")
    ctx = {"prom0": before, "prom1": after, "window_s": WINDOW_S,
           "client": {}, "trace": {}}
    for workload, wants in (
            ("share12k5.put256-c256", set(WANT)),
            ("mt1k.put256-c256", set(WANT)),
            ("share12k5.qget-c256", set(WANT) - WRITES_ONLY)):
        _, _, mix = run.load_cell(workload)
        got = run.read_layer_metrics(workload, mix, ctx)
        assert wants <= set(got), workload
        assert not (set(WANT) - wants) & set(got), workload
        for name in wants:
            assert got[name]["unit"] == specs()[name]["unit"]
    # the phases the benchmark already had plus gap and post add up to the
    # round (what PERF.md's section 5 checks on the chip; 2 %)
    _, _, mix = run.load_cell("share12k5.put256-c256")
    got = run.read_layer_metrics("share12k5.put256-c256", mix, ctx)
    post = prom.prom_delta(before, after, {
        "num": {"series": "etcd_engine_round_phase_seconds_sum",
                "labels": {"phase": "post"}},
        "den": {"series": "etcd_engine_rounds_total"}, "scale": 1000},
        WINDOW_S)
    parts = sum(got[k]["value"] for k in (
        "stage_ms", "dispatch_ms", "readback_ms", "record_ms", "tail_ms",
        "gap_ms")) + post
    assert parts == pytest.approx(got["round_ms"]["value"], rel=0.02)


def test_on_the_parents_scrapes_they_read_nothing_and_do_not_raise():
    before, after = scrapes("metrics_before.txt", "metrics_after.txt")
    for name, spec in specs().items():
        assert prom.prom_delta(before, after, spec["source"],
                               WINDOW_S) is None, name
    _, _, mix = run.load_cell("share12k5.put256-c256")
    got = run.read_layer_metrics("share12k5.put256-c256", mix, {
        "prom0": before, "prom1": after, "window_s": WINDOW_S,
        "client": {}, "trace": {}})
    assert not set(WANT) & set(got)
    assert "round_ms" in got
