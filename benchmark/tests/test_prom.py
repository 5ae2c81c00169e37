"""prom_delta on two /metrics texts recorded from a CPU member (G=8, two WAL
shards, bucket lines dropped): 24 writes and 5 quorum reads apart."""
import os

import prom
import pytest

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def scrapes():
    out = []
    for name in ("metrics_before.txt", "metrics_after.txt"):
        with open(os.path.join(DATA, name)) as f:
            out.append(prom.parse(f.read()))
    return out


ROUNDS = {"series": "etcd_engine_rounds_total"}


@pytest.mark.parametrize("source,want", [
    ({"num": {"series": "etcd_engine_acked_requests_total"}}, 24.0),
    ({"num": {"series": "etcd_engine_acked_requests_total"}, "den": ROUNDS},
     24.0 / 89.0),
    ({"num": {"window_seconds": True}, "den": ROUNDS, "scale": 1000},
     10.0 / 89.0 * 1000),
    # both shards summed
    ({"num": {"series": "etcd_wal_writer_fsync_seconds_sum"},
      "den": {"series": "etcd_wal_writer_fsync_seconds_count"},
      "scale": 1000}, 0.037360907 / 96 * 1000),
    ({"num": {"series": "etcd_wal_writer_fsync_seconds_count",
              "labels": {"shard": "1"}}}, 48.0),
    ({"num": {"series": "etcd_engine_round_phase_seconds_sum",
              "labels": {"phase": "stage"}}, "den": ROUNDS, "scale": 1000},
     (0.007241397998313914 - 0.0048687329983749805) / 89 * 1000),
    ({"num": {"series": "etcd_read_index_reads_total"}, "den": ROUNDS},
     5.0 / 89.0),
])
def test_prom_delta(scrapes, source, want):
    got = prom.prom_delta(scrapes[0], scrapes[1], source, window_s=10.0)
    assert got == pytest.approx(want, rel=1e-6)


def test_nothing_to_read_is_none(scrapes):
    before, after = scrapes
    assert prom.prom_delta(before, after,
                           {"num": {"series": "no_such_series"}}, 10) is None
    # nothing counted in the window: no ratio
    assert prom.prom_delta(after, after,
                           {"num": {"window_seconds": True}, "den": ROUNDS},
                           10) is None


def test_parse_labels_and_skips_comments():
    s = prom.parse('# HELP x y\nx_total{a="1",b="q\\"z"} 3\nbad line here\n'
                   'y NaN\n')
    assert s[("x_total", (("a", "1"), ("b", 'q\\"z')))] == 3.0
    assert prom.total(s, "x_total", {"a": "1"}) == 3.0
    assert prom.total(s, "x_total", {"a": "2"}) is None
