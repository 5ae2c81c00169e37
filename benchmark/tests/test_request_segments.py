"""The request-segment metric files (PR 41) read one labelled child of
`etcd_request_segment_seconds` each: two canned scrapes, 20 sampled writes
and 5 sampled quorum reads apart, through prom_delta."""
import json
import os

import prom
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BEFORE = """\
# TYPE etcd_request_segment_seconds histogram
etcd_request_segment_seconds_sum{kind="write",segment="gate"} 0.5
etcd_request_segment_seconds_count{kind="write",segment="gate"} 100
etcd_request_segment_seconds_sum{kind="write",segment="queue"} 1.0
etcd_request_segment_seconds_count{kind="write",segment="queue"} 100
etcd_request_segment_seconds_sum{kind="qread",segment="queue"} 0.25
etcd_request_segment_seconds_count{kind="qread",segment="queue"} 50
etcd_request_rounds_sum{kind="write"} 150.0
etcd_request_rounds_count{kind="write"} 100
etcd_request_rounds_sum{kind="qread"} 0.0
etcd_request_rounds_count{kind="qread"} 0
"""
AFTER = """\
etcd_request_segment_seconds_sum{kind="write",segment="gate"} 0.53
etcd_request_segment_seconds_count{kind="write",segment="gate"} 120
etcd_request_segment_seconds_sum{kind="write",segment="queue"} 1.2
etcd_request_segment_seconds_count{kind="write",segment="queue"} 120
etcd_request_segment_seconds_sum{kind="qread",segment="queue"} 0.26
etcd_request_segment_seconds_count{kind="qread",segment="queue"} 55
etcd_request_rounds_sum{kind="write"} 196.0
etcd_request_rounds_count{kind="write"} 120
etcd_request_rounds_sum{kind="qread"} 0.0
etcd_request_rounds_count{kind="qread"} 0
"""


def source(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)["source"]


@pytest.mark.parametrize("name,want", [
    ("wack_gate_ms", 0.03 / 20 * 1000),      # its own child, not queue's
    ("wack_queue_ms", 0.2 / 20 * 1000),      # the write's, not the read's
    ("qread_queue_ms", 0.01 / 5 * 1000),
    ("wack_rounds", 46.0 / 20),
    ("qread_rounds", None),                  # exported, nothing folded
    ("wack_apply_ms", None),                 # no such child in the scrape
])
def test_segment_files_read_their_own_child(name, want):
    got = prom.prom_delta(prom.parse(BEFORE), prom.parse(AFTER),
                          source(name), window_s=30.0)
    assert got == (want if want is None else pytest.approx(want))
