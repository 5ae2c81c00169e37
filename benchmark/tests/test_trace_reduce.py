"""The reduction from trace events to busy time, idle gaps and their labels:
on a synthetic event list, and on one small recorded CPU trace."""
import os

import pytest
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
US = 1000


def test_union_merges_overlaps_and_drops_empty():
    iv = [(0, 10), (5, 20), (30, 40), (40, 45), (50, 50), (12, 13)]
    assert tr.union(iv) == [(0, 20), (30, 45)]
    assert tr.length(tr.union(iv)) == 35
    assert tr.gaps_between(tr.union(iv)) == [(20, 30)]


def synthetic():
    # two device steps of 100 us (three ops each, two overlapping), a
    # gather of 20 us, on one device; 1 ms apart
    ops, mods = [], []
    for base in (0, 1000 * US):
        ops += [("fusion.1", base, base + 40 * US),
                ("fusion.2", base + 30 * US, base + 70 * US),
                ("copy.3", base + 80 * US, base + 100 * US)]
        mods.append(("jit_step_routed_compact(123)", base, base + 100 * US))
    ops.append(("gather.9", 500 * US, 520 * US))
    mods.append(("jit_gather_rows(7)", 500 * US, 520 * US))
    host = [("PjitFunction(step_routed_compact)", 90 * US, 480 * US),
            ("TransferFromDevice", 100 * US, 300 * US),
            ("np.asarray(jax.Array)", 530 * US, 900 * US)]
    return [{"ops": ops, "modules": mods}], host


def test_reduce_synthetic():
    planes, host = synthetic()
    red = tr.reduce_events(planes, host, patterns=["step_routed", "gather"])
    # busy: 2 x (70 + 20) us + 20 us
    assert red["busy_s"] == pytest.approx(200e-6)
    assert red["device_span_s"] == pytest.approx(1100e-6)
    assert red["module_events"] == {"step_routed": 2, "gather": 1}
    assert red["module_mean_ms"] == {"step_routed": pytest.approx(0.1),
                                     "gather": pytest.approx(0.02)}
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(80e-6)]
    assert red["modules"][0] == ["jit_step_routed_compact(123)",
                                 pytest.approx(200e-6)]
    gaps = dict(red["idle_gaps"])
    # 100-500 us: PjitFunction overlaps 380 us, the transfer 200 us
    assert gaps["host:PjitFunction(step_routed_compact)"] == pytest.approx(
        400e-6)
    assert gaps["host:np.asarray(jax.Array)"] == pytest.approx(480e-6)
    # the 10 us gap inside each step: no host event overlaps it
    assert gaps["host:unattributed"] == pytest.approx(20e-6)
    idle = red["device_span_s"] - red["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle)


def test_hlo_text_names_are_cut_to_the_op():
    ev = [("%cond.62 = (s32[12500,5]{0,1:T(8,128)}, ...) conditional(...)",
           0, 10), ("fusion.7", 0, 5)]
    assert tr.top_by_name(ev) == [["cond.62", 1e-8], ["fusion.7", 5e-9]]


def test_short_gaps_are_summed_unlabelled():
    gaps = [(0, 100), (200, 210), (300, 305)]
    out = dict(tr.label_gaps(gaps, [("h", 0, 1000)], labelled=1))
    assert out == {"host:h": pytest.approx(100e-9),
                   "gaps:short": pytest.approx(15e-9)}


def test_a_program_the_chip_did_not_run_is_an_error():
    """A renamed, split or fused step must not turn the metric into another
    quantity: on a TPU trace a pattern without a match fails the run."""
    planes, host = synthetic()
    with pytest.raises(tr.NoSuchModule, match="jit_gather_rows"):
        tr.reduce_events(planes, host, patterns=["step_fused"])


def test_the_rehearsal_divides_busy_time_by_rounds():
    planes, host = synthetic()
    planes[0]["modules"] = []
    red = tr.reduce_events(planes, host, rounds=4, source="cpu-hlo",
                           patterns=["step_routed"])
    assert red["module_mean_ms"] == {"step_routed": pytest.approx(0.05)}
    assert tr.reduce_events(planes, host, source="cpu-hlo",
                            patterns=["step_routed"])["module_mean_ms"] == {}


def test_recorded_cpu_trace():
    """Five runs of a small jitted matmul, recorded on the CPU (no device
    plane: the hlo_op events stand in, source cpu-hlo)."""
    pytest.importorskip("jax")
    path = tr.find_xplane(os.path.join(DATA, "cpu_trace"))
    planes, host, source, layout = tr.read_xplane(path)
    assert source == "cpu-hlo" and len(planes) == 1
    assert "/host:CPU" in layout
    red = tr.reduce_events(planes, host, source=source)
    assert red["op_events"] == 15                 # 3 HLO ops x 5 runs
    assert red["device_ops"][0][0] == "dot_general.1"
    assert 0 < red["busy_s"] < red["device_span_s"]
    assert red["idle_gaps"][0][0] == "host:PjitFunction(<lambda>)"
