"""The comparison that decides `correct` can fail: a lost acknowledged write,
a stale quorum read and a cross-tenant leak each turn it false; a clean
history passes."""
import checker
import pytest


def history():
    ref = checker.Reference()
    for t, k, v in [(3, "/c0/k1", "a1"), (3, "/c0/k1", "a2"),
                    (7, "/c1/k0", "b1")]:
        ref.sent(t, k, v)
        ref.acked(t, k, v)
    ref.sent(7, "/c1/k0", "b2")          # sent, never answered
    return ref


def numbers(ref, answers, probe=None):
    bad = checker.compare_reads(ref, answers)
    leaked = sum(1 for t, k, _ in bad if (t, k) == probe)
    return {"readback_mismatches": len(bad) - leaked,
            "cross_tenant_leaks": leaked}


CLEAN = {(3, "/c0/k1"): "a2", (7, "/c1/k0"): "b1", (4, "/c0/k1"): None}


@pytest.mark.parametrize("name,answers,correct", [
    ("clean", CLEAN, True),
    ("unacked_write_applied", {**CLEAN, (7, "/c1/k0"): "b2"}, True),
    ("lost_acked_write", {**CLEAN, (3, "/c0/k1"): None}, False),
    ("stale_quorum_read", {**CLEAN, (3, "/c0/k1"): "a1"}, False),
    ("cross_tenant_leak", {**CLEAN, (4, "/c0/k1"): "a2"}, False),
])
def test_verdict(name, answers, correct):
    ref = history()
    probe = checker.isolation_probe(ref, [(3, "/c0/k1")], groups=8)
    assert probe == (4, "/c0/k1")
    ok, lines = checker.verdict(numbers(ref, answers, probe))
    assert ok is correct
    assert all(line["limit"] == 0 for line in lines)
    if name == "cross_tenant_leak":
        assert lines[1] == {"check": "cross_tenant_leaks", "value": 1,
                            "limit": 0}


def test_values_are_seeded_and_sized():
    a = checker.value_for(2**31 + 5, 3, 9, 256)
    assert a == checker.value_for(2**31 + 5, 3, 9, 256) and len(a) == 256
    assert a != checker.value_for(2**31 + 6, 3, 9, 256)
    assert a != checker.value_for(2**31 + 5, 3, 10, 256)


def test_sample_is_seeded_and_only_acked_keys():
    ref = history()
    ref.sent(9, "/c2/k0", "never-acked")
    assert checker.sample_keys(ref, 1, 10) == [(3, "/c0/k1"), (7, "/c1/k0")]
    assert checker.sample_keys(ref, 5, 1) == checker.sample_keys(ref, 5, 1)


def test_two_writers_of_one_key_are_refused():
    ref = history()
    with pytest.raises(ValueError):
        ref.merge({(3, "/c0/k1"): ["x", []]})
    rows = ref.dump()
    assert checker.Reference.load(rows) == ref.model
