"""Differential conformance: the C-core NativeStore vs the Python Store.

Random op schedules (seeded) run against both implementations; every
result event, every raised error (code+cause+index), the stats counters,
the full save() snapshot bytes and the expiry stream must agree. This is
the native core's fuzz oracle, on top of the scripted matrix in
test_store.py which runs parametrized over both classes.
"""
import json
import random

import pytest

from etcd_tpu import errors
from etcd_tpu.store.store import Store

native_store = pytest.importorskip("etcd_tpu.store.native_store")
NativeStore = native_store.NativeStore


class Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def ev_sig(e):
    def nd(x):
        if x is None:
            return None
        return (x.key, x.value, x.dir, x.created_index, x.modified_index,
                x.expiration, x.ttl,
                None if x.nodes is None else tuple(nd(c) for c in x.nodes))
    return (e.action, nd(e.node), nd(e.prev_node), e.etcd_index)


def run_op(st, op):
    kind = op[0]
    if kind == "set":
        return st.set(op[1], is_dir=op[2], value=op[3], expire_time=op[4])
    if kind == "create":
        return st.create(op[1], is_dir=op[2], value=op[3], unique=op[4],
                         expire_time=op[5])
    if kind == "update":
        return st.update(op[1], value=op[2], expire_time=op[3],
                         refresh=op[4])
    if kind == "cas":
        return st.compare_and_swap(op[1], op[2], op[3], op[4])
    if kind == "cad":
        return st.compare_and_delete(op[1], op[2], op[3])
    if kind == "delete":
        return st.delete(op[1], is_dir=op[2], recursive=op[3])
    if kind == "get":
        return st.get(op[1], recursive=op[2], want_sorted=op[3])
    if kind == "expire":
        return st.delete_expired_keys(op[1])
    raise AssertionError(kind)


def gen_op(rng, clock):
    segs = ["a", "b", "_hid", "x", "longer-seg"]
    def path():
        return "/" + "/".join(rng.choice(segs)
                              for _ in range(rng.randint(1, 3)))
    k = rng.random()
    exp = clock.t + rng.choice([0.5, 2.0, 10.0]) if rng.random() < 0.3 \
        else None
    if k < 0.30:
        return ("set", path(), rng.random() < 0.15,
                rng.choice(["", "v", "w" * 40]), exp)
    if k < 0.45:
        return ("create", path(), rng.random() < 0.2, "cv",
                rng.random() < 0.2, exp)
    if k < 0.55:
        return ("update", path(), rng.choice([None, "", "u2"]), exp,
                rng.random() < 0.2)
    if k < 0.65:
        return ("cas", path(), rng.choice(["", "v", "nope"]),
                rng.choice([0, 1, 3]), "casv")
    if k < 0.72:
        return ("cad", path(), rng.choice(["", "v", "nope"]),
                rng.choice([0, 1, 3]))
    if k < 0.85:
        return ("delete", path(), rng.random() < 0.5, rng.random() < 0.5)
    if k < 0.95:
        return ("get", path(), rng.random() < 0.5, rng.random() < 0.5)
    return ("expire", clock.t + rng.choice([0.0, 1.0, 5.0]))


@pytest.mark.parametrize("seed", range(8))
def test_differential_random_schedule(seed):
    rng = random.Random(seed)
    clock = Clock()
    py = Store(clock=clock, namespaces=("/0",))
    na = NativeStore(clock=clock, namespaces=("/0",))
    for i in range(400):
        if rng.random() < 0.05:
            clock.t += rng.choice([0.25, 1.0, 3.0])
        op = gen_op(rng, clock)
        rp = rn = ep = en = None
        try:
            rp = run_op(py, op)
        except errors.EtcdError as e:
            ep = (e.code, e.cause, e.index)
        try:
            rn = run_op(na, op)
        except errors.EtcdError as e:
            en = (e.code, e.cause, e.index)
        assert ep == en, f"op {i} {op}: error mismatch {ep} vs {en}"
        if ep is None:
            if op[0] == "expire":
                assert [ev_sig(e) for e in rp] == [ev_sig(e) for e in rn], \
                    f"op {i} {op}"
            else:
                assert ev_sig(rp) == ev_sig(rn), f"op {i} {op}"
        assert py.current_index == na.current_index
    # end state: identical snapshots and counters
    assert py.save() == na.save()
    sp, sn = py.json_stats(), na.json_stats()
    assert sp == sn


def test_differential_recovery_roundtrip():
    rng = random.Random(99)
    clock = Clock()
    py = Store(clock=clock, namespaces=("/0",))
    na = NativeStore(clock=clock, namespaces=("/0",))
    for _ in range(150):
        op = gen_op(rng, clock)
        for st in (py, na):
            try:
                run_op(st, op)
            except errors.EtcdError:
                pass
    blob = py.save()
    na2 = NativeStore(clock=clock, namespaces=("/0",))
    na2.recovery(blob)
    assert na2.save() == blob  # byte-identical roundtrip through C load
    py2 = Store(clock=clock, namespaces=("/0",))
    py2.recovery(na.save())    # python recovers a native snapshot
    assert py2.save() == na.save()
    # clone is deep: mutating the clone leaves the original untouched
    c = na.clone()
    before = na.save()
    c.set("/mut", value="x")
    assert na.save() == before
    assert json.loads(c.save())["currentIndex"] == na.current_index + 1


def test_watch_vs_lazy_apply_race():
    """A watcher registering concurrently with set_applied must never
    lose an event: either registration completes first (the mutation's
    post-op locked count check sees it and notifies) or the watcher's
    history scan replays the already-recorded ring event. An unlocked
    pre-mutation count check had a window that dropped events forever
    (code-review finding, round 4)."""
    import threading

    st = NativeStore()
    stop = threading.Event()
    idx_hint = [0]

    def writer():
        while not stop.is_set():
            e = st.set_applied("/race/k", "v", None, False)
            if e is not None:
                idx_hint[0] = e.index

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        misses = 0
        for _ in range(300):
            since = st.current_index + 1
            w = st.watch("/race/k", since_index=since)
            e = w.next_event(timeout=2.0)
            if e is None:
                misses += 1
            else:
                assert e.index >= since
            w.remove()
        assert misses == 0, f"{misses}/300 watchers lost their event"
    finally:
        stop.set()
        t.join(timeout=5)


def test_watch_parity_through_native():
    clock = Clock()
    for cls in (Store, NativeStore):
        st = cls(clock=clock)
        w = st.watch("/w", recursive=True, stream=True)
        st.set("/w/a", value="1")
        st.delete("/w/a")
        st.set("/w/_h", value="hidden")     # hidden: invisible to recursive
        st.set("/w/b", value="2", expire_time=clock.t + 1)
        st.delete_expired_keys(clock.t + 2)
        acts = []
        while True:
            e = w.next_event(timeout=0.05)
            if e is None:
                break
            acts.append((e.action, e.node.key))
        assert acts == [("set", "/w/a"), ("delete", "/w/a"),
                        ("set", "/w/b"), ("expire", "/w/b")], (cls, acts)
        # history scan: a new watcher with since sees the old event
        w2 = st.watch("/w/a", since_index=1)
        e = w2.next_event(timeout=0.05)
        assert e is not None and e.action == "set" and e.node.key == "/w/a"


def test_set_many_inline_canonical_predicate_matches_norm():
    """set_applied_many's inline canonical-path fast check must accept a
    path ONLY when _norm would return it unchanged — exhaustively over
    every string up to length 6 from a hostile alphabet (slash, dot,
    letter). A path the inline check wrongly passes through would reach
    the C core un-canonicalized and create unreachable keys."""
    import itertools

    from etcd_tpu.store.native_store import _norm

    def inline_ok(p):
        return (p and p[0] == "/" and p[-1] != "/" and "//" not in p
                and "." not in p)

    alphabet = "/a."
    for n in range(0, 7):
        for tup in itertools.product(alphabet, repeat=n):
            p = "".join(tup)
            if inline_ok(p):
                assert _norm(p) == p, p


def test_set_applied_many_need_returns_descriptors():
    """With `need`, set_applied_many returns (applied, descs): one desc
    per listed position — (pos, nd, pd|None, index) for an applied op,
    (pos, None, (code, cause), index_at_failure) for a per-op etcd
    failure — aligned with the scalar path's error parity."""
    st = NativeStore(clock=Clock(), namespaces=("/0", "/1"))
    st.set_applied_many(["/1/pre"], ["old"])
    applied, descs = st.set_applied_many(
        ["/1/a", "/", "/1/pre", "/1/b"],
        ["1", "x", "new", "2"], need=[0, 1, 2])
    assert applied == 3
    assert len(descs) == 3
    pos, nd, pd, idx = descs[0]
    assert (pos, pd) == (0, None) and nd[0] == "/1/a" and nd[1] == "1"
    assert idx == 2 and nd[4] == 2          # modified index
    pos, nd, fail, idx = descs[1]           # root PUT: 107, cause "/"
    assert pos == 1 and nd is None
    assert fail == (errors.ECODE_ROOT_RONLY, "/")
    pos, nd, pd, idx = descs[2]             # overwrite carries prev desc
    assert pos == 2 and nd[1] == "new" and pd[1] == "old"
    # need=None keeps the int contract
    assert st.set_applied_many(["/1/c"], ["3"]) == 1


def test_set_applied_lazy_defers_event_materialization(monkeypatch):
    """With no watcher live, set_applied_lazy must not construct any
    Event/NodeExtern at apply time — the waiter's LazyWriteEvent resolves
    them later on the consuming thread. With a watcher live, the Event is
    built eagerly (the fan-out needs it) and returned directly."""
    from etcd_tpu.store import event as ev_mod
    from etcd_tpu.store.event import LazyWriteEvent

    st = NativeStore(clock=Clock(), namespaces=("/0", "/1"))
    st.set_applied_lazy("/1/k", "v0", None)

    def boom(*a, **kw):
        raise AssertionError("Event materialized on the apply hot path")

    monkeypatch.setattr(native_store, "Event", boom)
    monkeypatch.setattr(native_store, "_extern", boom)
    r = st.set_applied_lazy("/1/k", "v1", None)
    monkeypatch.undo()

    assert isinstance(r, LazyWriteEvent)
    e = r.resolve()
    assert e.action == ev_mod.SET
    assert e.node.key == "/1/k" and e.node.value == "v1"
    assert e.prev_node.value == "v0"
    assert e.etcd_index == 2 and e.node.modified_index == 2
    # C history recorded the lazy write: a since-scan replays it
    replay = st.watcher_hub.event_history.scan("/1/k", False, 2)
    assert replay is not None and replay.node.value == "v1"

    # live watcher: falls back to an eager Event + notify
    w = st.watch("/1", recursive=True, stream=True)
    r2 = st.set_applied_lazy("/1/k", "v2", None)
    assert not isinstance(r2, LazyWriteEvent)
    got = w.next_event(timeout=1.0)
    assert got is not None and got.node.value == "v2"


def test_history_wraparound_since_before_window_differential():
    """Ring-wraparound scan with `since` OLDER than the retained window:
    both histories must raise 401 EventIndexCleared (reference
    event_history.go:58-105) — the C facade used to silently return the
    oldest retained event instead, masking the evicted span from a
    watcher resuming with a stale waitIndex. In-window scans must agree
    event-for-event across the wrap."""
    cap = 8
    py = Store(cap, Clock())
    na = NativeStore(cap, Clock())
    n = cap * 3  # wrap the ring twice over
    for st in (py, na):
        for i in range(n):
            st.set(f"/w/k{i % 4}", value=str(i))

    for st, name in ((py, "python"), (na, "native")):
        h = st.watcher_hub.event_history
        assert h.last_index == n, name
        assert h.start_index == n - cap + 1, name
        with pytest.raises(errors.EtcdError) as ei:
            h.scan("/w/k0", False, h.start_index - 1)
        assert ei.value.code == errors.ECODE_EVENT_INDEX_CLEARED, name
        assert ei.value.index == h.last_index, name
        # The user-visible surface: a watch resuming at the stale index
        # gets the same 401 instead of a silently-skipped span.
        with pytest.raises(errors.EtcdError) as ei:
            st.watch("/w/k0", since_index=h.start_index - 1)
        assert ei.value.code == errors.ECODE_EVENT_INDEX_CLEARED, name

    # In-window differential: every retained since-index returns the
    # same event (or the same absence) from both rings.
    hp = py.watcher_hub.event_history
    hn = na.watcher_hub.event_history
    for key, recursive in (("/w/k1", False), ("/w", True)):
        for since in range(hp.start_index, hp.last_index + 2):
            ep = hp.scan(key, recursive, since)
            en = hn.scan(key, recursive, since)
            assert (ep is None) == (en is None), (key, since)
            if ep is not None:
                assert ev_sig(ep) == ev_sig(en), (key, since)


# -- one batch over many cores (storecore.set_many_multi) --------------------


def _multi_case(seed, shape, need_mode):
    """K cores' slices of one flat batch: PUTs over a few files, a PUT
    onto a directory (102), a read-only path (107) and a path through a
    file (104) mixed in by the seed."""
    rng = random.Random(seed * 7919 + 13)
    if shape == "ones":
        counts = [1] * 9
    elif shape == "zeros":
        counts = [rng.choice([0, 0, 1, 3]) for _ in range(9)]
        counts[0] = counts[-1] = 0
    else:                       # "many": deep slices, past the ring
        counts = [rng.randint(0, 40) for _ in range(6)]
    paths, vals = [], []
    for k, n in enumerate(counts):
        for j in range(n):
            roll = rng.random()
            if roll < 0.08:
                p = "/1/d"              # a directory (seeded below)
            elif roll < 0.14:
                p = "/0"                # read-only
            elif roll < 0.20:
                p = "/1/f0/x"           # through a file, once f0 exists
            else:
                p = f"/1/f{rng.randint(0, 4)}"
            paths.append(p)
            vals.append(f"v{seed}_{k}_{j}")
    n = len(paths)
    need = {"none": None, "empty": [],
            "partial": sorted(rng.sample(range(n), n // 2)),
            "full": list(range(n))}[need_mode]
    return counts, paths, vals, need


def _seeded_store(clock):
    st = NativeStore(history_capacity=16, clock=clock,
                     namespaces=("/0", "/1"))
    st.set("/1/d", is_dir=True)
    st.set("/1/f0", value="seed")
    return st


def _store_sig(st):
    hist = st.watcher_hub.event_history
    ring, i = [], hist.start_index
    while i <= hist.last_index:
        e = hist.scan("/", True, i)
        if e is None:
            break
        ring.append(ev_sig(e))
        i = e.etcd_index + 1
    return (st.current_index, st.json_stats(), st.save(), ring,
            (hist.start_index, hist.last_index, len(hist)))


@pytest.mark.parametrize("need_mode", ["none", "empty", "partial", "full"])
@pytest.mark.parametrize("shape", ["ones", "zeros", "many"])
@pytest.mark.parametrize("seed", range(4))
def test_set_many_multi_equals_a_set_many_per_core(seed, shape, need_mode):
    """set_many_multi over K cores gives the indices, descriptors, per-op
    errors, stats and history ring of K set_many calls on the same
    slices."""
    from etcd_tpu.native.storecore import set_many_multi

    counts, paths, vals, need = _multi_case(seed, shape, need_mode)
    clock = Clock()
    one = [_seeded_store(clock) for _ in counts]
    per = [_seeded_store(clock) for _ in counts]

    done, descs, spans = set_many_multi(
        [st._core for st in one], counts, paths, vals, clock(), need)
    assert done == len(paths)
    assert len(spans) == len(counts)

    want_descs = None if need is None else []
    off = 0
    for st, n, span in zip(per, counts, spans):
        mine = None if need is None else [p - off for p in need
                                          if off <= p < off + n]
        first, last, failed, recs, d = st._core.set_many(
            paths[off:off + n], vals[off:off + n], clock(), False, mine)
        assert recs is None
        assert span == (first, last)
        if need is not None:
            want_descs += [(x[0] + off,) + tuple(x[1:]) for x in d]
        off += n
    assert descs == want_descs
    for a, b in zip(one, per):
        assert _store_sig(a) == _store_sig(b)
    if need_mode == "full":
        codes = {d[2][0] for d in descs if d[1] is None}
        assert codes <= {errors.ECODE_NOT_FILE, errors.ECODE_ROOT_RONLY,
                         errors.ECODE_NOT_DIR}


def test_set_many_multi_refuses_what_it_cannot_slice():
    from etcd_tpu.native.storecore import set_many_multi

    a, b = (_seeded_store(Clock())._core for _ in range(2))
    with pytest.raises(ValueError, match="twice"):
        set_many_multi([a, a], [1, 1], ["/1/x", "/1/y"], ["1", "2"], 1.0)
    with pytest.raises(ValueError, match="counts"):
        set_many_multi([a, b], [1, 2], ["/1/x", "/1/y"], ["1", "2"], 1.0)
    with pytest.raises(ValueError, match="counts"):
        set_many_multi([a, b], [1, 0], ["/1/x", "/1/y"], ["1", "2"], 1.0)
    with pytest.raises(TypeError):
        set_many_multi([a, object()], [1, 1], ["/1/x", "/1/y"],
                       ["1", "2"], 1.0)
    with pytest.raises(IndexError):
        set_many_multi([a, b], [1, 1], ["/1/x", "/1/y"], ["1", "2"], 1.0,
                       [2])
    # nothing above touched a core, and every mutex was given back
    assert a.index == b.index == 2
    assert set_many_multi([a, b], [1, 1], ["/1/x", "/1/y"], ["1", "2"],
                          1.0) == (2, None, [(3, 3), (3, 3)])


def test_set_applied_view_notifies_a_watcher_that_raced_the_batch(
        monkeypatch):
    """A watcher that registers between the caller's quiet check and the
    native call sees the batch's events for its tenant, in order, from
    the ring; the other tenants' stores stay silent."""
    clock = Clock()
    stores = [_seeded_store(clock) for _ in range(3)]
    real = native_store.set_many_multi
    raced = []

    def racing(cores, counts, paths, vals, now, need=None):
        raced.append(stores[1].watch("/", recursive=True, stream=True,
                                     since_index=0))
        return real(cores, counts, paths, vals, now, need)

    monkeypatch.setattr(native_store, "set_many_multi", racing)
    done, descs, now = native_store.set_applied_view(
        stores, [1, 3, 1],
        ["/1/a", "/1/b", "/1/d", "/1/b", "/1/c"], ["1", "2", "x", "3", "4"],
        [1, 2])
    assert done == 5 and now == clock()
    assert [d[0] for d in descs] == [1, 2]
    assert descs[1][1] is None and descs[1][2][0] == errors.ECODE_NOT_FILE
    got = []
    while (e := raced[0].next_event(timeout=0.05)) is not None:
        got.append((e.action, e.node.key, e.node.value, e.etcd_index))
    assert got == [("set", "/1/b", "2", 3), ("set", "/1/b", "3", 4)]
