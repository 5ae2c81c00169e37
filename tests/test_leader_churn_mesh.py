"""Leader-election churn over a groups mesh (mt100k-p7-churn-mesh4, the cell
meshchurn50k.put256-zipf, in the small): the engine of tests/
test_leader_churn.py (c) with its state, its down map and its need-host
surgery sharded over four devices, under writes. A file of its own so that
the suite's workers share the churn tests' minutes."""
import numpy as np
import pytest

import jax

from test_leader_churn import (_crash_image, all_led, make_cfg, qread,
                               queue_values, run_until)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device CPU mesh")
def test_on_a_mesh_a_returning_leader_beyond_the_ring_is_installed(tmp_path):
    """mt100k-p7-churn-mesh4 in the small: seven peers over the 4x1 groups
    mesh, the down map, the state and the need-host surgery sharded, UNDER
    WRITES, one group hot. The hot group's cut-off leader returns beyond its
    successor's 32-entry ring and is snapshot-installed across the shards.
    Round for round the mesh engine equals the one-device engine fed the
    same requests in the same rounds; what was acknowledged equals a plain
    dict and list fed the same operations, every POST applied exactly once;
    crash images taken mid-cut and in the install round restart ON THE MESH
    and serve all that was acknowledged by then; the surgery leaves every
    state field on its pinned sharding."""
    from etcd_tpu.parallel.mesh import make_mesh, state_sharding
    from etcd_tpu.server.engine import MultiEngine
    from etcd_tpu.server.request import Request
    G = 8
    kw = dict(groups=G, peers=7, window=32, churn_down_rounds=60,
              churn_period_rounds=160, churn_seed=5, pipeline_applies=False)
    mesh = make_mesh(jax.devices()[:4], peers_axis=1)
    pinned = state_sharding(mesh)
    engs = [MultiEngine(make_cfg(tmp_path / "mesh", mesh=mesh, **kw)),
            MultiEngine(make_cfg(tmp_path / "one", **kw))]
    model, posted = {}, []      # the plain reference: acked PUTs and POSTs
    open_reqs = []              # (g, key or None, value, [token a engine])
    images = []
    n_sent = 0

    def submit(g, key, val):
        req = (Request(method="PUT", path=key, val=val) if key
               else Request(method="POST", path="/q", val=val))
        open_reqs.append((g, key, val, [
            eng.submit_many(g, [Request(**req.__dict__)])[0]
            for eng in engs]))

    def collect():
        """What both engines acknowledged in the round just run: the same
        requests, or the round differed."""
        for item in list(open_reqs):
            g, key, val, toks = item
            done = [not q.empty() for _, q in toks]
            assert done[0] == done[1], (g, key, val, done)
            if not done[0]:
                continue
            open_reqs.remove(item)
            for eng, tok in zip(engs, toks):
                (res,) = eng.collect_many(g, [tok], timeout=1.0)
                assert res.node.value == val, res
            if key:
                model[(g, key)] = val
            else:
                posted.append(val)

    def lockstep():
        for eng in engs:
            eng.run_round()
        collect()
        for name in ("h_term", "h_commit", "h_last", "h_ring", "h_state",
                     "_down"):
            assert np.array_equal(getattr(engs[0], name),
                                  getattr(engs[1], name)), (
                name, engs[0].round_no)
        assert engs[0].snap_installs == engs[1].snap_installs
        assert engs[0].reproposed == engs[1].reproposed

    try:
        for _ in range(60):
            lockstep()
        assert all_led(engs[0])
        cut_rounds = 0
        for _ in range(1200):
            # one POST a round into the hot group (a few outstanding at
            # most), one PUT every few rounds into each other group
            if sum(1 for r in open_reqs if r[0] == 0) < 6:
                submit(0, None, f"p{n_sent}")
                n_sent += 1
            if engs[0].round_no % 7 == 0:
                g = 1 + (engs[0].round_no // 7) % (G - 1)
                if not any(r[0] == g for r in open_reqs):
                    submit(g, f"/k{engs[0].round_no}", f"v{n_sent}")
                    n_sent += 1
            installs = engs[0].snap_installs
            lockstep()
            cut_rounds = cut_rounds + 1 if engs[0]._down[0].any() else 0
            if cut_rounds == 30 and not images:
                _crash_image(engs[0], tmp_path / "mid_cut")
                images.append(("mid_cut", dict(model), list(posted)))
            if engs[0].snap_installs > installs:
                # the surgery ran across the shards: on the device only,
                # not journalled yet, and every field where it was pinned
                assert engs[0]._force_full and engs[1]._force_full
                for f in engs[0].st._fields:
                    assert getattr(engs[0].st, f).sharding == \
                        getattr(pinned, f), f
                if len(images) == 1 and not engs[0]._down[0].any():
                    _crash_image(engs[0], tmp_path / "install_round")
                    images.append(("install_round", dict(model),
                                   list(posted)))
            if len(images) == 2 and engs[0].round_no % 160 == 0:
                break
        assert len(images) == 2, "no install of group 0's returning leader"
        assert engs[0].snap_installs >= 1 and engs[0].churn_cuts >= G
        while open_reqs:
            lockstep()
        assert len(posted) + len(model) == n_sent
        finals = [queue_values(eng, 0) for eng in engs]
        reads = [{k: qread(eng, k[0], k[1]).node.value for k in model}
                 for eng in engs]
    finally:
        for eng in engs:
            eng.stop()
    assert finals[0] == finals[1] and reads[0] == reads[1] == model
    # exactly once, and nothing that was not sent
    assert sorted(finals[0]) == sorted(posted)
    assert len(set(posted)) == len(posted)
    for name, model_then, posted_then in images:
        eng2 = MultiEngine(make_cfg(tmp_path / name, mesh=mesh, **kw))
        try:
            run_until(eng2, lambda: all_led(eng2), max_rounds=1200,
                      msg=f"{name}: leaders after the crash")
            assert eng2.st.state.sharding == pinned.state
            vals = queue_values(eng2, 0)
            assert len(set(vals)) == len(vals), name
            assert set(posted_then) <= set(vals), (
                name, set(posted_then) - set(vals))
            for (g, key), val in model_then.items():
                assert qread(eng2, g, key).node.value == val, (name, g, key)
        finally:
            eng2.stop()
