"""What the device step ran on each hop, as the engine counts it
(etcd_engine_step_hops_total{path}, etcd_engine_step_passes_total): the
counters add up (every hop of every round is counted under one path), a
quiet member counts `quiet` alone and no pass once its leaders stand, a
member under leader-election churn takes the full path on most hops and
makes far fewer passes there than one a peer slot, with the one blocking
read a round it had before (the counts ride gather_rows' header row), a
member with compact readback off reads them with the full readback's tuple,
and a mesh member counts too: its full hops make the passes by sender, one
a peer slot.
"""
import jax
import pytest

from etcd_tpu.server import engine as engine_mod
from etcd_tpu.server import obs


def _counts():
    """({path: hops}, rounds, blocking reads, passes) counted so far."""
    return ({labels["path"]: v for _, labels, v in obs.step_hops.samples()},
            obs.rounds_total.value, obs.d2h_syncs.value,
            obs.step_passes.value)


def _moved(a, b):
    return ({p: b[0][p] - a[0][p] for p in obs.STEP_PATHS},
            *(y - x for x, y in zip(a[1:], b[1:])))


def _engine(tmp_path, **kw):
    cfg = dict(groups=64, peers=3, data_dir=str(tmp_path), window=8,
               max_ents=2, heartbeat_tick=3, fsync=False, sync_interval=0.0,
               checkpoint_rounds=1 << 30, mask_check_rounds=0,
               request_timeout=60.0)
    cfg.update(kw)
    eng = engine_mod.MultiEngine(engine_mod.EngineConfig(**cfg))
    if not eng.obs.enabled:
        eng.stop()
        pytest.skip("ETCD_TPU_OBS=off")
    return eng


def _run(eng, rounds):
    a = _counts()
    for _ in range(rounds):
        eng.run_round()
    return _moved(a, _counts())


def _boot(eng):
    for _ in range(400):
        if eng._all_led():
            return
        eng.run_round()
    raise AssertionError("no leaders")


def test_a_churn_member_takes_the_full_path_and_makes_few_passes(tmp_path):
    eng = _engine(tmp_path, churn_down_rounds=8, churn_period_rounds=32,
                  churn_seed=40)
    try:
        assert eng.cfg.hops == 3
        a = _counts()
        _boot(eng)
        paths, rounds, _, passes = _moved(a, _counts())
        assert sum(paths.values()) == 3 * rounds
        assert paths["full"] >= 1       # every group elected once
        assert passes <= paths["full"]  # the ballots and tallies need none
        for _ in range(60):             # into the schedule's steady state
            eng.run_round()
        paths, rounds, syncs, passes = _run(eng, 200)
        assert rounds == 200 and sum(paths.values()) == 3 * 200
        # two cuts begin a round: some group is always electing
        assert paths["full"] > 0.5 * 3 * 200, paths
        # by rank: at most one pass a full hop here (each follower's
        # append; ballots and tallies need none), where the passes by
        # sender made three
        assert 0 < passes <= paths["full"], (paths, passes)
        assert eng.churn_cuts > 200 * 64 // 32 // 2
        # no read of its own: one packed buffer a round, as before
        assert syncs == 200
    finally:
        eng.stop()


def test_a_quiet_member_counts_quiet_hops_alone(tmp_path):
    eng = _engine(tmp_path, groups=8)
    try:
        _boot(eng)
        for _ in range(40):
            eng.run_round()
        paths, rounds, syncs, passes = _run(eng, 30)
        assert paths == {"quiet": 90, "full": 0} and passes == 0
        assert (rounds, syncs) == (30, 30)
    finally:
        eng.stop()


def test_with_compact_readback_off_the_full_readback_carries_the_counts(
        tmp_path):
    eng = _engine(tmp_path, groups=8, compact_readback=False)
    try:
        paths, rounds, syncs, passes = _run(eng, 12)
        assert sum(paths.values()) == 3 * 12 and paths["full"] >= 1
        assert passes <= 36 and (rounds, syncs) == (12, 12)
    finally:
        eng.stop()


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs four of the CPU mesh's devices")
def test_a_mesh_member_counts_quiet_and_full_hops(tmp_path):
    from etcd_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(jax.devices()[:4], peers_axis=1)
    eng = _engine(tmp_path, mesh=mesh)
    try:
        a = _counts()
        _boot(eng)
        for _ in range(20):
            eng.run_round()
        paths, rounds, _, passes = _moved(a, _counts())
        assert sum(paths.values()) == 3 * rounds
        assert paths["full"] >= 1 and paths["quiet"] >= 30
        assert passes == 3 * paths["full"]      # by sender: one a peer slot
    finally:
        eng.stop()
