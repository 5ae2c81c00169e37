"""Sharded-vs-unsharded kernel equivalence: the SAME randomized schedule
stepped (a) on single-device arrays and (b) through the ENGINE's exact
compiled program — jit(step_routed_auto, hops=cfg.hops) with a traced
drop mask and pinned (state, mailbox) out_shardings over the 8-device
mesh (engine.py builds the identical partial) — must produce
bit-identical state every round. Any divergence means the mesh layout,
the pinned-sharding constraints, the quiet-path cond, or the per-hop
routing collective changed semantics, not just placement.

Complements tests/test_equivalence.py (kernel vs scalar oracle) and
tests/test_multihost.py (multi-process execution); this one pins the
single-process sharded serving path (tests/test_engine_sharded.py runs
it end-to-end; here it is compared array-for-array against reference).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.ops import kernel
from etcd_tpu.ops.state import GroupState, KernelConfig, init_state
from etcd_tpu.parallel.mesh import (mailbox_sharding, make_mesh,
                                    replicated_sharding, shard_state,
                                    state_sharding)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")


@pytest.mark.parametrize("peers_axis", [1, 2], ids=["groups8", "g4xp2"])
def test_sharded_step_routed_is_bit_identical(peers_axis):
    G, P, W, E = 8, 4, 16, 3
    HOPS = 3   # EngineConfig.hops default
    cfg = KernelConfig(groups=G, peers=P, window=W, max_ents=E)
    mesh = make_mesh(jax.devices()[:8], peers_axis=peers_axis)
    mb = mailbox_sharding(mesh)
    # The engine's serving program, byte for byte (engine.py __init__):
    # auto kernel, cfg.hops, drop mask traced in and cut per hop.
    step_sh = jax.jit(
        functools.partial(kernel.step_routed_auto.__wrapped__, cfg,
                          hops=HOPS, by_sender=True),
        donate_argnums=(0, 1),
        out_shardings=(state_sharding(mesh), mb, replicated_sharding(mesh)))

    st_ref = init_state(cfg, stagger=True)
    st_sh = shard_state(init_state(cfg, stagger=True), mesh)
    inbox_ref = jnp.zeros((G, P, P, cfg.fields), jnp.int32)
    inbox_sh = jax.device_put(inbox_ref, mb)

    rng = np.random.RandomState(9)
    for i in range(60):
        pc = jnp.asarray(rng.randint(0, E + 1, G).astype(np.int32))
        ps = jnp.asarray(rng.randint(0, P, G).astype(np.int32))
        # Random drops, cut after every hop on both sides — the engine's
        # fault-injection point rides INTO the kernel.
        drop = jnp.asarray(
            1 - (rng.rand(G, P, P) < 0.25)[..., None].astype(np.int32))

        st_ref, inbox_ref, _ = kernel.step_routed_auto(
            cfg, st_ref, inbox_ref, pc, ps, jnp.asarray(True), drop, HOPS)
        st_sh, inbox_sh, _ = step_sh(st_sh, inbox_sh, pc, ps,
                                     jnp.asarray(True), drop)

        for name in GroupState._fields:
            a = np.asarray(getattr(st_ref, name))
            b = np.asarray(getattr(st_sh, name))
            assert (a == b).all(), f"round {i}: field {name} diverged"
        a, b = np.asarray(inbox_ref), np.asarray(inbox_sh)
        assert (a == b).all(), f"round {i}: routed inbox diverged"

    assert np.asarray(st_ref.commit).max() > 0
