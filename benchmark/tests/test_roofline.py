"""roofline.py's byte count for (G, P, W, max_ents) equals the summed nbytes
of the kernel's real state and inbox (imports JAX, on the CPU)."""
import pytest
import roofline


@pytest.mark.parametrize("G,P,W,E", [(8, 5, 32, 8), (8, 3, 32, 8),
                                     (16, 5, 16, 4)])
def test_bytes_match_the_kernels_arrays(G, P, W, E):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from etcd_tpu.ops.state import KernelConfig, init_state

    kcfg = KernelConfig(groups=G, peers=P, window=W, max_ents=E)
    st = init_state(kcfg)
    inbox = jnp.zeros((G, P, P, kcfg.fields), jnp.int32)   # engine.py
    assert roofline.state_bytes(G, P, W) == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(st))
    assert roofline.inbox_bytes(G, P, E) == inbox.nbytes
    assert roofline.step_min_bytes(G, P, W, E) == 2 * (
        roofline.state_bytes(G, P, W) + inbox.nbytes)


def test_max_ents_is_the_programs_default():
    pytest.importorskip("jax")
    from etcd_tpu.server.engine import EngineConfig

    assert roofline.MAX_ENTS == EngineConfig(groups=8, peers=3,
                                             data_dir="").max_ents


def test_least_time_and_unknown_device():
    b = roofline.step_min_bytes(12500, 5, 32, 8)
    assert b == 2 * (9 * 4 * 62500 + 62500 + 4 * 62500 * 32
                     + 5 * 4 * 312500 + 312500 + 4 * 312500 * 16)
    assert roofline.step_min_seconds(12500, 5, 32, "TPU v5 lite") == (
        pytest.approx(b / 819e9))
    with pytest.raises(KeyError):
        roofline.step_min_seconds(12500, 5, 32, "TPU v9 imaginary")
