"""Pallas ring-resolve kernel vs the jnp reference (interpret mode on
CPU; the same program runs compiled on TPU — scripts/pallas_bench.py
measures which path wins there)."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from etcd_tpu.ops.pallas_kernels import ring_resolve  # noqa: E402
from etcd_tpu.ops.state import GroupState, KernelConfig, init_state  # noqa: E402
from etcd_tpu.ops import state as state_mod  # noqa: E402


def _reference(ring, idx, last, W):
    """Straightforward numpy model of the windowed resolve."""
    out = np.zeros(idx.shape, np.int32)
    G, P = ring.shape[:2]
    flat = idx.reshape(G, P, -1)
    res = out.reshape(G, P, -1)
    for g in range(G):
        for p in range(P):
            for j, i in enumerate(flat[g, p]):
                i = int(i)
                if 1 <= i and (i > last[g, p] - W) and (i <= last[g, p]):
                    res[g, p, j] = ring[g, p, i % W]
    return out


@pytest.mark.parametrize("shape", [
    ((3, 5, 16), (3, 5, 4)),          # conflict-scan shape (G,P,E)
    ((4, 3, 8), (4, 3, 3, 2)),        # send-assembly shape (G,P,P,E)
    ((2, 2, 32), (2, 2, 7)),
])
def test_ring_resolve_matches_reference(shape):
    rshape, ishape = shape
    W = rshape[-1]
    rng = np.random.RandomState(0)
    ring = rng.randint(1, 9, rshape).astype(np.int32)
    last = rng.randint(0, 3 * W, rshape[:2]).astype(np.int32)
    idx = rng.randint(-2, 3 * W + 2, ishape).astype(np.int32)
    got = np.asarray(ring_resolve(jnp.asarray(ring), jnp.asarray(idx),
                                  jnp.asarray(last), block_rows=4,
                                  interpret=True))
    want = _reference(ring, idx, last, W)
    assert (got == want).all()


def test_ring_resolve_matches_kernel_term_at():
    """Against the production jnp path (state.term_at) on live state."""
    cfg = KernelConfig(groups=4, peers=3, window=16, max_ents=3)
    st = init_state(cfg, stagger=True)
    # Fabricate a populated ring.
    rng = np.random.RandomState(1)
    ring = rng.randint(1, 5, (4, 3, 16)).astype(np.int32)
    last = rng.randint(1, 40, (4, 3)).astype(np.int32)
    st = st._replace(log_term=jnp.asarray(ring),
                     last_index=jnp.asarray(last))
    idx = jnp.asarray(rng.randint(0, 44, (4, 3)).astype(np.int32))
    want = np.asarray(state_mod.term_at(st, cfg, idx))
    got = np.asarray(ring_resolve(st.log_term, idx[..., None],
                                  st.last_index, block_rows=3,
                                  interpret=True))[..., 0]
    assert (got == want).all()
