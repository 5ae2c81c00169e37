#!/usr/bin/env python
"""Measure the Pallas ring-resolve kernel against the XLA-fused jnp path
on the TPU (with JAX_PLATFORMS=cpu it runs the interpreter instead and
only validates correctness; with no TPU and no such request it fails).

Measures whether a Pallas ring-resolve could beat the production one-hot path (which would justify giving it a call site) —
SURVEY §7 scopes Pallas as "only if XLA fusion is insufficient", and the
jnp one-hot path won the last TPU measurement (README). Usage:

    python scripts/pallas_bench.py [groups] [peers] [window] [ents]
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from etcd_tpu.ops.pallas_kernels import ring_resolve
    from etcd_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    platform = jax.devices()[0].platform
    # The Mosaic kernel exists only on a TPU. JAX_PLATFORMS=cpu asks by
    # name for the interpreter (correctness only); anything else that
    # is not a TPU is an error, not a reason to interpret quietly.
    interpret = platform == "cpu" and os.environ.get("JAX_PLATFORMS") == "cpu"
    if platform != "tpu" and not interpret:
        print(f"no TPU visible (found {platform}); set JAX_PLATFORMS=cpu "
              "for an interpret-mode correctness run", file=sys.stderr)
        return 3
    G = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
    P = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    W = int(sys.argv[3]) if len(sys.argv) > 3 else 16
    E = int(sys.argv[4]) if len(sys.argv) > 4 else 4
    print(f"backend={platform} interpret={interpret} "
          f"G={G} P={P} W={W} E={E}")

    rng = np.random.RandomState(0)
    ring = jnp.asarray(rng.randint(1, 9, (G, P, W)).astype(np.int32))
    last = jnp.asarray(rng.randint(1, 5 * W, (G, P)).astype(np.int32))
    idx = jnp.asarray(rng.randint(0, 5 * W, (G, P, P, E)).astype(np.int32))

    @jax.jit
    def jnp_path(ring, idx, last):
        # The production formulation (state.ring_lookup + window mask).
        slot = jnp.mod(idx, W)
        iota = jnp.arange(W, dtype=jnp.int32)
        onehot = (slot[..., None] == iota).astype(jnp.int32)
        vals = jnp.sum(ring[:, :, None, None, :] * onehot, axis=-1,
                       dtype=jnp.int32)
        lastb = last[:, :, None, None]
        ok = (idx > lastb - W) & (idx <= lastb) & (idx >= 1)
        return jnp.where(ok, vals, 0)

    def bench(fn, *args, iters=50):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3, out

    t_jnp, out_jnp = bench(jnp_path, ring, idx, last)
    t_pal, out_pal = bench(
        functools.partial(ring_resolve, interpret=interpret),
        ring, idx, last)
    same = bool((np.asarray(out_jnp) == np.asarray(out_pal)).all())
    print(f"jnp one-hot: {t_jnp:8.3f} ms   pallas: {t_pal:8.3f} ms   "
          f"match={same}   speedup={t_jnp / t_pal:.2f}x")
    if interpret:
        print("(CPU interpret mode: timing not meaningful, "
              "correctness only)")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
