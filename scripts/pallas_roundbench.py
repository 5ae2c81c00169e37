#!/usr/bin/env python
"""End-to-end A/B of the Pallas ring resolve inside the full kernel round.

scripts/pallas_bench.py measures the resolve op in isolation (r4 on real
TPU: pallas 0.022 ms vs jnp one-hot 0.051 ms at G=100k — a 2.3x micro
win). That alone doesn't earn a call site on the hottest path: the op is
<1% of a 6.4 ms pipelined round, so the decision needs the full-round
number. This script times `step_routed_auto` (the serving engine's
program) with `_terms_at_many` either on the production jnp one-hot path
or patched to the Pallas kernel, same seed and schedule:

    python scripts/pallas_roundbench.py jnp    [G] [hops]
    python scripts/pallas_roundbench.py pallas [G] [hops]

Run each mode in its own process (the jit caches would otherwise key on
the same outer callables). The pallas mode compiles the Mosaic kernel
(interpret=False): it is a TPU measurement and fails on any other backend.
"""
import functools
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main() -> int:
    mode = sys.argv[1] if len(sys.argv) > 1 else "jnp"
    G = int(sys.argv[2]) if len(sys.argv) > 2 else 100_000
    hops = int(sys.argv[3]) if len(sys.argv) > 3 else 3

    import jax
    import jax.numpy as jnp

    from etcd_tpu.ops import kernel
    from etcd_tpu.ops.state import KernelConfig, init_state
    from etcd_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    if mode == "pallas":
        from etcd_tpu.ops.pallas_kernels import ring_resolve

        def terms_at_many_pallas(st, cfg, idx):
            return ring_resolve(st.log_term, idx, st.last_index,
                                interpret=False)

        kernel._terms_at_many = terms_at_many_pallas

    cfg = KernelConfig(groups=G, peers=5, window=16, max_ents=4,
                       election_tick=10, heartbeat_tick=3)
    st = init_state(cfg, stagger=True)
    inbox = jnp.zeros((G, cfg.peers, cfg.peers, cfg.fields), jnp.int32)
    zero = jnp.zeros(G, jnp.int32)
    step1 = functools.partial(kernel.step_routed_auto, cfg)
    for _ in range(40):
        st, inbox = step1(st, inbox, zero, zero, jnp.asarray(True))
    jax.block_until_ready(st.commit)
    state = np.asarray(st.state)
    assert (state == 2).any(axis=1).all(), "elections did not converge"
    slots = jnp.asarray(np.argmax(state == 2, axis=1).astype(np.int32))
    full = jnp.full(G, cfg.max_ents, jnp.int32)
    fn = functools.partial(kernel.step_routed_auto, cfg, hops=hops)
    st, inbox = fn(st, inbox, full, slots, jnp.asarray(True))
    jax.block_until_ready(st.commit)
    c0 = int(np.asarray(st.commit).max(axis=1).sum())
    rounds = 80
    t0 = time.perf_counter()
    for _ in range(rounds):
        st, inbox = fn(st, inbox, full, slots, jnp.asarray(True))
    jax.block_until_ready(st.commit)
    dt = (time.perf_counter() - t0) / rounds * 1000.0
    c1 = int(np.asarray(st.commit).max(axis=1).sum())
    cps = (c1 - c0) / (rounds * dt / 1000.0)
    print(f"mode={mode} G={G} hops={hops} backend={jax.default_backend()}: "
          f"{dt:6.2f} ms/round, {cps:,.0f} commits/s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
