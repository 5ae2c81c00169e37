"""Pallas TPU kernels for the batched consensus step's hottest op.

SURVEY §7 scopes Pallas as conditional: "Pallas kernels only if XLA
fusion is insufficient". Profiling on a real v5e chip showed the original
bottleneck (take_along_axis gathers, ~55% of a round) was eliminated by
reformulating ring reads as one-hot select-sums, which XLA fuses well —
so the jnp path remains the default. This module provides the same op as
an explicit Pallas kernel so the choice can be re-measured per backend
(scripts/pallas_bench.py) rather than assumed:

    ring_resolve(ring, idx): ring (G, P, W) terms, idx (G, P, T, E)
    absolute entry indices -> (G, P, T, E) terms, 0 outside each
    (g, p) row's window — the send-assembly / conflict-scan resolve
    (kernel.py _terms_at_many + the broadcast variant).

The kernel tiles the fused (G*P, T*E) problem over a grid of row blocks,
holding each block's ring rows (BR, W) and index rows (BR, TE) in VMEM
and computing the masked one-hot contraction in one pass — no HBM
intermediates regardless of how XLA would schedule the jnp version.

This module is a MEASURED-AND-REJECTED candidate, kept as the harness
for any future re-measurement: on real TPU v5 lite (2026-07-31,
G=100k P=5 W=16 E=4) the isolated op wins 2.3x over the jnp one-hot
path (0.022 ms vs 0.051 ms, scripts/pallas_bench.py), but wired into
`_terms_at_many` of the full hops=3 kernel round it LOSES 9.3x
(165.6 ms/round vs 17.7 ms, scripts/pallas_roundbench.py): the
pallas_call boundary blocks XLA from fusing the resolve into the
surrounding message-assembly ops, so every call site pays HBM
round-trips for operands the fused program never materializes. The
jnp path stays production; do not give this a call site without
beating scripts/pallas_roundbench.py first. `interpret` is the
caller's explicit choice: the tests pass True (they pin the
windowed-resolve semantics on the CPU), a measurement passes False and
gets the Mosaic kernel or the compiler's refusal — never a quiet switch
to the interpreter. tests/test_tpu_compile.py compiles it for a
described v5e at serving widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _resolve_block(ring_ref, idx_ref, last_ref, out_ref, *, W: int):
    ring = ring_ref[...]          # (BR, W)
    idx = idx_ref[...]            # (BR, TE)
    last = last_ref[...]          # (BR, 1)
    # One-hot contraction over the ring axis: slot = idx mod W. The
    # scalar W is pinned to int32 where it meets arrays (x64 configs
    # would promote the Python int to int64) but stays a Python int in
    # shapes.
    w32 = jnp.int32(W)
    slot = jax.lax.rem(idx, w32)
    onehot = (slot[:, :, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (1, 1, W), 2))
    vals = jnp.sum(ring[:, None, :] * onehot.astype(jnp.int32), axis=2,
                   dtype=jnp.int32)
    in_win = (idx > last - w32) & (idx <= last) & (idx >= 1)
    out_ref[...] = jnp.where(in_win, vals, 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ring_resolve(ring: jax.Array, idx: jax.Array, last: jax.Array,
                 block_rows: int = 512, *,
                 interpret: bool) -> jax.Array:
    """Pallas version of the windowed ring term resolve.

    ring: (G, P, W) int32 entry terms (entry i at slot i % W)
    idx:  (G, P, *T) int32 absolute indices (any trailing shape)
    last: (G, P) int32 last_index per row
    returns idx-shaped int32 terms; 0 for out-of-window / index < 1.
    """
    from jax.experimental import pallas as pl

    G, P, W = ring.shape
    trailing = idx.shape[2:]
    R = G * P
    TE = 1
    for d in trailing:
        TE *= d
    ring2 = ring.reshape(R, W)
    idx2 = idx.reshape(R, TE)
    last2 = last.reshape(R, 1)

    BR = min(block_rows, R)
    # Pad rows to a multiple of the block.
    pad = (-R) % BR
    if pad:
        ring2 = jnp.pad(ring2, ((0, pad), (0, 0)))
        idx2 = jnp.pad(idx2, ((0, pad), (0, 0)))
        last2 = jnp.pad(last2, ((0, pad), (0, 0)))
    Rp = R + pad

    out = pl.pallas_call(
        functools.partial(_resolve_block, W=W),
        grid=(Rp // BR,),
        in_specs=[
            pl.BlockSpec((BR, W), lambda i: (i, 0)),
            pl.BlockSpec((BR, TE), lambda i: (i, 0)),
            pl.BlockSpec((BR, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BR, TE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, TE), jnp.int32),
        interpret=interpret,
    )(ring2, idx2, last2)
    return out[:R].reshape((G, P) + trailing)
