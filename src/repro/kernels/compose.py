"""Pallas TPU kernel: function-composition combine as a one-hot MXU matmul.

The SFA monoid combine ``out[b, q] = g[b, f[b, q]]`` is a gather — latency
bound and VPU-serial on TPU. For the state-vector sizes the paper works with
(n ≤ a few thousand), re-expressing the gather as

    out[b, q] = Σ_j g[b, j] · onehot(f)[b, j, q]

turns it into an MXU contraction: n² MACs replace n dependent loads, and the
MXU's 128×128 systolic throughput makes that trade profitable for n ≥ ~128.
State ids are < 2^24 and the contraction runs at ``Precision.HIGHEST``, so
f32 accumulation is exact and the kernel is bit-exact against the gather
oracle.

Grid: (row blocks, q-tiles). Per cell the kernel holds an ``(8, block_q)``
tile of ``f``, the ``(8, n)`` rows of ``g``, and one ``(n, block_q)``
one-hot at a time in VMEM — ≤ ~3 MB at n = 2930, block_q = 256. On a TPU
``block_q`` is a multiple of 128 (or all of n).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Rows of ``f``/``g`` per grid cell: one sublane tile.
_ROWS = 8


def _compose_kernel(f_ref, g_ref, out_ref):
    f = f_ref[...]                                         # (bb, bq) int32
    g = g_ref[...].astype(jnp.float32)                     # (bb, n)
    bb, bq = f.shape
    n = g.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, bq), 0)
    rows = []
    for r in range(bb):
        onehot = (iota == f[r:r + 1]).astype(jnp.float32)  # (n, bq)
        rows.append(jax.lax.dot_general(
            g[r:r + 1], onehot, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ))                                                 # (1, bq)
    out_ref[...] = jnp.concatenate(rows, axis=0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def compose_pallas(
    f: jnp.ndarray,
    g: jnp.ndarray,
    *,
    block_q: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Composition combine. f, g: (B, n) int32 -> (B, n) int32 (f then g)."""
    B, n = f.shape
    block_b = min(_ROWS, B)
    block_q = min(block_q, n)
    Bp = -(-B // block_b) * block_b
    Np = -(-n // block_q) * block_q
    f = jnp.pad(f, ((0, Bp - B), (0, Np - n)))
    g = jnp.pad(g, ((0, Bp - B), (0, 0)))
    out = pl.pallas_call(
        _compose_kernel,
        grid=(Bp // block_b, Np // block_q),
        in_specs=[
            pl.BlockSpec((block_b, block_q), lambda b, q: (b, q)),
            pl.BlockSpec((block_b, n), lambda b, q: (b, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_q), lambda b, q: (b, q)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), jnp.int32),
        interpret=interpret,
    )(f, g)
    return out[:B, :n]
