"""Pallas TPU kernel: frontier × alphabet expansion as a one-hot MXU matmul.

The batched construction round's expansion stage is the gather

    cand[t·k + a, q] = table[ft[t, q], a]

— every frontier state vector ``ft[t]`` advanced by every symbol ``a`` at
once. XLA lowers ``table[ft]`` to a dynamic-gather; on TPU that is latency
bound and VPU-serial, exactly like the composition combine in
``kernels/compose.py``. The same one-hot re-expression applies: per frontier
row ``t``,

    tableᵀ (k, n) @ onehot(ft[t]) (n, n)  ->  cand rows t·k … t·k+k-1 (k, n)

turns the ``n·k`` dependent loads into one MXU contraction. State ids are
< 2^16 (the batched engine's packing bound), far under f32's 2^24
exact-integer range, and the contraction runs at ``Precision.HIGHEST``, so
the matmul is bit-exact against the gather oracle — the property the
construction tests pin (``expand_backend="pallas"`` must be bit-identical to
the XLA gather).

Grid: (pattern, frontier-tile blocks). Per cell the kernel loops over its
``block_t`` frontier rows, holding one ``(n, n)`` one-hot at a time and the
pattern's ``(k, n)`` transposed table in VMEM; each row's ``(k, n)`` result
lands on a leading (untiled) axis of a ``(T, k, n)`` output, which the
wrapper views as ``(T·k, n)`` at no cost. ``block_t`` auto-shrinks with
``k·n`` to bound the output block's residency.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Output-block residency bound: block_t · k · n int32 elements per grid
#: cell (~1 MB at the cap). ``_auto_block_t`` shrinks block_t to honor it.
_OUT_BUDGET = 1 << 18


def _auto_block_t(tile: int, row_elems: int) -> int:
    """Largest divisor of ``tile`` whose output block fits the budget."""
    bt = max(1, min(tile, _OUT_BUDGET // max(1, row_elems)))
    while tile % bt:
        bt -= 1
    return bt


def _expand_kernel(ft_ref, table_t_ref, out_ref):
    table_t = table_t_ref[0].astype(jnp.float32)     # (k, n)
    _, bt, n = ft_ref.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)

    def row(t, carry):
        f = ft_ref[0, pl.ds(t, 1), :]                # (1, n)
        onehot = (iota == f).astype(jnp.float32)     # [j, q] = ft[t, q] == j
        out_ref[0, t] = jax.lax.dot_general(
            table_t, onehot, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)                          # (k, n)
        return carry

    jax.lax.fori_loop(0, bt, row, 0)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def expand_bank_pallas(
    tables: jnp.ndarray,
    ft: jnp.ndarray,
    *,
    block_t: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Frontier expansion over a bank: (B, n, k) tables, (B, T, n) frontier
    tiles -> (B, T·k, n) candidates in row-major (frontier, symbol) order.
    ``block_t = 0`` picks the largest tile divisor fitting the VMEM budget.
    """
    B, T, n = ft.shape
    k = tables.shape[-1]
    if block_t <= 0:
        block_t = _auto_block_t(T, k * n)
    if T % block_t:
        raise ValueError(f"block_t ({block_t}) must divide the tile ({T})")
    out = pl.pallas_call(
        _expand_kernel,
        grid=(B, T // block_t),
        in_specs=[
            pl.BlockSpec((1, block_t, n), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, k, n), lambda b, t: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t, k, n), lambda b, t: (b, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, k, n), jnp.int32),
        interpret=interpret,
    )(ft, jnp.swapaxes(tables, 1, 2))
    return out.reshape(B, T * k, n)
