"""Pallas TPU kernel: enumeration-mode chunk matching with a VMEM-resident
transition table.

Per chunk, the DFA runs from ``R`` start states ``0..R-1`` simultaneously
(``R = n`` is the SFA idea applied at matching time: the chunk's whole
transition function). Layout: chunks ride the 128 lanes and the tracked
states the sublanes, so one grid cell advances ``block_b`` chunks in
lockstep. Each character step is

    cols[j, b] = table[j, sym[b]]            # (n, k) @ onehot(sym) (k, bb)
    v'[r, b]   = cols[v[r, b], b]             # per-lane row select over j

The first is one exact MXU contraction; the second is a loop over table
rows that selects row ``j`` of ``cols`` wherever ``v == j`` — a gather
written with compares and selects only, which is what Mosaic lowers.
Symbols arrive time-major, ``(L, block_b)``, so step ``t`` reads one row.

The table is pinned in VMEM for the whole chunk block — the TPU restatement
of the paper's L1-locality argument (§III-B3): one HBM read of the table
serves every character of every chunk in the block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _match_kernel(table_ref, chunks_ref, out_ref, cols_ref):
    """One (pattern, chunk-block) cell. table_ref (1, n, k); chunks_ref
    (L, bb) time-major symbols; out_ref (1, R, bb); cols_ref (n, bb) i32."""
    table = table_ref[0].astype(jnp.float32)              # (n, k)
    n, k = table.shape
    L, bb = chunks_ref.shape
    R = out_ref.shape[1]

    def step(t, v):
        sym = chunks_ref[pl.ds(t, 1), :]                  # (1, bb)
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (k, bb), 0) == sym
        ).astype(jnp.float32)                             # (k, bb)
        cols_ref[...] = jax.lax.dot_general(               # δ(j, sym[b])
            table, onehot, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)

        def select(j, acc):
            return jnp.where(v == j, cols_ref[pl.ds(j, 1), :], acc)

        return jax.lax.fori_loop(0, n, select, v)

    v0 = jax.lax.broadcasted_iota(jnp.int32, (R, bb), 0)
    out_ref[0] = jax.lax.fori_loop(0, L, step, v0)


@functools.partial(jax.jit, static_argnames=("starts", "block_b", "interpret"))
def match_bank_chunks_pallas(
    tables: jnp.ndarray,
    chunks: jnp.ndarray,
    *,
    starts: int | None = None,
    block_b: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Multi-automaton chunk matching: every (pattern, chunk) cell at once.

    ``tables``: (P, n, k) int32 padded bank stack; ``chunks``: (B, L) int32
    -> (P, B, R) chunk mappings restricted to start states ``0..R-1``, where
    ``R = starts`` (default ``n``: the whole transition function). The grid
    is ``(pattern, chunk-block)`` with the chunk axis innermost, so the
    VMEM-resident table block is swapped once per *pattern* and stays hot
    across all B chunks of that pattern — the §III-B3 table-locality
    argument applied to the bank axis. B pads up to a multiple of
    ``block_b`` (on a TPU a multiple of 128) and the padding is cropped.
    """
    Pn, n, k = tables.shape
    B, L = chunks.shape
    R = n if starts is None else starts
    block_b = min(block_b, B)
    Bp = -(-B // block_b) * block_b
    chunks_t = jnp.pad(chunks.T, ((0, 0), (0, Bp - B)))      # (L, Bp)
    out = pl.pallas_call(
        _match_kernel,
        grid=(Pn, Bp // block_b),
        in_specs=[
            pl.BlockSpec((1, n, k), lambda p, b: (p, 0, 0)),
            pl.BlockSpec((L, block_b), lambda p, b: (0, b)),
        ],
        out_specs=pl.BlockSpec((1, R, block_b), lambda p, b: (p, 0, b)),
        out_shape=jax.ShapeDtypeStruct((Pn, R, Bp), jnp.int32),
        scratch_shapes=[pltpu.VMEM((n, block_b), jnp.int32)],
        interpret=interpret,
    )(tables, chunks_t)
    return jnp.swapaxes(out[:, :, :B], 1, 2)


def match_chunks_pallas(
    table: jnp.ndarray,
    chunks: jnp.ndarray,
    *,
    block_b: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-table chunk matching, the bank kernel with P=1:
    table (n, k) int32; chunks (B, L) int32 -> (B, n) chunk mappings."""
    return match_bank_chunks_pallas(
        table[None], chunks, block_b=block_b, interpret=interpret
    )[0]
