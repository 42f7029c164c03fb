"""Jitted public wrappers around the Pallas kernels.

On a TPU the kernels compile to Mosaic (``interpret=False``); on the CPU
backend they run in Pallas interpret mode (the kernel body executes
step-by-step, validating semantics). The flag defaults from the active
backend so call-sites never branch, and any other backend is an error: a
kernel never silently falls back to the interpreter on an accelerator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.fingerprint import BarrettConstants, fold_weights_u32

from .clmul import consts_limbs_of, fingerprint_bank_pallas, fingerprint_pallas
from .compose import compose_pallas
from .expand import expand_bank_pallas
from .match_scan import match_bank_chunks_pallas, match_chunks_pallas


@functools.lru_cache(maxsize=None)
def _default_interpret() -> bool:
    """Cached backend probe: the active platform cannot change mid-process,
    so ``jax.default_backend()`` (which can trigger backend init) runs once.
    True on ``"cpu"``, False on ``"tpu"``; any other platform raises."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the active backend is {platform!r}"
    )


def _count(name: str) -> None:
    # ``kernels.<op>.calls`` counts *wrapper* invocations: eager dispatches,
    # or trace events when the wrapper is inlined into a jitted round — a
    # cheap "which kernels does this workload reach" signal, not a per-
    # execution count (XLA replays compiled programs without re-entering
    # Python).
    obs.counter(f"kernels.{name}.calls",
                help=f"dispatches of the {name} kernel wrapper").inc()


def fingerprint(
    words: jnp.ndarray,
    consts: BarrettConstants,
    *,
    block_b: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Batched Rabin fingerprints of packed (B, W) uint32 words -> (B, 2)."""
    if interpret is None:
        interpret = _default_interpret()
    _count("fingerprint")
    weights = fold_weights_u32(words.shape[-1], consts)
    return fingerprint_pallas(
        words, weights, consts_limbs_of(consts), block_b=block_b, interpret=interpret
    )


def fingerprint_bank(
    words: jnp.ndarray,
    consts_list,
    *,
    block_b: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Batched Rabin fingerprints over the pattern axis: (P, B, W) packed
    words with one :class:`BarrettConstants` per pattern -> (P, B, 2).

    This is the fold of the batched construction rounds
    (:mod:`repro.construction.batched`) as a standalone kernel: per-pattern
    constants (each pattern may sit on a different polynomial after a
    collision retry) ride the grid's pattern axis and stay VMEM-resident
    across that pattern's block row. On CPU the construction rounds keep
    their fused-XLA fold (interpret-mode Pallas would dominate); on a TPU
    runtime this kernel is the drop-in fold.
    """
    if interpret is None:
        interpret = _default_interpret()
    _count("fingerprint_bank")
    P, B, W = words.shape
    if len(consts_list) != P:
        raise ValueError(f"expected {P} per-pattern constants, got "
                         f"{len(consts_list)}")
    weights = jnp.stack(
        [fold_weights_u32(W, c) for c in consts_list])         # (P, W, 2)
    limbs = jnp.stack([consts_limbs_of(c) for c in consts_list])  # (P, 4)
    return fingerprint_bank_pallas(
        words, weights, limbs, block_b=block_b, interpret=interpret
    )


def fingerprint_bank_stacked(
    words: jnp.ndarray,
    weights: jnp.ndarray,
    limbs: jnp.ndarray,
    *,
    block_b: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """:func:`fingerprint_bank` with prestacked per-pattern constants:
    (P, B, W) packed words, (P, W, 2) fold weights, (P, 4) Barrett limbs ->
    (P, B, 2). Fully traceable (no host-side ``BarrettConstants`` objects),
    which is what lets ``repro.construction.batched`` select this kernel as
    the fingerprint stage *inside* its AOT-compiled round."""
    if interpret is None:
        interpret = _default_interpret()
    _count("fingerprint_bank_stacked")
    return fingerprint_bank_pallas(
        words, weights, limbs, block_b=block_b, interpret=interpret
    )


def expand_frontier_bank(
    tables: jnp.ndarray,
    ft: jnp.ndarray,
    *,
    block_t: int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Frontier × alphabet expansion over the pattern axis: (B, n, k)
    transition tables and (B, T, n) frontier state-vector tiles ->
    (B, T·k, n) candidate vectors, ``out[b, t·k + a, q] = tables[b,
    ft[b, t, q], a]`` — bit-identical to the XLA gather ``tables[b][ft[b]]``
    (the construction round's ``expand_backend="xla"`` stage). Formulated
    as a one-hot MXU contraction so each pattern's table stays VMEM-resident
    across its frontier blocks (see :mod:`repro.kernels.expand`).
    """
    if interpret is None:
        interpret = _default_interpret()
    _count("expand_frontier_bank")
    return expand_bank_pallas(tables, ft, block_t=block_t,
                              interpret=interpret)


def compose(
    f: jnp.ndarray,
    g: jnp.ndarray,
    *,
    block_q: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Function-composition combine (f then g): (B, n) x (B, n) -> (B, n)."""
    if interpret is None:
        interpret = _default_interpret()
    _count("compose")
    return compose_pallas(f, g, block_q=block_q, interpret=interpret)


def match_chunks(
    table: jnp.ndarray,
    chunks: jnp.ndarray,
    *,
    block_b: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-chunk transition functions: (n, k), (B, L) -> (B, n).

    ``block_b`` chunks share one grid cell / VMEM table residency (the same
    block-tiling knob as ``fingerprint``/``compose``).
    """
    if interpret is None:
        interpret = _default_interpret()
    _count("match_chunks")
    return match_chunks_pallas(table, chunks, block_b=block_b,
                               interpret=interpret)


def match_bank_chunks(
    tables: jnp.ndarray,
    chunks: jnp.ndarray,
    *,
    starts: int | None = None,
    block_b: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Multi-automaton chunk functions: (P, n, k), (B, L) -> (P, B, R), the
    mappings of start states ``0..R-1`` with ``R = starts`` (default n)."""
    if interpret is None:
        interpret = _default_interpret()
    _count("match_bank_chunks")
    return match_bank_chunks_pallas(tables, chunks, starts=starts,
                                    block_b=block_b, interpret=interpret)
