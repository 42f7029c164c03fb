"""Pallas TPU kernel: batched Rabin fingerprinting (clmul fold + Barrett).

The paper's hot loop is fingerprinting every candidate SFA state (frontier ×
alphabet of them per round). On x86 it leans on ``PCLMULQDQ``; the TPU has no
carry-less multiply, so the kernel bit-slices: a 32×32 clmul is 32 unrolled
mask/shift/XOR steps on the VPU, executed for a whole block of state vectors
at once — per-fingerprint cost is amortized across VPU lanes instead of
per-instruction silicon.

Layout (the paper's §III-B3 locality argument, restated for VMEM):
  - words arrive word-major, ``(W8, block_b)``: the batch rides the 128
    lanes and the ``W`` packed words the sublanes, zero-padded to ``W8``, a
    multiple of 8 (a zero word adds a zero product, so the padding is exact);
  - the fold constants ``x^(32 i) mod P`` are a ``(W8, 2)`` column block,
    broadcast across lanes, and the Barrett limbs a ``(1, 4)`` block; both
    stay VMEM-resident across a pattern's whole row of blocks;
  - the XOR fold over words is a loop of aligned 8-row slices followed by a
    three-step sublane tree, so the kernel needs no ``reduce`` primitive
    (Mosaic lowers none for XOR);
  - each block writes a ``(2, block_b)`` [hi; lo] fingerprint tile, which
    the wrapper transposes back to ``(B, 2)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fingerprint import BarrettConstants


def _clmul32_block(a: jnp.ndarray, b: jnp.ndarray) -> tuple:
    """(…,) u32 × (…,) u32 -> 64-bit (hi, lo) pair; fully unrolled 32 steps."""
    hi = jnp.zeros_like(a)
    lo = jnp.zeros_like(a)
    one = jnp.uint32(1)
    for i in range(32):
        bit = (b >> jnp.uint32(i)) & one
        mask = jnp.uint32(0) - bit
        lo = lo ^ ((a << jnp.uint32(i)) & mask)
        hi = hi ^ (((a >> jnp.uint32(31 - i)) >> one) & mask)
    return hi, lo


def _clmul64_hi(a: tuple, b: tuple) -> tuple:
    """High 64 bits (limbs 3, 2) of a 64×64 carry-less product."""
    ah, al = a
    bh, bl = b
    lh_h, _ = _clmul32_block(al, bh)
    hl_h, _ = _clmul32_block(ah, bl)
    hh_h, hh_l = _clmul32_block(ah, bh)
    return hh_h, lh_h ^ hl_h ^ hh_l


def _clmul64_lo(a: tuple, b: tuple) -> tuple:
    """Low 64 bits (limbs 1, 0) of a 64×64 carry-less product."""
    ah, al = a
    bh, bl = b
    ll_h, ll_l = _clmul32_block(al, bl)
    _, lh_l = _clmul32_block(al, bh)
    _, hl_l = _clmul32_block(ah, bl)
    return ll_h ^ lh_l ^ hl_l, ll_l


def _xor_rows(x: jnp.ndarray) -> jnp.ndarray:
    """XOR-fold a (8·m, N) block over its rows -> (1, N): aligned 8-row
    slices first, then a sublane tree 8 -> 4 -> 2 -> 1."""
    acc = x[0:8]
    for r in range(8, x.shape[0], 8):
        acc = acc ^ x[r:r + 8]
    acc = acc[0:4] ^ acc[4:8]
    acc = acc[0:2] ^ acc[2:4]
    return acc[0:1] ^ acc[1:2]


def _fingerprint_kernel(words_ref, weights_ref, limbs_ref, out_ref):
    """One (pattern, block) cell: (W8, Bb) words -> (2, Bb) [hi; lo]."""
    words = words_ref[0]                            # (W8, Bb) u32
    w = weights_ref[0]                              # (W8, 2)
    c = limbs_ref[0]                                # (1, 4)
    shape = words.shape
    p_lo_h, p_lo_l = _clmul32_block(words, jnp.broadcast_to(w[:, 1:2], shape))
    p_hi_h, p_hi_l = _clmul32_block(words, jnp.broadcast_to(w[:, 0:1], shape))
    l0 = _xor_rows(p_lo_l)                          # (1, Bb)
    l1 = _xor_rows(p_lo_h ^ p_hi_l)
    l2 = _xor_rows(p_hi_h)

    # Barrett reduction with constants [p_hi, p_lo, mu_hi, mu_lo].
    row = l2.shape

    def limb(i):
        return jnp.broadcast_to(c[:, i:i + 1], row)

    zeros = jnp.zeros(row, jnp.uint32)
    m3, m2 = _clmul64_hi((zeros, l2), (limb(2), limb(3)))
    q1, q0 = _clmul64_lo((m3, l2 ^ m2), (limb(0), limb(1)))
    out_ref[0] = jnp.concatenate([l1 ^ q1, l0 ^ q0], axis=0)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fingerprint_bank_pallas(
    words: jnp.ndarray,
    weights: jnp.ndarray,
    consts_limbs: jnp.ndarray,
    *,
    block_b: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-pattern batched fingerprints.

    words: (P, B, W) u32; weights: (P, W, 2) u32 per-pattern fold constants;
    consts_limbs: (P, 4) u32 per-pattern Barrett constants -> (P, B, 2) u32.
    Grid (pattern, block): pattern p's constants load once per row of blocks.
    On a TPU ``block_b`` must be a multiple of 128 unless it covers all of B.
    """
    P, B, W = words.shape
    block_b = min(block_b, B)
    Bp = -(-B // block_b) * block_b
    W8 = -(-W // 8) * 8
    words_t = jnp.pad(jnp.swapaxes(words, 1, 2),
                      ((0, 0), (0, W8 - W), (0, Bp - B)))      # (P, W8, Bp)
    weights = jnp.pad(weights, ((0, 0), (0, W8 - W), (0, 0)))  # (P, W8, 2)
    out = pl.pallas_call(
        _fingerprint_kernel,
        grid=(P, Bp // block_b),
        in_specs=[
            pl.BlockSpec((1, W8, block_b), lambda p, i: (p, 0, i)),
            pl.BlockSpec((1, W8, 2), lambda p, i: (p, 0, 0)),
            pl.BlockSpec((1, 1, 4), lambda p, i: (p, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 2, block_b), lambda p, i: (p, 0, i)),
        out_shape=jax.ShapeDtypeStruct((P, 2, Bp), jnp.uint32),
        interpret=interpret,
    )(words_t, weights, consts_limbs[:, None, :])
    return jnp.swapaxes(out[:, :, :B], 1, 2)


def fingerprint_pallas(
    words: jnp.ndarray,
    weights: jnp.ndarray,
    consts_limbs: jnp.ndarray,
    *,
    block_b: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched fingerprints under one polynomial: the bank kernel with P=1.
    words: (B, W) u32; weights: (W, 2) u32; consts_limbs: (4,) u32
    [p_hi, p_lo, mu_hi, mu_lo] -> (B, 2) u32."""
    return fingerprint_bank_pallas(
        words[None], weights[None], consts_limbs[None],
        block_b=block_b, interpret=interpret,
    )[0]


def consts_limbs_of(consts: BarrettConstants) -> jnp.ndarray:
    return jnp.asarray(
        [
            (consts.poly_low >> 32) & 0xFFFFFFFF,
            consts.poly_low & 0xFFFFFFFF,
            (consts.mu_low >> 32) & 0xFFFFFFFF,
            consts.mu_low & 0xFFFFFFFF,
        ],
        dtype=jnp.uint32,
    )
