"""Shared int8 block-quantization math for the compressed data plane.

One source of truth for the quantize / dequantize / error arithmetic used
by three layers that must agree bit-for-bit:

  * ``optim/compression.py``   -- host-side quantize for the legacy ring;
  * ``kernels/ref.py``         -- the jnp oracle for the fused round-step;
  * ``kernels/block_pack.py``  -- the Pallas kernel body (same jnp ops
    traced inside the kernel, so interpret and compiled agree).

Scheme: per-block symmetric int8.  A [nb, QBLOCK] f32 tile quantizes to
(q int8 [nb, QBLOCK], scale f32 [nb, 1]) with scale = amax/127 floored at
``SCALE_FLOOR``.

Non-finite handling: a NaN/inf entry must not silently poison its block
(the old ``quantize_int8`` let a single inf drive the scale to inf, so
every *other* entry in the block dequantized to 0 or NaN with no signal).
Here the finite entries quantize normally against a scale computed over
finite entries only, and the block's *scale* is set to NaN as a
deterministic per-block nonfinite flag: dequantization yields an all-NaN
block (visible to grad-norm / nonfinite checks downstream), while
``quant_error`` reports exactly 0 for flagged lanes so error feedback is
never poisoned.  No extra wire bytes are spent on the flag.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QBLOCK = 256
SCALE_FLOOR = 1e-12
# Explicit f32 reciprocal: XLA strength-reduces division by the
# constant 127 into multiplication by its reciprocal anyway (different
# rounding than true division); writing the multiply in the source
# makes the rounding reproducible by plain NumPy references.
INV127 = np.float32(1.0) / np.float32(127.0)

__all__ = [
    "QBLOCK",
    "SCALE_FLOOR",
    "quant_blocks",
    "dequant_blocks",
    "quant_error",
    "block_nonfinite",
]


def quant_blocks(x2d: jnp.ndarray):
    """Quantize a [nb, qb] f32 tile -> (q int8 [nb, qb], scale f32 [nb, 1]).

    The scale of any block containing a non-finite entry is NaN (the
    per-block nonfinite flag); its finite lanes are still quantized
    against the finite amax so no information is lost on the wire.
    """
    x2d = x2d.astype(jnp.float32)
    finite = jnp.isfinite(x2d)
    xf = jnp.where(finite, x2d, 0.0)
    amax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
    scale = jnp.maximum(amax * INV127, SCALE_FLOOR)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    all_finite = jnp.all(finite, axis=1, keepdims=True)
    scale = jnp.where(all_finite, scale, jnp.float32(jnp.nan))
    return q, scale.astype(jnp.float32)


def dequant_blocks(q: jnp.ndarray, scale: jnp.ndarray,
                   barrier: bool = True) -> jnp.ndarray:
    """Dequantize [nb, qb] int8 against [nb, 1] scales -> [nb, qb] f32.

    Flagged (NaN-scale) blocks dequantize to all-NaN deterministically.

    The result passes through an optimization barrier: without it XLA is
    free to contract the dequant multiply into a caller's accumulate add
    (FMA), and whether it does depends on the surrounding graph -- the
    jnp oracle and the interpreted Pallas kernel would then disagree in
    the last bit.  The barrier pins round-after-multiply semantics in
    every XLA backend.  Compiled Pallas TPU kernels pass
    ``barrier=False``: Mosaic has no barrier primitive, and it rounds
    the product before the add anyway.
    """
    dq = q.astype(jnp.float32) * scale
    return jax.lax.optimization_barrier(dq) if barrier else dq


def quant_error(x2d: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray,
                barrier: bool = True):
    """Elementwise quantization error x - dq, with non-finite lanes zeroed.

    Zeroing keeps error-feedback state finite even when a gradient leaf
    goes NaN/inf for a step -- the flag travels via the NaN scale, not
    via the feedback buffer.  ``barrier`` as in :func:`dequant_blocks`.
    """
    err = x2d.astype(jnp.float32) - dequant_blocks(q, scale, barrier)
    return jnp.where(jnp.isfinite(err), err, 0.0)


def block_nonfinite(scale: jnp.ndarray) -> jnp.ndarray:
    """Per-block nonfinite flag surfaced from a quantized scale vector."""
    return ~jnp.isfinite(scale)
