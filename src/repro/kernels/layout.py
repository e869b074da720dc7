"""Slot layout arithmetic shared by both round-step backends.

A slot laid out as a ``[rows, lanes]`` tile stack fills whole native TPU
tiles: 8x128 for 32-bit, 16x128 for 16-bit, 32x128 for 8-bit values.
The Pallas kernels (:mod:`repro.kernels.block_pack`) need that layout to
compile at all; the jnp backend (:mod:`repro.core.roundstep`) takes it
for large plain slots, so a slot write covers whole tiles.  Pure
arithmetic: importing this module pulls in neither JAX nor Pallas.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

#: Lane width of a slot tile (the TPU vector register width).
LANES = 128
#: Quantization blocks per tile row group: the int8 wire tile is 32 rows.
QROWS = 32


def sublanes(dtype) -> int:
    """Rows of one native TPU tile: 8 for 32-bit, 16 for 16-bit, 32 for
    8-bit values (64-bit values have no TPU tile; 8 keeps them working
    in interpret mode)."""
    return 8 * max(1, 4 // np.dtype(dtype).itemsize)


def tileable(dtype) -> bool:
    """True when the compiled kernels can hold ``dtype`` (not 64-bit)."""
    return np.dtype(dtype).itemsize <= 4


def slot_shape(bs: int, dtype, qblock: Optional[int] = None) -> Tuple[int, int]:
    """``(rows, lanes)`` of a buffer slot holding ``bs`` elements.

    Plain slots are ``LANES`` wide with rows padded to the dtype's tile
    (``sublanes(dtype) * LANES`` elements).  Quantized-wire slots
    (``qblock`` given) hold one quantization block per row, padded to
    ``QROWS`` rows so the int8 payload tiles too.
    """
    if qblock is not None:
        return -(-max(1, -(-bs // qblock)) // QROWS) * QROWS, int(qblock)
    sub = sublanes(dtype)
    return -(-max(1, -(-bs // LANES)) // sub) * sub, LANES
