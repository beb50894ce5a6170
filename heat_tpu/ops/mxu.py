"""What a contraction asks of the MXU, from the dtype of its rows alone.

The MXU multiplies bfloat16. Left at XLA's default, float32 operands are
rounded to bfloat16 and multiplied in one pass: a float32 ``KMeans.fit`` came
out 1.1-1.4 % off a plain float32 Lloyd (PERF.md, PR 26) and a float32
``cdist`` 9e-3 off (PR 21). The rule, for every contraction of this library
that takes rows of data: float32 rows (and wider, carried as float32) multiply
in float32, bfloat16 rows keep their one bfloat16 pass with float32
accumulation. No option, flag or environment variable chooses: the dtype does.
The Pallas Lloyd kernel (``ops/lloyd.py``) applies it through
:func:`bf16_pieces`, the XLA paths (``spatial/distance.py``,
``cluster/kmeans.py``) through :func:`matmul`.

Two routes give XLA a float32 product, and :func:`matmul` takes the cheaper
by the product's shape (timed on a v5e, PERF.md PR 33). ``Precision.HIGHEST``
is six bfloat16 passes at the contraction's own depth: right where the
operands are the traffic (2^24 x 16 rows against 8 centres: 4.8 ms, the
default's one pass 3.8, stacked pieces 20.9, which write and read six copies
of the rows). Where the product is far larger than its operands (a distance
matrix: 64 features fill half of the 128-deep array, six times), the three
pieces of each operand stacked along the contraction (six pairs, K = 6 f) are
ONE bfloat16 pass with every piece product exact in the float32 accumulator:
50 000 x 50 000 x 64 in 19.5 ms against 30.5 at ``HIGHEST`` and 18.9 at the
rounded default.

``core/linalg/qr.py`` (CholeskyQR2) keeps to the rule and does not come
through :func:`matmul`: it asks ``HIGHEST`` itself. Its four tall products
(two Grams, Q1 and Q of m x 512 rows; since PR 36 each runs as four block
products of 128 columns that leave out the blocks a triangle does not hold)
have 128 rows and columns and more, so :func:`matmul` would stack pieces for
them, yet none is larger than its operand: the pieces of the m x 512 rows are
six copies of them written and read back, 15 GB at the benchmark's 1 250 000
rows, for a product the size of one. The stack pays where the product dwarfs
its operands, and that is not a question of the product's rows and columns
alone.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["bf16_pieces", "matmul", "mxu_precision"]


def mxu_precision(dtype) -> Optional[jax.lax.Precision]:
    """What a contraction on rows of ``dtype`` asks of XLA: float32 and wider
    multiply in float32 (``HIGHEST``; the default rounds both operands to
    bfloat16 and multiplies once), bfloat16 rows keep their one bfloat16
    pass."""
    return None if dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST


_PIECE_PAIRS = ((2, 0), (0, 2), (1, 1), (1, 0), (0, 1), (0, 0))
"""(piece of a, piece of b), smallest products first: all pairs of the three
pieces but low x low, low x mid and mid x low, which lie under the product's
last float32 bit (``HIGHEST`` leaves the same three out)."""


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """The 2-D ``a @ b`` under the rule: in float32 unless both are bfloat16
    rows. float32 operands whose product has at least an MXU tile (128) of
    rows and of columns go as stacked bfloat16 pieces, everything else (skinny
    products, float64) asks XLA for ``HIGHEST``."""
    dtype = jnp.result_type(a, b)
    if dtype != jnp.float32 or min(a.shape[0], b.shape[1]) < 128:
        return jnp.matmul(a, b, precision=mxu_precision(dtype))
    pa, pb = bf16_pieces(a.astype(dtype)), bf16_pieces(b.astype(dtype))
    return jnp.matmul(
        jnp.concatenate([pa[i] for i, _ in _PIECE_PAIRS], axis=1),
        jnp.concatenate([pb[j] for _, j in _PIECE_PAIRS], axis=0),
        preferred_element_type=dtype,
    )


def bf16_pieces(x: jax.Array) -> tuple:
    """``x`` as bfloat16 arrays that add up to it exactly: itself if it is
    bfloat16, else a float32's significand cut into 8 + 8 + 8 bits. The
    product of two pieces is exact in the MXU's float32 accumulator, so one
    bfloat16 pass over all pairs of pieces is the float32 product.

    The cuts are made on the bits (the low half of the word masked off), not
    by converting to bfloat16 and back: XLA takes a float32 -> bfloat16 ->
    float32 round trip for the identity (``xla_allow_excess_precision``), and
    the remainder it was taken for would be zero (seen on a v5e: centres cut
    this way outside the kernel scored as bfloat16)."""
    if x.dtype == jnp.bfloat16:
        return (x,)

    def head(v):  # the leading 8 bits of the significand: a bfloat16's worth, as float32
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32) & jnp.uint32(0xFFFF0000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    hi = head(x)
    rest = x - hi  # exact: at most 16 bits are left
    mid = head(rest)
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, rest - mid))
