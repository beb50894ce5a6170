"""One full distance matrix of n rows of f features, on one device of
``chips``: the device's n / chips rows of the (n, n) float32 result must be
written once, and every operand row read once (its own block and the
visiting ones). FLOP are 2 per row pair and feature (the expansion's
product; the direct form's difference-and-square counts the same), at the
chip's bfloat16 rate whatever passes a float32 product takes. At 64 features
that is 32 FLOP per 4 bytes written, 8 a byte against the chip's 240:
HBM-bound by the count. Zero-filling the result, a tile stored twice (product,
then copied into place), extra MXU passes and operand rotations are
implementation traffic and are not counted."""

from __future__ import annotations

from chipbench.rooflines import least


def per_op(config: dict, chips: int, peaks: dict) -> dict:
    n, f = float(config["rows"][str(chips)]), float(config["features"])
    here = n / chips
    return least(here * n * 4.0 + n * f * 4.0, 2.0 * here * n * f, peaks)
