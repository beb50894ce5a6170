"""Moments trial: every forced result must read its operand once
(``std`` needs no second pass in principle); FLOP are a few per element."""

from __future__ import annotations

from chipbench.rooflines import least


def per_op(config: dict, traffic: dict, results_per_op: int, peaks: dict) -> dict:
    shape = config["resident_shape"] if traffic["operand"] == "resident" else config["small_shape"]
    elements = float(shape[0]) * float(shape[1])
    return least(results_per_op * elements * 4.0, results_per_op * 3.0 * elements, peaks)
