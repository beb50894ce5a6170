"""Least time the chip needs for an op's algorithmic work, from shapes.

Each function returns, for ONE op on ONE device, ``{"bytes", "flops",
"seconds", "bound"}``: the bytes that must cross HBM and the floating-point
operations whatever implements the op, the larger of bytes/peak-bandwidth and
flops/peak-rate, and which of the two it is. Implementation traffic (layout
copies, second passes, temporaries) is not counted, so no implementation can
read over 100 % of it.
"""

from __future__ import annotations

from chipbench import spec


def peaks(device_kind: str) -> dict:
    """The chip's peaks from ``chipbench/peaks.json``. Unknown kind = error."""
    table = spec.load_json("peaks.json")
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}: add it to chipbench/peaks.json with its source")
    return table[device_kind]


def least(bytes_: float, flops: float, peaks: dict) -> dict:
    t_mem = bytes_ / peaks["hbm_bytes_per_s"]
    t_cmp = flops / peaks["bf16_flops_per_s"]
    return {
        "bytes": bytes_, "flops": flops, "seconds": max(t_mem, t_cmp),
        "bound": "hbm" if t_mem >= t_cmp else "compute",
    }
