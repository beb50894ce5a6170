"""Op kind ``moments_trial``: upstream's statistical-moments trial
(benchmarks/statistical_moments/heat-cpu.py:21-28).

One op = six results on one operand, ``ht.mean`` and ``ht.std`` over axis
None, 0 and 1, each forced and waited for before the next call: scalars are
read with ``float()``, vector results are waited for on the device (upstream
copies none to the host). The traffic mix names the operand: the resident
data set, or the small one beside it with the resident one left untouched.
"""

from __future__ import annotations

import numpy as np

import heat_tpu as ht
from chipbench import datagen

AXES = (("all", None), ("0", 0), ("1", 1))


class Op:
    results_per_op = 6

    def __init__(self, ctx):
        cfg, data = ctx.config, ctx.config["data"]
        self.ctx, self.x_low = ctx, None
        comm = ht.get_comm()
        self.resident = datagen.normal(
            ctx.seed, cfg["resident_shape"], data["loc"], data["scale"], comm.sharding(2, 0)
        )
        self.resident_x = ht.array(self.resident, split=0)
        if ctx.traffic["operand"] == "resident":
            self.rows, self.x = self.resident, self.resident_x
        else:
            self.rows = datagen.normal(ctx.seed, cfg["small_shape"], data["loc"], data["scale"], None, stream=1)
            self.x = ht.array(self.rows, split=0)

    def run(self, trial: int):
        return self._trial(self.x)

    def control_run(self, trial: int):
        """The control: the program's own lower-precision path, the same
        trial on the operand cast to ``check.control_cast``. It has to come
        out not correct."""
        if self.x_low is None:
            self.x_low = self.x.astype(getattr(ht, self.ctx.config["check"]["control_cast"]))
        return self._trial(self.x_low)

    def _trial(self, x):
        span, out = self.ctx.span, {}
        for tag, axis in AXES:
            for fn_name, fn in (("mean", ht.mean), ("std", ht.std)):
                with span("bench.record"):
                    r = fn(x, axis=axis)
                with span("bench.force"):
                    if axis is None:
                        value = float(r)
                    else:
                        value = r.larray
                        value.block_until_ready()
                out[f"{fn_name}_{tag}"] = value
        return out

    def check(self, answers) -> dict:
        limits = self.ctx.config["check"]
        self.x = self.resident_x = self.x_low = None
        ref = self.ctx.reference.moments(self.rows)
        gaps = {"mean": 0.0, "std": 0.0}
        for _, a in answers:
            for key, want in ref.items():
                got = np.asarray(a[key]).astype(np.float64)
                gap = float(np.abs(got - want).max() / np.abs(want).max()) if got.shape == want.shape else float("inf")
                kind = key.split("_")[0]
                gaps[kind] = max(gaps[kind], gap)
        return {
            "mean_gap": [gaps["mean"], limits["mean_gap"]],
            "std_gap": [gaps["std"], limits["std_gap"]],
        }


def build(ctx) -> Op:
    return Op(ctx)
