"""Op kind ``lasso_fit``: upstream's lasso trial (``benchmarks/lasso``:
``ht.regression.Lasso(max_iter, tol=-1).fit(data, labels)``, timed trials).

One op = one whole ``ht.regression.Lasso(lam, max_iter, tol).fit(x, y)`` of
the resident rows and labels, both born split=0, through the public API. The
fit returns when theta is final: it reads the change of its last sweep on the
host, and that change is computed from theta. theta, 2 KB, stays on the device
until the check, as upstream copies nothing to the host. Every trial fits the
same rows from theta = 0, as upstream's trials do.

The comparison (``check``) runs the configuration's plain reference once
(``references/lasso_f32.py``: the second moments summed over row blocks and
finished in float64, then the same coordinate steps in float64) and holds
each sampled theta against it: ``theta_gap`` the largest difference of a
coefficient over the largest coefficient, ``support_wrong`` the coefficients
that are zero on one side and further than ``theta_gap``'s limit from it on
the other, ``objective_gap`` the relative gap of the two objectives, both
priced from the reference's moments (a theta that is not finite reads
infinite gaps), and ``lasso_path_wrong`` 1 unless the
fit took the configuration's mode, ran every sweep and returned theta as the
configuration guarantees it. The mode and the sweeps are the program's own
report: after the window, one more fit of the same operands with telemetry
on, and what it added to ``fusion.cache_stats()``'s ``phase_lasso_*`` keys
(Gram mode alone has a ``gram`` phase). A program that reports nothing, or
another mode, fails it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import heat_tpu as ht
from chipbench import design
from heat_tpu.core import fusion
from heat_tpu.regression import lasso


def require_stated_multiplication(cfg: dict) -> None:
    """The configuration states the precision its products are taken in. A
    program whose lasso cannot say it multiplies ``cfg["dtype"]`` rows that way
    (``heat_tpu.regression.lasso.mxu_precision``, the rule of ``ops/mxu.py``
    that the fit reads; before ISSUE 40 the Gram of float32 rows was one
    bfloat16 pass) does not run this configuration: the run ends here, at
    once, with an exit code and no line."""
    asks = getattr(lasso, "mxu_precision", lambda dtype: None)(jnp.dtype(cfg["dtype"]))
    if cfg["multiplication"] == "float32" and asks != jax.lax.Precision.HIGHEST:
        raise SystemExit(
            f"lasso_fit: the configuration states {cfg['multiplication']} multiplication of "
            f"{cfg['dtype']} rows and this program's Lasso.fit does not offer it. No result."
        )


class Op:
    results_per_op = 1

    def __init__(self, ctx):
        cfg, data = ctx.config, ctx.config["data"]
        require_stated_multiplication(cfg)
        self.ctx, self.x_low, self.fitted = ctx, None, None
        comm = ht.get_comm()
        self.n, self.m = int(cfg["rows"][str(int(ctx.chips))]), int(cfg["features"])
        # the coefficients the labels are made from: the intercept, and +-1 at columns drawn from the seed
        rng = np.random.default_rng(int(ctx.seed))
        truth = np.zeros(self.m, np.float32)
        truth[0] = data["intercept"]
        truth[1 + rng.choice(self.m - 1, size=int(data["nonzero"]), replace=False)] = rng.choice([-1.0, 1.0], size=int(data["nonzero"]))
        sharding = comm.sharding(2, 0)
        self.rows, self.labels = design.correlated_design(
            ctx.seed, (self.n, self.m), data["loc"], data["rho"], truth, data["noise"], data["block_rows"], sharding, sharding
        )
        self.x, self.y = ht.array(self.rows, split=0), ht.array(self.labels, split=0)

    def run(self, trial: int):
        return self._fit(self.x)

    def control_run(self, trial: int):
        """The control: the program's own lower-precision path, the same fit
        on the rows cast to ``check.control_cast`` (its Gram is then one
        bfloat16 pass). It has to come out not correct."""
        if self.x_low is None:
            self.x_low = self.x.astype(getattr(ht, self.ctx.config["check"]["control_cast"]))
        return self._fit(self.x_low)

    def _fit(self, x):
        cfg = self.ctx.config
        self.fitted = x
        with self.ctx.span("bench.fit"):
            est = ht.regression.Lasso(lam=cfg["lam"], max_iter=cfg["max_iter"], tol=cfg["tol"])
            est.fit(x, self.y)
        theta = est.theta
        as_guaranteed = tuple(theta.shape) == (self.m, 1) and theta.split is None and theta.dtype == ht.float32
        return {"theta": theta.larray, "n_iter": est.n_iter, "as_guaranteed": as_guaranteed}

    def reported(self) -> dict:
        """What the program says of a fit of the operands last fitted: its mode
        and its sweeps, from the counters one fit moves while telemetry is on.
        Empty where the program has no such counters."""
        keys = ("phase_lasso_fits", "phase_lasso_sweeps", "phase_lasso_gram_ns")
        before = fusion.cache_stats()
        if self.fitted is None or not all(k in before for k in keys):
            return {}
        with ht.telemetry.enabled(1):
            self._fit(self.fitted)
        fits, sweeps, gram_ns = (fusion.cache_stats()[k] - before[k] for k in keys)
        if fits != 1:
            return {}
        return {"mode": "gram" if gram_ns > 0 else "residual", "sweeps": sweeps}

    def check(self, answers) -> dict:
        cfg, ref = self.ctx.config, self.ctx.reference
        limits = cfg["check"]
        said = self.reported()
        self.x = self.x_low = self.fitted = None
        g, cy, yy, n = ref.moments(self.rows, self.labels)  # once a run, on the chip
        want = ref.descend(g, cy, n, cfg["lam"], cfg["max_iter"])
        price = ref.objective(want, g, cy, yy, n, cfg["lam"])
        theta_gap = objective_gap = 0.0
        support_wrong = path_wrong = 0
        for _, a in answers:
            ok = a["as_guaranteed"] and int(a["n_iter"]) == int(cfg["max_iter"]) and said == {"mode": cfg["lasso_mode"], "sweeps": int(cfg["max_iter"])}
            path_wrong = max(path_wrong, int(not ok))
            if not a["as_guaranteed"]:
                continue
            got = np.asarray(a["theta"], np.float64).reshape(-1)
            if not np.isfinite(got).all():  # a theta float32 could not hold is no answer: no gap is small enough (NaN would lose every max())
                theta_gap = objective_gap = float("inf")
                continue
            off = np.abs(got - want)
            theta_gap = max(theta_gap, float(off.max() / np.abs(want).max()))
            support_wrong = max(support_wrong, int((((got == 0) != (want == 0)) & (off > limits["theta_gap"] * np.abs(want).max())).sum()))
            objective_gap = max(objective_gap, abs(ref.objective(got, g, cy, yy, n, cfg["lam"]) - price) / price)
        return {
            "theta_gap": [theta_gap, limits["theta_gap"]],
            "support_wrong": [support_wrong, limits["support_wrong"]],
            "objective_gap": [objective_gap, limits["objective_gap"]],
            "lasso_path_wrong": [path_wrong, limits["lasso_path_wrong"]],
        }


def build(ctx) -> Op:
    return Op(ctx)
