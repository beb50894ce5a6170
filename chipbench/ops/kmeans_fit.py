"""Op kind ``kmeans_fit``: upstream's k-means trial
(benchmarks/kmeans/heat-cpu.py:20-26).

One op = one whole ``ht.cluster.KMeans(n_clusters, init, max_iter, tol).fit(x)``
of the resident rows through the public API, from initial centres that are
rows of the operand drawn from (seed, trial index). The fit returns when its
centres, labels and inertia are final (it reads the inertia on the host);
the labels stay on the device, as upstream copies none to the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import heat_tpu as ht
from chipbench import datagen
from heat_tpu.ops import lloyd


def require_stated_multiplication(cfg: dict) -> None:
    """The configuration states the precision its products are taken in. A
    program that cannot say it multiplies ``cfg["dtype"]`` rows that way
    (``heat_tpu.ops.lloyd.mxu_precision``; before ISSUE 29 a float32 fit
    multiplied in one bfloat16 pass) does not run this configuration: the
    run ends here, at once, with an exit code and no line."""
    asks = getattr(lloyd, "mxu_precision", lambda dtype: None)(jnp.dtype(cfg["dtype"]))
    if cfg["multiplication"] == "float32" and asks != jax.lax.Precision.HIGHEST:
        raise SystemExit(
            f"kmeans_fit: the configuration states {cfg['multiplication']} multiplication of "
            f"{cfg['dtype']} rows and this program's Lloyd paths do not offer it. No result."
        )


class Op:
    results_per_op = 1

    def __init__(self, ctx):
        cfg, data = ctx.config, ctx.config["data"]
        require_stated_multiplication(cfg)
        self.ctx, self.x_low = ctx, None
        comm = ht.get_comm()
        self.n = int(cfg["rows_per_chip"]) * int(ctx.chips)
        self.rows = datagen.normal(
            ctx.seed, (self.n, cfg["features"]), data["loc"], data["scale"], comm.sharding(2, 0)
        )
        self.x = ht.array(self.rows, split=0)

    def initial_centres(self, trial: int) -> jax.Array:
        """The rows of the operand that trial ``trial`` starts from."""
        rng = np.random.default_rng([int(self.ctx.seed), trial % (1 << 32)])
        picked = np.sort(rng.choice(self.n, size=int(self.ctx.config["n_clusters"]), replace=False))
        return self.rows[picked]

    def run(self, trial: int):
        return self._fit(self.x, trial)

    def control_run(self, trial: int):
        """The control: the program's own lower-precision path, the same fit
        on the rows cast to ``check.control_cast``. It has to come out not
        correct."""
        if self.x_low is None:
            self.x_low = self.x.astype(getattr(ht, self.ctx.config["check"]["control_cast"]))
        return self._fit(self.x_low, trial)

    def _fit(self, x, trial: int):
        cfg, span = self.ctx.config, self.ctx.span
        with span("bench.init"):
            init = ht.array(self.initial_centres(trial))
            km = ht.cluster.KMeans(
                n_clusters=cfg["n_clusters"], init=init, max_iter=cfg["max_iter"], tol=cfg["tol"]
            )
            mode = km._fused_mode(x)[0] or "jnp"
        with span("bench.fit"):
            km.fit(x)
        return {
            "centers": km.cluster_centers_.larray, "labels": km.labels_.larray,
            "inertia": km.inertia_, "n_iter": km.n_iter_, "mode": mode,
        }

    def check(self, answers) -> dict:
        cfg = self.ctx.config
        limits = cfg["check"]
        self.x = self.x_low = None
        want_mode = cfg["lloyd_mode"][str(self.ctx.chips)]
        gaps = {"centers": 0.0, "inertia": 0.0, "labels": 0.0, "iters_short": 0, "path_wrong": 0}
        for trial, a in answers:
            centers, labels, inertia = self.ctx.reference.lloyd(
                self.rows, self.initial_centres(trial), cfg["max_iter"]
            )
            got = np.asarray(a["centers"], np.float64)
            gaps["centers"] = max(gaps["centers"], float(np.linalg.norm(got - centers) / np.linalg.norm(centers)))
            gaps["inertia"] = max(gaps["inertia"], abs(a["inertia"] - inertia) / inertia)
            gaps["labels"] = max(gaps["labels"], float((a["labels"] != labels).mean()))
            gaps["iters_short"] = max(gaps["iters_short"], cfg["max_iter"] - int(a["n_iter"]))
            gaps["path_wrong"] = max(gaps["path_wrong"], int(a["mode"] != want_mode))
        return {
            "centers_gap": [gaps["centers"], limits["centers_gap"]],
            "inertia_gap": [gaps["inertia"], limits["inertia_gap"]],
            "labels_gap": [gaps["labels"], limits["labels_gap"]],
            "iters_short": [gaps["iters_short"], limits["iters_short"]],
            "lloyd_path_wrong": [gaps["path_wrong"], limits["lloyd_path_wrong"]],
        }


def build(ctx) -> Op:
    return Op(ctx)
