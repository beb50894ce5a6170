"""Op kind ``cdist_trial``: upstream's distance-matrix trial
(benchmarks/distance_matrix/heat-gpu.py:20-34).

One op = one ``ht.spatial.cdist(x, quadratic_expansion=...)`` of the resident
rows through the public API, waited for on the device: the (n, n) result is
whole on its chips when the ``bench.cdist`` span ends. Outside that span the
op reads ``check_rows_per_chip`` whole rows of the result from EVERY chip's
row block (indices from the seed's table, row = trial index; the pick is queued on the
device behind the distance matrix, so the host waits once), so that every
(row block, column block) tile is sampled, and drops the result: an answer is
the sampled rows (25.6 MB on four chips), never the 40 GB matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import heat_tpu as ht
from chipbench import datagen
from heat_tpu.spatial import distance


def require_stated_multiplication(cfg: dict) -> None:
    """The configuration states the precision its products are taken in. A
    program that cannot say it multiplies ``cfg["dtype"]`` rows that way
    (``heat_tpu.spatial.distance.mxu_precision``, the rule of
    ``heat_tpu/ops/mxu.py``; before ISSUE 33 the expansion's ``x @ y.T`` ran
    on bfloat16-rounded operands) does not run this configuration: the run
    ends here, at once, with an exit code and no line."""
    asks = getattr(distance, "mxu_precision", lambda dtype: None)(jnp.dtype(cfg["dtype"]))
    if cfg["multiplication"] == "float32" and asks != jax.lax.Precision.HIGHEST:
        raise SystemExit(
            f"cdist_trial: the configuration states {cfg['multiplication']} multiplication of "
            f"{cfg['dtype']} rows and this program's distance engine does not offer it. No result."
        )


TRIALS = 4096  # rows of the index table; trial i is checked on row i % TRIALS


class Op:
    results_per_op = 1

    def __init__(self, ctx):
        cfg, data = ctx.config, ctx.config["data"]
        require_stated_multiplication(cfg)
        self.ctx, self.x_low = ctx, None
        self.comm = ht.get_comm()
        self.n = int(cfg["rows"][str(ctx.chips)])
        self.per_chip = self.n // int(ctx.chips)
        self.rows = datagen.normal(
            ctx.seed, (self.n, cfg["features"]), data["loc"], data["scale"], self.comm.sharding(2, 0)
        )
        self.x = ht.array(self.rows, split=0)
        axis, k, chips = self.comm.axis_name, int(ctx.traffic["check_rows_per_chip"]), int(ctx.chips)
        # the rows every trial is checked on, drawn once from the seed and kept on the chips: row j of a chip's
        # k comes from the j-th of k equal strata of its block (distinct, sorted), trial i takes table row i
        stride = self.per_chip // k
        draws = np.random.default_rng(int(ctx.seed)).integers(0, stride, size=(chips, TRIALS, k))
        self.table = (np.arange(k) * stride + draws).astype(np.int32)
        self.table_on_chips = jax.device_put(self.table, self.comm.sharding(3, 0))

        def rows_of(block, table, trial):
            # row by row: as one gather XLA:TPU reads 31 ms for 16 rows of a 10 GB block, as slices 0.6
            local = table[0, trial]
            return jnp.concatenate([jax.lax.dynamic_slice_in_dim(block, local[j], 1, axis=0) for j in range(k)])

        # every chip picks its own sampled rows out of its own block of the result
        self.pick = jax.jit(
            jax.shard_map(
                rows_of, mesh=self.comm.mesh,
                in_specs=(P(axis, None), P(axis, None, None), P()), out_specs=P(axis, None),
            )
        )

    def sampled(self, trial: int) -> np.ndarray:
        """(chips, check_rows_per_chip) row indices, local to each chip's
        block, that trial ``trial`` is checked on."""
        return self.table[:, trial % TRIALS]

    def run(self, trial: int):
        return self._trial(self.x, trial)

    def control_run(self, trial: int):
        """The control: the same call on the rows cast to ``check.control_cast``.
        It has to come out not correct."""
        if self.x_low is None:
            self.x_low = self.x.astype(getattr(ht, self.ctx.config["check"]["control_cast"]))
        return self._trial(self.x_low, trial)

    def _trial(self, x, trial: int):
        # the pick is queued behind the distance matrix before the host waits
        # for either, and its indices are on the chips already: one round trip
        # an op and nothing sent up (a 17 ms op with 2 ms of host time in it
        # spreads as the host does)
        with self.ctx.span("bench.cdist"):
            d = ht.spatial.cdist(x, quadratic_expansion=self.ctx.config["quadratic_expansion"])
            whole = d.larray
            picked = self.pick(whole, self.table_on_chips, np.int32(trial % TRIALS))
            jax.block_until_ready(whole)
        blocks = sorted((s.index[0].start or 0, tuple(s.data.shape)) for s in whole.addressable_shards)
        laid_out = (
            tuple(d.shape) == (self.n, self.n) and d.split == 0 and len(whole.sharding.device_set) == int(self.ctx.chips)
            and blocks == [(c * self.per_chip, (self.per_chip, self.n)) for c in range(int(self.ctx.chips))]
        )
        return {"rows": jax.block_until_ready(picked), "laid_out": laid_out}  # d and whole, the matrix, end here

    def check(self, answers) -> dict:
        limits = self.ctx.config["check"]
        self.x = self.x_low = None
        x = np.asarray(self.rows, np.float64)
        norm = np.sqrt(np.einsum("jf,jf->j", x, x))
        dist = diag = 0.0
        wrong = 0
        for trial, a in answers:
            local = self.sampled(trial)
            idx = (local + self.per_chip * np.arange(local.shape[0])[:, None]).reshape(-1)
            got = np.asarray(a["rows"], np.float64)
            want = self.ctx.reference.rows(x, idx)
            wrong = max(wrong, int(not a["laid_out"] or got.shape != want.shape))
            at = np.arange(len(idx))
            diag = max(diag, float((got[at, idx] / norm[idx]).max()))
            gap = np.abs(got - want) / (norm[idx][:, None] + norm[None, :])
            gap[at, idx] = 0.0  # the diagonal is diag_gap's
            dist = max(dist, float(gap.max()))
        return {
            "dist_gap": [dist, limits["dist_gap"]],
            "diag_gap": [diag, limits["diag_gap"]],
            "split_wrong": [wrong, limits["split_wrong"]],
        }


def build(ctx) -> Op:
    return Op(ctx)
