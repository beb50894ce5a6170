"""Op kind ``qr_trial``: ``BASELINE.json`` config 4, the tall-skinny
``ht.linalg.qr`` of resident float32 rows born split=0.

One op = one ``ht.linalg.qr(a)`` with its defaults (``method="auto"``,
``calc_q=True``) through the public API, waited for on the device: Q (m, n)
and R (n, n) are whole on their chips when the ``bench.qr`` span ends. The
call itself reads one scalar (CholeskyQR2's probe) and returns while the
product that forms Q still runs; the pick of ``check_rows`` whole rows of Q
(indices from the seed's table, row = trial index; row slices on the chip,
not a gather) is queued behind it with R laid under the picked rows, and the
host waits ONCE more, for that one small array: it is cut from Q's finished
buffer, so Q is whole when it arrives. Two round trips an op, the program's
and this one: on a machine in its slow mode (PERF.md section 7) every round
trip costs a millisecond more, and an op that made five spread as the mode
came and went. Then the op drops Q: an answer is the sampled rows, R and
whether Q and R are laid out as the configuration guarantees, never the
2.56 GB of Q.

The comparison (``check``) never forms Q Q^T or a second Q: ``r_gap`` holds
R against the reference's Householder R (``references/qr_tall_f32.py``, signs
turned on both sides), ``q_gap`` the sampled rows of Q against the rows of A
solved against that R, ``recon_gap`` the program's own rows of Q times its own
R against the rows of A, and ``qr_path_wrong`` is 1 unless every entry of R's
diagonal came back positive: a Cholesky factor's mark, which a Householder
fallback (mixed signs) does not leave; an untraced run has no counter to ask.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import heat_tpu as ht
from chipbench import datagen

TRIALS = 4096  # rows of the index table; trial i is checked on row i % TRIALS


class Op:
    results_per_op = 1

    def __init__(self, ctx):
        cfg, data = ctx.config, ctx.config["data"]
        self.ctx, self.a_low = ctx, None
        self.comm = ht.get_comm()
        chips = int(ctx.chips)
        self.m, self.n = int(cfg["rows"][str(chips)]), int(cfg["columns"])
        self.per_chip = self.m // chips
        # column j of i.i.d. N(loc, scale) rows times 10^(-decades j / (n - 1))
        scales = (10.0 ** (-float(data["column_scale_decades"]) * np.arange(self.n) / (self.n - 1))).astype(np.float32)
        sharding = self.comm.sharding(2, 0)
        drawn = datagen.normal(ctx.seed, (self.m, self.n), data["loc"], data["scale"], sharding)
        self.rows = jax.jit(lambda x, s: x * s, out_shardings=sharding)(drawn, scales)
        del drawn
        self.a = ht.array(self.rows, split=0)
        axis, k = self.comm.axis_name, int(ctx.traffic["check_rows"]) // chips
        # the rows every trial is checked on, drawn once from the seed and kept on the chips: row j of a chip's
        # k comes from the j-th of k equal strata of its block (distinct, sorted), trial i takes table row i
        stride = self.per_chip // k
        draws = np.random.default_rng(int(ctx.seed)).integers(0, stride, size=(chips, TRIALS, k))
        self.table = (np.arange(k) * stride + draws).astype(np.int32)
        self.table_on_chips = jax.device_put(self.table, self.comm.sharding(3, 0))

        def rows_of(block, table, trial):
            # row by row: one gather of a few rows of a GB-sized block costs XLA:TPU fifty times these slices
            local = table[0, trial]
            return jnp.concatenate([jax.lax.dynamic_slice_in_dim(block, local[j], 1, axis=0) for j in range(k)])

        # every chip picks its own sampled rows out of its own block (of Q in a trial, of A in the check)
        self.pick = jax.jit(
            jax.shard_map(
                rows_of, mesh=self.comm.mesh,
                in_specs=(P(axis, None), P(axis, None, None), P()), out_specs=P(axis, None),
            )
        )
        self.stack = jax.jit(lambda rows, r: jnp.concatenate([rows, r]))  # one array, one read

    def run(self, trial: int):
        return self._trial(self.a, trial)

    def control_run(self, trial: int):
        """The control: the same call on the rows cast to ``check.control_cast``
        (``qr`` factors half-precision rows in float32: the rows are rounded,
        the arithmetic is the sound run's). It has to come out not correct."""
        if self.a_low is None:
            self.a_low = self.a.astype(getattr(ht, self.ctx.config["check"]["control_cast"]))
        return self._trial(self.a_low, trial)

    def _trial(self, a, trial: int):
        cfg, chips = self.ctx.config, int(self.ctx.chips)
        with self.ctx.span("bench.qr"):
            q, r = ht.linalg.qr(a, method=cfg["method"], calc_q=cfg["calc_q"])
            whole = q.larray
            picked = self.pick(whole, self.table_on_chips, np.int32(trial % TRIALS))
            both = np.asarray(self.stack(picked, r.larray))  # the one wait: Q is whole when its rows are here
        blocks = sorted((s.index[0].start or 0, tuple(s.data.shape)) for s in whole.addressable_shards)
        laid_out = (
            tuple(q.shape) == (self.m, self.n) and q.split == 0 and q.dtype == ht.float32
            and len(whole.sharding.device_set) == chips
            and blocks == [(c * self.per_chip, (self.per_chip, self.n)) for c in range(chips)]
            and tuple(r.shape) == (self.n, self.n) and r.split is None and r.dtype == ht.float32
        )
        return {"rows": both[: -self.n], "r": both[-self.n :], "laid_out": laid_out}  # q and whole, the 2.56 GB, end here

    def check(self, answers) -> dict:
        limits, ref = self.ctx.config["check"], self.ctx.reference
        self.a = self.a_low = None
        want_r = np.asarray(ref.r_factor(self.rows), np.float64)  # once a run, on the chip
        col = np.sqrt((want_r * want_r).sum(axis=0))
        r_gap = q_gap = recon = 0.0
        wrong = path_wrong = 0
        for trial, ans in answers:
            a_rows = np.asarray(self.pick(self.rows, self.table_on_chips, np.int32(trial % TRIALS)), np.float64)
            got_r, got_q = np.asarray(ans["r"], np.float64), np.asarray(ans["rows"], np.float64)
            if not ans["laid_out"] or got_q.shape != a_rows.shape or got_r.shape != want_r.shape:
                wrong = 1
                continue
            diag = np.diagonal(got_r)
            path_wrong = max(path_wrong, int(not (diag > 0).all()))
            sign = np.where(diag < 0, -1.0, 1.0)  # the reference's convention on the program's side too
            r_gap = max(r_gap, float((np.sqrt((((sign[:, None] * got_r) - want_r) ** 2).sum(axis=0)) / col).max()))
            q_gap = max(q_gap, float(np.abs(got_q * sign[None, :] - ref.q_rows(a_rows, want_r)).max() * np.sqrt(self.m)))
            norm = np.sqrt((a_rows * a_rows).sum(axis=1))
            recon = max(recon, float((np.sqrt(((got_q @ got_r - a_rows) ** 2).sum(axis=1)) / norm).max()))
        return {
            "r_gap": [r_gap, limits["r_gap"]],
            "q_gap": [q_gap, limits["q_gap"]],
            "recon_gap": [recon, limits["recon_gap"]],
            "split_wrong": [wrong, limits["split_wrong"]],
            "qr_path_wrong": [path_wrong, limits["qr_path_wrong"]],
        }


def build(ctx) -> Op:
    return Op(ctx)
