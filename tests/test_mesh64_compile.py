"""Compile-time scaling to a 64-device mesh (BASELINE.md's 1→64-chip north
star). The big distributed programs — panel QR, merge-exchange sort, exscan,
the distance ring, the fused triangular solve and det — are built around
``fori_loop``/``lax.cond``/one-shot collectives precisely so program size
and compile time stay bounded as the mesh grows (the reference CI scales by
adding MPI *processes*, reference Jenkinsfile:24-28; a single-controller
framework must scale the *program* instead).

The probe runs in a subprocess with 64 forced host devices and tiny shapes:
it compiles (never converges) each program and reports wall times plus the
collective-instruction count of the HLO, which must be O(1) in p.
"""

import json
import os
import subprocess
import sys
import unittest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import json, re, time
import jax

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp

sys_path_marker = None
import heat_tpu as ht

p = len(jax.devices())
assert p == 64, f"expected 64 forced devices, got {p}"
comm = ht.get_comm()
out = {"devices": p}


def timed(name, build):
    t0 = time.perf_counter()
    hlo = build()
    out[name + "_compile_s"] = round(time.perf_counter() - t0, 2)
    if hlo is not None:
        coll = re.findall(r"(?:all-gather|all-reduce|all-to-all|collective-permute)\(", hlo)
        out[name + "_collective_ops"] = len(coll)


# --- panel QR (split=1 blocked CGS2 loop) --------------------------------
from heat_tpu.core.linalg.qr import _panel_program

def build_panel():
    fn = _panel_program(comm.mesh, comm.axis_name, 4 * p, 2, 2 * p, p, "float32")
    return fn.lower(jnp.zeros((4 * p, 2 * p), jnp.float32)).compile().as_text()

timed("panel_qr", build_panel)

# --- merge-exchange sort (p rounds, 2 pairings) --------------------------
from heat_tpu.core.manipulations import _dist_sort_program

def build_sort():
    fn = _dist_sort_program(comm.mesh, comm.axis_name, p, 0, 1, False, True)
    return fn.lower(
        jnp.zeros((2 * p,), jnp.float32), jnp.zeros((2 * p,), jnp.int64)
    ).compile().as_text()

timed("sort", build_sort)

# --- exscan with a custom fold (gather + fori fold) ----------------------
from heat_tpu.core import communication as comm_mod
from jax.sharding import PartitionSpec as P

def build_exscan():
    def kern(x):
        return comm_mod.exscan(x, comm.axis_name, p, op="prod")

    fn = jax.jit(
        jax.shard_map(
            kern, mesh=comm.mesh, in_specs=P(comm.axis_name), out_specs=P(comm.axis_name),
            check_vma=False,
        )
    )
    return fn.lower(jnp.ones((2 * p,), jnp.float32)).compile().as_text()

timed("exscan", build_exscan)

# --- the distance engine's tile program (fori rotations of the operand shard) ----
from heat_tpu.spatial.distance import _sq_euclidian_fast, _tile_program

def build_ring():
    fn = _tile_program(comm.mesh, comm.axis_name, p, _sq_euclidian_fast)
    x = jnp.zeros((2 * p, 4), jnp.float32)
    return fn.lower(x, x).compile().as_text()

timed("ring", build_ring)

# --- fused distributed triangular solve ----------------------------------
from heat_tpu.core.linalg.solver import _tri_solve_program

def build_solve():
    fn = _tri_solve_program(
        comm.mesh, comm.axis_name, p, 2 * p, 1, 2, p, tuple(range(p)), True, "float32"
    )
    return fn.lower(
        jnp.zeros((2 * p, 2 * p), jnp.float32), jnp.zeros((2 * p, 1), jnp.float32)
    ).compile().as_text()

timed("tri_solve", build_solve)

# --- fused distributed det ------------------------------------------------
from heat_tpu.core.linalg.basics import _det_program

def build_det():
    fn = _det_program(
        comm.mesh, comm.axis_name, p, 2 * p, 2, p, tuple(range(p)), "float32"
    )
    return fn.lower(jnp.zeros((2 * p, 2 * p), jnp.float32)).compile().as_text()

timed("det", build_det)

# --- fused distributed cholesky ------------------------------------------
from heat_tpu.core.linalg.basics import _cholesky_program

def build_cholesky():
    fn = _cholesky_program(
        comm.mesh, comm.axis_name, p, 2 * p, 2, p, tuple(range(p)), "float32"
    )
    return fn.lower(jnp.zeros((2 * p, 2 * p), jnp.float32)).compile().as_text()

timed("cholesky", build_cholesky)

# --- jnp Lloyd iteration loop (the weak-scaling benchmark's program) ------
from heat_tpu.cluster.kmeans import _lloyd_run

def build_lloyd():
    data = jax.device_put(jnp.zeros((4 * p, 4), jnp.float32), comm.sharding(2, 0))
    c0 = jnp.zeros((2, 4), jnp.float32)
    return jax.jit(lambda d, c: _lloyd_run(d, c, 2, 10, -1.0)).lower(data, c0).compile().as_text()

timed("lloyd10", build_lloyd)

# --- lasso Gram mode: the descent is collective-FREE, precompute pays 2 ----
from heat_tpu.regression.lasso import _gram_precompute, lasso_descent

def build_lasso_gram_precompute():
    # the rows as they lie, (n, m) split=0: each device contracts axis 0 of its own rows (ISSUE 40)
    x = jax.device_put(jnp.zeros((4 * p, 6), jnp.float32), comm.sharding(2, 0))
    y = jax.device_put(jnp.zeros((4 * p, 1), jnp.float32), comm.sharding(2, 0))
    return _gram_precompute(comm.mesh, comm.axis_name).lower(x, y).compile().as_text()

timed("lasso_gram_pre", build_lasso_gram_precompute)

def build_lasso_gram_descent():
    # every sweep and the convergence check in one program (ISSUE 41), on G and cy
    # replicated over the mesh as the precompute leaves them: each device runs it whole
    G = jax.device_put(jnp.zeros((6, 6), jnp.float32), comm.sharding(2, None))
    cy = jax.device_put(jnp.zeros((6,), jnp.float32), comm.sharding(1, None))
    hlo = lasso_descent.lower(G, cy, jnp.float32(0.1), 4 * p, jnp.int32(30), jnp.float32(-1.0)).compile().as_text()
    out["lasso_gram_descent_loops"] = len(re.findall(r" while\(", hlo))
    return hlo

timed("lasso_gram_descent", build_lasso_gram_descent)

print(json.dumps(out))
"""


class TestMesh64Compile(unittest.TestCase):
    @classmethod
    def setUpClass(cls):
        env = os.environ.copy()
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
        env.pop("HEAT_TPU_TEST_DEVICES", None)
        proc = subprocess.run(
            [sys.executable, "-c", CHILD],
            cwd=REPO,
            env=env,
            capture_output=True,
            text=True,
            timeout=900,
        )
        if proc.returncode != 0:
            raise AssertionError(
                f"64-device compile probe failed:\n{proc.stderr[-3000:]}"
            )
        cls.out = json.loads(proc.stdout.strip().splitlines()[-1])

    NAMES = (
        "panel_qr", "sort", "exscan", "ring", "tri_solve", "det", "cholesky",
        "lloyd10", "lasso_gram_pre", "lasso_gram_descent",
    )

    def test_all_programs_compiled(self):
        for name in self.NAMES:
            self.assertIn(f"{name}_compile_s", self.out, f"{name} did not compile")

    def test_compile_times_bounded(self):
        # generous bound per program on a loaded CI box; the failure mode
        # being guarded (O(p)+ unrolled programs) costs minutes, not seconds
        for name in self.NAMES:
            self.assertLess(
                self.out[f"{name}_compile_s"], 120.0,
                f"{name} compile time blew up at mesh 64: {self.out}",
            )

    def test_collective_count_o1(self):
        # fori_loop/cond bodies keep the HLO's collective-instruction count
        # independent of p — a small constant, nowhere near O(p)=64
        for name, bound in (
            ("panel_qr", 8),
            ("sort", 12),
            ("exscan", 6),
            # one collective-permute of the operand shard in the loop body
            # (a start/done pair at most), whatever p
            ("ring", 2),
            ("tri_solve", 6),
            ("det", 8),
            ("cholesky", 8),
            # a 10-iteration Lloyd program carries a constant handful of
            # all-reduces, NOT 10x per-iteration growth
            ("lloyd10", 4),
            ("lasso_gram_pre", 2),
        ):
            self.assertLessEqual(
                self.out[f"{name}_collective_ops"], bound,
                f"{name} collective ops scale with p: {self.out}",
            )

    def test_lasso_gram_descent_collective_free(self):
        # the covariance-update sweeps, all of a fit in one program, run on
        # replicated (m,)-vectors only: ZERO collectives — the whole point of
        # Gram mode (the per-feature all-reduce of the residual form was the
        # lasso weak-scaling cost), in a loop of sweeps around a loop of steps
        self.assertEqual(self.out["lasso_gram_descent_collective_ops"], 0, self.out)
        self.assertEqual(self.out["lasso_gram_descent_loops"], 2, self.out)
