"""``ht.regression.Lasso.fit`` at the ``lasso_f32`` configuration's arithmetic
(ISSUE 40): the configuration's plain reference (``chipbench/references/
lasso_f32.py``, loaded through ``chipbench.spec``) in its two forms against
each other, and the program against it on seeded data, in Gram and in residual
mode, on float32 and on bfloat16 rows, replicated and split=0 on the CPU mesh;
the float32 Gram of the rows as they lie against a float64 one and against the
Gram of bfloat16-rounded rows; the generator's unit mean square, and what
upstream's step does on columns of mean square 2 (what refused PR 39); the
device's loop over sweeps against a host's (ISSUE 41: the same sweeps, the
same stop, one read a fit, one compiled program whatever ``max_iter`` and
``tol``); the sweep as one Pallas kernel (``ops/cd.py``, interpreted here)
against the loop, and when a fit takes it; the spans and counters of a fit;
the ``opsplane`` families.

What only the chip shows (the precompute at 3 145 728 x 512: its memory, its
products' precision, its all-reduces over four chips) is compiled for a
described v5e in ``test_kmeans_f32.py``, the one file that holds the topology
fixture.
"""

import ast
import glob
import os
import re
import tempfile
import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from chipbench import design, spec
from heat_tpu.core import fusion, telemetry
from heat_tpu.ops import cd
from heat_tpu.regression import lasso as lasso_mod

N, M, LAM, SWEEPS = 768, 24, 0.1, 30
CHUNK = lasso_mod._SUM_ROWS
_GRAM = jax.jit(lasso_mod.lasso_gram)  # the sums of rows at hand, as the one program takes them on each device
LASSO_KEYS = [f"phase_lasso_{name}_ns" for name in fusion._LASSO_PHASES] + ["phase_lasso_fits", "phase_lasso_sweeps", "phase_lasso_syncs"]

# CPU limits, largest coefficient = 1. Both sides take the same 720 coordinate
# steps; the program's run in float32 on c = cy - G theta, where cy / n is of
# order 1 and c / n at most lam near the fixed point, so a rounding (6e-8) comes
# back ten to fifty times larger: sound fits read at most 3e-6. Rows rounded to
# bfloat16 (2^-9 of every entry) read 2e-4 and more against the reference on
# the rows as they were.
THETA_LIMIT, ROUNDED_AT_LEAST = 1e-5, 1e-4


@pytest.fixture(scope="module")
def reference():
    return spec.load_module("references", "lasso_f32.py")


TRUTH = np.zeros(M, np.float32)
TRUTH[[0, 3, 9, 17]] = 0.5, 1.0, -1.0, 1.0


@pytest.fixture(scope="module")
def problem():
    """The configuration's generator at a CPU size: column 0 all ones, the
    rest off centre, correlated and of unit mean square, as upstream's step
    takes its columns to be; a sparse truth."""
    x, y = design.correlated_design(40, (N, M), 1.0, 0.9, TRUTH, 0.1, N)
    return np.asarray(x), np.asarray(y)


def gap(got, want):
    return float(np.abs(np.asarray(got, np.float64).reshape(-1) - want).max() / np.abs(want).max())


def fit(x, y, split, dtype=ht.float32, max_iter=SWEEPS, tol=-1.0):
    xs = ht.array(x, split=split).astype(dtype)
    est = ht.regression.Lasso(lam=LAM, max_iter=max_iter, tol=tol).fit(xs, ht.array(y, split=split))
    return est


def test_blocked_reference_is_upstreams_loop(reference, problem):
    """``fit_blocks`` (the second moments by row blocks, the steps in float64)
    gives the iterates of ``fit_residual`` (upstream's loop as written,
    float32), whatever the block; a short last block changes nothing."""
    x, y = problem
    slow = reference.fit_residual(x, y, LAM, SWEEPS)
    assert slow[0] != 0 and (slow[1:] == 0).sum() >= M // 2 and (slow[1:] != 0).sum() >= 3  # a lasso: sparse, not empty
    for block in (N, 256, 100):
        assert gap(slow, reference.fit_blocks(x, y, LAM, SWEEPS, block)) < THETA_LIMIT
    fewer = reference.fit_blocks(x, y, LAM, SWEEPS - 1, 256)
    assert gap(slow, fewer) > 10 * THETA_LIMIT  # thirty sweeps are not the fixed point: a sweep left out shows


def test_reference_prices_a_theta_by_the_objective(reference, problem):
    x, y = problem
    g, cy, yy, n = reference.moments(x, y, 256)
    theta = reference.descend(g, cy, n, LAM, SWEEPS)
    x64, y64 = x.astype(np.float64), y.astype(np.float64).reshape(-1)
    direct = 0.5 * np.mean((y64 - x64 @ theta) ** 2) + LAM * np.abs(theta[1:]).sum()
    assert reference.objective(theta, g, cy, yy, n, LAM) == pytest.approx(direct, rel=1e-6)
    assert reference.objective(np.zeros(M), g, cy, yy, n, LAM) > direct  # descent descends


def test_reference_shares_no_code_with_the_program():
    path = os.path.join(spec.HERE, "references", "lasso_f32.py")
    with open(path) as fh:
        tree = ast.parse(fh.read())
    imported = {a.name.split(".")[0] for node in ast.walk(tree) if isinstance(node, ast.Import) for a in node.names}
    imported |= {node.module.split(".")[0] for node in ast.walk(tree) if isinstance(node, ast.ImportFrom) and node.module}
    assert imported <= {"__future__", "functools", "jax", "numpy"}


@pytest.mark.parametrize("split", [None, 0], ids=["replicated", "split0"])
@pytest.mark.parametrize("dtype", [ht.float32, ht.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", ["gram", "residual"])
def test_fit_is_the_plain_reference(reference, problem, monkeypatch, mode, dtype, split):
    """Both modes give the reference's iterate after exactly ``max_iter``
    sweeps. bfloat16 rows are held against the reference on the rows THEY
    hold (rounded once, by the caller): their products are exact in the
    float32 accumulator, so the fit is as close as a float32 one."""
    x, y = problem
    if mode == "residual":
        monkeypatch.setattr(lasso_mod, "_GRAM_MAX_ELEMENTS", 0)
    held = np.asarray(jnp.asarray(x).astype(dtype.jax_type()).astype(jnp.float32))
    est = fit(x, y, split, dtype)
    theta = est.theta
    assert est.n_iter == SWEEPS and theta.shape == (M, 1) and theta.split is None and theta.dtype == ht.float32
    assert gap(theta.numpy(), reference.fit_blocks(held, y, LAM, SWEEPS, 256)) < THETA_LIMIT
    assert np.array_equal(est.coef_.numpy(), theta.numpy()[1:]) and est.intercept_.numpy().reshape(-1)[0] == theta.numpy()[0, 0]


def test_bfloat16_cast_rows_fall_outside_the_limit(reference, problem):
    """The benchmark's control: the same fit on rows rounded to bfloat16,
    against the reference on the rows as they were."""
    x, y = problem
    want = reference.fit_blocks(x, y, LAM, SWEEPS, 256)
    assert gap(fit(x, y, 0, ht.bfloat16).theta.numpy(), want) > ROUNDED_AT_LEAST
    assert gap(fit(x, y, 0).theta.numpy(), want) < THETA_LIMIT


def test_generator_columns_have_unit_mean_square():
    """Columns 1.. of the configuration's generator, at the configuration's
    own ``loc`` and ``rho``: mean square within 2 % of 1 (over all of them;
    one correlated column of 65 536 rows wanders a little further), mean
    1 / sqrt(2); column 0 all ones."""
    data = spec.Cell("lasso_1c").config["data"]
    x, _ = design.correlated_design(40, (1 << 16, 64), data["loc"], data["rho"], np.zeros(64, np.float32), data["noise"], 1 << 13)
    x = np.asarray(x, np.float64)
    assert np.array_equal(x[:, 0], np.ones(len(x)))
    assert abs((x[:, 1:] ** 2).mean() - 1.0) < 0.02 and np.abs((x[:, 1:] ** 2).mean(axis=0) - 1.0).max() < 0.05
    assert abs(x[:, 1:].mean() - np.sqrt(0.5)) < 0.02


def test_columns_of_mean_square_two_diverge(reference, problem):
    """What refused PR 39. ISSUE 39 named the columns 1 + Z B, of mean square
    2 (the generator's, times sqrt(2)): upstream's step sets theta_j =
    soft(rho_j) with no division by the column's mean square, every step
    overshoots, and the iterates grow by a factor every sweep, in the
    reference (float64) and in the program (float32) alike, until float32
    cannot hold them. On the generator's own columns the same sweeps settle."""
    x, _ = problem
    x = x.copy()
    x[:, 1:] *= np.sqrt(np.float32(2.0))
    y = (x @ TRUTH)[:, None] + 0.1 * np.random.default_rng(40).standard_normal((N, 1)).astype(np.float32)
    assert abs(float((x[:, 1:] ** 2).mean()) - 2.0) < 0.1
    sizes = [np.abs(reference.fit_blocks(x, y, LAM, sweeps)).max() for sweeps in (5, 10, 20)]
    assert sizes[0] > 10 and sizes[1] > 100 * sizes[0] and sizes[2] > 1e4 * sizes[1]
    assert gap(fit(x, y, 0, max_iter=10).theta.larray, reference.fit_blocks(x, y, LAM, 10)) < 1e-3  # the same growing iterates
    # a NaN change is not under any tol: the device's loop sweeps on to max_iter, as the host's did
    for tol in (-1.0, 1e-3):
        blown = fit(x, y, 0, max_iter=80, tol=tol)
        assert blown.n_iter == 80 and not np.isfinite(np.asarray(blown.theta.larray)).all()
    settled = [np.abs(reference.fit_blocks(*problem, LAM, sweeps)).max() for sweeps in (5, 30)]
    assert max(settled) < 2.0


def test_tolerance_stops_the_sweeps_and_counts_them(problem):
    x, y = problem
    est = fit(x, y, 0, max_iter=200, tol=1e-3)
    assert 1 < est.n_iter < 200
    assert fit(x, y, 0, max_iter=200, tol=None).n_iter == 200


def _host_descent(sweep, max_iter, tol):
    """The loop ``Lasso._fit`` held before ISSUE 41: one jitted sweep at a
    time, the change taken eagerly and read, ``float(diff) < tol`` on the host."""
    theta = jnp.zeros((M, 1), jnp.float32)
    for done in range(1, max_iter + 1):
        old, theta = theta, sweep(theta)
        diff = float(jnp.sqrt(jnp.mean((theta - old) ** 2)))
        if tol is not None and diff < tol:
            break
    return theta, done, diff


@pytest.mark.parametrize("tol", [None, -1.0, 1e-3, 10.0], ids=["none", "negative", "reached", "first_sweep"])
@pytest.mark.parametrize("mode", ["gram", "residual"])
def test_device_loop_is_the_host_loop(problem, monkeypatch, mode, tol):
    """The descent program stops where a host's loop over the jitted sweep
    does and gives its theta, to the bit on the CPU backend (the sweep is the
    same computation inside the ``while`` as alone)."""
    x, y = problem
    xs, lam = ht.array(x, split=0), jnp.float32(LAM)
    if mode == "gram":
        G, cy = lasso_mod._gram_precompute(xs.comm.mesh, xs.comm.axis_name)(xs.larray, jnp.asarray(y))
        one = jax.jit(lasso_mod.lasso_cd_sweep)
        sweep = lambda theta: one(G, cy, theta, lam, N)
    else:
        monkeypatch.setattr(lasso_mod, "_GRAM_MAX_ELEMENTS", 0)
        XT, one = jnp.transpose(xs.larray), jax.jit(lasso_mod._cd_sweep, static_argnames="precision")
        sweep = lambda theta: one(XT, jnp.asarray(y), theta, lam, precision=lasso_mod.mxu_precision(XT.dtype))
    theta, n_iter, _ = _host_descent(sweep, SWEEPS, tol)
    est = fit(x, y, 0, tol=tol)
    assert est.n_iter == n_iter == {None: SWEEPS, -1.0: SWEEPS, 10.0: 1}.get(tol, n_iter) and 1 <= n_iter <= SWEEPS
    assert np.array_equal(est.theta.numpy(), np.asarray(theta))


def test_descent_returns_the_last_change_and_rounds_tol_up():
    """``_descend``'s third result is the last sweep's change (what the host
    read after every sweep), and ``tol`` becomes the float32 at or above it:
    a float32 ``diff`` is under the one exactly when it is under the other."""
    halve = lambda theta: theta * jnp.float32(0.5)
    theta, n_iter, diff = jax.jit(lambda k, t: lasso_mod._descend(halve, jnp.ones((4, 1), jnp.float32), k, t))(np.int32(3), np.float32(-1))
    assert int(n_iter) == 3 and float(diff) == 0.125 and np.array_equal(theta, np.full((4, 1), 0.125, np.float32))
    _, none_ran, diff = lasso_mod._descend(halve, jnp.ones((4, 1), jnp.float32), np.int32(0), np.float32(-1))
    assert int(none_ran) == 0 and float(diff) == np.inf
    assert lasso_mod._tol_operand(None) == -np.inf and lasso_mod._tol_operand(0.5) == 0.5 and lasso_mod._tol_operand(-1.0) == -1.0
    for tol in (1e-3, 1e-6, 0.1, -0.1, 1e300, 1e-300):
        up = lasso_mod._tol_operand(tol)
        assert up.dtype == np.float32 and float(up) >= tol and float(np.nextafter(up, np.float32(-np.inf))) < tol
    assert np.isnan(lasso_mod._tol_operand(float("nan")))


@pytest.mark.parametrize("mode", ["gram", "residual"])
def test_max_iter_and_tol_are_operands_of_one_program(problem, monkeypatch, mode):
    """An analyst who changes ``max_iter`` or ``tol`` compiles nothing new:
    six fits add ONE entry to the descent program's cache (none where an
    earlier test fitted this shape), not six."""
    x, y = problem
    program = lasso_mod.lasso_descent if mode == "gram" else lasso_mod._descent_residual
    if mode == "residual":
        monkeypatch.setattr(lasso_mod, "_GRAM_MAX_ELEMENTS", 0)
    before = program._cache_size()
    for max_iter in (5, 6, 7):
        for tol in (None, 1e-3):
            assert 1 <= fit(x, y, 0, max_iter=max_iter, tol=tol).n_iter <= max_iter
    assert program._cache_size() - before <= 1 <= program._cache_size()


@pytest.mark.parametrize("m", [24, 256, 512], ids=["whole", "two_blocks", "four_blocks"])
def test_float32_gram_of_the_rows_as_they_lie(m):
    """``lasso_gram`` contracts axis 0 of (n, m) rows: the float64 Gram to
    float32 accuracy, symmetric to the bit where it is taken by blocks, and
    NOT the Gram of bfloat16-rounded rows; ``cy`` alike."""
    rng = np.random.default_rng(m)
    x = (1.0 + rng.standard_normal((3 * m, m))).astype(np.float32)
    y = rng.standard_normal((3 * m, 1)).astype(np.float32)
    with jax.default_matmul_precision("highest"):  # the CPU backend takes float32 products whole; on the chip the rule does
        g, cy = (np.asarray(v, np.float64) for v in _GRAM(jnp.asarray(x), jnp.asarray(y)))
        gl, cyl = (np.asarray(v, np.float64) for v in _GRAM(jnp.asarray(x, jnp.bfloat16), jnp.asarray(y)))
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    want, scale = x64.T @ x64, 3.0 * m * 2.0
    assert g.shape == (m, m) and cy.shape == (m,) and np.array_equal(g, g.T)
    assert np.abs(g - want).max() / scale < 1e-6 and np.abs(cy - (x64.T @ y64)[:, 0]).max() / scale < 1e-6
    assert np.abs(gl - want).max() / scale > 2e-5  # rounded rows: another Gram
    low = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32), np.float64)
    assert np.abs(gl - low.T @ low).max() / scale < 1e-6  # one bfloat16 pass, float32 accumulation: exact products
    assert np.abs(cyl - (low.T @ y64)[:, 0]).max() / scale < 1e-6  # and y is not rounded with them


def test_gram_mode_makes_no_transposed_copy_and_the_rule_reads_the_dtype():
    """The precompute's jaxpr holds no transpose of the rows, reads them in
    chunks inside one loop, and asks ``HIGHEST`` of every product of float32
    rows, the default (one pass) of the Gram of bfloat16 rows; ``cy`` is no
    product at all (a float32 multiply-reduce)."""
    def products(dtype):
        n = 3 * CHUNK + 2
        jaxpr = jax.make_jaxpr(lasso_mod.lasso_gram)(jnp.zeros((n, 512), dtype), jnp.zeros((n, 1), jnp.float32))
        assert sum(e.primitive.name in ("scan", "while") for e in jaxpr.jaxpr.eqns) == 1  # the one loop over the chunks
        eqns, flat = list(jaxpr.jaxpr.eqns), []
        while eqns:
            e = eqns.pop()
            flat.append(e)
            for sub in jax.core.jaxprs_in_params(e.params):
                eqns.extend(sub.eqns)
        assert not [e for e in flat if e.primitive.name == "transpose" and max(e.invars[0].aval.shape) > 512]
        return [(e.invars[0].aval.shape[0], e.invars[0].aval.dtype, e.params["precision"]) for e in flat if e.primitive.name == "dot_general"]

    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    f32 = products(jnp.float32)
    # four block rows of a chunk inside the loop, four of the 2 rows left over
    assert sorted(r for r, _, _ in f32) == [2] * 4 + [CHUNK] * 4
    assert all(p in (highest, jax.lax.Precision.HIGHEST) for _, _, p in f32)
    assert [(d, p) for _, d, p in products(jnp.bfloat16)] == [(jnp.bfloat16, None)] * 8  # one pass


@pytest.mark.parametrize("n", [3 * CHUNK, 3 * CHUNK + 2, 1000], ids=["whole_chunks", "rows_left_over", "one_chunk"])
def test_chunked_sums_are_the_float64_moments(n):
    """The rows in chunks of ``_SUM_ROWS`` with a compensated sum: G and cy of
    off-centre rows to float32's last bits, symmetric to the bit, whatever is
    left over."""
    rng = np.random.default_rng(n)
    x = (0.7 + 0.7 * rng.standard_normal((n, 256))).astype(np.float32)
    y = (2.0 + rng.standard_normal((n, 1))).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        g, cy = (np.asarray(v, np.float64) for v in _GRAM(jnp.asarray(x), jnp.asarray(y)))
    x64, y64 = x.astype(np.float64), y.astype(np.float64)
    want_g, want_cy = x64.T @ x64, (x64.T @ y64)[:, 0]
    assert np.array_equal(g, g.T)
    # a chunk's own product is the CPU backend's float32 sum of _SUM_ROWS terms; the chunks add up exactly
    assert np.abs((g - want_g) / want_g).max() < 2e-6 and np.abs((cy - want_cy) / want_cy).max() < 2e-6


def test_two_sum_keeps_what_an_addition_rounds_off():
    total, lost = jnp.float32(1e8), jnp.float32(0.0)
    for _ in range(1000):
        total, lost = lasso_mod._two_sum(total, lost, jnp.float32(1.0))  # each 1.0 is under 1e8's last bit (8)
    assert float(total) == 1e8 and float(total + lost) == 1e8 + 1000


@pytest.mark.parametrize("split", [0, None], ids=["split0", "replicated"])
def test_sharded_precompute_is_the_local_one(problem, split):
    """The one ``shard_map`` program on the mesh (each device its own rows,
    one all-reduce): the moments are those of the whole rows, whether the rows
    arrive sharded or not."""
    x, y = problem
    xs = ht.array(x, split=split)
    comm = xs.comm
    assert N % comm.size == 0
    whole = [np.asarray(v, np.float64) for v in _GRAM(jnp.asarray(x), jnp.asarray(y))]
    parts = [np.asarray(v, np.float64) for v in lasso_mod._gram_precompute(comm.mesh, comm.axis_name)(xs.larray, jnp.asarray(y))]
    for a, b in zip(whole, parts):
        assert a.shape == b.shape and np.abs(a - b).max() / np.abs(a).max() < 2e-6


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("split", [0, None, 1], ids=["split0", "replicated", "split1"])
def test_ragged_rows_fit_as_the_whole_rows_do(reference, problem, split, dtype):
    """Rows that do not divide by the mesh: the program takes the physical
    payload, whose padding nobody promises (poisoned here), and reads it as
    zeros; theta is the reference's, and the fit gathers nothing: the same
    one program, with the row count."""
    x, y = problem
    n = N - 3
    assert n % ht.get_comm().size
    xs = ht.array(x[:n], split=split)
    if dtype == "bfloat16":
        xs = xs.astype(ht.bfloat16)
    if split == 0:
        assert xs.padded
        xs = ht.DNDarray(xs.parray.at[n:].set(jnp.nan), xs.shape, xs.dtype, 0, xs.device, xs.comm)
    est = ht.regression.Lasso(lam=LAM, max_iter=SWEEPS, tol=-1.0).fit(xs, ht.array(y[:n], split=0 if split == 0 else None))
    want = reference.fit_blocks(np.asarray(xs.larray.astype(jnp.float32)), y[:n], LAM, SWEEPS)
    got = np.asarray(est.theta.larray, np.float64).reshape(-1)
    assert np.isfinite(got).all() and np.abs(got - want).max() / np.abs(want).max() < 2e-4


# -- spans and counters ------------------------------------------------------
def _stats():
    stats = fusion.cache_stats()
    return {k: stats[k] for k in LASSO_KEYS}


@pytest.mark.parametrize("mode", ["gram", "residual"])
def test_counters_of_a_fit_with_telemetry_on(problem, monkeypatch, mode):
    x, y = problem
    if mode == "residual":
        monkeypatch.setattr(lasso_mod, "_GRAM_MAX_ELEMENTS", 0)
    before = _stats()
    with telemetry.enabled(1):
        est = fit(x, y, 0, max_iter=7)
    after = _stats()
    assert est.n_iter == 7 and [after[k] - before[k] for k in LASSO_KEYS[-3:]] == [1, 7, 1]  # one read a fit
    timed = {k: after[k] - before[k] for k in LASSO_KEYS[:-3]}
    assert (timed.pop("phase_lasso_gram_ns") > 0) == (mode == "gram") and all(v > 0 for v in timed.values())
    with telemetry.enabled(1):
        est = fit(x, y, 0, max_iter=50, tol=1e-2)  # stopped by its tolerance: the sweeps the device ran, and still one read
    assert [_stats()[k] - after[k] for k in LASSO_KEYS[-3:]] == [1, est.n_iter, 1] and 1 < est.n_iter < 50


def test_counters_stay_where_they_are_with_telemetry_off(problem, monkeypatch):
    x, y = problem
    before = _stats()
    fit(x, y, 0)
    monkeypatch.setattr(lasso_mod, "_GRAM_MAX_ELEMENTS", 0)
    fit(x, y, None, max_iter=3)
    assert _stats() == before


def test_telemetry_changes_no_bit_of_theta(problem):
    x, y = problem
    plain = fit(x, y, 0).theta.numpy()
    with telemetry.enabled(1):
        traced = fit(x, y, 0).theta.numpy()
    assert np.array_equal(plain, traced)


def _spans_of_a_fit(x, y, **kw):
    fit(x, y, 0, **kw)  # compiled before the session
    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            fit(x, y, 0, **kw)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)  # nanobind's stats type
            return [
                (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for plane in jax.profiler.ProfileData.from_file(path).planes if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events if e.name.startswith("heat.lasso")
            ]


@pytest.mark.parametrize("mode", ["gram", "residual"])
def test_spans_of_a_fit_in_a_profiler_session(problem, monkeypatch, mode):
    x, y = problem
    if mode == "residual":
        monkeypatch.setattr(lasso_mod, "_GRAM_MAX_ELEMENTS", 0)
    before = _stats()
    spans = _spans_of_a_fit(x, y, max_iter=3)
    after = _stats()
    assert after["phase_lasso_fits"] - before["phase_lasso_fits"] == 1  # a profiler session is the switch too
    (parent,) = [s for s in spans if s[0] == "heat.lasso.fit"]
    assert {k: str(v) for k, v in parent[3].items()}.items() >= {
        "mode": mode, "n": str(N), "m": str(M), "p": str(ht.get_comm().size), "sweeps": "3", "sweep": "loop"
    }.items()
    children = sorted((s for s in spans if s[0].startswith("heat.lasso.fit.")), key=lambda s: s[1])
    names = [s[0].rsplit(".", 1)[1] for s in children]
    assert names == ["prepare"] + ["gram"] * (mode == "gram") + ["dispatch", "sync", "copy", "wrap"]  # each once, whatever max_iter
    assert all(parent[1] <= s[1] and s[2] <= parent[2] for s in children)
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:])), "children overlap"


def test_opsplane_exports_the_lasso_counters(problem):
    from heat_tpu.core import opsplane

    x, y = problem
    with telemetry.enabled(1):
        fit(x, y, 0, max_iter=2)
    text = opsplane.render()
    assert not opsplane.validate_exposition(text)
    assert all(f"heat_tpu_lasso_{c}_total" in text for c in ("fits", "sweeps", "syncs", "kernel_sweeps"))
    assert all(f'heat_tpu_lasso_phase_seconds_total{{phase="{ph}"}}' in text for ph in fusion._LASSO_PHASES)


def test_the_programs_are_named_for_the_trace():
    """The device's trace names a program by its jitted function: the
    precompute and the descent carry names of their own, and stay importable
    under the names the compile tests use. The descent is a ``while`` (the
    sweeps) around a ``while`` (a sweep's coordinate steps), with the one
    product of a sweep at ``HIGHEST``."""
    comm = ht.get_comm()
    assert lasso_mod._gram_precompute(comm.mesh, comm.axis_name).__wrapped__.__name__ == "lasso_gram"
    assert lasso_mod.lasso_descent.__wrapped__.__name__ == "lasso_descent"
    text = lasso_mod.lasso_descent.lower(
        jnp.zeros((8, 8), jnp.float32), jnp.zeros(8, jnp.float32), jnp.float32(0.1), 64, np.int32(30), np.float32(-1)
    ).as_text()
    assert "jit_lasso_descent" in text and text.count("stablehlo.while") == 2
    (product,) = [line for line in text.splitlines() if "stablehlo.dot_general" in line]
    assert "precision = [HIGHEST, HIGHEST]" in product  # c = cy - G theta at the head of a sweep, in float32


# -- the sweep as one kernel (ops/cd.py) ----------------------------------------
def _moments(m, seed):
    """G and cy of off-centre rows of unit mean square, m features over 4 m
    rows: column 0 the intercept, four coefficients in the truth."""
    rng = np.random.default_rng(seed)
    x = ((1.0 + rng.standard_normal((4 * m, m))) / np.sqrt(2.0)).astype(np.float32)
    x[:, 0] = 1.0
    y = x[:, :4] @ np.array([0.5, 1.0, -1.0, 0.3], np.float32) + 0.1 * rng.standard_normal(4 * m).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        return _GRAM(jnp.asarray(x), jnp.asarray(y[:, None]))


@pytest.mark.parametrize("m", [8, 130, 256], ids=["one_tile", "ragged", "two_tiles"])
def test_sweep_kernel_is_the_loop_sweep(m):
    """Three sweeps from 0 as the kernel (interpreted) and as the loop: the
    same theta to float32's rounding, the intercept kept, the same
    coordinates set to 0 by the threshold, padding never stepped."""
    G, cy = _moments(m, m)
    n, lam = 4 * m, jnp.float32(LAM)
    held = cd.hold(G)
    assert held.shape == (m, -(-m // 128), 128) and not np.asarray(held).reshape(m, -1)[:, m:].any()
    loop = jax.jit(lasso_mod.lasso_cd_sweep)
    theta = jnp.zeros((m, 1), jnp.float32)
    for _ in range(3):
        want = loop(G, cy, theta, lam, n)
        got = cd.sweep(held, lasso_mod._head(G, cy, theta), theta.reshape(-1), lam, n, interpret=True)
        assert got.shape == (m,) and np.abs(np.asarray(got) - np.asarray(want)[:, 0]).max() <= 1e-6 * np.abs(np.asarray(want)).max()
        theta = want
    theta = np.asarray(theta)[:, 0]
    assert theta[0] != 0 and ((np.asarray(got) == 0) == (theta == 0)).all() and (theta[1:] == 0).any()


def _one_device_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), ("x",))


@pytest.mark.parametrize("tol", [-1.0, 10.0], ids=["every_sweep", "first_sweep"])
def test_descent_on_the_kernels_path_is_the_loops(tol):
    """``lasso_descent`` with the kernel's path forced (a mesh given; on the
    CPU's devices the kernel is interpreted): the loop's theta, sweeps and
    last change."""
    G, cy = _moments(130, 1)
    args = (G, cy, np.float32(LAM), 520, np.int32(3), np.float32(tol))
    loop = lasso_mod.lasso_descent(*args)
    kernel = lasso_mod.lasso_descent(*args, _one_device_mesh())
    assert int(loop[1]) == int(kernel[1]) == (3 if tol < 0 else 1)
    assert np.abs(np.asarray(loop[0]) - np.asarray(kernel[0])).max() <= 1e-6 * np.abs(np.asarray(loop[0])).max()
    assert float(kernel[2]) == pytest.approx(float(loop[2]), rel=1e-5)


def test_kernels_path_on_the_mesh_runs_no_collective():
    """On the CPU mesh G and cy are replicated: each device runs the kernel on
    its own copy, and the program holds no all-reduce and no all-gather."""
    from jax.sharding import NamedSharding

    comm = ht.get_comm()
    assert comm.size > 1
    G, cy = (jax.device_put(v, NamedSharding(comm.mesh, jax.sharding.PartitionSpec())) for v in _moments(64, 2))
    args = (G, cy, np.float32(LAM), 256, np.int32(3), np.float32(-1))
    theta = lasso_mod.lasso_descent(*args, comm.mesh)[0]
    assert theta.sharding.is_fully_replicated
    assert np.abs(np.asarray(theta) - np.asarray(lasso_mod.lasso_descent(*args)[0])).max() < 1e-6
    text = lasso_mod.lasso_descent.lower(*args, comm.mesh).compile().as_text()
    assert not re.search(r" all-reduce(-start)?\(| all-gather(-start)?\(", text)


def test_takes_reads_devices_shape_dtype_and_the_budget():
    """The kernel is G's sweep on TPUs only, for a square float32 G held within
    the module's VMEM budget: every m up to 1 024, none past it."""
    tpu = types.SimpleNamespace(device_set=[types.SimpleNamespace(platform="tpu")] * 4)
    cpu = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    assert cd.takes((512, 512), jnp.float32, tpu) and cd.takes((1024, 1024), jnp.float32, tpu)
    assert cd.takes((130, 130), jnp.float32, tpu)
    assert not cd.takes((512, 512), jnp.float32, cpu)
    assert cd.held_bytes(1024) <= cd.VMEM_BUDGET < cd.held_bytes(1025)
    assert not cd.takes((1025, 1025), jnp.float32, tpu) and not cd.takes((2048, 2048), jnp.float32, tpu)
    for dtype in (jnp.bfloat16, jnp.float64):
        assert not cd.takes((512, 512), dtype, tpu)
    assert not cd.takes((512, 256), jnp.float32, tpu)


def test_kernel_sweeps_count_a_fit_on_the_kernels_path(problem, monkeypatch):
    """Where G takes the kernel (pretended here, the kernel interpreted), a
    traced fit counts its sweeps in ``phase_lasso_kernel_sweeps`` and
    ``opsplane``'s ``heat_tpu_lasso_kernel_sweeps_total``, says ``sweep=kernel``
    on its span and gives the loop's theta; on the loop's path (the CPU, as it
    is) it counts none and says ``sweep=loop``; an untraced fit counts
    nothing."""
    from heat_tpu.core import opsplane

    def exported():
        (line,) = [line for line in opsplane.render().splitlines() if line.startswith("heat_tpu_lasso_kernel_sweeps_total ")]
        return float(line.split()[1])

    x, y = problem
    key, stats = "phase_lasso_kernel_sweeps", []
    monkeypatch.setattr(telemetry.Phases, "note", lambda self, **kw: stats.append((self.name, kw)))
    before, counted = fusion.cache_stats()[key], exported()
    with telemetry.enabled(1):
        loop = fit(x, y, 0, max_iter=3).theta.numpy()
    assert fusion.cache_stats()[key] == before and exported() == counted
    monkeypatch.setattr(cd, "takes", lambda shape, dtype, sharding: True)
    assert np.abs(fit(x, y, 0, max_iter=3).theta.numpy() - loop).max() <= 1e-6 * np.abs(loop).max()
    assert fusion.cache_stats()[key] == before
    with telemetry.enabled(1):
        est = fit(x, y, 0, max_iter=3)
    assert fusion.cache_stats()[key] - before == 3 == est.n_iter and exported() - counted == 3
    assert [kw["sweep"] for span, kw in stats if span == "heat.lasso.fit"] == ["loop", "kernel"]
