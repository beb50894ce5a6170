"""``ht.cluster.Spectral.fit`` at the ``spectral_f32_k8`` configuration's
arithmetic (ISSUE 42): the data recipe in float64 (8 distinct smallest
eigenvalues that 300 Lanczos steps converge on, an embedding that separates
the planted blobs); the program against the configuration's plain reference
(``chipbench/references/spectral_f32_k8.py``, loaded through
``chipbench.spec``) on the CPU mesh, and products rounded to bfloat16, or rows
cast to it, far outside the same limits; the one-buffer Laplacian build
(donation, ``simple``, ``eNeighbour``) against dense NumPy; the spans and
counters of a fit; the ``opsplane`` families.

What only the chip shows (the Laplacian and Lanczos programs at 32 768 rows:
their memory, their products' precision, the loop ``lanczos_step_us`` finds)
is compiled for a described v5e in ``test_kmeans_f32.py``, the one file that
holds the topology fixture.
"""

import functools
import glob
import os
import tempfile
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from chipbench import blobs, spec
from heat_tpu.cluster import spectral as spectral_mod
from heat_tpu.core import fusion, telemetry
from heat_tpu.core.linalg import solver
from heat_tpu.graph import laplacian as laplacian_mod
from heat_tpu.ops import symv

CFG = spec.Cell("spectral_1c").config
K, F, STEPS, N = int(CFG["n_clusters"]), int(CFG["features"]), 100, 8 * 128
SPECTRAL_KEYS = [f"phase_spectral_{name}_ns" for name in fusion._SPECTRAL_PHASES] + [
    "phase_spectral_fits", "phase_spectral_syncs", "phase_spectral_lanczos_steps", "phase_spectral_upper_steps"
]
# CPU limits at 1 024 rows. Sound fits read 2e-7 (eigenvalues), 4e-7 (subspace)
# and 4e-7 (residuals); products rounded to bfloat16, or rows cast to it, read
# 4e-5, 5e-4 and 4e-4 and more
LIMITS = {"eig": 1e-5, "subspace": 1e-4, "residual": 1e-4}


def rows_of(n, seed=3000000001):
    data = CFG["data"]
    x, planted = blobs.blobs(seed, n, F, K, data["within_var"], data["step_sq"])
    return np.asarray(x), np.asarray(planted)


def dense_laplacian(x, gamma=1.0):
    """L_sym of the rows' rbf graph, float64, held whole."""
    x = np.asarray(x, np.float64)
    sq = (x * x).sum(1)
    a = np.exp(-gamma * np.maximum(sq[:, None] + sq[None, :] - 2.0 * x @ x.T, 0.0))
    np.fill_diagonal(a, 0.0)
    d = 1.0 / np.sqrt(a.sum(1))
    lap = -a * d[:, None] * d[None, :]
    np.fill_diagonal(lap, 1.0)
    return lap


def lanczos64(lap, steps, seed=0):
    """Ritz values and vectors of ``steps`` Lanczos steps, float64, full
    reorthogonalisation (twice)."""
    n = lap.shape[0]
    V, alpha, beta = np.zeros((steps, n)), np.zeros(steps), np.zeros(steps)
    w = np.random.default_rng(seed).standard_normal(n)
    for i in range(steps):
        beta[i] = np.linalg.norm(w) if i else 0.0
        v = w / np.linalg.norm(w)
        for _ in range(2):
            v -= V[:i].T @ (V[:i] @ v)
        V[i] = v / np.linalg.norm(v)
        w = lap @ V[i]
        alpha[i] = w @ V[i]
        w = w - alpha[i] * V[i] - (beta[i] * V[i - 1] if i else 0.0)
    values, q = np.linalg.eigh(np.diag(alpha) + np.diag(beta[1:], 1) + np.diag(beta[1:], -1))
    return values, V.T @ q


def test_the_recipe_in_float64_has_eight_distinct_small_eigenvalues_that_lanczos_converges_on():
    """At 4 096 rows (512 a blob) in float64: the 8 smallest eigenvalues of
    L_sym are distinct by at least 4e-3 (one start vector cannot resolve a
    double one), the ninth lies past 0.9, 300 Lanczos steps give the 8 to
    1e-9, and the embedding's rows lie nearest the mean of their own planted
    blob. The values are the ones the configuration's file quotes."""
    x, planted = rows_of(4096)
    lap = dense_laplacian(x)
    exact = np.linalg.eigvalsh(lap)
    assert np.diff(exact[:8]).min() > 4e-3 and exact[8] > 0.9
    np.testing.assert_allclose(exact[1:8], [4.5e-3, 1.8e-2, 3.6e-2, 5.9e-2, 9.6e-2, 0.157, 0.263], rtol=0.05)
    ritz, vectors = lanczos64(lap, 300)
    assert np.abs(ritz[:8] - exact[:8]).max() < 1e-9
    emb = vectors[:, :8]
    centres = np.stack([emb[planted == a].mean(0) for a in range(K)])
    nearest = ((emb[:, None, :] - centres[None]) ** 2).sum(-1).argmin(1)
    assert (nearest == planted).all()


@pytest.fixture(scope="module")
def reference():
    return spec.load_module("references", "spectral_f32_k8.py")


@pytest.fixture(scope="module")
def rows():
    return rows_of(N)


@pytest.fixture(scope="module")
def want(reference, rows):
    values, vectors, apply = reference.spectrum(rows[0], CFG["gamma"], STEPS, K, seed=11)
    return values, vectors, apply, reference.kmeans(vectors, K, CFG["max_iter"], seed=12)[0]


def fit(x, n_clusters=K, seed=5):
    ht.random.seed(seed)
    return ht.cluster.Spectral(
        n_clusters=n_clusters, gamma=CFG["gamma"], n_lanczos=STEPS, init=CFG["init"],
        max_iter=CFG["max_iter"], tol=CFG["tol"],
    ).fit(x)


def gaps(est, want):
    """The cell's comparison of one fit (``chipbench/ops/spectral_fit.py::gaps``)."""
    answer = {
        "values": est.eigenvalues_.larray, "embedding": est.embedding_.larray, "labels": est.labels_.larray,
        "centres": est._cluster.cluster_centers_.larray,
    }
    return {name[: -len("_gap")]: value for name, value in spec.load_module("ops", "spectral_fit.py").gaps(answer, *want).items()}


@pytest.mark.parametrize("split", [0, None])
def test_float32_fit_is_the_plain_reference(rows, want, split):
    est = fit(ht.array(rows[0], split=split))
    assert est.embedding_.shape == (N, K) and est.eigenvalues_.shape == (STEPS,)
    assert est.embedding_.dtype == ht.float32 and est.embedding_.split == split
    got = gaps(est, want)
    assert all(got[name] <= limit for name, limit in LIMITS.items()), got
    assert got["labels"] == 0.0 and got["assign"] == 0.0 and got["centres"] < CFG["check"]["centres_gap"] / 10


def test_the_reference_recovers_the_planted_blobs(rows, want):
    _, planted = rows
    vectors = want[1]
    centres = np.stack([vectors[planted == a].mean(0) for a in range(K)])
    assert (((vectors[:, None, :] - centres[None]) ** 2).sum(-1).argmin(1) == planted).all()


def _rounded(a, v):
    """A product at the MXU's default: both operands rounded to bfloat16,
    multiplied, added up in float32 (what a CPU does not do by itself)."""
    low = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.matmul(low(a), low(v))


@pytest.mark.parametrize("how", ["products", "rows"])
def test_bfloat16_falls_outside_the_limits(rows, want, monkeypatch, how):
    x = ht.array(rows[0], split=0)
    if how == "products":
        # a function of its own: jit would find the body's trace with the float32 products in its cache
        body = lambda a, v, m, key: solver._lanczos_body(a, v, m, key)  # noqa: E731
        monkeypatch.setattr(solver, "_lanczos_fused", jax.jit(body, static_argnums=(2,)))
        monkeypatch.setattr(solver, "_matvec", _rounded)
    else:
        x = x.astype(ht.bfloat16)
    got = gaps(fit(x), want)
    assert got["eig"] > LIMITS["eig"] and got["residual"] > LIMITS["residual"], got


def _primitives(jaxpr, out):
    """Every equation of ``jaxpr`` and of the jaxprs its equations carry."""
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


def _interpreted(block=128):
    """The upper-triangle product as the CPU runs it: Pallas interpret mode."""
    return functools.partial(symv.upper_matvec, block=block, interpret=True)


def test_no_lanczos_or_embedding_product_is_left_at_the_default():
    """What only the chip shows: XLA's default takes float32 operands on the
    MXU in one bfloat16 pass (a matrix-vector product it lowers to the VPU,
    at any precision, on a v5e: ``test_kmeans_f32.py``). So every contraction
    of the traced programs asks for ``HIGHEST``. On the kernel's path the
    product with L is the one ``pallas_call`` (``ops/symv.py``: float32 on the
    VPU) beside the three float32 products of the reorthogonalisation and
    alpha, and nothing anywhere is bfloat16."""
    from test_kmeans_f32 import _dots, _float32_products_only

    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    v = jax.ShapeDtypeStruct((256,), jnp.float32)
    dots = _dots(jax.make_jaxpr(lambda a, v: solver._lanczos_body(a, v, 20, jax.random.PRNGKey(0)))(a, v).jaxpr, [])
    assert len(dots) == 4 and all(_float32_products_only(eqn) for eqn in dots), [str(e) for e in dots]
    upper = jax.make_jaxpr(lambda a, v: solver._lanczos_body(a, v, 20, jax.random.PRNGKey(0), product=_interpreted()))(a, v)
    eqns = _primitives(upper.jaxpr, [])
    dots = _dots(upper.jaxpr, [])
    assert len(dots) == 3 and all(_float32_products_only(eqn) for eqn in dots), [str(e) for e in dots]
    assert [e.primitive.name for e in eqns].count("pallas_call") == 1
    assert not any(getattr(var.aval, "dtype", None) == jnp.bfloat16 for e in eqns for var in (*e.invars, *e.outvars))
    embed = jax.make_jaxpr(lambda V, T: spectral_mod._embed(V, T, 3))(
        jax.ShapeDtypeStruct((256, 20), jnp.float32), jax.ShapeDtypeStruct((20, 20), jnp.float32)
    )
    (product,) = _dots(embed.jaxpr, [])
    assert _float32_products_only(product)


def test_lanczos_through_the_upper_product_finds_the_same_ritz_values(rows):
    """A symmetric L (the rows' L_sym, float32, 1 024 rows) through the kernel,
    interpreted, and through XLA's product over all of it: 100 steps give the
    same Ritz values to 1e-5."""
    lap = jnp.asarray(dense_laplacian(rows[0]), jnp.float32)
    v0 = jax.random.normal(jax.random.PRNGKey(3), (N,), jnp.float32)
    v0 = v0 / jnp.linalg.norm(v0)
    key = jax.random.PRNGKey(0)
    _, full = solver._lanczos_fused(lap, v0, STEPS, key)
    body = functools.partial(solver._lanczos_body, product=_interpreted(256))
    _, upper = jax.jit(body, static_argnums=(2,))(lap, v0, STEPS, key)
    ritz = [np.linalg.eigvalsh(np.asarray(t, np.float64)) for t in (full, upper)]
    assert np.abs(ritz[0] - ritz[1]).max() < 1e-5 and np.abs(ritz[0][:K]).max() < 0.3


# -- the one-buffer Laplacian -----------------------------------------------------------------
def _rbf(a):
    return ht.spatial.rbf(a, sigma=float(np.sqrt(0.5)), quadratic_expansion=True)


def _affinity(x):
    x = np.asarray(x, np.float64)
    sq = (x * x).sum(1)
    return np.exp(-np.maximum(sq[:, None] + sq[None, :] - 2.0 * x @ x.T, 0.0))


def _spy(monkeypatch):
    """The similarities handed to the donating Laplacian program."""
    seen = []
    program = laplacian_mod._laplacian_program
    monkeypatch.setattr(laplacian_mod, "_laplacian_program", lambda s, *a, **kw: seen.append(s) or program(s, *a, **kw))
    return seen


def test_the_similarity_of_a_distance_metric_is_donated_and_any_other_copied(rows, monkeypatch):
    x = ht.array(rows[0][:64], split=0)
    seen = _spy(monkeypatch)
    L = ht.graph.Laplacian(functools.partial(ht.spatial.rbf, sigma=float(np.sqrt(0.5)), quadratic_expansion=True)).construct(x)
    assert len(seen) == 1 and seen[0].is_deleted() and L.split == 0
    held = _rbf(x)
    kept = ht.graph.Laplacian(lambda a: held).construct(x)
    assert len(seen) == 1 and not held.larray.is_deleted()  # a copy, not the held array
    fresh = ht.graph.Laplacian(_rbf).construct(x)  # a callable of the user's: copied, whatever it returns
    assert len(seen) == 1
    for other in (kept, fresh):
        np.testing.assert_allclose(other.numpy(), L.numpy(), atol=1e-6)
    np.testing.assert_allclose(L.numpy(), dense_laplacian(rows[0][:64]), atol=2e-6)


@pytest.mark.parametrize("metric", ["rbf", "euclidean"])
def test_a_spectral_fit_donates_its_affinity(rows, monkeypatch, metric):
    seen = _spy(monkeypatch)
    ht.random.seed(5)
    ht.cluster.Spectral(n_clusters=K, metric=metric, n_lanczos=20, max_iter=3).fit(ht.array(rows[0][:256], split=0))
    assert len(seen) == 1 and seen[0].is_deleted()


@pytest.mark.parametrize("key,weighted", [("upper", True), ("lower", True), ("upper", False)])
@pytest.mark.parametrize("definition", ["simple", "norm_sym"])
def test_each_definition_and_mode_through_the_one_program(rows, definition, key, weighted):
    x = rows[0][:64]
    a = _affinity(x)
    keep = (a < 0.3) if key == "upper" else (a > 0.3)
    a = np.where(keep, a if weighted else 1.0, 0.0)
    np.fill_diagonal(a, 0.0)
    degree = a.sum(1)
    if definition == "simple":
        want = np.diag(degree) - a
    else:
        d = np.where(degree > 0, 1.0 / np.sqrt(np.where(degree > 0, degree, 1.0)), 0.0)
        want = -a * d[:, None] * d[None, :]
        np.fill_diagonal(want, 1.0)
    lap = ht.graph.Laplacian(
        _rbf, weighted=weighted, definition=definition, mode="eNeighbour", threshold_key=key, threshold_value=0.3
    ).construct(ht.array(x, split=0))
    np.testing.assert_allclose(lap.numpy(), want, atol=2e-5)
    dense = ht.graph.Laplacian(_rbf, definition=definition).construct(ht.array(x, split=0)).numpy()
    assert np.count_nonzero(lap.numpy()) <= np.count_nonzero(dense)


# -- spans and counters ------------------------------------------------------------------------
def _stats():
    stats = fusion.cache_stats()
    return {k: stats[k] for k in SPECTRAL_KEYS}


@pytest.mark.parametrize("n_clusters", [K, None])
def test_counters_of_a_fit_with_telemetry_on(rows, n_clusters):
    x = ht.array(rows[0], split=0)
    before = _stats()
    with telemetry.enabled(1):
        est = fit(x, n_clusters)
    after = _stats()
    k = est.n_clusters
    # k-means++ reads its first index, then a draw and an index a centre; the Lloyd fit one; the eigengap one
    reads = 2 * k - 1 + 1 + (n_clusters is None)
    # on the CPU each product reads all of L: no upper-triangle step
    assert [after[key] - before[key] for key in SPECTRAL_KEYS[-4:]] == [1, reads, STEPS, 0]
    assert all(after[key] - before[key] > 0 for key in SPECTRAL_KEYS[:-4])


def test_counters_stay_where_they_are_with_telemetry_off(rows):
    x = ht.array(rows[0], split=0)
    before = _stats()
    fit(x)
    assert _stats() == before


def test_telemetry_changes_no_bit_of_the_fit(rows):
    x = ht.array(rows[0], split=0)
    plain = fit(x)
    with telemetry.enabled(1):
        traced = fit(x)
    for name in ("labels_", "embedding_", "eigenvalues_"):
        assert np.array_equal(getattr(plain, name).numpy(), getattr(traced, name).numpy())


def test_spans_of_a_fit_in_a_profiler_session(rows):
    x = ht.array(rows[0], split=0)
    fit(x)  # compiled before the session
    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            fit(x)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)  # nanobind's stats type
            spans = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for plane in jax.profiler.ProfileData.from_file(path).planes if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events if e.name.startswith("heat.")
            ]
    (parent,) = [s for s in spans if s[0] == "heat.spectral.fit"]
    assert {k: str(v) for k, v in parent[3].items()}.items() >= {
        "n": str(N), "f": str(F), "k": str(K), "m": str(STEPS), "p": str(ht.get_comm().size), "product": "full"
    }.items()
    children = sorted((s for s in spans if s[0].startswith("heat.spectral.fit.")), key=lambda s: s[1])
    assert [s[0].rsplit(".", 1)[1] for s in children] == list(fusion._SPECTRAL_PHASES)
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:])), "children overlap"
    phase = {s[0].rsplit(".", 1)[1]: s for s in children}
    for inner, outer in (("heat.cdist", "affinity"), ("heat.kmeans.fit", "assign")):
        (span,) = [s for s in spans if s[0] == inner]
        assert phase[outer][1] <= span[1] and span[2] <= phase[outer][2], inner


def test_opsplane_exports_the_spectral_counters(rows):
    from heat_tpu.core import opsplane

    with telemetry.enabled(1):
        fit(ht.array(rows[0], split=0))
    text = opsplane.render()
    assert not opsplane.validate_exposition(text)
    assert all(f"heat_tpu_spectral_{c}_total" in text for c in ("fits", "syncs", "lanczos_steps", "upper_steps"))
    assert all(f'heat_tpu_spectral_phase_seconds_total{{phase="{ph}"}}' in text for ph in fusion._SPECTRAL_PHASES)


def _exported(name):
    from heat_tpu.core import opsplane

    (line,) = [line for line in opsplane.render().splitlines() if line.startswith(name + " ")]
    return float(line.split()[1])


def test_upper_steps_count_a_fit_on_the_kernels_path(rows, monkeypatch):
    """Where L takes the upper-triangle product (pretended here, the kernel
    interpreted), a traced fit counts its m steps in
    ``phase_spectral_upper_steps`` and ``opsplane``'s
    ``heat_tpu_spectral_upper_steps_total``, and says ``product=upper`` on its
    span; an untraced fit counts nothing."""
    monkeypatch.setattr(symv, "takes", lambda shape, dtype, sharding: True)
    monkeypatch.setattr(solver, "_lanczos_upper", jax.jit(
        functools.partial(solver._lanczos_body, product=_interpreted(256)), static_argnums=(2,)
    ))
    x = ht.array(rows[0], split=None)
    key, name = "phase_spectral_upper_steps", "heat_tpu_spectral_upper_steps_total"
    before, exported = fusion.cache_stats()[key], _exported(name)
    fit(x)
    assert fusion.cache_stats()[key] == before and _exported(name) == exported
    stats = []
    monkeypatch.setattr(telemetry.Phases, "note", lambda self, **kw: stats.append((self.name, kw)))
    with telemetry.enabled(1):
        fit(x)
    assert fusion.cache_stats()[key] - before == STEPS and _exported(name) - exported == STEPS
    assert [kw for span, kw in stats if span == "heat.spectral.fit"] == [{"product": "upper"}]


def test_predict_embeds_new_rows_in_the_same_way(rows):
    x = ht.array(rows[0], split=0)
    est = fit(x)
    assert np.array_equal(est.predict(x).numpy(), est.labels_.numpy())
    assert spectral_mod.Spectral(n_clusters=2).eigenvalues_ is None
