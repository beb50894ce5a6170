"""``ht.linalg.qr`` at the ``qr_tall_f32`` configuration's arithmetic (ISSUE
35): float32 rows whose columns span a decade of scales, against the
configuration's plain Householder reference (``chipbench/references/
qr_tall_f32.py``, loaded through ``chipbench.spec``) on p in {1, 4}, even and
ragged m, ``calc_q`` both ways and every ``method``, by the three numbers the
benchmark's op kind compares; the same call on bfloat16-rounded rows, and
with the tall products rounded as the MXU's default precision rounds them,
falls outside the limits; the blocked reference is the float64 QR of the whole
operand; the spans and counters of a call; and (ISSUE 36) CholeskyQR2's tall
products by column blocks, which leave out the blocks that are a mirror or
zero, against the same products taken whole.

What only the chip shows (the program at 1 250 000 x 512: its memory, and that
no product is left at the MXU's default) is compiled for a described v5e in
``test_kmeans_f32.py``, the one file that holds the topology fixture.
"""

import ast
import glob
import importlib
import os
import tempfile
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from chipbench import spec
from heat_tpu.core import fusion, telemetry

qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")  # the package exports the function under the same name

N = 48
QR_KEYS = [f"phase_qr_{name}_ns" for name in fusion._QR_PHASES] + ["phase_qr_calls", "phase_qr_syncs", "phase_qr_fallbacks"]

# CPU limits, from float32's rounding (6e-8) at these sizes. A column of R is
# good to a few roundings times sqrt(n) on either side of the comparison
# (Householder's R here, CholeskyQR2's or TSQR's there): sound calls read at
# most 2.1e-7. A row of Q times sqrt(m) has entries of order 1 and carries R's
# error through R^-1 (cond about 10): at most 3.5e-6. A row of Q R is the row
# of A to a few roundings: at most 7e-7. Rows rounded to bfloat16 (2^-9 of
# every entry) read 6e-4, 1e-2 and 2.7e-3; products of rounded operands 3e-3,
# 2e-2 and 5e-3. The benchmark's own limits, at 1 250 000 rows on the chip,
# are in the configuration's file.
R_LIMIT, Q_LIMIT, RECON_LIMIT = 2e-6, 3e-5, 5e-6


@pytest.fixture(scope="module")
def reference():
    return spec.load_module("references", "qr_tall_f32.py")


@pytest.fixture(scope="module", params=[1, 4], ids=["p1", "p4"])
def comm(request):
    devices = jax.devices()
    if len(devices) < request.param:
        pytest.skip(f"needs {request.param} devices")
    return ht.MeshCommunication(devices[: request.param])


def scaled_rows(m, n=N, seed=35):
    """The configuration's data: i.i.d. N(0, 1), column j times 10^(-j / (n - 1))."""
    scales = 10.0 ** (-np.arange(n) / (n - 1))
    return (np.random.default_rng(seed + m).standard_normal((m, n)) * scales).astype(np.float32)


def gaps(reference, x, q, r, block=200):
    """``chipbench/ops/qr_trial.py``'s three numbers, over every row: R's
    columns against the reference's (signs turned on both sides), the rows of
    Q times sqrt(m) against the rows of A solved against the reference's R,
    and the rows of Q R against the rows of A."""
    want = np.asarray(reference.r_factor(jnp.asarray(x), block), np.float64)
    got = np.asarray(r, np.float64)
    sign = np.where(np.diagonal(got) < 0, -1.0, 1.0)
    r_gap = (np.sqrt(((sign[:, None] * got - want) ** 2).sum(axis=0)) / np.sqrt((want * want).sum(axis=0))).max()
    if q is None:
        return r_gap, 0.0, 0.0
    a, q = np.asarray(x, np.float64), np.asarray(q, np.float64)
    q_gap = np.abs(q * sign[None, :] - reference.q_rows(a, want)).max() * np.sqrt(len(a))
    recon_gap = (np.sqrt(((q @ got - a) ** 2).sum(axis=1)) / np.sqrt((a * a).sum(axis=1))).max()
    return r_gap, q_gap, recon_gap


@pytest.mark.parametrize("method", ["auto", "cholqr2", "tsqr"])
@pytest.mark.parametrize("calc_q", [True, False], ids=["q", "r_only"])
@pytest.mark.parametrize("m", [768, 765], ids=["even", "ragged"])
def test_qr_is_the_plain_reference(reference, comm, m, calc_q, method):
    x = scaled_rows(m)
    q, r = ht.linalg.qr(ht.array(x, split=0, comm=comm), method=method, calc_q=calc_q)
    assert r.shape == (N, N) and r.split is None and r.dtype == ht.float32
    assert (q is None) == (not calc_q)
    if calc_q:
        assert q.shape == (m, N) and q.split == 0 and q.dtype == ht.float32
    r_np = r.numpy()
    assert np.array_equal(r_np, np.triu(r_np))
    if method != "tsqr":  # a Cholesky factor's mark, which the benchmark reads the path from
        assert (np.diagonal(r_np) > 0).all()
    r_gap, q_gap, recon_gap = gaps(reference, x, q.numpy() if calc_q else None, r_np)
    assert r_gap <= R_LIMIT and q_gap <= Q_LIMIT and recon_gap <= RECON_LIMIT, (r_gap, q_gap, recon_gap)


@pytest.mark.parametrize("method", ["auto", "tsqr"])
def test_bfloat16_cast_rows_fall_outside_the_limits(reference, comm, method):
    """The benchmark's control: the rows rounded to bfloat16, factored in
    float32 as ``qr`` factors every half-precision operand."""
    x = scaled_rows(768)
    q, r = ht.linalg.qr(ht.array(x, split=0, comm=comm).astype(ht.bfloat16), method=method)
    assert q.dtype == ht.float32
    r_gap, q_gap, recon_gap = gaps(reference, x, q.numpy(), r.numpy())
    assert r_gap > 100 * R_LIMIT and q_gap > 100 * Q_LIMIT and recon_gap > 100 * RECON_LIMIT, (r_gap, q_gap, recon_gap)


def test_products_at_the_mxu_default_fall_outside_the_limits(reference, comm, monkeypatch):
    """What a v5e does to a float32 product left at the default precision,
    made visible on the CPU: both operands of CholeskyQR2's tall products (the
    two Grams, Q1 and Q) rounded to bfloat16 before they are multiplied."""
    x = scaled_rows(768)
    a = ht.array(x, split=0, comm=comm)
    real = jax.lax.dot_general

    def rounded(lhs, rhs, dims, precision=None, preferred_element_type=None):
        low = [v.astype(jnp.bfloat16).astype(v.dtype) for v in (lhs, rhs)]
        return real(*low, dims, preferred_element_type=preferred_element_type)

    monkeypatch.setattr(qr_mod.jax.lax, "dot_general", rounded)
    fusion.clear_cache()  # the sound program of this shape is not the one to run
    try:
        q, r = ht.linalg.qr(a)
        q_np, r_np = q.numpy(), r.numpy()
    finally:
        monkeypatch.undo()
        fusion.clear_cache()
    r_gap, q_gap, recon_gap = gaps(reference, x, q_np, r_np)
    assert r_gap > 100 * R_LIMIT and q_gap > 100 * Q_LIMIT and recon_gap > 100 * RECON_LIMIT, (r_gap, q_gap, recon_gap)
    q, r = ht.linalg.qr(a)  # and the sound program is back
    assert max(np.divide(gaps(reference, x, q.numpy(), r.numpy()), (R_LIMIT, Q_LIMIT, RECON_LIMIT))) <= 1.0


@pytest.mark.parametrize("m,block", [(768, 200), (765, 255), (768, 768), (4096, 512)])
def test_blocked_reference_is_the_float64_qr_of_the_whole_operand(reference, m, block):
    """R of the stacked block factors is R of the operand (ragged last block,
    one block, many), to float32's rounding, with a positive diagonal; and the
    rows of A against R are the rows of the float64 Q."""
    x = scaled_rows(m)
    q64, r64 = np.linalg.qr(x.astype(np.float64))
    sign = np.sign(np.diagonal(r64))
    q64, r64 = q64 * sign[None, :], sign[:, None] * r64
    r = reference.r_factor(jnp.asarray(x), block)
    assert r.dtype == jnp.float32 and r.shape == (N, N)
    r = np.asarray(r, np.float64)
    assert (np.diagonal(r) > 0).all() and np.array_equal(r, np.triu(r))
    assert (np.sqrt(((r - r64) ** 2).sum(axis=0)) / np.sqrt((r64 * r64).sum(axis=0))).max() < 1e-6
    assert np.abs(reference.q_rows(x[::7], r64) - q64[::7]).max() * np.sqrt(m) < 1e-9


def test_reference_shares_no_code_with_the_program(reference):
    """It imports jax and numpy alone, calls no Cholesky factorisation, and
    factors under ``highest``."""
    with open(reference.__file__) as fh:
        tree = ast.parse(fh.read())
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names}
    imported |= {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert imported == {"__future__", "jax", "jax.numpy", "numpy"}
    called = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    assert "qr" in called and "default_matmul_precision" in called and not {"cholesky", "cho_factor"} & called


# -- spans and counters ----------------------------------------------------
def _qr_stats():
    stats = fusion.cache_stats()
    return {k: stats[k] for k in QR_KEYS}


def ill_conditioned(m=768):
    """cond about 1e6: past 1/sqrt(eps), where CholeskyQR2's probe refuses."""
    x = scaled_rows(m)
    x[:, 1] = x[:, 0] * (1.0 + 1e-6) + 1e-6 * x[:, 1]
    assert np.linalg.cond(x.astype(np.float64)) > 1e4
    return x


def test_counters_of_a_call_with_telemetry_on(comm):
    a = ht.array(scaled_rows(768), split=0, comm=comm)
    before, forces = _qr_stats(), fusion.cache_stats()["forces"]
    with telemetry.enabled(1):
        ht.linalg.qr(a)
    after = _qr_stats()
    assert fusion.cache_stats()["forces"] - forces == 1  # Q, R and the probe: one multi-output node, one force
    assert [after[k] - before[k] for k in QR_KEYS[-3:]] == [1, 1, 0]
    assert all(after[k] > before[k] for k in QR_KEYS[:-3])
    with telemetry.enabled(1):
        ht.linalg.qr(a, method="tsqr", calc_q=False)  # Householder asked for: no probe, nothing to fall from
    assert [_qr_stats()[k] - after[k] for k in QR_KEYS[-3:]] == [1, 0, 0]


def test_a_refused_probe_counts_one_fallback(comm):
    x = ill_conditioned()
    a = ht.array(x, split=0, comm=comm)
    before = _qr_stats()
    with telemetry.enabled(1):
        q, r = ht.linalg.qr(a)
    after = _qr_stats()
    assert [after[k] - before[k] for k in QR_KEYS[-3:]] == [1, 1, 1]
    np.testing.assert_allclose(q.numpy() @ r.numpy(), x, atol=1e-5)  # Householder's answer
    with telemetry.enabled(1), pytest.raises(ValueError, match="cholqr2 broke down"):
        ht.linalg.qr(a, method="cholqr2")
    assert [_qr_stats()[k] - after[k] for k in QR_KEYS[-3:]] == [0, 0, 0]  # a call that raised is not counted


def test_counters_stay_where_they_are_with_telemetry_off(comm):
    a = ht.array(scaled_rows(768), split=0, comm=comm)
    bad = ht.array(ill_conditioned(), split=0, comm=comm)
    before = _qr_stats()
    for method in ("auto", "cholqr2", "tsqr"):
        ht.linalg.qr(a, method=method)
    ht.linalg.qr(bad)
    assert _qr_stats() == before


def test_telemetry_changes_no_bit_of_the_factors(comm):
    a = ht.array(scaled_rows(765), split=0, comm=comm)
    q0, r0 = ht.linalg.qr(a)
    with telemetry.enabled(1):
        q1, r1 = ht.linalg.qr(a)
    assert np.array_equal(q0.numpy(), q1.numpy()) and np.array_equal(r0.numpy(), r1.numpy())


def _spans_of_a_call(a, **kw):
    ht.linalg.qr(a, **kw)  # compiled before the session
    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            ht.linalg.qr(a, **kw)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)  # nanobind's stats type
            return [
                (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for plane in jax.profiler.ProfileData.from_file(path).planes if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events if e.name.startswith(("heat.qr", "heat.force"))
            ]


@pytest.mark.parametrize("method,mode", [("auto", "cholqr2"), ("tsqr", "tsqr")])
def test_spans_of_a_call_in_a_profiler_session(method, mode):
    p = ht.get_comm().size
    if p == 1 and mode == "tsqr":
        mode = "replicated"
    a = ht.array(scaled_rows(96 * p), split=0)
    before = _qr_stats()
    spans = _spans_of_a_call(a, method=method)
    after = _qr_stats()
    assert after["phase_qr_calls"] - before["phase_qr_calls"] == 1  # a profiler session is the switch too
    (parent,) = [s for s in spans if s[0] == "heat.qr"]
    assert {k: str(v) for k, v in parent[3].items()}.items() >= {
        "mode": mode, "m": str(96 * p), "n": str(N), "p": str(p), "calc_q": "1", "blocks": "1"
    }.items()
    children = sorted((s for s in spans if s[0].startswith("heat.qr.")), key=lambda s: s[1])
    names = [s[0].rsplit(".", 1)[1] for s in children]
    assert names == (["prepare", "dispatch", "sync", "copy", "wrap"] if method == "auto" else ["prepare", "dispatch", "wrap"])
    assert all(parent[1] <= s[1] and s[2] <= parent[2] for s in children)
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:])), "children overlap"
    if method == "auto":  # the engine's own force lies under the call's one blocking read
        sync = children[2]
        forces = [s for s in spans if s[0] == "heat.force"]
        assert len(forces) == 1 and sync[1] <= forces[0][1] and forces[0][2] <= sync[2]


def test_opsplane_exports_the_qr_counters():
    from heat_tpu.core import opsplane

    with telemetry.enabled(1):
        ht.linalg.qr(ht.array(scaled_rows(256), split=0))
    text = opsplane.render()
    assert not opsplane.validate_exposition(text)
    assert all(f"heat_tpu_qr_{c}_total" in text for c in ("calls", "syncs", "fallbacks", "blocked"))
    assert all(f'heat_tpu_qr_phase_seconds_total{{phase="{ph}"}}' in text for ph in fusion._QR_PHASES)


# -- ISSUE 36: the tall products by column blocks ---------------------------
BLOCKS = {128: 1, 200: 1, 256: 2, 384: 3, 512: 4, 640: 5}  # columns: blocks (1 = whole products)


def spectrum_rows(m, n, cond, dtype=np.float32, seed=36):
    """Orthonormal columns times singular values from 1 down to 1 / cond,
    turned by a random rotation: conditioning a column scale does not undo."""
    rng = np.random.default_rng(seed + n)
    draw = rng.standard_normal if dtype != np.complex64 else (lambda size: rng.standard_normal(size) + 1j * rng.standard_normal(size))
    u, _ = np.linalg.qr(draw((m, n)))
    v, _ = np.linalg.qr(draw((n, n)))
    return ((u * np.logspace(0, -np.log10(cond), n)) @ v.conj().T).astype(dtype)


def body(x, monkeypatch, whole=False, calc_q=True, seen=None):
    """``_cholqr2_body`` on ``x``, run eagerly (a jitted wrapper would keep the
    program of the other form); ``whole`` takes the four products whole as
    before ISSUE 36; ``seen`` receives what the probe was handed."""
    with monkeypatch.context() as patch:
        if whole:
            patch.setattr(qr_mod, "_block_width", lambda n: n)
        if seen is not None:
            probe = qr_mod._cholqr2_probe_ok

            def watched(r1, r2, g2, eye):
                seen.update(r1=np.asarray(r1), r2=np.asarray(r2), g2=np.asarray(g2))
                return probe(r1, r2, g2, eye)

            patch.setattr(qr_mod, "_cholqr2_probe_ok", watched)
        q, r, ok = qr_mod._cholqr2_body(jnp.asarray(x), calc_q)
    return (None if q is None else np.asarray(q)), np.asarray(r), bool(ok)


@pytest.mark.parametrize("n", sorted(BLOCKS))
def test_blocked_products_are_the_whole_products(n, monkeypatch):
    """Q, R and the probe of the blocked products against the same products
    taken whole: what is left out is a block of zeros of R^-1 or the mirror
    of a block that is computed, so the factors agree to float32's rounding
    (and to the bit where ``n`` is ragged or under 256 and nothing is
    blocked); R is upper triangular with zeros below."""
    assert qr_mod._block_count(n) == BLOCKS[n]
    assert qr_mod._gram_entries(n) == (n * n if BLOCKS[n] == 1 else 128 * 128 * BLOCKS[n] * (BLOCKS[n] + 1) // 2)
    x = scaled_rows(3 * n, n)
    q, r, ok = body(x, monkeypatch)
    q0, r0, ok0 = body(x, monkeypatch, whole=True)
    assert ok and ok0 and q.dtype == np.float32 and r.dtype == np.float32
    assert np.array_equal(r, np.triu(r)) and (np.diagonal(r) > 0).all()
    if BLOCKS[n] == 1:
        assert np.array_equal(q, q0) and np.array_equal(r, r0)
    scale = np.sqrt(3 * n)  # a row of Q times sqrt(m) has entries of order 1
    assert np.abs(q - q0).max() * scale < 2e-6 and np.abs(r - r0).max() / np.abs(r0).max() < 1e-6
    assert np.abs(q.T @ q - np.eye(n)).max() < 5e-6


@pytest.mark.parametrize("dtype,n", [(np.float32, 256), (np.float32, 512), (np.complex64, 256)], ids=["f32-256", "f32-512", "c64-256"])
def test_blocked_gram_is_exactly_hermitian(dtype, n, monkeypatch):
    """The strictly lower block triangle is the conjugate mirror of the
    upper, entry for entry, and the diagonal is real: what ``cholesky`` and
    the probe's norm are handed is Hermitian to the bit."""
    seen = {}
    _, _, ok = body(spectrum_rows(4 * n, n, 10.0, dtype), monkeypatch, seen=seen)
    g2 = seen["g2"]
    assert ok and g2.shape == (n, n) and np.array_equal(g2, g2.conj().T)
    assert np.linalg.norm(g2 - np.eye(n)) < 1e-3


def test_blocked_complex_operand_is_unitary():
    """``test_qr_depth.py::test_complex_operand_unitary`` at a blocked width:
    the mirror conjugates."""
    a_np = spectrum_rows(1024, 256, 10.0, np.complex64)
    q, r = ht.linalg.qr(ht.array(a_np, split=0), method="cholqr2")
    q_np, r_np = q.numpy(), r.numpy()
    assert np.array_equal(r_np, np.triu(r_np)) and (np.diagonal(r_np).real > 0).all()
    np.testing.assert_allclose(q_np.conj().T @ q_np, np.eye(256), atol=3e-5)
    np.testing.assert_allclose(q_np @ r_np, a_np, atol=3e-6)


def test_blocked_bfloat16_stream(monkeypatch):
    """A half-precision operand streams at its own width through the blocked
    products too: Q comes back bfloat16, R float32, and both are the whole
    products' to bfloat16's rounding."""
    x = jnp.asarray(scaled_rows(1024, 256)).astype(jnp.bfloat16)
    q, r, ok = body(x, monkeypatch)
    q0, r0, ok0 = body(x, monkeypatch, whole=True)
    assert ok and ok0 and q.dtype == jnp.bfloat16 and r.dtype == np.float32
    assert np.array_equal(r, np.triu(r))
    assert np.abs(q.astype(np.float32) - q0.astype(np.float32)).max() * 32 < 0.1
    assert np.abs(r - r0).max() / np.abs(r0).max() < 2e-2


def test_blocked_r_only_is_the_r_of_the_full_call(comm, monkeypatch):
    x = scaled_rows(1024, 256)
    _, r_full, _ = body(x, monkeypatch)
    q, r, ok = body(x, monkeypatch, calc_q=False)
    assert q is None and ok and np.array_equal(r, r_full)
    q_ht, r_ht = ht.linalg.qr(ht.array(x, split=0, comm=comm), calc_q=False)
    assert q_ht is None and np.abs(r_ht.numpy() - r_full).max() / np.abs(r_full).max() < 1e-6


@pytest.mark.parametrize("m", [1024, 1021], ids=["even", "ragged"])
def test_blocked_products_on_sharded_rows(reference, comm, m):
    """Rows split over the suite's mesh (and one device): the block rows of
    the Gram are all-reduced, Q is born row-sharded, and both are the plain
    reference's."""
    x = scaled_rows(m, 256)
    q, r = ht.linalg.qr(ht.array(x, split=0, comm=comm))
    assert q.split == 0 and q.shape == (m, 256) and r.split is None
    r_np = r.numpy()
    assert np.array_equal(r_np, np.triu(r_np)) and (np.diagonal(r_np) > 0).all()
    r_gap, q_gap, recon_gap = gaps(reference, x, q.numpy(), r_np, block=1024)
    assert r_gap <= R_LIMIT and q_gap <= Q_LIMIT and recon_gap <= RECON_LIMIT, (r_gap, q_gap, recon_gap)


def test_probe_refuses_a_degraded_first_pass_at_a_blocked_width(comm, monkeypatch):
    """``test_qr_depth.py::test_probe_rejects_finite_but_degraded_orthogonality``
    with an operand: cond 7e3, past 1 / sqrt(eps), keeps both Cholesky factors
    finite while Q1 drifts from orthonormal; the probe reads that off the
    mirrored Gram as it does off the whole one."""
    x = spectrum_rows(1024, 256, 7e3)
    for whole in (False, True):
        seen = {}
        _, _, ok = body(x, monkeypatch, whole=whole, seen=seen)
        assert np.isfinite(seen["r1"]).all() and np.isfinite(seen["r2"]).all()
        assert np.linalg.norm(seen["g2"] - np.eye(256)) >= 0.5 and not ok
    a = ht.array(x, split=0, comm=comm)
    with pytest.raises(ValueError, match="cholqr2 broke down"):
        ht.linalg.qr(a, method="cholqr2")
    q, r = ht.linalg.qr(a)  # Householder's answer
    np.testing.assert_allclose(q.numpy() @ r.numpy(), x, atol=1e-5)


@pytest.mark.parametrize("n", [48, 256, 384], ids=["whole", "two", "three"])
def test_blocked_counter_and_the_span_stat(n):
    """``phase_qr_blocked`` counts the calls whose CholeskyQR2 program took the
    blocked products and the span's ``blocks`` says how many; a Householder
    call has no such products; with telemetry off nothing moves."""
    p = ht.get_comm().size
    blocks = qr_mod._block_count(n)
    a = ht.array(scaled_rows(4 * n * p, n), split=0)
    before = fusion.cache_stats()["phase_qr_blocked"]
    ht.linalg.qr(a)
    assert fusion.cache_stats()["phase_qr_blocked"] == before
    with telemetry.enabled(1):
        ht.linalg.qr(a)
    assert fusion.cache_stats()["phase_qr_blocked"] - before == int(blocks > 1)
    with telemetry.enabled(1):
        ht.linalg.qr(a, method="tsqr")
    assert fusion.cache_stats()["phase_qr_blocked"] - before == int(blocks > 1)
    (parent,) = [s for s in _spans_of_a_call(a) if s[0] == "heat.qr"]
    assert str(parent[3]["blocks"]) == str(blocks) and str(parent[3]["mode"]) == "cholqr2"
    (parent,) = [s for s in _spans_of_a_call(a, method="tsqr") if s[0] == "heat.qr"]
    assert str(parent[3]["blocks"]) == "1"
