"""``KMeans.fit`` on float32 rows computes in float32 (ISSUE 29): against the
plain reference of the ``kmeans_f32_k8`` configuration on every path a fit
can take, the bfloat16-cast fit as the control, a structural guard on the
traced programs for what only the chip shows (the MXU's rounding), and the
spans and counters inside ``fit``.

The limits. Unstructured N(0, 1) rows, 30 iterations from seeded rows. A
program that multiplies in float32 makes every assignment the reference
makes, so its centres differ from the reference's by summation order alone
(a few 1e-8 relative) and no label differs; ONE differing assignment would
move a centre by about |x - c| / n_k, 1e-3 of the centres' norm at these
sizes, and the fits part from there. So: centres within 1e-5 (relative
Frobenius norm), no label different, inertia within 1e-5 (the fused paths
restore it as sum|x|^2 + sum min(score), a float32 cancellation of about
1e-7 of sum|x|^2). The seeds are ones on which no score of the two
formulations (quadratic expansion here, direct differences there) ties
within rounding: about one fit in ten at this size has such a tie.
"""

import collections
import functools
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
from chipbench import spec
from heat_tpu.cluster import KMeans, kmeans
from heat_tpu.cluster.kmeans import _lloyd_iter
from heat_tpu.core import fusion, telemetry
from heat_tpu.ops import lloyd

N, F, K, ITERS = 8192, 16, 8, 30
CENTERS_LIMIT, INERTIA_LIMIT = 1e-5, 1e-5
MODES = ("single", "sharded", "jnp")
KMEANS_KEYS = [f"phase_kmeans_{name}_ns" for name in fusion._KMEANS_PHASES] + [
    "phase_kmeans_fits", "phase_kmeans_dispatches", "phase_kmeans_syncs", "phase_kmeans_label_epilogues",
    "phase_kmeans_blocks", "phase_kmeans_tail_blocks",
]


@pytest.fixture(scope="module")
def reference():
    return spec.load_module("references", "kmeans_f32_k8.py")


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(29)
    data = rng.standard_normal((N, F)).astype(np.float32)
    return data, data[np.sort(rng.choice(N, size=K, replace=False))]


def fit(mode, data, init, monkeypatch, dtype=None, iters=ITERS, tol=-1.0):
    """One ``KMeans.fit`` through the public API on the path ``mode``: the
    sharded or the single-device kernel in interpret mode on the suite's
    virtual devices (``lloyd.takes`` pretended, as the chip would answer), or
    the jnp path the CPU takes. ``tol`` is the cell's: one that no shift
    reaches, so that all ``iters`` iterations run."""
    x = ht.array(data, split=None if mode == "single" else 0)
    if dtype is not None:
        x = x.astype(dtype)
    km = KMeans(n_clusters=K, init=ht.array(init), max_iter=iters, tol=tol)
    if mode != "jnp":
        monkeypatch.setattr(lloyd, "takes", lambda shape, k, split, sharding: mode)
    assert (km._fused_mode(x)[0] or "jnp") == mode
    return km.fit(x)


def gaps(km, want):
    centers, labels, inertia = want
    got = np.asarray(km.cluster_centers_.numpy(), np.float64)
    return (
        float(np.linalg.norm(got - centers) / np.linalg.norm(centers)),
        abs(km.inertia_ - inertia) / inertia,
        int((km.labels_.numpy() != np.asarray(labels)).sum()),
    )


@pytest.fixture(scope="module")
def want(reference, rows):
    data, init = rows
    return reference.lloyd(jnp.asarray(data), init, ITERS)


@pytest.mark.parametrize("mode", MODES)
def test_float32_fit_is_the_plain_reference(mode, rows, want, monkeypatch):
    km = fit(mode, *rows, monkeypatch)
    centers_gap, inertia_gap, labels_differ = gaps(km, want)
    assert km.n_iter_ == ITERS
    assert centers_gap <= CENTERS_LIMIT, centers_gap
    assert inertia_gap <= INERTIA_LIMIT, inertia_gap
    assert labels_differ == 0


@pytest.mark.parametrize("mode", MODES)
def test_bfloat16_cast_fit_falls_outside_the_limits(mode, rows, want, monkeypatch):
    """The control: rows cast to bfloat16 (the kernel then multiplies
    bfloat16 with float32 accumulation, the jnp path float32 on rounded
    rows). It has to fail the float32 limits, and by a wide margin."""
    km = fit(mode, *rows, monkeypatch, dtype=ht.bfloat16)
    centers_gap, _, labels_differ = gaps(km, want)
    assert centers_gap > 100 * CENTERS_LIMIT, centers_gap
    assert labels_differ > 0


# -- ISSUE 34: one program a fit, the convergence check on the device. The rule
# (``KMeans``' docstring) against a plain NumPy Lloyd, on every path a fit can take.
TOL, BLOBS_N = 1e-4, 2048


@pytest.fixture(scope="module")
def blobs():
    """Eight well-separated clusters and eight seeded rows to start from, some
    of them of one cluster, so that the centres travel for several iterations
    before the assignment stands still and the shift reads exactly 0."""
    rng = np.random.default_rng(34)
    means = 8.0 * rng.standard_normal((K, F))
    data = (means[rng.integers(0, K, BLOBS_N)] + rng.standard_normal((BLOBS_N, F))).astype(np.float32)
    return data, data[np.sort(rng.choice(BLOBS_N, size=K, replace=False))]


def numpy_lloyd(data, init, iters):
    """Plain Lloyd in float64: per iteration the centres that went in, the
    assignment against them, its sum of squared distances, and the shift."""
    x, c = data.astype(np.float64), init.astype(np.float64)
    steps = []
    for _ in range(iters):
        d = ((x[:, None, :] - c[None, :, :]) ** 2).sum(axis=2)
        labels = d.argmin(axis=1)
        new = np.stack([x[labels == j].mean(axis=0) if (labels == j).any() else c[j] for j in range(len(c))])
        steps.append((c, labels, d.min(axis=1).sum(), ((new - c) ** 2).sum()))
        c = new
    return steps, c


@pytest.fixture(scope="module")
def blobs_want(blobs):
    """(n_iter, labels, inertia, centres) the rule gives on ``blobs``: one
    iteration more than the first whose shift is at most ``TOL``."""
    steps, _ = numpy_lloyd(*blobs, 40)
    shifts = [shift for *_, shift in steps]
    first = next(i for i, shift in enumerate(shifts, 1) if shift <= TOL)
    assert first >= 3 and first % 8 != 7, shifts  # the centres travel, and no multiple of 8 would do
    assert not any(TOL / 10 < shift < TOL * 10 for shift in shifts), shifts  # float32 decides as float64 does
    _, labels, inertia, _ = steps[first]
    return first + 1, labels, inertia, steps[first + 1][0]


@pytest.mark.parametrize("mode", MODES)
def test_fit_stops_one_iteration_after_the_first_shift_within_tol(mode, blobs, blobs_want, monkeypatch):
    """The same ``n_iter_`` in all three modes (each against the one NumPy
    run), no multiple of 8, and ``labels_`` / ``inertia_`` those of the last
    iteration's input centres."""
    n_iter, labels, inertia, centers = blobs_want
    km = fit(mode, *blobs, monkeypatch, iters=300, tol=TOL)
    assert km.n_iter_ == n_iter
    np.testing.assert_array_equal(km.labels_.numpy(), labels)
    np.testing.assert_allclose(km.inertia_, inertia, rtol=1e-5)
    np.testing.assert_allclose(km.cluster_centers_.numpy(), centers, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", MODES)
def test_max_iter_of_one_runs_the_labelled_pass_alone(mode, blobs, monkeypatch):
    data, init = blobs
    ((_, labels, inertia, _),), centers = numpy_lloyd(data, init, 1)
    km = fit(mode, data, init, monkeypatch, iters=1, tol=TOL)
    assert km.n_iter_ == 1
    np.testing.assert_array_equal(km.labels_.numpy(), labels)
    np.testing.assert_allclose(km.inertia_, inertia, rtol=1e-5)
    np.testing.assert_allclose(km.cluster_centers_.numpy(), centers, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", MODES)
def test_a_nan_shift_does_not_stop_the_fit(mode, blobs, monkeypatch):
    """A NaN row makes a NaN centre and a NaN shift, which is not "at most
    ``tol``": the fit runs out its ``max_iter``, as the host's
    ``float(shift) <= tol`` had it."""
    data, init = blobs
    poisoned = data.copy()
    poisoned[7] = np.nan
    km = fit(mode, poisoned, init, monkeypatch, iters=9, tol=TOL)
    assert km.n_iter_ == 9 and np.isnan(km.inertia_)


def _programs(mode):
    """How many programs the path's jitted function holds (after a fit: the
    sharded path's function is found in its cache, not made here)."""
    if mode == "jnp":
        return kmeans._lloyd_run._cache_size()
    if mode == "single":
        return lloyd.fused_lloyd_run._cache_size()
    comm = ht.get_comm()
    run = lloyd._sharded_run_fn(comm.mesh, comm.axis_name, comm.size, K, BLOBS_N, True)
    return lloyd._sharded_run_fn.cache_info().currsize, run._cache_size()


@pytest.mark.parametrize("mode", MODES)
def test_max_iter_and_tol_are_operands_and_not_programs(mode, blobs, monkeypatch):
    """``max_iter`` and ``tol`` are traced scalars: fits with other values of
    them add no entry to the jitted program's cache (ROADMAP A4: a 30-iteration
    fit compiled an 8-step and a 6-step program)."""
    data, init = blobs
    fit(mode, data, init, monkeypatch, iters=30, tol=TOL)
    before = _programs(mode)
    assert fit(mode, data, init, monkeypatch, iters=12, tol=-1.0).n_iter_ == 12
    assert fit(mode, data, init, monkeypatch, iters=30, tol=1e-2).n_iter_ < 12
    assert _programs(mode) == before


def _dots(jaxpr, out):
    """Every ``dot_general`` equation of ``jaxpr``, descending into the
    jaxprs its equations carry (``pallas_call``, ``jit``, ``while`` ...)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _dots(inner, out)
    return out


def _float32_products_only(eqn) -> bool:
    """A contraction multiplies in float32 if XLA is told to (``HIGHEST`` on
    both operands) or if it is built from exact pieces: bfloat16 operands
    accumulated in float32, whose products the MXU does not round."""
    operands = [v.aval.dtype for v in eqn.invars]
    if all(d == jnp.bfloat16 for d in operands):
        return eqn.params["preferred_element_type"] == jnp.float32
    precision = eqn.params["precision"]
    return precision is not None and all(p == jax.lax.Precision.HIGHEST for p in tuple(precision))


TRACED = {
    "fused_lloyd_run": lambda x, c: lloyd.fused_lloyd_run(x, c, K, 2, -1.0),
    "fused_lloyd_run_of_one_step": lambda x, c: lloyd.fused_lloyd_run(x, c, K, 1, -1.0),
    "lloyd_iter": lambda x, c: _lloyd_iter(x, c, K),
}


@pytest.mark.parametrize("name", sorted(TRACED))
def test_no_contraction_on_float32_rows_is_left_at_the_default(name):
    """What only the chip shows: the XLA default multiplies float32 operands
    on the MXU in one bfloat16 pass, and a CPU computes the same program in
    float32 whatever it asks for. So the traced programs are held to asking."""
    x = jax.ShapeDtypeStruct((4096, F), jnp.float32)
    c = jax.ShapeDtypeStruct((K, F), jnp.float32)
    dots = _dots(jax.make_jaxpr(TRACED[name])(x, c).jaxpr, [])
    assert dots, "the traced program holds no contraction"
    assert all(_float32_products_only(eqn) for eqn in dots), [str(eqn) for eqn in dots]


def test_bfloat16_rows_keep_their_one_bfloat16_pass():
    x = jax.ShapeDtypeStruct((4096, F), jnp.bfloat16)
    c = jax.ShapeDtypeStruct((K, F), jnp.float32)
    for name in ("fused_lloyd_run", "fused_lloyd_run_of_one_step"):
        dots = _dots(jax.make_jaxpr(TRACED[name])(x, c).jaxpr, [])
        assert dots and all(v.aval.dtype == jnp.bfloat16 for eqn in dots for v in eqn.invars), name
        assert all(eqn.params["precision"] is None for eqn in dots), name
    assert lloyd.mxu_precision(jnp.bfloat16) is None
    assert lloyd.mxu_precision(jnp.float32) == lloyd.mxu_precision(jnp.float64) == jax.lax.Precision.HIGHEST


def test_bf16_pieces_add_up_exactly():
    rng = np.random.default_rng(3)
    x = jnp.asarray((rng.standard_normal(4096) * 10.0 ** rng.integers(-6, 7, 4096)).astype(np.float32))
    pieces = lloyd._bf16_pieces(x)
    assert len(pieces) == 3 and all(p.dtype == jnp.bfloat16 for p in pieces)
    total = sum(np.asarray(p, np.float64) for p in pieces)
    np.testing.assert_array_equal(total, np.asarray(x, np.float64))
    low = x.astype(jnp.bfloat16)
    assert lloyd._bf16_pieces(low) == (low,)


def _kmeans_delta(before):
    after = fusion.cache_stats()
    return {key: after[key] - before[key] for key in KMEANS_KEYS}


@pytest.mark.parametrize("mode", ("sharded", "jnp"))
def test_counters_of_one_fit_with_telemetry_on(mode, rows, monkeypatch):
    data, init = rows
    fit(mode, data[:1024], init, monkeypatch)  # compiled before the counted fit
    before = fusion.cache_stats()
    with telemetry.enabled(1):
        km = fit(mode, data[:1024], init, monkeypatch)
    got = _kmeans_delta(before)
    assert km.n_iter_ == ITERS
    # one program a fit, which checks convergence itself, and one blocking read
    # of n_iter_ and inertia_ together (ISSUE 34; four and five before)
    assert (got["phase_kmeans_fits"], got["phase_kmeans_dispatches"], got["phase_kmeans_syncs"]) == (1, 1, 1)
    # the program runs no XLA label pass over the rows: the fused program's last
    # kernel pass writes the labels, the jnp program's last iteration gives them
    assert got["phase_kmeans_label_epilogues"] == 0
    # the grid steps of one kernel pass and those that took the masked body, once
    # a fit (ISSUE 38): a device's 128 rows are one block, which is the tail block;
    # the jnp program runs no kernel
    blocks = (got["phase_kmeans_blocks"], got["phase_kmeans_tail_blocks"])
    assert blocks == ((1, 1) if mode == "sharded" else (0, 0))
    for name in fusion._KMEANS_PHASES:
        assert got[f"phase_kmeans_{name}_ns"] > 0, name


@pytest.mark.parametrize("mode,whole,over,want", [("sharded", 2, 5, (3, 1)), ("single", 2, 0, (2, 0))])
def test_block_counters_of_a_fit_over_whole_blocks_and_a_tail(mode, whole, over, want, monkeypatch):
    """Sharded rows, two whole blocks and five rows a device with three rows
    of padding on the last: ``phase_kmeans_blocks`` 3 a fit and
    ``phase_kmeans_tail_blocks`` 1, whatever ``max_iter``; one device's two
    whole blocks alone: 2 and 0, no block is masked."""
    devices = ht.get_comm().size if mode == "sharded" else 1
    n = devices * (whole * lloyd._block_cols(F, K) + over) - (3 if over else 0)
    data = np.random.default_rng(38).standard_normal((n, F)).astype(np.float32)
    before = fusion.cache_stats()
    with telemetry.enabled(1):
        fit(mode, data, data[:K], monkeypatch, iters=2)
    got = _kmeans_delta(before)
    assert (got["phase_kmeans_fits"], got["phase_kmeans_blocks"], got["phase_kmeans_tail_blocks"]) == (1, *want)


def test_label_epilogues_reader_on_a_made_up_window():
    """``label_epilogues_per_op``: the counter's growth over the fits', 0 when
    it stood still, ``None`` for a program without it or a window without fits."""
    import types

    read = spec.load_module("layer_metrics", "label_epilogues_per_op.py").read

    def window(before, after):
        return types.SimpleNamespace(counters={"before": {"fusion": before}, "after": {"fusion": after}})

    fits, key = "phase_kmeans_fits", "phase_kmeans_label_epilogues"
    assert read(window({fits: 3, key: 12}, {fits: 21, key: 12})) == 0
    assert read(window({fits: 3, key: 12}, {fits: 21, key: 84})) == 4
    assert read(window({fits: 3}, {fits: 21})) is None
    assert read(window({fits: 3, key: 12}, {fits: 3, key: 12})) is None


def test_counters_stay_where_they_are_with_telemetry_off(rows, monkeypatch):
    data, init = rows
    before = fusion.cache_stats()
    assert not telemetry.tracing()
    fit("jnp", data[:1024], init, monkeypatch, iters=3)
    assert _kmeans_delta(before) == dict.fromkeys(KMEANS_KEYS, 0)


@pytest.mark.parametrize("mode", ("jnp", "sharded"))
def test_spans_of_one_fit_in_a_profiler_session(mode, rows, monkeypatch):
    """``heat.kmeans.fit`` with its children side by side, in the session's
    own ``.xplane.pb``, and the ``gaps`` verb's reader sees them; the parent
    carries the grid steps of a kernel pass and its masked ones (ISSUE 38)."""
    data, init = rows
    fit(mode, data[:1024], init, monkeypatch)
    with tempfile.TemporaryDirectory() as directory:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(directory, profiler_options=options)
        try:
            fit(mode, data[:1024], init, monkeypatch)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
        spans = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)  # nanobind's stats type
            for plane in jax.profiler.ProfileData.from_file(path).planes:
                if plane.name == "/host:CPU":
                    spans += [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                        for line in plane.lines for e in line.events if e.name.startswith("heat.kmeans.")
                    ]
    (parent,) = [sp for sp in spans if sp[0] == "heat.kmeans.fit"]
    assert (parent[3]["mode"], int(parent[3]["n"]), int(parent[3]["f"]), int(parent[3]["k"])) == (mode, 1024, F, K)
    assert (int(parent[3]["blocks"]), int(parent[3]["tail_blocks"])) == ((1, 1) if mode == "sharded" else (0, 0))
    children = sorted((sp for sp in spans if sp is not parent), key=lambda sp: sp[1])
    names = [sp[0].rsplit(".", 1)[1] for sp in children]
    assert names == ["init", "prepare", "dispatch", "sync", "copy", "wrap"]
    assert all(parent[1] <= sp[1] and sp[2] <= parent[2] for sp in children)
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:])), "children overlap"


# -- the kernel at the benchmark's widths, compiled for a described v5e (no chip):
# what interpret mode cannot show (scoped VMEM, Mosaic's refusals). All in this
# one file, behind one fixture (on-chip-measurement guide, section 2).
@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # no TPU compiler here, or its library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_v5e(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2[0])


def _compiled(fn, *shapes):
    """``fn`` compiled for the described chip(s). Such a compile cannot be read
    back from the compilation cache without a chip, and the chip runs with
    64-bit mode off: under the suite's x64 ``jnp.argmin`` asks for an int64
    index, which Mosaic refuses (PERF.md, section 7)."""
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with jax.enable_x64(False):
            return fn.lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def _compiled_text(fn, *shapes):
    return _compiled(fn, *shapes).as_text()


@pytest.mark.parametrize("last", [False, True], ids=["plain", "labels"])
@pytest.mark.parametrize(
    "dtype,f,k,n",
    [("float32", 16, 8, None), ("bfloat16", 16, 8, None), ("float32", 512, 128, None), ("float32", 20, 17, None),
     ("float32", 16, 8, 1000)],
)
def test_kernel_compiles_for_a_v5e_inside_scoped_vmem(one_v5e, dtype, f, k, n, last):
    """The plain pass and the program's last one, which also stores its
    (1, block) labels row and adds up the squares of the float32 block it
    holds (an (f, 128) accumulator more), at the block ``_block_cols`` gives both, on an operand that ends
    inside its last block, off a lane-tile boundary (as the labels do); and
    on fewer rows than one block (ISSUE 32), where the (f, block) input block
    and the (1, block) labels block are WIDER than their arrays: interpret
    mode cannot show Mosaic refusing that. Since ISSUE 38 the kernel holds two
    bodies, the masked one and the one for whole blocks, under ``pl.when``:
    they are never live together and both fit the block one fitted."""
    block = lloyd._block_cols(f, k, jnp.dtype(dtype).itemsize)
    assert n is None or n < block
    xT = jax.ShapeDtypeStruct((f, n or 4 * block - 77), jnp.dtype(dtype), sharding=one_v5e)
    c = jax.ShapeDtypeStruct((k, f), jnp.float32, sharding=one_v5e)
    nv = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_v5e)
    call = jax.jit(lambda xT, c, nv: lloyd._kernel_call_T(xT, c, k, nv, False, last))
    text = _compiled_text(call, xT, c, nv)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert ("lloyd_pass_labels" in text) == last and "lloyd_pass" in text


def _opcodes_on_rows(text, dtype, n, f):
    """The opcodes, with their counts, of the compiled instructions that give
    an array of the rows' type ((n, f) or (f, n) of ``dtype``, alone or in a
    tuple) or take the result of one that does."""
    rows = re.compile(rf"\b{dtype}\[(?:{n},{f}|{f},{n})\]")
    instructions = []
    for line in text.splitlines():
        for _ in range(3):  # layouts, attributes: nothing in braces is a shape to read here
            line = re.sub(r"\{[^{}]*\}", "", line)
        name, equals, instruction = line.partition(" = ")
        opcode = re.search(r"([a-z][\w-]*)\(", instruction)
        if equals and opcode:
            gives = bool(rows.search(instruction[: opcode.start()]))
            takes = set(re.findall(r"%([\w.-]+)", instruction[opcode.end():]))
            instructions.append((name.split("%")[-1].strip(), opcode.group(1), gives, takes))
    holders = {name for name, _, gives, _ in instructions if gives}
    return collections.Counter(
        opcode for _, opcode, gives, takes in instructions if gives or takes & holders
    )


ROWS_PLUMBING = {"parameter", "bitcast", "tuple", "get-tuple-element", "while", "custom-call"}


@pytest.mark.parametrize("dtype,short", [("float32", "f32"), ("bfloat16", "bf16")])
def test_nothing_but_the_kernel_reads_the_rows_of_a_run(one_v5e, dtype, short):
    """ISSUE 32: in the compiled ``fused_lloyd_run`` at a ragged n, the rows
    parameter meets a ``bitcast`` (the samples-in-lanes view) and the two
    kernels, through the loop's tuple: no ``pad``, ``copy``, ``transpose``,
    ``convert`` or fusion takes or gives an array of the rows' size."""
    n = 3 * lloyd._block_cols(F, K, jnp.dtype(dtype).itemsize) - 77
    x = jax.ShapeDtypeStruct((n, F), jnp.dtype(dtype), sharding=one_v5e)
    c = jax.ShapeDtypeStruct((K, F), jnp.float32, sharding=one_v5e)
    max_iter = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_v5e)  # traced operands, as a fit's are
    tol = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_v5e)
    text = _compiled_text(jax.jit(lambda x, c, m, t: lloyd.fused_lloyd_run(x, c, K, m, t)), x, c, max_iter, tol)
    opcodes = _opcodes_on_rows(text, short, n, F)
    assert set(opcodes) <= ROWS_PLUMBING and opcodes["bitcast"] == 1 and opcodes["custom-call"] == 2, opcodes
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_nothing_but_the_kernel_reads_the_rows_of_a_sharded_run(v5e_2x2):
    """The same inside the sharded program's ``shard_map`` on the 2 x 2 mesh:
    per device a ``bitcast`` and the two kernels, on a payload that is ragged
    against the block and against the mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(v5e_2x2), ("x",))
    local = 2 * lloyd._block_cols(F, K) + 77
    x = jax.ShapeDtypeStruct((4 * local, F), jnp.float32, sharding=NamedSharding(mesh, P("x", None)))
    c = jax.ShapeDtypeStruct((K, F), jnp.float32, sharding=NamedSharding(mesh, P()))
    max_iter = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    tol = jax.ShapeDtypeStruct((), jnp.float32, sharding=NamedSharding(mesh, P()))
    run = lloyd._sharded_run_fn(mesh, "x", 4, K, 4 * local - 3, False)
    text = _compiled_text(run, x, c, max_iter, tol)
    opcodes = _opcodes_on_rows(text, "f32", local, F)
    assert set(opcodes) <= ROWS_PLUMBING and opcodes["bitcast"] == 1 and opcodes["custom-call"] == 2, opcodes
    assert "all-reduce" in text


# -- ISSUE 33: the distance engine's tile program at the cdist_f32 configuration's
# sizes, compiled for the described chips (here because this file holds the fixture)
@pytest.fixture(scope="module", params=["cdist_ring_4c", "cdist_50k_1c"])
def cdist_program(request, v5e_2x2):
    """(chips, rows of the operand, the compiled tile program) of one cell."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from heat_tpu.spatial import distance

    cell = spec.Cell(request.param)
    chips, f = cell.chips, cell.config["features"]
    n = cell.config["rows"][str(chips)]
    mesh = Mesh(np.array(v5e_2x2[:chips]), ("x",))
    x = jax.ShapeDtypeStruct((n, f), jnp.float32, sharding=NamedSharding(mesh, P("x", None)))
    build = distance._tile_program.__wrapped__  # the cache keeps no program of a described mesh
    return chips, n, _compiled(build(mesh, "x", chips, distance._euclidian_fast), x, x)


def test_cdist_program_holds_its_rows_of_the_result_and_one_chunk(cdist_program):
    """10.0 GB of result a device, born uninitialised and written in place;
    beside it at most one column chunk of a tile (0.5 GB), never a tile
    (2.5 GB on four chips, 10 GB on one), and the operand."""
    from heat_tpu.spatial import distance

    chips, n, compiled = cdist_program
    memory = compiled.memory_analysis()
    assert abs(memory.output_size_in_bytes - n // chips * n * 4) < 1 << 24  # the rows held here, and tile padding
    assert memory.temp_size_in_bytes <= 1.1 * distance._CHUNK_BYTES < 2.5e9, memory
    assert memory.output_size_in_bytes + memory.temp_size_in_bytes + memory.argument_size_in_bytes < 10.7e9
    text = compiled.as_text()
    assert 'custom_call_target="AllocateBuffer"' in text, "the result buffer is filled before it is written"
    assert not re.search(rf"f32\[{n // chips},{n}\][^ ]* broadcast\(", text)


def test_cdist_program_moves_operand_shards_only(cdist_program):
    """The ring's collectives on the 2 x 2 mesh are collective-permutes of one
    (25 000, 64) operand shard; one chip has none."""
    chips, n, compiled = cdist_program
    lines = re.findall(r"(?:all-gather|all-reduce|all-to-all|collective-permute)[^\n]*", compiled.as_text())
    if chips == 1:
        assert not lines
        return
    assert lines and all(line.startswith("collective-permute") for line in lines), lines
    for line in lines:
        for shape in re.findall(r"f32\[([\d,]+)\]", line):
            assert int(np.prod([int(d) for d in shape.split(",")])) <= n // chips * 64, line[:200]


def test_cdist_program_multiplies_in_float32(cdist_program):
    """No product of the compiled program is left to the MXU's default on
    float32 operands: each takes bfloat16 pieces stacked six deep (K = 6 x 64,
    ``ops/mxu.py``) or asks for ``HIGHEST``."""
    _, _, compiled = cdist_program
    text = compiled.as_text()
    products = re.findall(r"convolution\(([^)]*)\)([^\n]*)", text)
    assert products
    for operands, rest in products:
        if "operand_precision={highest,highest}" in rest:
            continue
        for name in re.findall(r"%[\w.\-]+", operands):
            (defined,) = set(re.findall(rf"{re.escape(name)} = (\w+)\[([\d,]+)\]", text))
            assert defined[0] == "bf16" and "384" in defined[1].split(","), (name, defined)


# -- ISSUE 35: CholeskyQR2 at the qr_tall_f32 configuration's size, the program the
# fusion engine compiles for ``ht.linalg.qr(a)`` on one chip (here because this file
# holds the fixture)
@pytest.fixture(scope="module")
def qr_program(one_v5e):
    """(rows, columns, the compiled ``(Q, R, ok)`` program) of ``qr_tall_1c``."""
    import functools
    import importlib

    qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
    cfg = spec.Cell("qr_tall_1c").config
    m, n = cfg["rows"]["1"], cfg["columns"]
    x = jax.ShapeDtypeStruct((m, n), jnp.float32, sharding=one_v5e)
    return m, n, _compiled(jax.jit(functools.partial(qr_mod._cholqr2_op, calc_q=True)), x)


def test_qr_program_holds_three_operand_sized_buffers_on_one_chip(qr_program):
    """A, the first pass's Q1 (alive until its Gram and the product that forms
    Q have read it) and Q: 7.68 GB of the chip's 16e9 B, and nothing else of
    the operand's size."""
    m, n, compiled = qr_program
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == m * n * 4 and memory.alias_size_in_bytes == 0
    assert abs(memory.output_size_in_bytes - (m * n + n * n) * 4) < 1 << 16
    assert m * n * 4 <= memory.temp_size_in_bytes < 1.01 * m * n * 4  # Q1
    assert memory.peak_memory_in_bytes < 7.7e9 < 16e9


def test_qr_program_leaves_no_product_at_the_mxu_default(qr_program):
    """Every product of the compiled program (the two Grams, Q1, Q, R2 R1 and
    the blocks of the Cholesky factorisations and triangular solves) asks for
    ``HIGHEST``; none is a plain ``dot``."""
    _, _, compiled = qr_program
    text = compiled.as_text()
    products = re.findall(r" convolution\([^\n]*", text)
    assert len(products) >= 5 and not re.search(r" dot\(", text)
    assert all("operand_precision={highest,highest}" in line for line in products)
    assert text.count('custom_call_target="Cholesky"') >= 2


# -- ISSUE 36: the tall products by column blocks, the blocks a triangle holds
def test_qr_program_multiplies_only_the_blocks_a_triangle_holds(qr_program):
    """Four whole products are ``4 * 2 m n^2`` FLOP; by column blocks of 128
    the Grams stop at their upper block triangle and Q1, Q skip the zero
    blocks of R^-1: ten blocks of sixteen, and at most 0.80 with the rest."""
    m, n, compiled = qr_program
    assert compiled.cost_analysis()["flops"] <= 0.80 * 4 * 2 * m * n * n


def test_qr_program_copies_no_rows(qr_program):
    """Each column block is stored where it stays (a constant offset that is a
    multiple of 128): nothing of the operand's rows is copied, concatenated or
    padded on the way."""
    m, _, compiled = qr_program
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    assert not re.findall(rf"= \w+\[{m},[^\n]*? (?:copy|concatenate|pad)\([^\n]*", entry)
    assert len(re.findall(rf"= f32\[{m},\d+\][^ ]* fusion\(", entry)) == 8  # four column blocks of Q1, four of Q


def test_qr_program_on_sharded_rows_reduces_block_rows_once_a_pass(v5e_2x2):
    """Rows sharded over the four described chips: ONE all-reduce a pass, of
    the Gram's four block rows together (655 360 B against the whole Gram's
    1 048 576), none of the operand's size, and no all-gather."""
    import functools
    import importlib

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
    cfg = spec.Cell("qr_tall_1c").config
    m, n = cfg["rows"]["1"], cfg["columns"]
    mesh = Mesh(np.array(v5e_2x2), ("x",))
    x = jax.ShapeDtypeStruct((4 * m, n), jnp.float32, sharding=NamedSharding(mesh, P("x", None)))
    text = _compiled_text(jax.jit(functools.partial(qr_mod._cholqr2_op, calc_q=True)), x)
    assert not re.search(r" all-gather(-start)?\(| all-to-all\(| collective-permute(-start)?\(", text)
    reduces = re.findall(r"= (\([^=]*\)|\S+) all-reduce(?:-start)?\(", text)
    assert 1 <= len(reduces) <= 2, reduces
    for shapes in reduces:
        entries = sum(int(np.prod([int(d) for d in dims.split(",")])) for dims in re.findall(r"f32\[([\d,]+)\]", shapes))
        assert entries == qr_mod._gram_entries(n) == 128 * 1280, shapes



# -- ISSUE 40: the lasso's precompute at the benchmark's size (3 145 728 x 512), the Gram
# it shares with the QR program above (``core/linalg/qr.py::tall_gram``; here for the fixture)
@pytest.fixture(scope="module")
def lasso_rows():
    cfg = spec.Cell("lasso_1c").config
    return cfg["rows"]["1"], cfg["features"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_lasso_precompute_holds_the_rows_and_nothing_of_their_size(one_v5e, lasso_rows, dtype):
    """G and cy from the rows as they lie, read in place chunk by chunk: no
    transposed copy, no float32 copy of bfloat16 rows, no temporary to speak
    of. 6.455 GB of arguments (the rows and the labels) is what the chip holds."""
    from heat_tpu.regression import lasso

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n, m = lasso_rows
    mesh = Mesh(np.array([one_v5e._device]), ("x",))  # the one program, on a mesh of one
    rows = NamedSharding(mesh, P("x", None))
    x = jax.ShapeDtypeStruct((n, m), jnp.dtype(dtype), sharding=rows)
    y = jax.ShapeDtypeStruct((n, 1), jnp.float32, sharding=rows)
    compiled = _compiled(lasso._gram_precompute(mesh, "x"), x, y)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == n * m * jnp.dtype(dtype).itemsize + n * 4
    assert memory.temp_size_in_bytes < 1 << 22 and memory.output_size_in_bytes < (m * m + m) * 4 + (1 << 12)
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    assert not re.findall(rf"= \w+\[(?:{n},{m}|{m},{n})\][^\n]*? (?:copy|transpose|convert|concatenate|pad)\([^\n]*", entry)
    products = re.findall(r" convolution\([^\n]*", text)
    assert len(products) == 4 and not re.search(r" dot\(", text)  # a chunk's four block rows; cy is a multiply-reduce on the VPU
    assert len(re.findall(r" while\(", entry)) == 1  # the chunks of _SUM_ROWS rows
    if dtype == "float32":
        assert all("operand_precision={highest,highest}" in line for line in products)
    else:
        assert not any("highest" in line for line in products)  # one bfloat16 pass


def test_lasso_precompute_on_sharded_rows_is_one_all_reduce(v5e_2x2, lasso_rows):
    """Rows sharded over the four described chips: each sums its own rows, then
    G and cy cross the chips together (at most two all-reduces, the budget
    ``test_mesh64_compile.py`` pins at 64 devices), nothing of the operand's
    size moves, and no all-gather."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from heat_tpu.regression import lasso

    n, m = lasso_rows
    mesh = Mesh(np.array(v5e_2x2), ("x",))
    rows = NamedSharding(mesh, P("x", None))
    x = jax.ShapeDtypeStruct((4 * n, m), jnp.float32, sharding=rows)
    y = jax.ShapeDtypeStruct((4 * n, 1), jnp.float32, sharding=rows)
    text = _compiled_text(lasso._gram_precompute(mesh, "x"), x, y)
    assert not re.search(r" all-gather(-start)?\(| all-to-all\(| collective-permute(-start)?\(", text)
    reduces = re.findall(r"= (\([^=]*\)|\S+) all-reduce(?:-start)?\(", text)
    assert 1 <= len(reduces) <= 2, reduces
    entries = sum(int(np.prod([int(d) for d in dims.split(",")])) for shapes in reduces for dims in re.findall(r"f32\[([\d,]+)\]", shapes))
    assert entries == m * m + m  # G, summed and mirrored on each chip, and cy


def test_lasso_descent_is_a_loop_of_sweeps_without_collectives(one_v5e, lasso_rows):
    """The descent program on the chip's path (``Lasso._fit`` hands it the
    mesh where ``ops/cd.py::takes`` G) is ONE ``while`` over the sweeps,
    which carries the count, theta and the stop flag, around ONE
    ``lasso_cd_sweep`` kernel (G held in VMEM, a sweep's 512 steps inside it:
    the custom call ``cd_kernel_step_us`` finds by its name) and ONE product,
    the head's ``c = cy - G theta`` at ``HIGHEST`` (a VPU multiply-reduce on a
    v5e, no bfloat16 pass). No loop of coordinate steps is left for
    ``cd_step_us`` to find. ``max_iter`` and ``tol`` are operands; no
    collective."""
    from jax.sharding import Mesh

    from heat_tpu.ops import cd
    from heat_tpu.regression import lasso

    is_sweep_loop = spec.load_module("layer_metrics", "cd_step_us.py").is_sweep_loop
    is_sweep_kernel = spec.load_module("layer_metrics", "cd_kernel_step_us.py").is_sweep_kernel
    _, m = lasso_rows
    assert cd.held_bytes(m) <= cd.VMEM_BUDGET
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_v5e) for s, d in
              (((m, m), jnp.float32), ((m,), jnp.float32), ((), jnp.float32), ((), jnp.int32), ((), jnp.int32), ((), jnp.float32))]
    mesh = Mesh(np.array([one_v5e._device]), ("x",))
    bound = functools.partial(lasso.lasso_descent.lower, mesh=mesh)
    with jax.enable_x64(False):
        products = [line for line in bound(*shapes).as_text().splitlines() if "stablehlo.dot_general" in line]
    assert len(products) == 1 and "precision = [HIGHEST, HIGHEST]" in products[0]
    text = _compiled_text(types.SimpleNamespace(lower=bound), *shapes)
    assert "jit_lasso_descent" in text and "bf16" not in text
    loops = [line.strip() for line in text.splitlines() if re.search(r" while\(", line)]
    assert len(loops) == 1 and loops[0] in text[text.index("ENTRY"):]
    assert re.sub(r"\{[^}]*\}", "", loops[0].partition(" = ")[2]).startswith(f"(s32[], f32[{m},1], pred[],")
    assert not is_sweep_loop(loops[0], m)
    calls = [line.strip() for line in text.splitlines() if " custom-call(" in line]
    assert len(calls) == 1 and 'custom_call_target="tpu_custom_call"' in calls[0] and is_sweep_kernel(calls[0])
    assert len([line for line in text.splitlines() if re.search(r' fusion\(.*op_name="[^"]*/dot_general"', line)]) == 1
    assert not re.search(r" all-reduce(-start)?\(| all-gather(-start)?\(", text)


# -- ISSUE 42: the spectral fit's programs at the benchmark's size (32 768 rows of 64,
# 300 Lanczos steps): the affinity is the cdist tile program above, then these two
@pytest.fixture(scope="module")
def spectral_shape():
    cfg = spec.Cell("spectral_1c").config
    return cfg["rows"]["1"], cfg["n_lanczos"]


def test_laplacian_program_writes_over_the_similarity(one_v5e, spectral_shape):
    """The one n x n buffer a fit holds: the Laplacian program takes the
    similarity (4.295 GB), donated, and writes L over it, with nothing of
    n x n beside it."""
    from heat_tpu.graph import laplacian

    n, _ = spectral_shape
    s = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_v5e)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_v5e)
    for mode in ("fully_connected", "eNeighbour"):
        for definition in ("norm_sym", "simple"):
            bound = functools.partial(laplacian._laplacian_program.lower, definition=definition, mode=mode, key="upper", weighted=True)
            memory = _compiled(types.SimpleNamespace(lower=bound), s, t).memory_analysis()
            assert memory.output_size_in_bytes == memory.alias_size_in_bytes == n * n * 4
            assert n * n * 4 <= memory.argument_size_in_bytes < n * n * 4 + 4096  # and the threshold
            assert memory.temp_size_in_bytes < 1 << 20, (mode, definition)


def test_lanczos_program_holds_the_laplacian_and_its_basis(one_v5e, spectral_shape):
    """Lanczos reads L and holds V (m, n), T and vectors: L + 40 MB, within
    ISSUE 42's 1.1 n^2 4 B + 64 MB. Its products are matrix-vector products,
    which XLA:TPU takes on the VPU as a float32 multiply and row reduce (no
    ``dot``, no ``bf16``: at ``HIGHEST`` as at the default), no custom call. The
    loop is the one ``lanczos_step_us`` finds by its carry, and the only
    ``while``."""
    from heat_tpu.core.linalg import solver

    is_lanczos_loop = spec.load_module("layer_metrics", "lanczos_step_us.py").is_lanczos_loop
    n, m = spectral_shape
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_v5e)
    v = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_v5e)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_v5e)
    lower = lambda a, v, key: solver._lanczos_fused.lower(a, v, m, key)  # noqa: E731
    compiled = _compiled(types.SimpleNamespace(lower=lower), a, v, key)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.output_size_in_bytes + memory.temp_size_in_bytes
    assert n * n * 4 <= held <= 1.1 * n * n * 4 + (64 << 20)
    text = compiled.as_text()
    assert not re.search(r" dot\(| convolution\(", text) and "bf16" not in text
    loops = [line.strip() for line in text.splitlines() if re.search(r" while\(", line)]
    assert len(loops) == 1 and is_lanczos_loop(loops[0], m, n), loops
    assert not is_lanczos_loop(loops[0], m + 1, n)
    assert "tpu_custom_call" not in text


def test_upper_lanczos_program_reads_the_laplacian_in_place(one_v5e, spectral_shape):
    """The program a v5e runs for the cell: each step's product with L is the
    one Pallas kernel of ``ops/symv.py`` (its upper block triangle, float32 on
    the VPU), L read in place: no pad, no transpose, no copy, under 2 MB of
    temporaries beside V. The loop keeps the carry ``lanczos_step_us`` finds,
    and stays the only ``while``."""
    from heat_tpu.core.linalg import solver
    from heat_tpu.ops import symv

    is_lanczos_loop = spec.load_module("layer_metrics", "lanczos_step_us.py").is_lanczos_loop
    n, m = spectral_shape
    assert n % symv.BLOCK == 0
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_v5e)
    v = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_v5e)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_v5e)
    lower = lambda a, v, key: solver._lanczos_upper.lower(a, v, m, key)  # noqa: E731
    compiled = _compiled(types.SimpleNamespace(lower=lower), a, v, key)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < n * n * 4 + (1 << 20)
    assert memory.output_size_in_bytes < 4 * m * n + (1 << 20) and memory.temp_size_in_bytes < 2 << 20  # V, T
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "bf16" not in text and not re.search(r" dot\(| convolution\(", text)
    assert not re.search(rf"f32\[{n},{n}\]\S* (copy|transpose|pad)\(", text)
    loops = [line.strip() for line in text.splitlines() if re.search(r" while\(", line)]
    assert len(loops) == 1 and is_lanczos_loop(loops[0], m, n), loops
