"""``ht.spatial.cdist`` / ``rbf`` on row-split operands run ONE tile program
for every mesh size, one device included, and multiply float32 rows in
float32 (ISSUE 33): against a plain float64 reference on p in {1, 4}, Y = X
and Y != X, even and ragged row counts, both forms of the metric; a float32
case that a bfloat16-rounded contraction fails; the collective budget of the
compiled ring; the spans and counters of a call.

What only the chip shows (the MXU's rounding of a float32 product left at the
default) is guarded structurally: every contraction of the traced program on
float32 rows asks for ``HIGHEST``, bfloat16 rows keep their one pass. The
program at the benchmark's size, compiled for a described v5e, is in
``test_kmeans_f32.py``, the one file that holds the topology fixture.
"""

import glob
import os
import re
import tempfile
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import fusion, telemetry
from heat_tpu.spatial import distance

CDIST_KEYS = [f"phase_cdist_{name}_ns" for name in fusion._CDIST_PHASES] + ["phase_cdist_calls", "phase_cdist_rotations"]


@pytest.fixture(scope="module", params=[1, 4], ids=["p1", "p4"])
def comm(request):
    devices = jax.devices()
    if len(devices) < request.param:
        pytest.skip(f"needs {request.param} devices")
    return ht.MeshCommunication(devices[: request.param])


def plain(a, b):
    """Direct differences in float64: the reference of ``chipbench``'s
    configuration, for two operands."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    diff = a[:, None, :] - b[None, :, :]
    return np.sqrt((diff * diff).sum(-1))


def off_centre(n, f, seed):
    return (1.0 + np.random.default_rng(seed).standard_normal((n, f))).astype(np.float32)


@pytest.mark.parametrize("quadratic", [False, True], ids=["direct", "expansion"])
@pytest.mark.parametrize("n,m", [(32, None), (37, None), (32, 20), (37, 18)], ids=["sym", "sym_ragged", "xy", "xy_ragged"])
def test_cdist_is_the_plain_reference(comm, n, m, quadratic):
    a = off_centre(n, 6, n)
    b = a if m is None else off_centre(m, 6, 1000 + m)
    X = ht.array(a, split=0, comm=comm)
    Y = None if m is None else ht.array(b, split=0, comm=comm)
    d = ht.spatial.cdist(X, Y, quadratic_expansion=quadratic)
    assert d.shape == (n, len(b)) and d.split == 0 and d.dtype == ht.float32
    # the expansion cancels: |x|^2 + |y|^2 - 2 x.y is good to a few roundings
    # of |x|^2 + |y|^2 (about 14 here), so d^2 to 1e-5 and d to 2e-3 at d = 0
    np.testing.assert_allclose(d.numpy(), plain(a, b), rtol=2e-5, atol=2e-3 if quadratic else 1e-5)


@pytest.mark.parametrize("quadratic", [False, True], ids=["direct", "expansion"])
@pytest.mark.parametrize("n,m", [(37, None), (32, 20)], ids=["sym_ragged", "xy"])
def test_rbf_is_the_plain_reference(comm, n, m, quadratic):
    a = off_centre(n, 6, n)
    b = a if m is None else off_centre(m, 6, 1000 + m)
    X = ht.array(a, split=0, comm=comm)
    Y = None if m is None else ht.array(b, split=0, comm=comm)
    r = ht.spatial.rbf(X, Y, sigma=2.0, quadratic_expansion=quadratic)
    assert r.shape == (n, len(b)) and r.split == 0
    np.testing.assert_allclose(r.numpy(), np.exp(-plain(a, b) ** 2 / 8.0), rtol=1e-4, atol=1e-6)


def test_replicated_operand_stays_one_expression(comm):
    a, b = off_centre(32, 6, 1), off_centre(20, 6, 2)
    d = ht.spatial.cdist(ht.array(a, split=0, comm=comm), ht.array(b, comm=comm), quadratic_expansion=True)
    assert d.split == 0
    np.testing.assert_allclose(d.numpy(), plain(a, b), rtol=2e-5, atol=2e-3)
    m = ht.spatial.manhattan(ht.array(a, split=0, comm=comm))
    np.testing.assert_allclose(m.numpy(), np.abs(a[:, None] - a[None]).sum(-1), rtol=1e-5, atol=1e-5)


# -- float32 rows multiply in float32 -------------------------------------
def near_pairs(seed=33, n=64, f=64):
    """Off-centre rows (|x| about 11) with a near pair for every row: row
    2k + 1 is row 2k moved by 1e-2 of a coordinate's spread. The distance of
    a near pair is about 0.08; a product taken on bfloat16-rounded operands
    is off by about |x|^2 * 2^-9 = 0.25 on d^2, larger than the whole d^2."""
    rng = np.random.default_rng(seed)
    x = (1.0 + rng.standard_normal((n, f))).astype(np.float32)
    x[1::2] = x[0::2] + (1e-2 * rng.standard_normal((n // 2, f))).astype(np.float32)
    return x


def gaps(got, x):
    """``chipbench/ops/cdist_trial.py``'s two numbers: the largest
    |d - ref| / (|x_i| + |x_j|) off the diagonal, the largest d_ii / |x_i|."""
    want, norm = plain(x, x), np.sqrt((np.asarray(x, np.float64) ** 2).sum(1))
    gap = np.abs(np.asarray(got, np.float64) - want) / (norm[:, None] + norm[None, :])
    diag = np.diag(np.asarray(got, np.float64)) / norm
    np.fill_diagonal(gap, 0.0)
    return gap.max(), diag.max()


# Set from the dtype: in float32 the expansion's d^2 is good to a few roundings
# of |x|^2 + |y|^2 = 256, 3e-5, which on a near pair (d = 0.08) is 2e-4 of d and
# 1e-5 of |x_i| + |x_j|; on the diagonal sqrt(3e-5) / 11 = 5e-4. The benchmark's
# rows have no near pairs and its dist_gap limit is ten times tighter.
DIST_LIMIT, DIAG_LIMIT = 1e-4, 5e-3


def test_float32_rows_pass_what_a_bfloat16_contraction_fails(comm, monkeypatch):
    x = near_pairs()
    X = ht.array(x, split=0, comm=comm)
    dist, diag = gaps(ht.spatial.cdist(X, quadratic_expansion=True).numpy(), x)
    assert dist < DIST_LIMIT and diag < DIAG_LIMIT, (dist, diag)

    # the MXU's default, made visible on the CPU: both operands rounded to
    # bfloat16 before the product (what a v5e does to a float32 dot left at
    # the default precision)
    def rounded(a, b):
        return jnp.matmul(a.astype(jnp.bfloat16).astype(a.dtype), b.astype(jnp.bfloat16).astype(b.dtype))

    monkeypatch.setattr(distance, "_matmul", rounded)
    distance._tile_program.cache_clear()
    try:
        dist_low, diag_low = gaps(ht.spatial.cdist(X, quadratic_expansion=True).numpy(), x)
    finally:
        monkeypatch.undo()
        distance._tile_program.cache_clear()
    assert dist_low > 10 * DIST_LIMIT and diag_low > 2 * DIAG_LIMIT, (dist_low, diag_low)


def test_bfloat16_cast_rows_fall_outside_the_limit(comm):
    """The benchmark's control: rows rounded to bfloat16 before both terms."""
    x = near_pairs()
    low = ht.array(x, split=0, comm=comm).astype(ht.bfloat16)
    dist, _ = gaps(ht.spatial.cdist(low, quadratic_expansion=True).numpy(), x)
    assert dist > 3 * DIST_LIMIT, dist  # 2^-9 of every coordinate: about 5e-4


def _dot_precisions(fn, *args):
    return re.findall(r"dot_general.*?precision\s*=\s*\[?([^\]\n]*)", str(jax.make_jaxpr(fn)(*args)))


@pytest.mark.parametrize(
    "dtype,rows,want",
    [("float32", 8, "HIGHEST"), ("float64", 8, "HIGHEST"), ("float64", 256, "HIGHEST"), ("bfloat16", 8, None),
     ("bfloat16", 256, None), ("float32", 256, "pieces")],
)
def test_the_expansion_asks_the_mxu_by_dtype_alone(dtype, rows, want):
    """float32 rows never meet the default: skinny products ask for
    ``HIGHEST``, products of an MXU tile and more take stacked bfloat16
    pieces (six pairs: K = 6 f) into a float32 accumulator; bfloat16 rows
    keep their one pass."""
    x = jnp.ones((rows, 4), jnp.dtype(dtype))
    text = str(jax.make_jaxpr(distance._sq_euclidian_fast)(x, x))
    assert text.count("dot_general") == 1
    assert ("HIGHEST" in text) == (want == "HIGHEST"), text
    assert (f"bf16[{rows},24]" in text and "preferred_element_type=float32" in text) == (want == "pieces"), text
    assert distance.mxu_precision(jnp.dtype(dtype)) == (jax.lax.Precision.HIGHEST if want else None)


def test_stacked_pieces_are_the_float32_product():
    """On the CPU ``HIGHEST`` is the float32 product itself: the six piece
    pairs reproduce it to the three pairs left out, 2^-24 of |a||b|."""
    from heat_tpu.ops import mxu

    rng = np.random.default_rng(5)
    a = jnp.asarray((1.0 + rng.standard_normal((256, 64))).astype(np.float32))
    b = jnp.asarray((1.0 + rng.standard_normal((64, 384))).astype(np.float32))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    scale = np.linalg.norm(np.asarray(a), axis=1)[:, None] * np.linalg.norm(np.asarray(b), axis=0)[None, :]
    assert "bf16[256,384]" in str(jax.make_jaxpr(mxu.matmul)(a, b))
    assert np.abs(np.asarray(mxu.matmul(a, b), np.float64) - exact).max() < 4e-7 * scale.max()
    rounded = jnp.matmul(a.astype(jnp.bfloat16).astype(jnp.float32), b.astype(jnp.bfloat16).astype(jnp.float32))
    assert np.abs(np.asarray(rounded, np.float64) - exact).max() > 1e-4 * scale.max()


def test_kmeans_and_cdist_multiply_by_the_one_rule():
    from heat_tpu.cluster import kmeans, kmedians, kmedoids
    from heat_tpu.ops import lloyd, mxu

    assert lloyd.mxu_precision is mxu.mxu_precision is distance.mxu_precision
    assert lloyd._bf16_pieces is mxu.bf16_pieces
    assert kmeans._sq_dist is kmedians._sq_dist is kmedoids._sq_dist is distance._sq_euclidian_fast
    a = jnp.asarray(off_centre(8, 4, 0))  # a skinny product: HIGHEST
    np.testing.assert_array_equal(mxu.matmul(a, a.T), jnp.matmul(a, a.T, precision=jax.lax.Precision.HIGHEST))


# -- the ring's collectives, and its memory --------------------------------
COLLECTIVE = re.compile(r"(all-gather|all-reduce|all-to-all|collective-permute)[^\n]*")


def _compiled_ring(comm, n, f, metric=distance._euclidian_fast):
    x = jax.ShapeDtypeStruct((n, f), jnp.float32, sharding=comm.sharding(2, 0))
    return distance._tile_program(comm.mesh, comm.axis_name, comm.size, metric).lower(x, x).compile()


def test_ring_collectives_move_operand_shards_only(comm):
    """HLO proof: the only collectives are shift-1 collective-permutes of ONE
    operand shard; nothing of a result tile's size travels, nothing is
    gathered, and the count does not grow with p (the rotations are a loop).
    On one device there is no collective at all."""
    p, mb, f = comm.size, 16, 3
    text = _compiled_ring(comm, mb * p, f).as_text()
    lines = [m.group(0) for m in COLLECTIVE.finditer(text)]
    if p == 1:
        assert not lines
        return
    assert lines and all(line.startswith("collective-permute") for line in lines), lines
    assert len(lines) <= 4, "the collective count must not scale with p"
    for line in lines:
        for shape in re.findall(r"f\d+\[([\d,]+)\]", line):
            assert int(np.prod([int(d) for d in shape.split(",")])) <= mb * f, line[:160]


def test_ring_on_eight_devices_keeps_the_same_budget():
    comm8 = ht.get_comm()
    if comm8.size < 8:
        pytest.skip("needs the suite's 8-device mesh")
    text = _compiled_ring(comm8, 8 * 16, 3).as_text()
    lines = [m.group(0) for m in COLLECTIVE.finditer(text)]
    assert lines and len(lines) <= 4 and all(line.startswith("collective-permute") for line in lines), lines


@pytest.mark.parametrize("rows,cols", [(25_000, 24_960), (50_000, 49_920), (1000, 384), (8, 128), (3_000_000, 512)])
def test_column_chunks_cover_a_tile_within_the_budget(rows, cols):
    starts, width = distance._column_chunks(rows, cols, 4)
    covered = np.zeros(cols, bool)
    for s in starts:
        assert 0 <= s and s + width <= cols
        covered[s : s + width] = True
    assert covered.all() and starts == sorted(starts)
    assert len(starts) == 1 or (width % 128 == 0 and rows * width * 4 <= max(distance._CHUNK_BYTES, rows * 128 * 4))
    assert sum(width for _ in starts) <= cols + width  # at most one chunk's worth computed twice


@pytest.mark.parametrize("n", [4 * 520, 4 * 512, 4 * 300], ids=["ragged_lanes", "whole_lanes", "small"])
def test_tiles_in_lane_aligned_chunks_and_merged_ends_are_the_result(comm, monkeypatch, n):
    """Tiles of 520 columns start at lane 0, 8, 16, 24 of a lane tile: the
    aligned middle goes in chunks (one lane tile each here, the last one
    overlapping its neighbour), the ends are merged into the lane tiles they
    share with the neighbouring tiles. 512 columns have no ends; 300 are one
    plain store a tile on four devices."""
    x = off_centre(n, 5, 7)
    X = ht.array(x, split=0, comm=comm)
    whole = ht.spatial.cdist(X, quadratic_expansion=True).numpy()
    np.testing.assert_allclose(whole, plain(x, x), rtol=2e-5, atol=5e-3)  # the diagonal's cancellation: sqrt(2e-5)
    monkeypatch.setattr(distance, "_CHUNK_BYTES", 128 * 4 * (n // comm.size))  # one lane tile a chunk
    distance._tile_program.cache_clear()
    try:
        chunked = ht.spatial.cdist(X, quadratic_expansion=True).numpy()
        other = off_centre(n // 2 + 3, 5, 8)  # Y != X, ragged against the mesh
        xy = ht.spatial.cdist(X, ht.array(other, split=0, comm=comm), quadratic_expansion=True).numpy()
    finally:
        monkeypatch.undo()
        distance._tile_program.cache_clear()
    np.testing.assert_array_equal(chunked, whole)
    np.testing.assert_allclose(xy, plain(x, other), rtol=2e-5, atol=5e-3)


def test_result_is_born_row_sharded_and_placed_as_it_is(comm):
    """No slice when nothing was padded, and ``_ensure_split`` hands the tile
    program's output back as it came."""
    from heat_tpu.core.dndarray import _ensure_split

    x = jnp.asarray(off_centre(8 * comm.size, 4, 3))
    xs = jax.device_put(x, comm.sharding(2, 0))
    out = distance._tile_program(comm.mesh, comm.axis_name, comm.size, distance._euclidian_fast)(xs, xs)
    assert out.sharding.is_equivalent_to(comm.sharding(2, 0), 2)
    assert _ensure_split(out, 0, comm) is out
    d = ht.spatial.cdist(ht.array(np.asarray(x), split=0, comm=comm), quadratic_expansion=True)
    assert len(d.larray.sharding.device_set) == comm.size


# -- spans and counters ----------------------------------------------------
def _cdist_stats():
    stats = fusion.cache_stats()
    return {k: stats[k] for k in CDIST_KEYS}


def test_counters_of_a_call_with_telemetry_on(comm):
    X = ht.array(off_centre(8 * comm.size, 4, 5), split=0, comm=comm)
    Y = ht.array(off_centre(6, 4, 6), comm=comm)
    before = _cdist_stats()
    with telemetry.enabled(1):
        ht.spatial.cdist(X, quadratic_expansion=True)
        ht.spatial.rbf(X, X, quadratic_expansion=True)
        ht.spatial.cdist(X, Y)  # replicated operand: no ring, no rotation
    after = _cdist_stats()
    assert after["phase_cdist_calls"] - before["phase_cdist_calls"] == 3
    assert after["phase_cdist_rotations"] - before["phase_cdist_rotations"] == 2 * (comm.size - 1)
    assert all(after[f"phase_cdist_{p}_ns"] > before[f"phase_cdist_{p}_ns"] for p in fusion._CDIST_PHASES)


def test_counters_stay_where_they_are_with_telemetry_off(comm):
    X = ht.array(off_centre(8 * comm.size, 4, 5), split=0, comm=comm)
    before = _cdist_stats()
    ht.spatial.cdist(X, quadratic_expansion=True)
    ht.spatial.manhattan(X)
    assert _cdist_stats() == before


def test_spans_of_a_call_in_a_profiler_session():
    X = ht.array(off_centre(64, 4, 5), split=0)
    ht.spatial.cdist(X, quadratic_expansion=True)  # compiled before the session
    before = _cdist_stats()
    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            ht.spatial.cdist(X, quadratic_expansion=True)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)  # nanobind's stats type
            spans = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for plane in jax.profiler.ProfileData.from_file(path).planes if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events if e.name.startswith("heat.cdist")
            ]
    after = _cdist_stats()
    assert after["phase_cdist_calls"] - before["phase_cdist_calls"] == 1  # a profiler session is the switch too
    (parent,) = [s for s in spans if s[0] == "heat.cdist"]
    p = ht.get_comm().size
    assert {k: str(v) for k, v in parent[3].items()}.items() >= {
        "mode": "ring", "n": "64", "m": "64", "f": "4", "p": str(p), "metric": "euclidian_fast"
    }.items()
    children = sorted((s for s in spans if s[0] != "heat.cdist"), key=lambda s: s[1])
    assert [s[0].rsplit(".", 1)[1] for s in children] == ["prepare", "dispatch", "place"]
    assert all(parent[1] <= s[1] and s[2] <= parent[2] for s in children)
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:])), "children overlap"


def test_opsplane_exports_the_cdist_counters():
    from heat_tpu.core import opsplane

    with telemetry.enabled(1):
        ht.spatial.cdist(ht.array(off_centre(16, 4, 5), split=0), quadratic_expansion=True)
    text = opsplane.render()
    assert not opsplane.validate_exposition(text)
    assert "heat_tpu_cdist_calls_total" in text and 'heat_tpu_cdist_phase_seconds_total{phase="dispatch"}' in text
    assert "heat_tpu_kmeans_fits_total" in text
