"""Fused pallas Lloyd kernel vs the jnp reference implementation, its labels
(the last pass's own argmin row, ISSUE 30) and the rows read in place, with
the inertia from that same pass's accumulators (ISSUE 32), included.

Runs in pallas interpret mode on CPU; real-TPU timing is the benchmark cell
``kmeans_fit_1c`` (``BENCHMARK.json``, ``chipbench/run.py``).
"""

import numpy as np
import pytest

from harness import TestCase


def _rows(seed, n, f, k, scale=2.0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    data = rng.standard_normal((n, f)).astype(np.float32)
    return data, jnp.asarray(rng.standard_normal((k, f)).astype(np.float32) * scale)


def _devices(platform, count):
    """A stand-in for a sharding over ``count`` devices of ``platform``."""
    import types

    return types.SimpleNamespace(device_set=[types.SimpleNamespace(platform=platform, id=i) for i in range(count)])


@pytest.mark.parametrize("platform,count,split,f,k,mode", [
    ("tpu", 1, None, 16, 8, "single"),  # one TPU: the single form, whatever the split
    ("tpu", 4, 0, 512, 128, "sharded"),  # TPUs, rows split: the sharded form, up to the widest f and k
    ("tpu", 4, None, 16, 8, None),  # TPUs, rows not split: the jnp path
    ("tpu", 1, 0, 513, 8, None),  # wider than the kernel holds
    ("cpu", 1, 0, 16, 8, None),  # not TPUs
    ("tpu", 4, 0, 600, 8, None),  # f past 512
    ("tpu", 1, 0, 16, 129, None),  # k past 128
])
def test_lloyd_takes(platform, count, split, f, k, mode):
    """``lloyd.takes`` picks the fused form from the operand alone: its
    devices, their number, its split, f and k."""
    import jax

    from heat_tpu.ops import lloyd

    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0]) if platform == "cpu" else _devices(platform, count)
    assert lloyd.takes((100_000, f), k, split, sharding) == mode


@pytest.mark.parametrize("path,n_steps", [("single", 1), ("single", 8), ("sharded", 1), ("sharded", 8)])
def test_run_labels_are_the_assignment_to_the_last_steps_input_centres(path, n_steps):
    """A program's labels come from its LAST kernel pass: the assignment
    against the centres that went into that iteration (what the jnp program
    carries), whatever the number of plain passes before it, none included."""
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.cluster.kmeans import _lloyd_iter, _lloyd_run
    from heat_tpu.ops.lloyd import fused_lloyd_run, fused_lloyd_run_sharded

    comm = ht.get_comm()
    n, f, k = 512 * comm.size + 5, 8, 5  # ragged: a masked tail, a physical pad when sharded
    data_np, centers = _rows(21, n, f, k)
    if path == "single":
        got = fused_lloyd_run(jnp.asarray(data_np), centers, k, n_steps, -1.0, interpret=True)
    else:
        x = ht.array(data_np, split=0)
        got = fused_lloyd_run_sharded(x.parray, centers, k, comm, n, n_steps, -1.0, interpret=True)
    ref = _lloyd_run(jnp.asarray(data_np), centers, k, n_steps, -1.0)
    assert got[1].shape == (n,) and got[1].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]), rtol=1e-4, atol=1e-4)
    # and spelled out: one more oracle step from the centres of n_steps - 1
    before = _lloyd_run(jnp.asarray(data_np), centers, k, n_steps - 1, -1.0)[0] if n_steps > 1 else centers
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(_lloyd_iter(jnp.asarray(data_np), before, k)[1]))


@pytest.mark.parametrize("path", ["single", "sharded", "jnp"])
def test_unreachable_tol_runs_max_iter_steps_bit_equal_to_as_many_programs_of_one(path):
    """With a ``tol`` no shift reaches the one program runs exactly
    ``max_iter`` iterations, through the same passes from the same centres as
    ``max_iter`` programs of one iteration each: centres bit-equal, the labels
    and the inertia the last of those programs'."""
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.cluster.kmeans import _lloyd_run
    from heat_tpu.ops.lloyd import fused_lloyd_run, fused_lloyd_run_sharded

    comm = ht.get_comm()
    n, f, k, max_iter = 512 * comm.size + 5, 8, 5, 5
    data_np, centers = _rows(34, n, f, k)
    if path == "single":
        run = lambda c, m: fused_lloyd_run(jnp.asarray(data_np), c, k, m, -1.0, interpret=True)
    elif path == "sharded":
        payload = ht.array(data_np, split=0).parray
        run = lambda c, m: fused_lloyd_run_sharded(payload, c, k, comm, n, m, -1.0, interpret=True)
    else:
        run = lambda c, m: _lloyd_run(jnp.asarray(data_np), c, k, m, -1.0)
    got = run(centers, max_iter)
    assert int(got[4]) == max_iter
    one = (centers,)
    for _ in range(max_iter):
        one = run(one[0], 1)
        assert int(one[4]) == 1
    for a, b in zip(got[:4], one[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "shifts,max_iter,tol,n_iter",
    [
        ([np.nan] * 9, 7, 1e-4, 7),  # a NaN shift is not "at most tol": the loop runs out
        ([np.nan] * 9, 7, np.inf, 7),
        ([3.0, 2.0, 1e-5, 0.0, 0.0], 7, 1e-4, 4),  # one iteration more than the first within tol
        ([3.0, 2.0, 1e-4, 0.0, 0.0], 7, 1e-4, 4),  # "at most": equal stops
        ([0.0] * 9, 7, 1e-4, 2),
        ([0.0] * 9, 1, 1e-4, 1),  # the labelled step alone
        ([0.0] * 9, 7, -1.0, 7),  # a tol no shift reaches
    ],
)
def test_the_loop_rule_on_made_up_shifts(shifts, max_iter, tol, n_iter):
    """``_steps`` alone, on a step that counts itself and reads its shift from
    a list: how many steps run, and that only the last is asked for labels."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.ops.lloyd import _steps

    table = jnp.asarray(shifts, jnp.float32)

    def step(c, last):
        moved = (c + 1, table[c])
        return (*moved, c, 10.0 * c) if last else moved

    centers, labels, inertia, shift, got = jax.jit(lambda m, t: _steps(step, jnp.zeros((), jnp.int32), m, t))(max_iter, tol)
    assert (int(got), int(centers), int(labels), float(inertia)) == (n_iter, n_iter, n_iter - 1, 10.0 * (n_iter - 1))
    np.testing.assert_array_equal(np.asarray(shift), np.float32(shifts[n_iter - 1]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_labels_bincount_is_the_kernels_counts_with_garbage_in_the_pad(dtype):
    """The labels ARE the assignment that built the sums and counts: their
    bincount is the kernel's counts exactly, in either dtype, and inf / NaN
    rows beyond ``n_valid`` reach neither."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.ops.lloyd import _kernel_call_T, _prepare

    n, f, k = 3000, 16, 6
    data_np, centers = _rows(23, n, f, k)
    poisoned = np.concatenate(
        [data_np, np.full((40, f), np.inf, np.float32), np.full((8, f), np.nan, np.float32)]
    )
    _, counts, xsq_sum, labels = jax.jit(
        lambda d, c: _kernel_call_T(_prepare(d), c, k, jnp.asarray(n, jnp.int32), True, last=True)
    )(jnp.asarray(poisoned).astype(dtype), centers)
    assert labels.shape == (n + 48,) and np.isfinite(float(xsq_sum))  # one per row handed over
    valid = np.asarray(labels)[:n]
    assert valid.min() >= 0 and valid.max() < k
    np.testing.assert_array_equal(np.bincount(valid, minlength=k), np.asarray(counts)[:, 0])


def test_bfloat16_labels_are_the_argmin_of_the_streamed_scores():
    """bfloat16 rows score against -2c rounded to bfloat16 and |c|^2 of the
    unrounded centres: the labels are that argmin, not the float32 one."""
    import jax.numpy as jnp

    from heat_tpu.ops.lloyd import fused_lloyd_run

    n, f, k = 4096, 16, 4
    data_np, centers = _rows(11, n, f, k)
    low = jnp.asarray(data_np).astype(jnp.bfloat16)
    got = np.asarray(fused_lloyd_run(low, centers, k, 1, -1.0, interpret=True)[1])
    c64 = np.asarray(centers, np.float64)
    cq = np.asarray((-2.0 * centers).astype(jnp.bfloat16).astype(jnp.float32), np.float64)
    score = (c64 * c64).sum(axis=1)[None, :] + np.asarray(low.astype(jnp.float32), np.float64) @ cq.T
    np.testing.assert_array_equal(got, score.argmin(axis=1))


_F, _K = 8, 5  # narrow rows: the kernel's largest blocks, 44 928 samples


@pytest.mark.parametrize("mode", ["single", "sharded"])
@pytest.mark.parametrize("blocks,over", [(1, -1), (1, 0), (1, 1), (3, -77)])
def test_rows_are_read_in_place_whatever_their_number(blocks, over, mode):
    """Nothing pads the sample axis (ISSUE 32): the grid is ``cdiv(n, block)``
    and the last block ends inside the operand, one sample short of a block,
    whole, one sample over, and ragged after three. In sharded mode each
    device's rows end inside its one block and the physical payload's tail,
    whose content is unspecified, holds NaN. Against the jnp program: centres
    to 1e-5, labels identical, inertia to 1e-5, and the inertia is that of the
    LAST assignment step: the labels' own squared distances to the centres that
    went into it."""
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.cluster.kmeans import _lloyd_run
    from heat_tpu.ops.lloyd import _block_cols, fused_lloyd_run, fused_lloyd_run_sharded

    n, n_steps = blocks * _block_cols(_F, _K) + over, 3
    data_np, centers = _rows(32, n, _F, _K)
    if mode == "single":
        got = fused_lloyd_run(jnp.asarray(data_np), centers, _K, n_steps, -1.0, interpret=True)
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        comm = ht.get_comm()
        physical = np.full((-(-n // comm.size) * comm.size, _F), np.nan, np.float32)
        physical[:n] = data_np
        payload = jax.device_put(physical, NamedSharding(comm.mesh, P(comm.axis_name, None)))
        got = fused_lloyd_run_sharded(payload, centers, _K, comm, n, n_steps, -1.0, interpret=True)
    ref = _lloyd_run(jnp.asarray(data_np), centers, _K, n_steps, -1.0)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]), rtol=1e-5, atol=1e-5)
    assert got[1].shape == (n,)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
    np.testing.assert_allclose(float(got[2]), float(ref[2]), rtol=1e-5)
    before = np.asarray(_lloyd_run(jnp.asarray(data_np), centers, _K, n_steps - 1, -1.0)[0], np.float64)
    last = ((data_np.astype(np.float64) - before[np.asarray(got[1])]) ** 2).sum()
    np.testing.assert_allclose(float(got[2]), last, rtol=1e-5)


def _garbage(rows, f):
    """Rows of NaN and inf by turns: what the payload may hold beyond ``n_valid``."""
    bad = np.full((rows, f), np.nan, np.float32)
    bad[1::2] = np.inf
    bad[3::4] = -np.inf
    return bad


def _last_pass(payload, centers, n_valid, mode, masked, last=True):
    """The accumulators of ONE kernel pass, per device and unmerged, with the
    kernel's body chosen by ``masked`` (``None``: the block decides; ``True``:
    the masked body on every block, as before ISSUE 38): the sums, the counts
    and, from a ``last`` pass, Σ|x|² and the labels. ``sharded``: each device
    runs the pass on its own rows with its own ``n_valid``, as
    ``fused_lloyd_run_sharded`` has it, and nothing is summed over devices."""
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.ops.lloyd import _kernel_call_T, _prepare

    def one(xl, valid):
        got = _kernel_call_T(_prepare(xl), centers, _K, valid, True, last, masked)
        return tuple(jnp.reshape(g, (1, -1)) for g in got[:3]) + tuple(got[3:])  # a row a device; the labels as they are

    if mode == "single":
        return jax.jit(lambda x: one(x, jnp.asarray(n_valid, jnp.int32)))(jnp.asarray(payload))
    from jax.sharding import NamedSharding, PartitionSpec as P

    comm = ht.get_comm()
    local = payload.shape[0] // comm.size

    def device(xl):
        valid = jnp.clip(n_valid - jax.lax.axis_index(comm.axis_name) * local, 0, local)
        return one(xl, valid)

    run = jax.jit(jax.shard_map(
        device, mesh=comm.mesh, in_specs=P(comm.axis_name, None), out_specs=P(comm.axis_name), check_vma=False
    ))
    return run(jax.device_put(payload, NamedSharding(comm.mesh, P(comm.axis_name, None))))


@pytest.mark.parametrize("mode", ["single", "sharded"])
@pytest.mark.parametrize("blocks,over", [(2, 0), (2, 1), (3, -77), (0, 1000)], ids=["2b", "2b+1", "3b-77", "under-a-block"])
def test_whole_blocks_take_the_unmasked_body_bit_equal_to_the_masked_one(blocks, over, mode):
    """ISSUE 38: a block whose last column lies under ``n_valid`` runs a body
    without ``cols``, ``valid``, the ``where`` on the rows and the ``and`` on
    the one-hot rows; every other block runs the masked body. On a whole block
    the mask is the identity, so a pass's sums, counts, labels and Σ|x|² are
    BIT-equal to the masked body's run on every block, with NaN, +inf and -inf in
    the payload beyond ``n_valid``: at two whole blocks, one sample over, ragged
    after three, and under one block (the one block is the tail block). ``single``:
    48 rows of garbage follow the valid ones, so at two whole blocks the grid's
    third block lies wholly beyond ``n_valid``. ``sharded``: every device holds
    that many rows, the last but one is valid to a little over its half (a tail
    inside its second block, whole garbage blocks after it) and the last device
    holds no valid row at all: every block of it takes the masked body and adds
    nothing."""
    import heat_tpu as ht
    from heat_tpu.ops.lloyd import _block_cols, pass_blocks

    block = _block_cols(_F, _K)
    rows = blocks * block + over
    data_np, centers = _rows(38, rows, _F, _K)
    if mode == "single":
        n_valid = rows
        payload = np.concatenate([data_np, _garbage(48, _F)])
        want_tail = pass_blocks(rows + 48, n_valid, _F, _K)[1]
    else:
        p = ht.get_comm().size
        n_valid = (p - 2) * rows + rows // 2 + 3
        payload = np.concatenate([np.tile(data_np, (p, 1))[:n_valid], _garbage(p * rows - n_valid, _F)])
        want_tail = pass_blocks(rows, 0, _F, _K)[1]  # the last device's: all of its blocks
        assert want_tail == -(-rows // block)
    assert want_tail >= 1
    for last in (True, False):
        got = _last_pass(payload, centers, n_valid, mode, None, last)
        want = _last_pass(payload, centers, n_valid, mode, True, last)
        assert len(got) == (4 if last else 2)
        for name, a, b in zip(("sums", "counts", "xsq", "labels"), got, want):
            a, b = np.asarray(a), np.asarray(b)
            if name == "labels":  # beyond n_valid a label is unspecified, by either body
                a, b = a[:n_valid], b[:n_valid]
            assert np.isfinite(a).all(), name
            np.testing.assert_array_equal(a, b, err_msg=f"{name}, last={last}")
    counts = np.asarray(got[1])
    assert counts.sum() == n_valid  # no row of garbage was counted, by any device
    if mode == "sharded":
        assert counts[-1].sum() == 0 and counts[-2].sum() == rows // 2 + 3


def test_a_device_whose_blocks_lie_wholly_beyond_its_n_valid_adds_nothing_to_a_sharded_run():
    """``fused_lloyd_run_sharded`` on a payload whose logical length leaves
    the last device no valid row and the one before it whole blocks of
    garbage: the fit is the jnp program's on the logical rows, labels for
    labels, and no NaN or inf of the payload reaches a centre or the inertia."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import heat_tpu as ht
    from heat_tpu.cluster.kmeans import _lloyd_run
    from heat_tpu.ops.lloyd import _block_cols, fused_lloyd_run_sharded

    comm = ht.get_comm()
    local = 2 * _block_cols(_F, _K) + 1
    n = (comm.size - 2) * local + 5  # five valid rows on the last device but one
    data_np, centers = _rows(39, n, _F, _K)
    physical = np.concatenate([data_np, _garbage(comm.size * local - n, _F)])
    payload = jax.device_put(physical, NamedSharding(comm.mesh, P(comm.axis_name, None)))
    got = fused_lloyd_run_sharded(payload, centers, _K, comm, n, 2, -1.0, interpret=True)
    ref = _lloyd_run(jnp.asarray(data_np), centers, _K, 2, -1.0)
    assert got[1].shape == (n,)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(got[2]), float(ref[2]), rtol=1e-5)


@pytest.mark.parametrize(
    "n,n_valid,f,k,itemsize,want",
    [
        (1 << 26, 1 << 26, 16, 8, 4, (2140, 1)),  # the kmeans_fit_1c cell: 31 360 columns a block
        (2 * 31360, 2 * 31360, 16, 8, 4, (2, 0)),  # whole blocks alone: no block is masked
        (2 * 31360, 2 * 31360 - 1, 16, 8, 4, (2, 1)),
        (2 * 31360 + 1, 31360, 16, 8, 4, (3, 2)),
        (1000, 1000, 16, 8, 4, (1, 1)),  # under one block: the one block is the tail block
        (3 * 31360, 0, 16, 8, 4, (3, 3)),  # a device of padding alone
    ],
)
def test_pass_blocks_counts_the_grid_and_its_masked_steps(n, n_valid, f, k, itemsize, want):
    from heat_tpu.ops.lloyd import pass_blocks

    assert pass_blocks(n, n_valid, f, k, itemsize) == want


@pytest.mark.parametrize("offset", [0.0, 10.0, 100.0])
def test_inertia_from_the_accumulators_is_the_per_sample_sum_on_uncentred_rows(offset):
    """``_inertia`` takes Σ d² from the last pass's Σ|x|², sums and counts, and
    sums no per-sample distance. Where the rows lie ``offset`` standard
    deviations off the origin, Σ|x|² is (1 + offset²) times the Σ d² it is
    cancelled down to: the worst case of the form. Against the float64 sum of
    the labels' own squared distances to the centres that went into the last
    step, it is held to 16 roundings of Σ|x|² in float32 (read: at most 7.9
    over four seeds, three sizes and offsets to 1 000; the jnp program's
    per-sample ``Σ min(score) + Σ|x|²`` reads at most 3.7 and is held to the
    same: both forms add |x|² up in float32, and neither is of use at an
    offset of 1 000)."""
    import jax.numpy as jnp

    from heat_tpu.cluster.kmeans import _lloyd_run
    from heat_tpu.ops.lloyd import _block_cols, fused_lloyd_run

    n, n_steps = _block_cols(_F, _K) + 1, 3
    rng = np.random.default_rng(41)
    data_np = (rng.standard_normal((n, _F)) + offset).astype(np.float32)
    data = jnp.asarray(data_np)
    centers = jnp.asarray(data_np[rng.choice(n, _K, replace=False)])
    x64 = data_np.astype(np.float64)
    floor = 2.0**-24 * (x64**2).sum()  # one rounding of Σ|x|²
    for run in (lambda s: fused_lloyd_run(data, centers, _K, s, -1.0, interpret=True),
                lambda s: _lloyd_run(data, centers, _K, s, -1.0)):
        _, labels, inertia, _, _ = run(n_steps)
        before = np.asarray(run(n_steps - 1)[0], np.float64)
        per_sample = ((x64 - before[np.asarray(labels)]) ** 2).sum()
        assert abs(float(inertia) - per_sample) <= 16 * floor, (float(inertia), per_sample, floor)
        if offset == 0.0:
            np.testing.assert_allclose(float(inertia), per_sample, rtol=1e-6)


class TestFusedLloyd(TestCase):
    def _compare(self, n, f, k, seed=0):
        import jax
        import jax.numpy as jnp

        from heat_tpu.cluster.kmeans import _lloyd_iter
        from heat_tpu.ops.lloyd import fused_lloyd_run

        rng = np.random.default_rng(seed)
        data = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
        centers = jnp.asarray(rng.standard_normal((k, f)).astype(np.float32) * 2)

        ref_c, ref_lab, ref_inertia, ref_shift = jax.jit(
            _lloyd_iter, static_argnames="k"
        )(data, centers, k)
        got_c, got_lab, got_inertia, got_shift, _ = fused_lloyd_run(
            data, centers, k, 1, -1.0, interpret=True
        )

        np.testing.assert_array_equal(np.asarray(got_lab), np.asarray(ref_lab))
        np.testing.assert_allclose(np.asarray(got_c), np.asarray(ref_c), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(float(got_inertia), float(ref_inertia), rtol=1e-4)
        np.testing.assert_allclose(float(got_shift), float(ref_shift), rtol=1e-4, atol=1e-6)

    def test_block_multiple(self):
        self._compare(8192, 16, 8)

    def test_ragged_tail_block(self):
        # n smaller than the row block: the single partial block must be masked
        self._compare(5000, 16, 8, seed=1)

    def test_small_n_single_partial_block(self):
        self._compare(300, 4, 3, seed=2)

    def test_wide_features_many_centers(self):
        self._compare(2048, 64, 17, seed=3)

    def test_multi_iteration_run_matches(self):
        import jax.numpy as jnp

        from heat_tpu.cluster.kmeans import _lloyd_run
        from heat_tpu.ops.lloyd import fused_lloyd_run

        rng = np.random.default_rng(4)
        data = jnp.asarray(rng.standard_normal((4096, 8)).astype(np.float32))
        centers = jnp.asarray(rng.standard_normal((5, 8)).astype(np.float32) * 2)
        ref = _lloyd_run(data, centers, 5, 4, -1.0)
        got = fused_lloyd_run(data, centers, 5, 4, -1.0, interpret=True)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]), rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
        np.testing.assert_allclose(float(got[2]), float(ref[2]), rtol=1e-3)

    def test_empty_cluster_keeps_center(self):
        import jax.numpy as jnp

        from heat_tpu.ops.lloyd import fused_lloyd_run

        data = jnp.asarray(np.zeros((128, 2), np.float32))
        centers = jnp.asarray(np.array([[0.0, 0.0], [100.0, 100.0]], np.float32))
        new_c, labels, _, _, _ = fused_lloyd_run(data, centers, 2, 1, -1.0, interpret=True)
        assert (np.asarray(labels) == 0).all()
        np.testing.assert_array_equal(np.asarray(new_c)[1], centers[1])  # empty keeps old

    def test_sharded_wrapper_matches_reference(self):
        import jax
        import jax.numpy as jnp

        import heat_tpu as ht
        from heat_tpu.cluster.kmeans import _lloyd_iter
        from heat_tpu.ops.lloyd import fused_lloyd_run_sharded

        comm = ht.get_comm()
        rng = np.random.default_rng(7)
        n, f, k = 4 * comm.size + 3, 6, 4  # ragged: physical pad on last device
        data_np = rng.standard_normal((n, f)).astype(np.float32)
        centers = jnp.asarray(rng.standard_normal((k, f)).astype(np.float32) * 2)

        x = ht.array(data_np, split=0)  # physical payload padded to p blocks
        got_c, got_lab, got_inertia, got_shift, _ = fused_lloyd_run_sharded(
            x.parray, centers, k, comm, n, 1, -1.0, interpret=True
        )
        ref_c, ref_lab, ref_inertia, ref_shift = jax.jit(
            _lloyd_iter, static_argnames="k"
        )(jnp.asarray(data_np), centers, k)

        np.testing.assert_array_equal(np.asarray(got_lab)[:n], np.asarray(ref_lab))
        np.testing.assert_allclose(np.asarray(got_c), np.asarray(ref_c), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(float(got_inertia), float(ref_inertia), rtol=1e-4)
        np.testing.assert_allclose(float(got_shift), float(ref_shift), rtol=1e-4, atol=1e-6)

    def test_pad_garbage_does_not_poison_accumulators(self):
        # dndarray.parray's pad region is UNSPECIFIED: pad-aware elementwise
        # ops can leave inf/NaN there. Regression for the advisor-verified
        # bug where 0·inf = NaN leaked through the multiplicative mask into
        # sums/centers and inertia.
        import jax
        import jax.numpy as jnp

        from heat_tpu.cluster.kmeans import _lloyd_iter

        rng = np.random.default_rng(9)
        n, f, k = 1000, 4, 3
        data_np = rng.standard_normal((n, f)).astype(np.float32)
        centers = jnp.asarray(rng.standard_normal((k, f)).astype(np.float32))

        # simulate garbage tail padding by asking the kernel to mask rows
        # beyond n while feeding inf/NaN content there
        poisoned = np.concatenate(
            [data_np, np.full((24, f), np.inf, np.float32), np.full((8, f), np.nan, np.float32)]
        )
        from heat_tpu.ops.lloyd import _inertia, _kernel_call_T, _prepare

        sumsT, counts, xsq_sum, _ = jax.jit(
            lambda d, c: _kernel_call_T(_prepare(d), c, k, jnp.asarray(n, jnp.int32), True, last=True)
        )(jnp.asarray(poisoned), centers)
        assert np.isfinite(np.asarray(sumsT)).all()
        np.testing.assert_allclose(float(xsq_sum), (data_np.astype(np.float64) ** 2).sum(), rtol=1e-6)

        # the accumulator VALUES must equal the clean oracle's — finiteness
        # alone would admit a finite-but-garbage pad score leaking through
        ref_c, ref_lab, ref_inertia, _ = jax.jit(_lloyd_iter, static_argnames="k")(
            jnp.asarray(data_np), centers, k
        )
        got_counts = np.asarray(counts)[:, 0]
        assert got_counts.sum() == n  # no pad sample counted
        onehot = np.eye(k, dtype=np.float32)[np.asarray(ref_lab)]
        np.testing.assert_array_equal(got_counts, onehot.sum(axis=0))
        np.testing.assert_allclose(
            np.asarray(sumsT), (onehot.T @ data_np).T, rtol=1e-5, atol=1e-4
        )
        # the last pass's Σ|x|², sums and counts give the Σ d² of the valid rows alone
        np.testing.assert_allclose(
            float(_inertia(xsq_sum, sumsT, counts, centers)), float(ref_inertia), rtol=1e-5
        )

    def test_bf16_stream_matches_f32_oracle_loosely(self):
        # bf16 operands stream as bf16 (half the HBM bytes); accumulators
        # are f32, so centers/inertia track the f32 oracle to bf16 precision
        import jax
        import jax.numpy as jnp

        from heat_tpu.cluster.kmeans import _lloyd_iter
        from heat_tpu.ops.lloyd import fused_lloyd_run

        rng = np.random.default_rng(11)
        n, f, k = 4096, 16, 4
        data_np = rng.standard_normal((n, f)).astype(np.float32)
        centers = jnp.asarray(rng.standard_normal((k, f)).astype(np.float32) * 2)
        got = fused_lloyd_run(
            jnp.asarray(data_np).astype(jnp.bfloat16), centers, k, 1, -1.0, interpret=True
        )
        ref = jax.jit(_lloyd_iter, static_argnames="k")(jnp.asarray(data_np), centers, k)
        np.testing.assert_allclose(
            np.asarray(got[0], np.float32), np.asarray(ref[0]), rtol=0.05, atol=0.05
        )
        np.testing.assert_allclose(float(got[2]), float(ref[2]), rtol=0.05)
        # labels are the kernel's bfloat16-scored argmin: the float32
        # oracle's but for samples near a boundary
        assert (np.asarray(got[1]) == np.asarray(ref[1])).mean() > 0.97

    def test_bf16_labels_consistent_with_kernel_counts(self):
        # advisor r04#2: labels_ must agree with the assignment that produced
        # cluster_centers_. They are the kernel's own argmin row (the one its
        # one-hot rows are built from), so bincount(labels) IS its counts.
        import jax.numpy as jnp

        from heat_tpu.ops.lloyd import _kernel_call_T, _prepare

        rng = np.random.default_rng(17)
        n, f, k = 4096, 16, 4
        data = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32)).astype(
            jnp.bfloat16
        )
        centers = jnp.asarray(rng.standard_normal((k, f)).astype(np.float32) * 2)
        _, counts, _, labels = _kernel_call_T(
            _prepare(data), centers, k, jnp.asarray(n, jnp.int32), True, last=True
        )
        binc = np.bincount(np.asarray(labels), minlength=k).astype(np.float32)
        np.testing.assert_array_equal(binc, np.asarray(counts)[:, 0])

    def test_bf16_sharded_ragged_matches_oracle(self):
        # the harshest combination: bfloat16 stream x physical pad (ragged
        # rows) x shard_map psum — accumulators must stay f32-exact w.r.t.
        # masking while the streamed operand is half-precision
        import jax
        import jax.numpy as jnp

        import heat_tpu as ht
        from heat_tpu.cluster.kmeans import _lloyd_iter
        from heat_tpu.ops.lloyd import fused_lloyd_run_sharded

        comm = ht.get_comm()
        rng = np.random.default_rng(13)
        n, f, k = 6 * comm.size + 1, 5, 3  # ragged
        data_np = rng.standard_normal((n, f)).astype(np.float32)
        centers = jnp.asarray(rng.standard_normal((k, f)).astype(np.float32))
        x = ht.array(data_np, split=0).astype(ht.bfloat16)
        got = fused_lloyd_run_sharded(x.parray, centers, k, comm, n, 1, -1.0, interpret=True)
        ref = jax.jit(_lloyd_iter, static_argnames="k")(jnp.asarray(data_np), centers, k)
        np.testing.assert_allclose(
            np.asarray(got[0], np.float32), np.asarray(ref[0]), rtol=0.05, atol=0.05
        )
        np.testing.assert_allclose(float(got[2]), float(ref[2]), rtol=0.05)
        assert got[1].shape[0] == n
        assert (np.asarray(got[1]) == np.asarray(ref[1])).mean() > 0.9

    def test_kmeans_fit_keeps_bf16_stream(self):
        import jax.numpy as jnp

        import unittest.mock

        import heat_tpu as ht
        from heat_tpu.cluster import KMeans
        from heat_tpu.ops import lloyd

        rng = np.random.default_rng(12)
        x = ht.array(rng.standard_normal((600, 4)).astype(np.float32), split=0).astype(
            ht.bfloat16
        )
        km = KMeans(n_clusters=3, max_iter=8, random_state=0)
        with unittest.mock.patch.object(lloyd, "takes", lambda shape, k, split, sharding: "sharded"):
            assert km._fused_mode(x) == ("sharded", True)
            km.fit(x)
        # centroids computed (and exposed) in at-least-f32
        assert km.cluster_centers_.dtype in (ht.float32, ht.float64)
        assert km.labels_.shape[0] == 600

    def test_block_cols_lane_aligned_and_budgeted(self):
        # samples-in-lanes sizing: lane-multiple blocks, bounded VMEM
        # footprint (the r04 v5e capture OOM'd the 16 MB scoped budget by
        # ignoring padding — this pins the corrected accounting)
        from heat_tpu.ops.lloyd import _block_cols

        for f in (2, 16, 128, 512):
            for k in (2, 8, 128):
                blk = _block_cols(f, k)
                assert blk % 128 == 0
                fp, kp = 8 * ((f + 7) // 8), 8 * ((k + 7) // 8)
                live_bytes = 4 * blk * (2 * fp + 3 * kp + 8)
                assert live_bytes <= (12 << 20) or blk == 1024

    def test_sharded_wrapper_divisible(self):
        import jax.numpy as jnp

        import heat_tpu as ht
        from heat_tpu.cluster.kmeans import _lloyd_iter
        from heat_tpu.ops.lloyd import fused_lloyd_run_sharded

        comm = ht.get_comm()
        rng = np.random.default_rng(8)
        n, f, k = 8 * comm.size, 5, 3
        data_np = rng.standard_normal((n, f)).astype(np.float32)
        centers = jnp.asarray(rng.standard_normal((k, f)).astype(np.float32))
        x = ht.array(data_np, split=0)
        got = fused_lloyd_run_sharded(x.parray, centers, k, comm, n, 1, -1.0, interpret=True)
        ref = _lloyd_iter(jnp.asarray(data_np), centers, k)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]), rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))
