"""Fused single-pass Lloyd iteration (pallas), in samples-in-lanes layout.

The jnp Lloyd step (`cluster/kmeans.py:_lloyd_iter`) necessarily reads the
(n, f) data from HBM twice per iteration — once for the assignment matmul
``x @ cᵀ`` and once for the update matmul ``onehotᵀ @ x`` — and materializes
the (n, k) one-hot operand for the MXU. At the benchmark shape (2^26 x 16
f32) the iteration is pure HBM bandwidth, so the floor is set by bytes
moved, not FLOPs.

This kernel streams each sample block into VMEM ONCE and produces everything
the iteration needs in that single pass. Crucially it operates on the
TRANSPOSED operand ``xT (f, n)`` — features in sublanes, samples in lanes:

    score   = |c|² − 2·c @ xb           (k, block)   MXU
    labels  = argmin₀(score)             (1, block)  sublane reduce
    onehot  = (labels == iota_k)         (k, block)  VMEM-only
    sumsᵀ  += xb ·ₗ onehot               (f, k)      MXU (lane contraction)
    counts += Σₗ onehot                  (k, 1)      accumulator
    |x|²   += xb²                        (f, 128)    the LAST pass of a program only

Why transposed: TPU vector memory pads the MINOR axis to 128 lanes. In the
natural (block, f) layout a narrow f (the benchmark's f=16) pads 8x, so the
kernel would move eight times the bytes the rows hold. With samples in lanes
the minor axis is the long one (no padding, any f), the sublane axis is f
(padded to 8), and every reduction in the kernel is lane-preserving. The
kernel is the ONLY reader of the rows. The ``transpose`` to (f, n) sits in
the program that holds the kernel, where it is a bitcast of the rows as
XLA:TPU lays a narrow (n, f) array out (samples already in lanes); nothing
pads the sample axis: the grid is ``cdiv(n, block)``, the last block ends
inside the operand, and what its tail holds is masked like any column at or
beyond ``n_valid``. Only a block that HAS such columns pays for the mask: the
kernel tests ``(i + 1) * block <= n_valid`` on the scalar unit (``n_valid``
is an SMEM operand) and runs its body without the column index, the selects
on the rows and the ``and`` on the one-hot rows wherever the block lies
wholly under ``n_valid``: 2 139 blocks of 2 140 at the benchmark shape, to
the bit the same sums, counts, labels and squares (PERF.md, PR 38).
Per-iteration HBM traffic is n·f reads and nothing
per-row written, except in the LAST pass of a program: that one stores the
``labels`` row it already holds, a lane-dense (1, block) int32 block (4 bytes
a sample beside the 4·f it reads), and adds up the squares of the float32
block it holds; with them the pass's inertia follows from its own sums and
counts (:func:`_inertia`). So a program's labels are the assignment against
the centers that went INTO its last iteration (the jnp path's exact label
convention), they are the very assignment that produced that iteration's
sums, counts and inertia, and no XLA pass over the rows computes labels,
Σ|x|² or a padded copy. The passes before the last compute no inertia:
nobody reads it.

Precision follows the rows' dtype alone. The MXU multiplies bfloat16: left
at the default, float32 operands are rounded to bfloat16 and multiplied in
one pass, which put ``KMeans.fit``'s float32 centres 1.1-1.4 % off a plain
float32 Lloyd (PERF.md, PR 26). float32 rows (and wider, carried as float32)
therefore multiply in float32: both contractions take their float32 operand
as three bfloat16 pieces that add up to it exactly (``ops/mxu.py::bf16_pieces``),
and because (k, f) fills a corner of a 128 x 128 MXU tile, the pieces are
stacked along the contracted and the output axes of ONE bfloat16 pass, so
every piece product is exact in the float32 accumulator, at the MXU cost of
the single rounded pass. bfloat16 rows are one piece: they keep their
bfloat16 multiplication with float32 accumulation and half the HBM stream.
The jnp path asks XLA for the same (``ops/mxu.py::matmul``).

This kernel IS the product path: ``cluster.KMeans.fit`` dispatches here on
TPU (``fused_supported`` / ``fused_sharded_supported``) and takes the jnp
path (``cluster/kmeans.py:_lloyd_run``) for wider shapes or
``use_fused=False``; a kernel that fails to lower raises.
:func:`fused_lloyd_run` is
single-device (its pallas_call has no partitioning spec);
:func:`fused_lloyd_run_sharded` is the
multi-chip form: a shard_map running the kernel per device and merging the
(f, k)/(k, 1) accumulators with one psum per iteration (the last one's
Σ|x|² with them) — the exact collective budget of the jnp path. In the
sharded run the whole loop lives INSIDE the shard_map.

One program is one fit, and convergence is checked on the device
(:func:`_steps`, the one loop of all three modes, the jnp path's too).
The rule: iterations run until the shift of an iteration (the squared
distance its centres moved, all clusters added up) is at most ``tol`` or
``max_iter - 1`` have run, and one more iteration then assigns the labels,
sums the inertia and moves the centres a last time; ``n_iter`` counts it.
That is the reference's per-iteration check plus one iteration; a ``tol`` the
shift never reaches (a negative one) runs exactly ``max_iter``. ``max_iter``
and ``tol`` are traced scalars: the host dispatches once and reads once, and
one compiled program per (shape, k) serves every value of them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mxu import bf16_pieces as _bf16_pieces, mxu_precision  # noqa: F401  (the rule lives in ops/mxu.py)

__all__ = [
    "fused_lloyd_run",
    "fused_lloyd_run_sharded",
    "fused_sharded_supported",
    "fused_supported",
]


RUN_LABEL_EPILOGUES = 0
"""XLA passes over the rows that one ``fused_lloyd_run*`` program makes for
its labels: none, its last kernel pass writes them. ``KMeans.fit`` adds it per
program it dispatches (``fusion.cache_stats()["phase_kmeans_label_epilogues"]``)."""


def _pad8(n: int) -> int:
    return 8 * ((n + 7) // 8)


def _block_cols(f: int, k: int, itemsize: int = 4) -> int:
    """Samples (lanes) per grid step, sized against the scoped-VMEM limit
    of a v5e (16 MB) for rows of ``itemsize`` bytes. What stays live per
    lane for a whole block is the double-buffered (f, block) input, the
    stack of bfloat16 pieces (three per float32 row, one per bfloat16 row)
    and the (k, block)-shaped dot/score/onehot chain, sublane-padded to
    multiples of 8. The constants are measured, not aspirational: the
    largest block Mosaic accepts under 16 MB was bisected over 13 (f, k)
    from (4, 3) to (512, 128) in both dtypes (compiles for a described v5e)
    and asks ``15 f + 12 k + 56`` bytes a lane for float32 rows and
    ``6.1 f + 11.7 k + 65`` for bfloat16 (8 % more at f = 512); the 12 MB
    budget leaves the rest for the accumulators and the centre operand. (An
    earlier (block, f) kernel ignored lane padding and hit the limit to
    within 1.5 KB.)"""
    fp, kp = _pad8(f), _pad8(k)
    pieces = 3 if itemsize >= 4 else 1
    per_lane = (2 * itemsize + 2 * pieces + 1) * fp + 12 * kp + 64
    blk = (12 << 20) // per_lane
    return max(1024, min(65536, blk // 128 * 128))


def pass_blocks(n: int, n_valid: int, f: int, k: int, itemsize: int = 4) -> tuple:
    """``(blocks, tail_blocks)``: the grid steps of one kernel pass over a
    device's ``n`` samples, and those of them that take the masked body
    because the block does not lie wholly under ``n_valid`` (the ragged tail,
    padding). From shapes alone: what ``KMeans.fit`` notes on its span."""
    block = _block_cols(f, k, itemsize)
    blocks = -(-n // block)
    return blocks, blocks - n_valid // block


def fused_supported(n: int, f: int, k: int) -> bool:
    """TPU backend, single device (the kernel has no partitioning spec —
    a sharded operand would be gathered), and sublane-safe f/k."""
    return (
        jax.default_backend() == "tpu"
        and len(jax.devices()) == 1
        and f <= 512
        and k <= 128
    )


def fused_sharded_supported(f: int, k: int) -> bool:
    """TPU backend and sublane-safe shapes; device count is irrelevant (the
    shard_map wrapper runs the kernel per device)."""
    return jax.default_backend() == "tpu" and f <= 512 and k <= 128


def _fold_lanes(rows: jax.Array) -> jax.Array:
    """(r, block) -> (r, 128): the lane tiles added up pairwise (a tree, so
    no add waits on a chain of the block's 245 tiles)."""
    parts = [rows[:, j : j + 128] for j in range(0, rows.shape[1], 128)]
    while len(parts) > 1:
        even = len(parts) // 2 * 2
        parts = [a + b for a, b in zip(parts[0:even:2], parts[1:even:2])] + parts[even:]
    return parts[0]


def _lloyd_kernel(
    xT_ref,
    csq_ref,
    c_ref,
    nvalid_ref,
    sums_ref,
    counts_ref,
    xsq_ref=None,
    labels_ref=None,
    *,
    kp: int,
    block: int,
    masked=None,
):
    """One (f, block) sample block; accumulators live across the whole grid.
    Samples at column index >= nvalid (the last block's tail beyond the
    operand's end, or a device's share of the global padding under the sharded
    wrapper) are masked out of every accumulator. n_valid is a runtime (1, 1)
    scalar operand in SMEM, so each device can carry its own count and the
    kernel can branch on it.

    **Only the block that has a tail is masked.** The mask is vector work in a
    kernel that its vector unit binds (eight operations a 128-lane tile as
    Mosaic lowers it: the column index, its compare, the selects on the rows,
    the ``and`` on the one-hot rows), and at the benchmark's shape 2 139
    blocks of 2 140 lie wholly under ``n_valid``, where every select returns
    its operand. So the kernel picks its body from the block in hand:
    ``(i + 1) * block <= n_valid``, a scalar test, runs the body without
    ``cols`` and ``valid``; any other block (the ragged tail, a device's
    padding, a block wholly beyond ``n_valid``) runs the masked body. Both are
    built by one function (``body(masked)``), so the arithmetic is written
    once, and a whole block's sums, counts, labels and squares are the masked
    body's to the bit. ``masked`` is for the tests: ``None`` lets the block
    decide, ``True`` runs the masked body on every block (6.56 ms a pass of
    2^26 x 16 rows that way, 6.40 as shipped; the last pass 7.40 and 6.84,
    where the select's result was a second copy of the block: PERF.md, PR 38).

    ``xsq_ref`` and ``labels_ref`` are the two outputs of a program's last
    pass alone. ``labels_ref`` takes the block's (1, block) argmin row as it
    is, unmasked: what it holds at columns >= nvalid is unspecified (the
    output ends at the operand's own length, beyond which nothing is kept).
    ``xsq_ref`` is an (f, 128) accumulator of the squares of the float32
    block in hand, one partial sum a feature and lane with no reduction
    across sublanes: the caller folds it once into Σ|x|². (A (1, 1) scalar
    would carry the 2^26-row cell's 1e9 through 2 140 sequential float32
    adds; summing ``Σ_f x² + min₀(score)`` a sample, two sublane reductions
    of the block, cost a pass 2.5 ms where this costs 1.0: PERF.md, PR 32.)

    ``kp`` is k padded to a sublane multiple: the centre rows beyond k are
    zero and carry ``csq = +inf``, so no sample is ever assigned to them.
    With ``p`` the number of bfloat16 pieces of a row's dtype, ``c_ref`` is
    (p·kp, p·f): row group ``i`` holds piece ``i`` of −2c against every
    piece of x, so the groups of the one dot add up to −2 c·x with every
    piece product exact; ``sums_ref`` is (kp, p·f), one column group per piece
    of x against the one-hot matrix (exact in bfloat16), folded by the caller.

    Every intermediate is 2-D: Mosaic lays a 1-D (block,) value out with a
    replicated sublane and chaining argmin / where / reduce through that
    layout hits "Invalid relayout: non-singleton logical dimension is
    replicated in destination but not in source" (observed on a v5e: each
    construct passes alone — only the 1-D chain fails)."""
    i = pl.program_id(0)
    nvalid = nvalid_ref[0, 0]

    @pl.when(i == 0)
    def _init():
        sums_ref[:, :] = jnp.zeros_like(sums_ref)
        counts_ref[:, :] = jnp.zeros_like(counts_ref)
        if xsq_ref is not None:
            xsq_ref[:, :] = jnp.zeros_like(xsq_ref)

    def body(masked: bool):
        xb = xT_ref[:, :]  # (f, block)
        if masked:
            cols = i * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
            valid = cols < nvalid  # (1, block) bool
            # Content at columns >= nvalid is UNSPECIFIED (the dndarray.parray
            # contract; the last block's tail beyond the operand) — inf/NaN there
            # would poison the accumulators through 0·inf = NaN in the sums
            # contraction, so zero invalid samples rather than relying on
            # multiplicative masking downstream.
            xb = jnp.where(valid, xb, 0)
        pieces = _bf16_pieces(xb)
        stack = jnp.concatenate(pieces, axis=0)  # (p·f, block) bf16

        # (kp, block) assignment scores; |x|² omitted (sample-constant for argmin)
        dots = jnp.dot(c_ref[:, :], stack, preferred_element_type=jnp.float32)
        score = csq_ref[:, :] + sum(
            dots[g * kp : (g + 1) * kp] for g in range(len(pieces))
        )
        kcol = jax.lax.broadcasted_iota(jnp.int32, (kp, 1), 0)
        labels = jnp.argmin(score, axis=0, keepdims=True).astype(jnp.int32)  # (1, block)
        hit = labels == kcol
        if masked:
            hit = jnp.logical_and(hit, valid)
        onehot = hit.astype(jnp.bfloat16)  # (kp, block)

        # sums by piece, (kp, p·f): contract the lane (sample) axes of both
        # operands on the MXU — dot_general, so neither is transposed. The one-hot
        # rows are the streamed operand: with the p·f stack rows streamed against
        # it the same product took 9.5 ms a pass where this takes 7.4 (v5e, 2^26 x
        # 16, k = 8: PERF.md, PR 29)
        sums_ref[:, :] += jax.lax.dot_general(
            onehot, stack, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        # accumulate the count in f32: a bf16 onehot sum saturates at 256
        counts_ref[:, :] += jnp.sum(
            onehot, axis=1, keepdims=True, dtype=counts_ref.dtype
        )
        if labels_ref is None:
            return
        labels_ref[:, :] = labels
        x32 = xb.astype(jnp.float32)  # zero at invalid samples
        xsq_ref[:, :] += _fold_lanes(x32 * x32)

    if masked is not None:
        body(masked)
        return
    whole = (i + 1) * block <= nvalid
    pl.when(whole)(functools.partial(body, False))
    pl.when(jnp.logical_not(whole))(functools.partial(body, True))


def _prepare(data: jax.Array) -> jax.Array:
    """(n, f) -> (f, n): the samples-in-lanes view the kernel streams. Inside
    the program that holds the kernel it is a bitcast of float32 rows as
    XLA:TPU lays them out, not a pass; nothing pads the sample axis.

    bfloat16 stays bfloat16 — the kernel's contractions accumulate in f32
    (``preferred_element_type``) while the streamed operand keeps half the
    HBM footprint, doubling the bandwidth-bound iteration rate. Everything
    else (f64 included: Mosaic cannot lower it) is carried as f32 and
    multiplied in f32."""
    x = data if data.dtype == jnp.bfloat16 else data.astype(jnp.float32)
    return jnp.transpose(x)


def _kernel_call_T(xT, centers, k: int, n_valid, interpret: bool, last: bool = False, masked=None):
    """Invoke the kernel on a samples-in-lanes (f, n) operand, read in place:
    the grid is ``cdiv(n, block)`` and the last block ends inside the operand
    (its tail is unspecified and masked, as every column >= ``n_valid``).
    Returns the (sumsT (f, k), counts (k, 1)) accumulators and, from a
    program's ``last`` pass (a kernel of its own name), Σ|x|² of the valid
    samples (a scalar) and the (n,) int32 assignment of the operand's samples
    against ``centers`` as a third and fourth. The labels output is exactly
    n long: Pallas clips the last block's write to it, and
    nobody copies a padded row to cut it (0.84 ms for 2^26 labels on a v5e:
    PERF.md, PR 30)."""
    f, n = xT.shape
    block = _block_cols(f, k, xT.dtype.itemsize)
    kp = _pad8(k)
    c32 = centers.astype(jnp.float32)
    rows = ((0, kp - k), (0, 0))  # rows beyond k: zero centres under csq = +inf, never the argmin
    csq = jnp.pad(  # always from f32 centres
        jnp.sum(c32 * c32, axis=1, keepdims=True), rows, constant_values=jnp.inf
    )
    # the score dot's operands share the streamed dtype's pieces: −2c (exact)
    # as bf16 for bf16 rows, as its three pieces for f32 rows, each group
    # repeated against every piece of x (kernel docstring)
    c_pieces = _bf16_pieces(jnp.pad(-2.0 * c32, rows).astype(xT.dtype))
    p = len(c_pieces)
    cx = jnp.tile(jnp.concatenate(c_pieces, axis=0), (1, p))  # (p·kp, p·f)
    nv = jnp.reshape(n_valid.astype(jnp.int32), (1, 1))

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0), memory_space=pltpu.VMEM)

    out_shape = [
        jax.ShapeDtypeStruct((kp, p * f), jnp.float32),
        jax.ShapeDtypeStruct((kp, 1), jnp.float32),
    ]
    out_specs = [whole((kp, p * f)), whole((kp, 1))]
    if last:
        out_shape += [
            jax.ShapeDtypeStruct((f, 128), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ]
        out_specs += [
            whole((f, 128)),
            pl.BlockSpec((1, block), lambda i: (0, i), memory_space=pltpu.VMEM),
        ]
    sums, counts, *rest = pl.pallas_call(
        functools.partial(_lloyd_kernel, kp=kp, block=block, masked=masked),
        out_shape=out_shape,
        grid=(pl.cdiv(n, block),),
        in_specs=[
            pl.BlockSpec((f, block), lambda i: (0, i), memory_space=pltpu.VMEM),
            whole((kp, 1)),
            whole((p * kp, p * f)),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),  # a scalar to branch on
        ],
        out_specs=out_specs,
        interpret=interpret,
        name="lloyd_pass_labels" if last else "lloyd_pass",
    )(xT, csq, cx, nv)
    sums = sum(sums[:k, g * f : (g + 1) * f] for g in range(p))  # fold x's pieces
    if not last:
        return sums.T, counts[:k]
    xsq, labels = rest
    return sums.T, counts[:k], jnp.sum(xsq), labels[0]


def _finalize(sumsT, counts, centers):
    """Shared epilogue: centroid update (empty clusters keep their center)
    and the convergence shift. One body for the single-device and sharded
    paths so their numerics cannot drift."""
    counts = counts[:, 0]  # (k,)
    sums = sumsT.T  # (k, f) — tiny
    new_centers = jnp.where(
        counts[:, None] > 0,
        sums / jnp.maximum(counts[:, None], 1.0),
        centers.astype(jnp.float32),
    ).astype(centers.dtype)
    shift = jnp.sum((new_centers - centers).astype(jnp.float32) ** 2)
    return new_centers, shift


def _inertia(xsq_sum, sumsT, counts, centers):
    """Σ d² of one assignment from that pass's own accumulators: every
    sample's ``|x|² + min₀(score)``, the scores added up by cluster,
    ``Σ|x|² + Σ_k n_k·|c_k|² − 2 Σ_k c_k·s_k`` (float32 products of k·f
    numbers: no pass over the rows, and for bfloat16 rows the distance to the
    unrounded centres). Good to a few roundings of Σ|x|² in float32, as the
    jnp path's per-sample sum is: on rows far off the origin that is what
    is left of it (``KMeans``' docstring)."""
    c32 = centers.astype(jnp.float32)
    scores = jnp.sum(counts[:, 0] * jnp.sum(c32 * c32, axis=1)) - 2.0 * jnp.sum(c32 * sumsT.T)
    return jnp.maximum(xsq_sum + scores, 0.0)


def _kernel_step(accumulate):
    """The ``step`` of :func:`_steps` on the kernel: ``accumulate(centers,
    last)`` is one kernel pass, merged over devices where there are several:
    ``(sumsT, counts)``, with the pass's Σ|x|² and labels behind them when it
    is the ``last``."""

    def step(centers, last):
        sumsT, counts, *rest = accumulate(centers, last)
        moved = _finalize(sumsT, counts, centers)
        if not last:
            return moved
        xsq_sum, labels = rest
        return (*moved, labels, _inertia(xsq_sum, sumsT, counts, centers))

    return step


def _steps(step, centers, max_iter, tol):
    """One fit's iterations, the one loop of all three Lloyd modes (the module
    docstring's rule). ``step(centers, last)`` is one iteration: ``(new
    centres, shift)``, with the assignment it made and its inertia behind them
    when it is the ``last``. Plain steps run under a ``while_loop`` until one's
    shift is at most ``tol`` or ``max_iter - 1`` have run; the carry holds the
    outcome of ``shift <= tol``, not the shift, so a NaN shift keeps iterating
    as a host's ``float(shift) <= tol`` would. The last step is peeled off the
    loop: it is the one pass that writes labels and whose inertia anybody
    reads. ``max_iter`` and ``tol`` are traced scalars. Returns ``(centers,
    labels, inertia, shift, n_iter)``, the shift the last step's."""

    def body(carry):
        done, c, _ = carry
        new_c, shift = step(c, False)
        return done + 1, new_c, jnp.logical_not(shift <= tol)

    done, centers, _ = jax.lax.while_loop(
        lambda carry: jnp.logical_and(carry[0] < max_iter - 1, carry[2]),
        body,
        (jnp.zeros((), jnp.int32), centers, jnp.ones((), bool)),
    )
    new_centers, shift, labels, inertia = step(centers, True)
    return new_centers, labels, inertia, shift, done + 1


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def fused_lloyd_run(
    data: jax.Array, centers: jax.Array, k: int, max_iter, tol, interpret: bool = False
):
    """One fit's fused iterations in one XLA program (the pallas analog of
    ``cluster.kmeans._lloyd_run``) that reads ``data`` in place: one kernel
    pass per iteration and no other pass over the rows, as many iterations as
    the traced scalars ``max_iter`` and ``tol`` let run (:func:`_steps`): one
    compiled program per (shape, k) serves every value of them. The last pass
    also writes its labels: the assignment against the last iteration's input
    centers (the jnp oracle's exact label convention), which the inertia
    belongs to. Returns ``(centers, labels, inertia, shift, n_iter)``."""
    xT = _prepare(data)
    n_valid = jnp.asarray(data.shape[0], jnp.int32)

    def accumulate(c, last):
        return _kernel_call_T(xT, c, k, n_valid, interpret, last)

    return _steps(_kernel_step(accumulate), centers, max_iter, tol)


def fused_lloyd_run_sharded(
    data: jax.Array,
    centers: jax.Array,
    k: int,
    comm,
    n_global: int,
    max_iter,
    tol,
    interpret: bool = False,
):
    """One fit's fused sharded iterations in ONE XLA program — the multi-chip
    analog of :func:`fused_lloyd_run`.

    ``data`` is the PHYSICAL payload (``DNDarray.parray``): row count a
    multiple of the mesh size, suffix-padded when the logical ``n_global``
    is ragged. Each device runs the single-pass kernel on its own rows, read
    in place — masking its share of the global padding — with the while_loop
    of kernel steps INSIDE the shard_map and one psum of the (f, k)/(k, 1)
    accumulators per step (the last step's Σ|x|² beside them). The shift the
    loop's condition reads is made from the merged accumulators, so every
    device takes the same number of trips; ``max_iter`` and ``tol`` go in
    replicated. Labels are each device's last pass's output for its own rows
    (no collective), row-sharded like ``data`` and sliced to the logical
    length ``n_global``. Cached per (mesh, k, n_global)."""
    fn = _sharded_run_fn(comm.mesh, comm.axis_name, comm.size, k, int(n_global), bool(interpret))
    return fn(data, centers, max_iter, tol)


@functools.lru_cache(maxsize=None)
def _sharded_run_fn(mesh, axis, p, k, n_global, interpret):
    """Jitted sharded run, cached per static config (the
    attention.py:_ring_attention_fn closure-cache pattern — comm objects are
    unhashable, their mesh/axis are)."""
    from jax.sharding import PartitionSpec as P

    def device_run(xl, c0, max_iter, tol):
        local_rows = xl.shape[0]
        idx = jax.lax.axis_index(axis)
        local_valid = jnp.clip(n_global - idx * local_rows, 0, local_rows)
        xT = _prepare(xl)

        def accumulate(c, last):
            sumsT, counts, *rest = _kernel_call_T(xT, c, k, local_valid, interpret, last)
            if not last:
                return jax.lax.psum((sumsT, counts), axis)
            xsq_sum, labels = rest
            return (*jax.lax.psum((sumsT, counts, xsq_sum), axis), labels)

        return _steps(_kernel_step(accumulate), c0.astype(jnp.float32), max_iter, tol)

    @jax.jit
    def run(data, centers, max_iter, tol):
        new_c, labels, inertia, shift, n_iter = jax.shard_map(
            device_run,
            mesh=mesh,
            in_specs=(P(axis, None), P(), P(), P()),
            out_specs=(P(), P(axis), P(), P(), P()),
            check_vma=False,  # pallas_call outputs carry no vma annotation
        )(data, centers, max_iter, tol)
        return new_c.astype(centers.dtype), labels[:n_global], inertia, shift, n_iter

    return run
