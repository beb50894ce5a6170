"""Smoke test of the main path on the chip: ``python chip_smoke.py``.

One process, public entry points, full width, random data from fixed seeds.
It refuses to run without a TPU (exit code != 0, no result line), runs every
phase below over every device present, and prints two JSON lines on stdout:
the report (per-phase seconds and measured errors, compiles, compile-cache
directory) and then, as the last line, the verdict with exactly these keys —
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` —
exiting 0 only when every phase passed. It is NOT a benchmark: the seconds
it reports are per-phase wall clock including compilation.

Phases: analytics (KMeans on the fused Lloyd kernel vs the jnp oracle, cdist,
moments, QR, an eager chain + resplit), server (four ``serving.Session``
client threads), trainer (``nn.DataParallel(ResNet18)``, plus DASO on more
than one device), the three pallas kernels compiled (``interpret=False``),
and a final "nothing was swallowed" audit of the fusion degrade counters.

The check functions take sizes so ``tests/test_chip_smoke.py`` can call them
tiny on the CPU mesh (passing ``interpret=True``); ``main`` always runs the
``FULL`` sizes and always demands the chip.

Size note: BASELINE.md's cdist config (100k x 64) writes a 40 GB result:
no single 16 GB chip holds it, four hold 10.0 GB each, and that size runs on
four chips as the benchmark cell ``cdist_ring_4c`` (50 000 x 64, the same
10.0 GB, on one as ``cdist_50k_1c``). The width run here, on however many
chips there are, is 32768 x 64 (a 4.3 GB result).
"""

import functools
import importlib.metadata
import json
import sys
import threading
import time
import traceback
import warnings

import numpy as np

#: the widths BASELINE.md tracks (cdist cut to one chip: see above)
FULL = {
    "kmeans": dict(n=10_000_000, f=16, k=8, iters=10),
    "cdist": dict(n=32768, f=64, block=256),
    "moments": dict(n=1_000_000),
    "qr": dict(m=1_250_000, n=512, rows=1024),  # the qr_tall_1c cell's shape: one chip's rows of BASELINE config 4
    "eager": dict(rows=100_003, cols=7),
    "server": dict(clients=4, requests=20, n=4096),
    "trainer": dict(batch=256, steps=5),
    "daso": dict(batch=256),
    "kernels": dict(n=4096, f=64, seq=4096, heads=8, dim=64),
}


def device_facts() -> dict:
    """Platform, device kind, device count and the toolchain versions, as
    JAX reports them (initializes the backend)."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    return {
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu},
    }


def _max_abs_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))))


# ----------------------------------------------------------------------
# analytics
# ----------------------------------------------------------------------
def check_kmeans(n, f, k, iters, interpret=False, center_atol=2e-3, inertia_rtol=1e-3):
    """``KMeans.fit`` on the fused Lloyd kernel (the product dispatch on TPU)
    against a ``use_fused=False`` fit from the same seed.

    Tolerances: both fits run ``iters`` Lloyd steps from identical initial
    centers on unstructured N(0, 1) data, and both multiply float32 rows in
    float32 (the kernel from exact bfloat16 pieces, the jnp path at
    ``HIGHEST``). Their scores still differ in the last bit (the order of
    accumulation), so a sample within rounding of a cluster boundary may
    flip, each flip moves a center by O(1/n_k), and Lloyd's iteration carries
    the difference on. On the v5e at 10M x 16 the largest center difference
    read 5.4e-4 (PR 29; the bfloat16 product of the kernel before it needed
    2e-2); ``center_atol`` leaves that four times of room,
    ``inertia_rtol`` the objective. On more than one device it also
    establishes that the work is spread: one shard per device with equal
    shapes, the ``sharded`` mode, an all-reduce and no temporary of the
    rows' size in the compiled Lloyd program, and balanced per-device peak
    memory (where the backend reports it)."""
    import heat_tpu as ht
    from heat_tpu.core import telemetry
    from heat_tpu.ops import lloyd

    ht.random.seed(1)
    x = ht.random.randn(n, f, split=0)
    comm = x.comm
    out = {}

    fused = ht.cluster.KMeans(
        n_clusters=k, max_iter=iters, tol=0.0, random_state=7,
        use_fused=True if interpret else None,
    )
    mode, interp = fused._fused_mode(x)
    out["mode"], out["interpret"] = mode, interp
    assert mode in ("single", "sharded"), f"KMeans did not take the fused path: {mode!r}"
    assert interp is interpret, f"fused Lloyd interpret={interp}, expected {interpret}"
    fused.fit(x)
    oracle = ht.cluster.KMeans(
        n_clusters=k, max_iter=iters, tol=0.0, random_state=7, use_fused=False
    ).fit(x)

    centers = fused.cluster_centers_.numpy()
    assert centers.shape == (k, f), centers.shape
    assert np.isfinite(centers).all() and np.isfinite(fused.inertia_)
    assert fused.labels_.shape == (n,) and fused.n_iter_ == iters
    out["center_err"] = _max_abs_err(centers, oracle.cluster_centers_.numpy())
    out["inertia_rel"] = abs(fused.inertia_ - oracle.inertia_) / oracle.inertia_
    assert out["center_err"] <= center_atol, out
    assert out["inertia_rel"] <= inertia_rtol, out

    if comm.size > 1:
        assert mode == "sharded", f"{comm.size} devices but KMeans mode {mode!r}"
        shards = x.parray.addressable_shards
        out["shard_devices"] = sorted(s.device.id for s in shards)
        out["shard_shapes"] = sorted({tuple(s.data.shape) for s in shards})
        assert len(set(out["shard_devices"])) == comm.size, out
        assert len(out["shard_shapes"]) == 1, out
        run = lloyd._sharded_run_fn(comm.mesh, comm.axis_name, comm.size, k, n, interpret)
        compiled = run.lower(x.parray, fused.cluster_centers_.larray, iters, 0.0).compile()
        out["lloyd_collectives"] = telemetry.hlo_collective_counts(compiled.as_text())
        assert out["lloyd_collectives"].get("all-reduce", 0) >= 1, out
        # the kernel reads each device's rows in place: a padded copy of them
        # would be a temporary of at least their size (compiled for a described
        # v5e:2x2 at 2.5M x 16 a device: 160.9 MB before PR 32, 0 since)
        (rows_per_device, _), = out["shard_shapes"]
        out["lloyd_temp_bytes"] = int(compiled.memory_analysis().temp_size_in_bytes)
        if not interpret:  # the interpreter's emulation of the kernel holds copies of its own
            assert out["lloyd_temp_bytes"] < rows_per_device * f * x.parray.dtype.itemsize, out
        stats = [d.memory_stats() for d in comm.devices]
        if all(s and "peak_bytes_in_use" in s for s in stats):
            peaks = [int(s["peak_bytes_in_use"]) for s in stats]
            out["peak_bytes_per_device"] = peaks
            assert max(peaks) / max(min(peaks), 1) < 1.5, out
    return out


def check_cdist(n, f, block, atol=1e-4):
    """``spatial.cdist`` (quadratic expansion) against numpy float64 on an
    off-diagonal (block, block) sample. ``atol``: the expansion's ``x @ yᵀ``
    multiplies float32 rows in float32 (``ops/mxu.py``'s rule), so what is
    left is the float32 cancellation of |x|² + |y|² − 2x·y: a few roundings
    of 2f on d², 1e-5 on distances of ~√(2f) = 11 (a v5e reads 2.5e-6 at
    f = 64; at the MXU's default precision, before PR 33, it read 9e-3 and
    this check allowed 5e-2)."""
    import heat_tpu as ht

    ht.random.seed(2)
    x = ht.random.randn(n, f, split=0)
    d = ht.spatial.cdist(x, quadratic_expansion=True)
    assert d.shape == (n, n) and d.split == 0, (d.shape, d.split)
    lo = n // 2
    got = d[0:block, lo : lo + block].numpy()
    a = x[0:block].numpy().astype(np.float64)
    b = x[lo : lo + block].numpy().astype(np.float64)
    want = np.sqrt(((a[:, None, :] - b[None, :, :]) ** 2).sum(-1))
    err = _max_abs_err(got, want)
    total = float(ht.sum(d))
    assert np.isfinite(total) and total > 0, total
    assert err <= atol, err
    return {"block_err": err}


def check_moments(n, rtol=1e-4, atol=1e-5):
    """``ht.mean`` / ``ht.std`` of a split f32 vector against numpy float64."""
    import heat_tpu as ht

    ht.random.seed(3)
    x = ht.random.randn(n, split=0)
    mean, std = float(ht.mean(x)), float(ht.std(x))
    ref = x.numpy().astype(np.float64)
    np.testing.assert_allclose([mean, std], [ref.mean(), ref.std()], rtol=rtol, atol=atol)
    return {"mean_err": float(abs(mean - ref.mean())), "std_err": float(abs(std - ref.std()))}


def check_qr(m, n, rows, orth_atol=1e-4, resid_rtol=1e-4):
    """Tall-skinny ``linalg.qr``: ‖QᵀQ − I‖_max (computed at HIGHEST matmul
    precision so the check measures Q, not the checker) and the relative
    residual ‖QR − A‖/‖A‖ on the first ``rows`` rows."""
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    ht.random.seed(4)
    a = ht.random.randn(m, n, split=0)
    q, r = ht.linalg.qr(a)
    assert q.shape == (m, n) and r.shape == (n, n) and q.split == 0, (q.shape, r.shape, q.split)
    hi = jax.lax.Precision.HIGHEST
    qtq = jnp.dot(q.larray.T, q.larray, precision=hi)
    orth = _max_abs_err(qtq, np.eye(n))
    a_top = a[0:rows].numpy().astype(np.float64)
    qr_top = q[0:rows].numpy().astype(np.float64) @ r.numpy().astype(np.float64)
    resid = float(np.linalg.norm(qr_top - a_top) / np.linalg.norm(a_top))
    out = {"orth_err": orth, "resid_rel": resid}
    assert orth <= orth_atol and resid <= resid_rtol, out
    return out


def check_eager(rows, cols, rtol=1e-4):
    """One eager elementwise+reduction chain and ``resplit`` 0 → 1 → None on
    a ragged shape (neither extent divides the mesh), against numpy."""
    import heat_tpu as ht

    ht.random.seed(5)
    x = ht.random.randn(rows, cols, split=0)
    y = ht.random.randn(rows, cols, split=0)
    xn, yn = x.numpy().astype(np.float64), y.numpy().astype(np.float64)
    got = float(ht.sum(ht.sqrt(ht.abs(ht.exp((x + y) * 0.25) - y)) / (ht.abs(x) + 1.0)))
    want = (np.sqrt(np.abs(np.exp((xn + yn) * 0.25) - yn)) / (np.abs(xn) + 1.0)).sum()
    np.testing.assert_allclose(got, want, rtol=rtol)
    x1 = ht.resplit(x, 1)
    x_none = ht.resplit(x1, None)
    assert x1.split == 1 and x_none.split is None
    np.testing.assert_array_equal(x1.numpy(), x.numpy())
    np.testing.assert_array_equal(x_none.numpy(), x.numpy())
    return {"chain_rel": float(abs(got - want) / abs(want))}


# ----------------------------------------------------------------------
# server
# ----------------------------------------------------------------------
def _serve_request(arr, w, b):
    """The one request shape (a shared code object: the fusion DAG dedups
    leaves by identity, so prebake and clients must build the same chain)."""
    import heat_tpu as ht

    return ht.sum(arr * w + b)


def check_server(clients, requests, n, rtol=1e-4):
    """``clients`` threads, each in its own ``serving.Session``, each
    answering ``requests`` requests; every value checked, zero steady-state
    retraces, no incidents."""
    import heat_tpu as ht
    from heat_tpu.core import fusion, serving

    comm = ht.get_comm()
    n = max(n // comm.size, 1) * comm.size

    def make_input(seed):
        ht.random.seed(seed)
        arr = ht.random.randn(n, split=0)
        return arr, float(arr.numpy().astype(np.float64).sum())

    if fusion.active():
        # cross-session batching groups j same-shaped roots into one program
        # whose signature depends on j: compile every batch size up front
        for j in range(1, clients + 1):
            outs = [
                _serve_request(make_input(100 + i)[0], 1.0 + i * 0.25, 0.5 + i)
                for i in range(j)
            ]
            for o in outs:
                float(o)
    inputs = [make_input(200 + c) for c in range(clients)]
    compiles_before = fusion.cache_stats()["compiles"]
    barrier = threading.Barrier(clients)
    failures, reports = [], [None] * clients

    def client(idx):
        try:
            with serving.Session(f"smoke-client-{idx}") as sess:
                arr, total = inputs[idx]
                barrier.wait(timeout=120)
                for i in range(requests):
                    w, b = 1.0 + i * 0.25, 0.5 + i
                    got = float(_serve_request(arr, w, b))
                    np.testing.assert_allclose(got, w * total + b * n, rtol=rtol, atol=1e-2)
            reports[idx] = sess.report()
        except Exception:  # noqa: BLE001 - thread boundary: reported below
            failures.append(f"client {idx}:\n{traceback.format_exc()}")

    threads = [threading.Thread(target=client, args=(i,)) for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not any(t.is_alive() for t in threads), "a client thread hung"
    assert not failures, "\n".join(failures)
    retraces = fusion.cache_stats()["compiles"] - compiles_before
    assert retraces == 0, f"{retraces} steady-state retraces"
    for rep in reports:
        assert rep["incidents"] == [] and rep["quarantine"] == [], rep
        assert rep["stats"]["degraded"] == 0, rep
        if fusion.active():
            assert rep["stats"]["dispatches"] >= 1, rep
    return {"retraces": retraces, "requests": clients * requests}


# ----------------------------------------------------------------------
# trainer
# ----------------------------------------------------------------------
def _fixed_batch(batch, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((batch, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=batch).astype(np.int32)
    return x, y


def check_trainer(batch, steps, model=None):
    """``nn.DataParallel`` on a fixed CIFAR-shaped batch: the loss is finite
    every step and lower at the last step than at the first."""
    import heat_tpu as ht

    comm = ht.get_comm()
    batch = max(batch // comm.size, 1) * comm.size
    x, y = _fixed_batch(batch)
    module = model if model is not None else ht.nn.ResNet18(num_classes=10)
    dp = ht.nn.DataParallel(module, comm=comm, optimizer=ht.optim.SGD(0.05))
    dp.init(0, x[:2])
    losses = [dp.train_step(x, y) for _ in range(steps)]
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses
    return {"loss_first": losses[0], "loss_last": losses[-1]}


def check_daso(batch, model=None):
    """Two ``optim.DASO`` steps (an ici-only step, then the dcn merge) on the
    2-group dcn x ici mesh; needs more than one device."""
    import heat_tpu as ht

    comm = ht.get_comm()
    assert comm.size > 1 and comm.size % 2 == 0, comm.size
    batch = max(batch // comm.size, 1) * comm.size
    x, y = _fixed_batch(batch, seed=1)
    daso = ht.optim.DASO(
        local_optimizer=ht.optim.SGD(0.01), total_epochs=2,
        warmup_epochs=0, cooldown_epochs=0, comm=comm, nodes=2,
    )
    mesh_shape = dict(daso.mesh.shape)
    assert mesh_shape == {"dcn": 2, "ici": comm.size // 2}, mesh_shape
    daso.add_model(model if model is not None else ht.nn.ResNet18(num_classes=10), 0, x[:2])
    loss_ici = daso.step(x, y)
    daso.global_skip = 0
    loss_dcn = daso.step(x, y)
    assert np.isfinite([loss_ici, loss_dcn]).all(), (loss_ici, loss_dcn)
    return {"mesh": mesh_shape, "loss_ici": loss_ici, "loss_dcn": loss_dcn}


# ----------------------------------------------------------------------
# kernels
# ----------------------------------------------------------------------
def check_kernels(n, f, seq, heads, dim, interpret=False,
                  pairwise_rtol=1e-4, flash_atol=3e-2):
    """The pairwise-distance and flash-attention pallas kernels against
    their jnp references (the Lloyd kernel is covered by
    :func:`check_kmeans`). The attention reference is
    ``dot_product_attention`` at HIGHEST matmul precision on f32 copies of
    the operands. ``flash_atol`` is bf16 rounding of O(1) scores and
    probabilities (2⁻⁸ relative) for BOTH dtypes: on the MXU an f32 matmul
    at default precision rounds its operands to bf16 too, in the kernel as
    in XLA — ``dense_default_f32_err`` reports the same reference's error at
    the default precision for comparison."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.nn.attention import dot_product_attention, flash_attention
    from heat_tpu.ops import pairwise_distance
    from heat_tpu.ops.flash import flash_attention_tpu
    from heat_tpu.spatial.distance import _euclidian, _manhattan

    out = {}
    kx, ky, kq, kk, kv = jax.random.split(jax.random.PRNGKey(6), 5)
    x = jax.random.normal(kx, (n, f), jnp.float32)
    y = jax.random.normal(ky, (n, f), jnp.float32)
    for p, ref in ((1, _manhattan), (2, _euclidian)):  # the exact jnp expressions
        got = np.asarray(pairwise_distance(x, y, p=p, interpret=interpret))
        want = np.asarray(jax.jit(ref)(x, y))
        assert got.shape == (n, n)
        out[f"pairwise_p{p}_err"] = _max_abs_err(got, want)
        np.testing.assert_allclose(got, want, rtol=pairwise_rtol, atol=1e-4, err_msg=str(out))

    q, k, v = (jax.random.normal(kk_, (1, seq, heads, dim), jnp.float32) for kk_ in (kq, kk, kv))
    if interpret:
        flash = functools.partial(flash_attention_tpu, causal=True, interpret=True)
    else:
        flash = functools.partial(flash_attention, causal=True, impl="pallas")
    for name, dtype in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        qd, kd, vd = (t.astype(dtype) for t in (q, k, v))
        got = flash(qd, kd, vd)
        assert got.shape == q.shape and got.dtype == dtype, (got.shape, got.dtype)
        q32, k32, v32 = (t.astype(jnp.float32) for t in (qd, kd, vd))
        with jax.default_matmul_precision("highest"):
            want = dot_product_attention(q32, k32, v32, causal=True)
        out[f"flash_{name}_err"] = _max_abs_err(got.astype(jnp.float32), want)
        if dtype == jnp.float32:
            out["dense_default_f32_err"] = _max_abs_err(
                dot_product_attention(q32, k32, v32, causal=True), want
            )
        assert out[f"flash_{name}_err"] <= flash_atol, out
    return out


# ----------------------------------------------------------------------
# audit
# ----------------------------------------------------------------------
def check_nothing_swallowed():
    """The fusion engine's degrade-to-eager and quarantine are product
    features; on this path they must not have fired."""
    from heat_tpu.core import fusion

    stats = fusion.cache_stats()
    assert stats["degraded"] == 0 and stats["quarantined"] == 0, {
        k: stats[k] for k in ("degraded", "quarantined", "quarantine_hits")
    }
    return {"degraded": stats["degraded"], "quarantined": stats["quarantined"]}


def arm_warnings() -> None:
    """Failures the library would only warn about are errors for the whole
    run: a degraded dispatch, a KMeans fallback to the jnp path, and a dtype
    request the 32-bit default silently truncates."""
    from heat_tpu.core import resilience

    warnings.filterwarnings("error", category=resilience.DegradedDispatchWarning)
    warnings.filterwarnings("error", message=".*falling back.*")
    warnings.filterwarnings("error", message=".*requested dtype.*truncated.*")


def verdict(facts: dict, failed: list) -> dict:
    """The last stdout line. The driver parses it and accepts exactly these
    keys; everything else belongs in the report line before it."""
    dev = facts["device"]
    return {
        "ok": not failed,
        "device": {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"]},
    }


def main() -> int:
    facts = device_facts()
    print(f"chip_smoke: {json.dumps(facts)}", file=sys.stderr, flush=True)
    if facts["device"]["platform"] != "tpu":
        print(
            f"chip_smoke: no TPU found (jax.devices()[0].platform == "
            f"{facts['device']['platform']!r}); this script runs on the chip only",
            file=sys.stderr,
        )
        return 2

    from heat_tpu.core import fusion, serving

    cache_dir = serving.use_entry_point_compile_cache()
    arm_warnings()

    # kmeans first: its spread check reads process-lifetime peak memory
    phases = [
        ("kmeans", check_kmeans),
        ("cdist", check_cdist),
        ("moments", check_moments),
        ("qr", check_qr),
        ("eager", check_eager),
        ("server", check_server),
        ("trainer", check_trainer),
        *([("daso", check_daso)] if facts["device"]["count"] > 1 else []),
        ("kernels", check_kernels),
        ("audit", check_nothing_swallowed),
    ]
    seconds, measured, failed = {}, {}, []
    for name, fn in phases:
        start = time.perf_counter()
        try:
            measured[name] = fn(**FULL.get(name, {}))
        except Exception:  # noqa: BLE001 - phase boundary: report, run the rest
            failed.append(name)
            print(f"chip_smoke: phase {name} FAILED\n{traceback.format_exc()}", file=sys.stderr)
        seconds[name] = round(time.perf_counter() - start, 2)
        print(f"chip_smoke: {name} {'FAILED' if name in failed else 'ok'} {seconds[name]}s",
              file=sys.stderr, flush=True)

    report = {
        **facts,
        "failed": failed,
        "seconds": seconds,
        "measured": measured,
        "compiles": fusion.cache_stats()["compiles"],
        "compile_cache_dir": cache_dir,
    }
    print(json.dumps({"report": report}), flush=True)
    print(json.dumps(verdict(facts, failed)), flush=True)
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
