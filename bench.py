"""Benchmark entry point: one in-process run on the chip, ONE JSON record.

Tracked configs of BASELINE.md measured here:
  * config 3 (primary metric): kmeans, k=8 on 10M x 16 float32, split=0 —
    Lloyd iterations/second (reference benchmarks/kmeans/heat-cpu.py:20-26).
  * config 2 (extra field): cdist (quadratic expansion) GB/s/chip.
  * config 1 (extra field): statistical moments — mean+std of a 1M-elem
    float32 split=0 array, milliseconds
    (reference benchmarks/statistical_moments/heat-cpu.py:21-28).
  * config 4 (extra field): tall-skinny TSQR throughput, TFLOP/s
    (2mn^2 FLOP model).
  * achieved TFLOP/s of the fused Lloyd iteration (extra field).
  * eager_chain_ops_per_sec (extra field): dispatch rate of a representative
    10-op eager chain under the fusion engine (core/fusion.py), side by side
    with the HEAT_TPU_FUSION=0 unfused rate.

``vs_baseline`` is the measured speedup over a torch-CPU implementation of
the same Lloyd iteration at the same problem size on this machine (the
reference's single-node comparison baseline; the reference repo publishes no
absolute numbers, see BASELINE.md). The other tracked configs carry their
own external baselines (reference benchmarks/*/{numpy,torch}-*.py):
``moments_vs_numpy`` (full wall — the fused-collective chain costs one
sync), ``cdist_vs_numpy``, ``qr_vs_torch``.

Contract: ``python bench.py`` runs the measurement once, full size, in this
process (the process that holds the chip starts no child that can reach for
it). No TPU -> exit 2 and no record: a CPU number is never printed under a
device metric's name. Every diagnostic leg that raises is recorded as
``errors[<leg>] = repr(exc)`` in the printed record, and the process exits 1
when ``errors`` is non-empty.
"""

import json
import os
import subprocess
import sys
import time

# the tracked problem sizes
N_FULL, F, K = 10_000_000, 16, 8
ITERS = 10
CDIST_N, CDIST_F = 32768, 64
MOMENTS_N = 1_000_000
QR_M, QR_N = 1 << 21, 256
METRIC = "kmeans_iters_per_sec_10Mx16_k8"

# Roofline peaks, keyed by ``jax.devices()[0].device_kind``. Source: Google
# Cloud documentation, "TPU v5e" (197 bf16 TFLOP/s, 819 GB/s HBM per chip).
# A device that is not in the table is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "mxu_bf16_tflops": 197.0},
}


def _roofline_peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no roofline peaks for device_kind {device_kind!r}; add it to "
            "bench.PEAKS with its source"
        )
    return dict(PEAKS[device_kind], chip=device_kind)


def annotate_roofline(rec: dict) -> None:
    """Attach bytes/s, FLOP/s and %-of-peak fields to a worker record
    (BASELINE.md's targets are unfalsifiable without them)."""
    peaks = _roofline_peaks(rec["device_kind"])
    n = rec.get("n") or 0
    # kmeans (config 3): HBM-bound. The fused pallas path reads the operand
    # ONCE per iteration and writes nothing per-row (labels are the last
    # pass's one-off store, cancelled by the marginal); the jnp path reads twice
    # (assignment + update contractions) and writes the label vector.
    rate = rec.get("lloyd_iters_per_sec_marginal") or rec.get("value")
    if rate and n:
        fused = rec.get("lloyd_path") == "fused_pallas"
        iter_bytes = n * (F * 4 * (1 if fused else 2) + (0 if fused else 4))
        gbps = rate * iter_bytes / 1e9
        rec["lloyd_hbm_gbps"] = round(gbps, 1)
        rec["pct_hbm_roofline_kmeans"] = round(100.0 * gbps / peaks["hbm_gbps"], 1)
    # marginal (dispatch-cost-cancelled) rates represent the hardware; the
    # raw fields keep the API cost including the per-dispatch fixed cost
    cd_rate = rec.get("cdist_gbps_per_chip_marginal") or rec.get("cdist_gbps_per_chip")
    if cd_rate:
        rec["pct_hbm_roofline_cdist"] = round(100.0 * cd_rate / peaks["hbm_gbps"], 1)
    gbps = rec.get("moments_gbps_marginal")
    if not gbps and rec.get("moments_ms_1M"):
        # eager API path: mean + std = two full reads of the 1M f32 operand
        # (std reuses the mean, so each pass reads the data once)
        gbps = 2 * MOMENTS_N * 4 / (rec["moments_ms_1M"] / 1e3) / 1e9
    if gbps:
        rec["moments_hbm_gbps"] = round(gbps, 2)
        rec["pct_hbm_roofline_moments"] = round(100.0 * gbps / peaks["hbm_gbps"], 1)
    for key, out in (("qr_tflops", "pct_mxu_roofline_qr"), ("qr_cholqr2_tflops", "pct_mxu_roofline_qr_cholqr2")):
        if rec.get(key):
            rec[out] = round(100.0 * rec[key] / peaks["mxu_bf16_tflops"], 1)
    rec["roofline_peaks"] = peaks


def _marginal_sec(best1: float, bestN: float, extra_units: int):
    """Marginal seconds per unit from a (1x, Nx) two-point pair, or None
    when the spread is inside timing noise — the ONE acceptance rule for
    every marginal here. A near-zero delta
    would imply an unboundedly inflated rate, so the Nx run must clearly
    dominate the fixed cost first; and because the overstatement a noisy
    delta can bank grows with the work multiple (a 10x pair at a flat 1.2x
    floor could report ~45x the wall rate), large multiples demand a larger
    spread: 1.2x up to 16 extra units, 1.5x beyond."""
    floor = 1.2 if extra_units <= 16 else 1.5
    if bestN < floor * best1:
        return None
    return (bestN - best1) / extra_units


def _flops_per_lloyd_iter(n: int) -> float:
    # assignment matmul (2nFK) + one-hot update matmul (2nKF) + O(nK) argmin etc.
    return 2.0 * n * F * K * 2 + 10.0 * n * K


def worker() -> dict:
    """Measure everything once on the chip; returns the record (its
    ``errors`` object names every leg that raised)."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"bench: no TPU found (jax.devices()[0].platform == {dev.platform!r}); "
            "the benchmark measures the chip only",
            file=sys.stderr,
        )
        sys.exit(2)
    import jax.numpy as jnp
    import numpy as np

    import heat_tpu as ht
    from heat_tpu.cluster.kmeans import _lloyd_run
    from heat_tpu.core import serving as _serving

    compile_cache_dir = _serving.use_entry_point_compile_cache()
    comm = ht.get_comm()
    n = (N_FULL // comm.size) * comm.size
    cd_n = CDIST_N
    qr_m = QR_M // comm.size * comm.size
    errors = {}  # leg name -> repr(exc); non-empty fails the run

    rng = np.random.default_rng(0)
    centers = jnp.asarray(rng.standard_normal((K, F)).astype(np.float32) * 3)
    data = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (n, F), dtype=jnp.float32),
        comm.sharding(2, 0),
    )

    # -- kmeans (primary, config 3) ---------------------------------------
    # The PRODUCT path: KMeans.fit dispatches the fused single-pass pallas
    # kernel on one TPU device (cluster/kmeans.py:_fused_mode) — the primary
    # number measures what the product would run here. A kernel that fails
    # to lower or run raises: there is no fallback to the jnp path.
    from heat_tpu.ops.lloyd import fused_lloyd_run, fused_supported

    use_fused = fused_supported(n, F, K)
    lloyd_path = "fused_pallas" if use_fused else "jnp"

    def _primary_run(steps):
        if use_fused:
            return fused_lloyd_run(data, centers, K, steps)
        return _lloyd_run(data, centers, K, steps)

    # warmup/compile (fused ITERS-step program, one dispatch); every timing
    # below synchronizes via a scalar host read
    _, _, _, shift = _primary_run(ITERS)
    float(shift)
    best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        _, _, _, shift = _primary_run(ITERS)
        float(shift)
        best = min(best, time.perf_counter() - start)
    iters_per_sec = ITERS / best
    lloyd_tflops = _flops_per_lloyd_iter(n) * iters_per_sec / 1e12

    # -- cdist GB/s/chip (config 2) ---------------------------------------
    from heat_tpu.spatial.distance import _euclidian_fast

    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(2), (cd_n, CDIST_F), dtype=jnp.float32),
        comm.sharding(2, 0),
    )
    cfn = jax.jit(_euclidian_fast)
    out = cfn(x, x)
    float(out[0, 0])
    cd_best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        out = cfn(x, x)
        float(out[0, 0])
        cd_best = min(cd_best, time.perf_counter() - start)
    # bytes that must cross HBM at minimum: read both operands once, write the
    # full (n, n) float32 result
    cd_bytes = 2 * cd_n * CDIST_F * 4 + cd_n * cd_n * 4
    cd_gbps = cd_bytes / cd_best / 1e9 / comm.size

    # per-bench telemetry attribution (core/telemetry.py): collective counts
    # + forcing-point histograms banked NEXT TO each metric so the artifact
    # explains its own numbers (ISSUE 2); each snapshot is one extra run of
    # the measured op with telemetry on and must never cost the record
    from heat_tpu.core import telemetry as _telemetry

    # counts cover explicitly-scheduled verbs and declared linalg schedules
    # recorded at Python call time; GSPMD-inserted collectives (the fused
    # chain / moments reductions) are not verb calls, so an empty dict there
    # means "no explicit schedule", NOT "zero bytes moved"
    telem_bank = {
        "note": "collective_counts = explicit verb calls + declared linalg "
        "schedules only; GSPMD-inserted collectives are not counted"
    }

    def _telemetry_snapshot(run):
        with _telemetry.enabled():
            _telemetry.reset()
            run()
            snap = {
                "collective_counts": _telemetry.collective_counts(),
                "forcing_points": {
                    k: v["count"] for k, v in _telemetry.forcing_points().items()
                },
            }
            fused_coll = _telemetry.fused_collectives()
            if fused_coll:
                snap["fused_collectives"] = fused_coll
            async_f = _telemetry.async_forcing()
            if async_f["dispatches"]:
                snap["async_forcing"] = {
                    "dispatches": async_f["dispatches"],
                    "blocking_syncs": async_f["blocking_total"],
                }
            return snap

    # -- statistical moments (config 1) ------------------------------------
    mom = ht.array(
        jax.device_put(
            jax.random.normal(jax.random.PRNGKey(3), (MOMENTS_N,), dtype=jnp.float32),
            comm.sharding(1, 0),
        ),
        is_split=0,
    )
    # record BOTH reductions before reading: under collective-aware fusion
    # the first read dispatches ONE multi-output program (psums inside) and
    # the second read finds its value already in flight, so the chain costs
    # one host sync instead of one per reduction — the same user API, in the
    # order a user who wants both numbers naturally writes it
    def _moments_once():
        m_ = ht.mean(mom)
        s_ = ht.std(mom)
        return float(m_.larray), float(s_.larray)

    _moments_once()  # compile
    # the numpy baseline runs on the SAME data in ALTERNATING best-of rounds
    # (the telemetry overhead guard's noise-robust pattern): measuring the
    # two sides minutes apart under different machine states is what made
    # moments_vs_numpy swing — and with the chain fused to one sync the full
    # wall is the honest headline, so the comparison must be fair
    mom_np = np.asarray(jax.device_get(mom.larray))
    float(mom_np.mean() + mom_np.std())  # warm numpy's caches
    mom_best = mom_np_best = float("inf")
    for _ in range(7):
        start = time.perf_counter()
        _moments_once()
        mom_best = min(mom_best, time.perf_counter() - start)
        start = time.perf_counter()
        mom_np.mean(), mom_np.std()
        mom_np_best = min(mom_np_best, time.perf_counter() - start)
    moments_ms = mom_best * 1e3
    moments_numpy_ms = mom_np_best * 1e3

    # -- eager op-chain dispatch rate (core/fusion.py) ---------------------
    # a representative 10-op elementwise+reduce chain on a small split array:
    # dispatch-bound by construction. Fused (default) should approach one
    # cached program dispatch per chain; the HEAT_TPU_FUSION=0 leg pays one
    # dispatch per op — the ratio is the fusion engine's win.
    from heat_tpu.core import fusion as _fusion

    chain_fused = chain_unfused = chain_telemetry = None
    try:
        cn = max((2048 // comm.size) * comm.size, comm.size)
        ca = ht.array(
            jax.device_put(
                jax.random.normal(jax.random.PRNGKey(5), (cn, 4), dtype=jnp.float32),
                comm.sharding(2, 0),
            ),
            is_split=0,
        )
        cb = ht.array(
            jax.device_put(
                jax.random.normal(jax.random.PRNGKey(6), (cn, 4), dtype=jnp.float32),
                comm.sharding(2, 0),
            ),
            is_split=0,
        )

        def _chain_once():
            c = (ca + cb) * 2.0       # 1, 2
            c = ht.exp(c)             # 3
            c = c - cb                # 4
            d = ht.abs(c)             # 5
            e = d + ca                # 6
            f = ht.sqrt(ht.abs(e))    # 7, 8
            g = f / (d + 1.0)         # ~9 (the +1.0 rides the same dispatch class)
            h = g * cb
            return float(ht.sum(h).larray)  # 10: reduction + the one sync

        def _chain_rate():
            _chain_once()  # warm: compile/caches
            reps = 10
            best = float("inf")
            for _ in range(3):
                start = time.perf_counter()
                for _ in range(reps):
                    _chain_once()
                best = min(best, time.perf_counter() - start)
            return 10.0 * reps / best

        chain_fused = _chain_rate()
        with _fusion.disabled():
            chain_unfused = _chain_rate()
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["eager_chain"] = repr(exc)

    # -- split-axis reduction chain (collective-aware fusion, ISSUE 5) -----
    # mean -> var -> std of a distributed array, all three read back: the
    # whole chain (psums included) must compile into one cached program and
    # cost ONE blocking sync. The telemetry assertion is load-bearing — a
    # regression to force-at-collective would bank 3 syncs/chain and the
    # metric is withheld rather than banked mislabelled.
    reduction_chain = reduction_chain_syncs = None
    try:
        def _reduction_chain_once():
            m_ = ht.mean(mom)
            v_ = ht.var(mom)
            s_ = ht.std(mom)
            # read via item() — the instrumented host boundary — so the
            # telemetry assertion below counts real blocking syncs
            return float(m_) + float(v_) + float(s_)

        def _reduction_chain_rate():
            _reduction_chain_once()  # warm: compile/caches
            reps = 10
            best = float("inf")
            for _ in range(3):
                start = time.perf_counter()
                for _ in range(reps):
                    _reduction_chain_once()
                best = min(best, time.perf_counter() - start)
            return 3.0 * reps / best

        with _telemetry.enabled():
            _telemetry.reset()
            _reduction_chain_once()
            _sync0 = _telemetry.async_forcing()["blocking_total"]
            _reduction_chain_once()
            _per_chain = _telemetry.async_forcing()["blocking_total"] - _sync0
        reduction_chain_syncs = _per_chain
        if _fusion.collectives_active() and _per_chain > 1:
            raise AssertionError(
                f"fused reduction chain took {_per_chain} blocking syncs, expected <= 1"
            )
        reduction_chain = _reduction_chain_rate()
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["reduction_chain"] = repr(exc)

    # -- tall-skinny QR (config 4) -----------------------------------------
    qa = ht.array(
        jax.device_put(
            jax.random.normal(jax.random.PRNGKey(4), (qr_m, QR_N), dtype=jnp.float32),
            comm.sharding(2, 0),
        ),
        is_split=0,
    )
    qq, qrr = ht.linalg.qr(qa)
    float(qrr.larray[0, 0])  # compile + sync
    qr_best = float("inf")
    for _ in range(2):
        start = time.perf_counter()
        qq, qrr = ht.linalg.qr(qa)
        float(qrr.larray[0, 0])
        qr_best = min(qr_best, time.perf_counter() - start)
    qr_tflops = 2.0 * qr_m * QR_N * QR_N / qr_best / 1e12

    # -- torch-CPU baseline, measured at the same n (not extrapolated) -----
    vs = None
    try:
        vs = round(iters_per_sec / _torch_cpu_iters_per_sec(n), 2)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["torch_baseline"] = repr(exc)

    record = {
        "metric": METRIC,
        "value": round(iters_per_sec, 3),
        "unit": "iters/s",
        "vs_baseline": vs,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "compile_cache_dir": compile_cache_dir,
        "n": n,
        "lloyd_path": lloyd_path,
        "lloyd_tflops": round(lloyd_tflops, 3),
        "cdist_gbps_per_chip": round(cd_gbps, 2),
        "cdist_n": cd_n,
        "moments_ms_1M": round(moments_ms, 3),
        "moments_numpy_ms": round(moments_numpy_ms, 3),
        "moments_vs_numpy": round(moments_numpy_ms / moments_ms, 2),
        "qr_tflops": round(qr_tflops, 3),
        "qr_shape": [qr_m, QR_N],
    }
    if chain_fused:
        record["eager_chain_ops_per_sec"] = round(chain_fused, 1)
    if chain_unfused:
        record["eager_chain_ops_per_sec_unfused"] = round(chain_unfused, 1)
        if chain_fused:
            record["eager_chain_fused_vs_unfused"] = round(chain_fused / chain_unfused, 2)
    if reduction_chain:
        record["reduction_chain_ops_per_sec"] = round(reduction_chain, 1)
    if reduction_chain_syncs is not None:
        record["reduction_chain_syncs_per_chain"] = reduction_chain_syncs

    # whole-algorithm estimator leg (ISSUE 20): the collective-DAG-node
    # contract witnesses.
    # (a) estimator_syncs_per_iter — blocking syncs of ONE warm
    # reduce->matmul estimator iteration (mean -> centered matmul -> sum,
    # the Lloyd/CG shape): with matmul and the split-axis reductions
    # recording as DAG nodes the whole iteration compiles into one program
    # and costs <= 1 blocking sync. The assertion is load-bearing — a
    # regression to force-at-collective would bank 3+ syncs/iter and the
    # gauge is withheld rather than banked mislabelled (same contract as
    # reduction_chain_syncs_per_chain).
    try:
        est_n = (32768 // comm.size) * comm.size
        est_x = ht.array(
            jax.device_put(
                jax.random.normal(jax.random.PRNGKey(11), (est_n, 16), dtype=jnp.float32),
                comm.sharding(2, 0),
            ),
            is_split=0,
        )
        est_w = ht.array(
            jax.random.normal(jax.random.PRNGKey(12), (16, 8), dtype=jnp.float32),
            split=None,
        )

        def _estimator_iter_once():
            mu = ht.mean(est_x)
            return float(ht.sum((est_x - mu) @ est_w))

        _estimator_iter_once()  # warm: compile + program cache
        _estimator_iter_once()
        with _telemetry.enabled():
            _telemetry.reset()
            _estimator_iter_once()
            _sync0 = _telemetry.async_forcing()["blocking_total"]
            _estimator_iter_once()
            _per_iter = _telemetry.async_forcing()["blocking_total"] - _sync0
        if _fusion.collectives_active() and _per_iter > 1:
            raise AssertionError(
                f"whole-algorithm estimator iteration took {_per_iter} "
                "blocking syncs, expected <= 1"
            )
        record["estimator_syncs_per_iter"] = _per_iter
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["estimator_iter"] = repr(exc)

    # (b) lasso_sweeps_per_sec — warm coordinate-descent sweep rate of a
    # Lasso fit over sharded samples (regression/lasso.py): the CD sweep is
    # the lasso half of the whole-algorithm acceptance budget (ISSUE 20),
    # so its rate banks next to kmeans_iters_per_sec and gates via the
    # _RATE_KEYS -30% floor like the other throughput metrics.
    try:
        lasso_n = (16384 // comm.size) * comm.size
        lasso_x = ht.array(
            jax.device_put(
                jax.random.normal(jax.random.PRNGKey(13), (lasso_n, 12), dtype=jnp.float32),
                comm.sharding(2, 0),
            ),
            is_split=0,
        )
        lasso_y = ht.array(
            jax.device_put(
                jax.random.normal(jax.random.PRNGKey(14), (lasso_n,), dtype=jnp.float32),
                comm.sharding(1, 0),
            ),
            is_split=0,
        )
        _sweeps = 20
        _lasso_est = ht.regression.Lasso(lam=0.1, max_iter=_sweeps, tol=None)
        _lasso_est.fit(lasso_x, lasso_y)  # warm: compile the sweep programs
        lasso_best = float("inf")
        for _ in range(3):
            start = time.perf_counter()
            _lasso_est.fit(lasso_x, lasso_y)
            lasso_best = min(lasso_best, time.perf_counter() - start)
        record["lasso_sweeps_per_sec"] = round(_sweeps / lasso_best, 1)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["lasso"] = repr(exc)

    # telemetry legs (core/telemetry.py): the chain rate with the observability
    # layer on (contract >= 0.9x, banked as telemetry_overhead_pct) plus
    # per-bench collective/forcing attribution
    try:
        if chain_fused:
            with _telemetry.enabled():
                chain_telemetry = _chain_rate()
            record["telemetry_overhead_pct"] = round(
                100.0 * (1.0 - chain_telemetry / chain_fused), 1
            )
            telem_bank["eager_chain"] = _telemetry_snapshot(_chain_once)
        telem_bank["moments"] = _telemetry_snapshot(_moments_once)
        telem_bank["qr"] = _telemetry_snapshot(
            lambda: float(ht.linalg.qr(qa).R.larray[0, 0])
        )
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["telemetry"] = repr(exc)
    if len(telem_bank) > 1:  # more than the static note: a snapshot banked
        record["telemetry"] = telem_bank

    # trace-timeline leg (ISSUE 6): the full verbose event log (timestamps,
    # correlation ids, timeline deque) against telemetry-off, in ALTERNATING
    # best-of rounds like telemetry_overhead_pct so ambient machine noise hits
    # both legs equally; plus one exported trace validated as Chrome trace-event
    # JSON with its dispatch->blocking-sync async pairs counted.
    try:
        if chain_fused:
            off_rate = verbose_rate = 0.0
            for _ in range(3):
                off_rate = max(off_rate, _chain_rate())
                with _telemetry.enabled("verbose"):
                    verbose_rate = max(verbose_rate, _chain_rate())
            record["trace_overhead_pct"] = round(
                100.0 * (1.0 - verbose_rate / off_rate), 1
            )
            import tempfile as _tempfile

            with _telemetry.enabled("verbose"):
                _telemetry.reset()
                _reduction_chain_once()
                with _tempfile.TemporaryDirectory() as _td:
                    _tp = os.path.join(_td, "trace.json")
                    _telemetry.export_trace(_tp)
                    _problems = _telemetry.validate_trace(_tp)
                _pairs = _telemetry.async_pairs()
                _keys = _fusion.cache_stats()["program_keys"]
                _correlated = sum(
                    1 for _d, _s in _pairs if _d.get("program") in _keys
                )
                _telemetry.reset()
            if _problems:
                # an invalid export is BANKED, not raised: raising here would
                # be eaten by this block's swallow-all and the failure would
                # be indistinguishable from the leg never running
                record["trace_invalid"] = [str(p) for p in _problems[:3]]
            else:
                record["trace_async_pairs"] = len(_pairs)
                record["trace_pairs_with_program_key"] = _correlated
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["trace_timeline"] = repr(exc)

    # tracelens leg (ISSUE 13): post-hoc diagnosis of a verbose reduction- chain
    # window — attribution coverage (every wall-clock second of the window
    # bucketed, unattributed remainder banked as a monotone-quality metric), the
    # critical path's device-wait share, and the analyzer's own cost.
    try:
        if reduction_chain:
            from heat_tpu.core import tracelens as _tracelens

            with _telemetry.enabled("verbose"):
                _telemetry.reset()
                _reduction_chain_once()
                _reduction_chain_once()
                _tl_events = _telemetry.events()
                _telemetry.reset()
            _tl_t0 = time.perf_counter()
            _tl_ana = _tracelens.analyze(_tl_events)
            record["analyze_ms"] = round((time.perf_counter() - _tl_t0) * 1e3, 3)
            record["unattributed_time_pct"] = _tl_ana["attribution"]["unattributed_pct"]
            record["critical_path_sync_pct"] = _tl_ana["critical_path"]["sync_pct"]
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["tracelens"] = repr(exc)

    # guarded-dispatch overhead (core/resilience.py): the chain rate with the
    # fault harness ARMED but never firing (an exhausted times=0 spec), so every
    # injection-site check on the force/io hot paths is actually paid — "guards
    # on, no faults".
    try:
        if chain_fused:
            from heat_tpu.core import resilience as _resilience

            with _resilience.inject("bench.noop", times=0):
                chain_guarded = _chain_rate()
            record["guarded_dispatch_overhead_pct"] = round(
                100.0 * (1.0 - chain_guarded / chain_fused), 1
            )
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["guarded_dispatch"] = repr(exc)

    # memory-observability leg (core/memledger.py, ISSUE 8): the live-buffer
    # ledger's dispatch-rate cost (sampling hooks on vs off, telemetry on,
    # ALTERNATING best-of rounds — contract <= 5%, banked as
    # memory_ledger_overhead_pct), the workloads' high watermark
    # (peak_live_bytes), and the static per-host memory peak of a resplit
    # program (resplit_peak_bytes — the gauge ROADMAP 3's O(n/p) rewrite
    # will be asserted against).
    try:
        from heat_tpu.core import memledger as _memledger

        if chain_fused:
            with _telemetry.enabled():
                ledger_on = ledger_off = 0.0
                for _ in range(3):
                    _memledger.set_enabled(False)
                    try:
                        ledger_off = max(ledger_off, _chain_rate())
                    finally:
                        _memledger.set_enabled(True)
                    ledger_on = max(ledger_on, _chain_rate())
            if ledger_off:
                record["memory_ledger_overhead_pct"] = round(
                    100.0 * (1.0 - ledger_on / ledger_off), 1
                )
        _memledger.sample("bench", force=True)
        record["peak_live_bytes"] = int(_memledger.watermark()["bytes"])
        # the resplit program's static peak: force a 0->1 redistribution of
        # a split array and read the reshard program's XLA memory_analysis
        # (today's un-pad -> re-pad -> constraint path can sit at O(n);
        # arxiv 2112.01075's schedule should pull this toward O(n/p))
        rs = ht.ones((2048 * max(1, ht.get_comm().size), 32), split=0) + 0.0
        rs.resplit_(1)
        float(rs.larray[0, 0])  # force the reshard program
        _resplit_peak = None
        # estimate ONLY the reshard program(s): program_costs() over the whole
        # bench-warmed cache would pay one AOT compile per cached program
        for _sig, _info in list(_fusion._PROGRAM_INFO.items()):
            if "_reshard_op" not in _info["family"]:
                continue
            _cost = _fusion._COSTS.get(_info["key"])
            if _cost is None:
                _cost = _fusion._COSTS[_info["key"]] = _fusion._estimate_cost(_sig)
            _mem = _cost.get("memory") or {}
            if _mem.get("peak_bytes"):
                _resplit_peak = max(_resplit_peak or 0, int(_mem["peak_bytes"]))
        if _resplit_peak is not None:
            record["resplit_peak_bytes"] = _resplit_peak
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["memledger"] = repr(exc)

    # runtime-health leg (core/health_runtime.py, ISSUE 11): the flight recorder
    # + armed stall watchdog's dispatch-rate cost (ring appends + per-dispatch
    # guard arm/disarm, telemetry on — contract <= 2%, banked as
    # flight_overhead_pct) and the dispatch->done latency percentiles.
    try:
        from heat_tpu.core import health_runtime as _health

        if chain_fused:
            # the flight cost is per DISPATCH (~a few us of ring/guard
            # bookkeeping), so it is measured against a chain with enough
            # device work per dispatch to represent a real workload — on
            # the 2048-row micro-chain above the same microseconds read as
            # several percent of a ~100us chain and the gauge measures the
            # benchmark, not the recorder
            _hn = (262144 // comm.size) * comm.size
            _hk = jax.random.PRNGKey(7)
            _ha = ht.array(
                jax.device_put(
                    jax.random.normal(_hk, (_hn, 4), dtype=jnp.float32),
                    comm.sharding(2, 0),
                ),
                is_split=0,
            )
            _hb_arr = ht.array(
                jax.device_put(
                    jax.random.normal(_hk, (_hn, 4), dtype=jnp.float32),
                    comm.sharding(2, 0),
                ),
                is_split=0,
            )

            def _health_chain_once(sync_seam=False):
                c = ht.exp((_ha + _hb_arr) * 2.0) - _hb_arr
                d = ht.abs(c)
                h = (ht.sqrt(ht.abs(d + _ha)) / (d + 1.0)) * _hb_arr
                total = ht.sum(h)
                # the item() path crosses the blocking-sync seam (cid-joined
                # dispatch->done observation); .larray blocks inside jax,
                # invisible to the histograms — use it for pure rate legs
                return float(total) if sync_seam else float(total.larray)

            def _health_chain_rate():
                # one ~120ms window per sample: the box's scheduler noise
                # lives at the tens-of-ms scale, so short windows alias it
                # into the rate; the paired-round medians below absorb the
                # remaining outliers
                _health_chain_once()
                start = time.perf_counter()
                for _ in range(256):
                    _health_chain_once()
                return 2560.0 / (time.perf_counter() - start)

            def _median(xs):
                xs = sorted(xs)
                mid = len(xs) // 2
                return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])

            with _telemetry.enabled():
                # PAIRED rounds, median of per-round overheads: each round's
                # off/on windows are adjacent so they see the same ambient
                # machine noise, and the median across rounds is robust to
                # scheduler outliers in either direction (the effect is
                # small; the noise here is not)
                overheads = []
                for _ in range(9):
                    _prev_f = _health.set_flight(False)
                    _prev_w = _health.set_watchdog(enabled=False)
                    try:
                        f_off = _health_chain_rate()
                    finally:
                        _health.set_flight(_prev_f[0], _prev_f[1])
                        _health.set_watchdog(enabled=_prev_w[2])
                    if f_off:
                        overheads.append(
                            100.0 * (1.0 - _health_chain_rate() / f_off)
                        )
                if overheads:
                    record["flight_overhead_pct"] = round(_median(overheads), 1)
                # percentile source: chains that sync through the item()
                # seam so the dispatch->done clock actually closes
                for _ in range(10):
                    _health_chain_once(sync_seam=True)
            _hblock = _health.health_block(global_view=True)
            _disp = (_hblock.get("dispatch") or {}).get("*") or {}
            if _disp.get("count"):
                record["dispatch_p50_ms"] = round(1e3 * _disp["p50_s"], 3)
                record["dispatch_p99_ms"] = round(1e3 * _disp["p99_s"], 3)
            record["flight_events_captured"] = int(
                _health.flight_stats().get("events", 0)
            )
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["health"] = repr(exc)

    # numerics-lens leg (core/numlens.py, ISSUE 14): the lens's dispatch-rate
    # cost in SAMPLE mode (per-dispatch hook check + every-16th stats kernel,
    # shadow replay off for the rate gauge — contract <= 2%, banked as
    # numlens_overhead_pct, paired rounds + median like the flight gauge), the
    # shadow-replay drift ledger's worst ULP over a reorder-sensitive reduction
    # battery (drift_max_ulp — how far XLA's fusion/reassociation moved the
    # answer on this box), and the SDC canary's warm wall time (sdc_canary_ms).
    try:
        from heat_tpu.core import numlens as _numlens

        if chain_fused:
            _nn = (262144 // comm.size) * comm.size
            _nk = jax.random.PRNGKey(9)
            _na = ht.array(
                jax.device_put(
                    jax.random.normal(_nk, (_nn, 4), dtype=jnp.float32),
                    comm.sharding(2, 0),
                ),
                is_split=0,
            )
            _nb = ht.array(
                jax.device_put(
                    jax.random.normal(_nk, (_nn, 4), dtype=jnp.float32),
                    comm.sharding(2, 0),
                ),
                is_split=0,
            )

            def _numlens_chain_once():
                c = ht.exp((_na + _nb) * 2.0) - _nb
                d = ht.abs(c)
                h = (ht.sqrt(ht.abs(d + _na)) / (d + 1.0)) * _nb
                return float(ht.sum(h).larray)

            def _numlens_chain_rate():
                _numlens_chain_once()
                start = time.perf_counter()
                for _ in range(256):
                    _numlens_chain_once()
                return 2560.0 / (time.perf_counter() - start)

            def _nl_median(xs):
                xs = sorted(xs)
                mid = len(xs) // 2
                return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])

            _prev_shadow = _numlens._SHADOW_EVERY
            _prev_mode = _numlens.set_mode(0)
            with _telemetry.enabled():
                _numlens._SHADOW_EVERY = 0  # rate gauge: stats only, no replay
                overheads = []
                try:
                    for _ in range(9):
                        _numlens.set_mode(0)
                        n_off = _numlens_chain_rate()
                        _numlens.set_mode("sample")
                        if n_off:
                            overheads.append(
                                100.0 * (1.0 - _numlens_chain_rate() / n_off)
                            )
                finally:
                    _numlens._SHADOW_EVERY = _prev_shadow
                    _numlens.set_mode(_prev_mode)
            if overheads:
                record["numlens_overhead_pct"] = round(_nl_median(overheads), 1)
            # drift ledger: full mode, shadow every sampled dispatch, over a
            # reduction battery whose fused programs reassociate (split-axis
            # psums + tree reductions) — the eager replay orders them
            # differently, so max_ulp is the real fused-vs-eager drift
            _numlens.set_mode("full")
            _numlens._SHADOW_EVERY = 1
            try:
                _dr = ht.array(
                    jax.device_put(
                        jax.random.normal(_nk, (4096, 32), dtype=jnp.float32),
                        comm.sharding(2, 0),
                    ),
                    is_split=0,
                )
                float(ht.sum((_dr / 3.0).sum(axis=1)))
                float(ht.std(_dr * _dr + 1.0))
                float(ht.mean(ht.exp(_dr * 0.1) * _dr))
                record["drift_max_ulp"] = int(_numlens.drift_ledger()["max_ulp"])
                _numlens.run_canary()  # warm: compiles the per-device probe
                _canary = _numlens.run_canary()
                if _canary is not None:
                    record["sdc_canary_ms"] = round(_canary["ms"], 2)
            finally:
                _numlens._SHADOW_EVERY = _prev_shadow
                _numlens.set_mode(_prev_mode)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["numlens"] = repr(exc)

    # serving leg (core/serving.py, ISSUE 15): the multi-tenant session layer's
    # steady-state latency — p99 of one warm client vs p99 of 8 concurrent
    # session threads riding cross-session batching (serving_p99_ms_n1 /
    # serving_p99_ms_n8), the retrace count during the N=8 measured phase
    # (serving_steady_state_retraces — MUST stay 0: steady traffic never
    # recompiles), and the persistent program cache's cross-process proof
    # (serving_warm_start_compiles — a second process against the populated
    # cache dir MUST record 0 compiles).
    try:
        import tempfile as _sv_tempfile
        import threading as _sv_threading

        from heat_tpu.core import fusion as _sv_fusion
        from heat_tpu.core import serving as _serving

        if chain_fused and _sv_fusion.active():

            def _sv_chain(arr, k):
                # one shared code object: leaf dedup is by identity, so the
                # chain's signature is only reproducible when prebake and
                # clients build it through the SAME constants
                return float(ht.sum(arr * k + 1.0))

            def _sv_input(seed):
                _k = jax.random.PRNGKey(seed)
                _n = (4096 // comm.size) * comm.size
                return ht.array(
                    jax.device_put(
                        jax.random.normal(_k, (_n,), dtype=jnp.float32),
                        comm.sharding(1, 0),
                    ),
                    is_split=0,
                )

            def _sv_p99(lats):
                xs = sorted(lats)
                return 1e3 * xs[min(len(xs) - 1, int(0.99 * len(xs)))]

            _sv_rounds = 40
            with _serving.Session("bench-n1"):
                _sv_arr = _sv_input(70)
                for _i in range(5):
                    _sv_chain(_sv_arr, 1.0 + _i * 0.5)  # warm
                _sv_lats1 = []
                for _i in range(_sv_rounds):
                    _t0 = time.perf_counter()
                    _sv_chain(_sv_arr, 1.0 + _i * 0.5)
                    _sv_lats1.append(time.perf_counter() - _t0)
            record["serving_p99_ms_n1"] = round(_sv_p99(_sv_lats1), 3)

            # prebake every batch-size signature 1..8, then 8 client threads
            for _k in range(1, 9):
                _outs = [
                    ht.sum(_sv_input(80 + _j) * (1.0 + _j * 0.25) + 1.0)
                    for _j in range(_k)
                ]
                for _o in _outs:
                    float(_o)
            _sv_before = _sv_fusion.cache_stats()["compiles"]
            _sv_barrier = _sv_threading.Barrier(8)
            _sv_all = [[] for _ in range(8)]

            def _sv_client(idx):
                with _serving.Session(f"bench-n8-{idx}"):
                    arr = _sv_input(90 + idx)
                    _sv_barrier.wait(timeout=60)
                    for i in range(_sv_rounds):
                        t0 = time.perf_counter()
                        _sv_chain(arr, 1.0 + i * 0.25)
                        _sv_all[idx].append(time.perf_counter() - t0)

            _sv_threads = [
                _sv_threading.Thread(target=_sv_client, args=(i,)) for i in range(8)
            ]
            for _t in _sv_threads:
                _t.start()
            for _t in _sv_threads:
                _t.join()
            record["serving_p99_ms_n8"] = round(
                _sv_p99([v for lats in _sv_all for v in lats]), 3
            )
            record["serving_steady_state_retraces"] = int(
                _sv_fusion.cache_stats()["compiles"] - _sv_before
            )

            # cross-process warm start: cold process populates the cache dir,
            # warm process against it must record ZERO compiles
            _sv_script = (
                "import json, sys\n"
                "import heat_tpu as ht\n"
                "from heat_tpu.core import serving, fusion\n"
                "import numpy as np\n"
                "a = ht.array(np.arange(32, dtype=np.float32), split=0)\n"
                "float(ht.sum(a * 3.0 + 1.0))\n"
                "print(json.dumps(serving.cache_stats()))\n"
            )
            with _sv_tempfile.TemporaryDirectory() as _sv_dir:
                _sv_env = dict(os.environ)
                for _v in (
                    "HEAT_TPU_FUSION", "HEAT_TPU_FAULTS", "HEAT_TPU_NUMLENS",
                    "HEAT_TPU_MEMORY_BUDGET", "HEAT_TPU_TELEMETRY",
                ):
                    _sv_env.pop(_v, None)
                _sv_env["HEAT_TPU_PROGRAM_CACHE_DIR"] = _sv_dir
                _sv_env["JAX_PLATFORMS"] = "cpu"
                for _ in range(2):  # cold run, then warm run
                    _sv_proc = subprocess.run(
                        [sys.executable, "-c", _sv_script], env=_sv_env,
                        capture_output=True, text=True, timeout=240,
                    )
                    if _sv_proc.returncode != 0:
                        raise RuntimeError(
                            f"warm-start child failed: {_sv_proc.stderr[-300:]}"
                        )
                    _sv_out = json.loads(_sv_proc.stdout.strip().splitlines()[-1])
                record["serving_warm_start_compiles"] = int(_sv_out["compiles"])
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["serving"] = repr(exc)

    # ops-plane leg (core/opsplane.py, ISSUE 17): the live ops endpoint's
    # steady-state cost — dispatch rate with the sampler thread armed AND a
    # client scraping /metrics every 50ms vs fully disarmed
    # (ops_overhead_pct, paired rounds + median like the flight/numlens
    # gauges, contract <= 2%: pure module-state reads must be invisible to
    # the dispatch path), plus the wall time of one warm /metrics GET
    # against the live registry (metrics_scrape_ms — what a sidecar
    # Prometheus pays per scrape).
    try:
        import threading as _op_threading
        import urllib.request as _op_request

        from heat_tpu.core import opsplane as _opsplane

        if chain_fused:
            _op_n = (262144 // comm.size) * comm.size
            _op_k = jax.random.PRNGKey(11)
            _op_a = ht.array(
                jax.device_put(
                    jax.random.normal(_op_k, (_op_n, 4), dtype=jnp.float32),
                    comm.sharding(2, 0),
                ),
                is_split=0,
            )

            def _op_chain_once():
                c = ht.exp((_op_a + 1.0) * 2.0) - _op_a
                return float(ht.sum(ht.abs(c) / (ht.abs(_op_a) + 1.0)).larray)

            def _op_chain_rate():
                _op_chain_once()
                start = time.perf_counter()
                for _ in range(256):
                    _op_chain_once()
                return 2560.0 / (time.perf_counter() - start)

            def _op_median(xs):
                xs = sorted(xs)
                mid = len(xs) // 2
                return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])

            def _op_scraper(url, stop):
                while not stop.is_set():
                    try:
                        with _op_request.urlopen(url, timeout=5) as r:
                            r.read()
                    except Exception:  # noqa: BLE001 - scrape noise is fine
                        pass
                    stop.wait(0.05)

            overheads = []
            with _telemetry.enabled():
                for _ in range(9):
                    _opsplane.shutdown()
                    _op_off = _op_chain_rate()
                    _op_port = _opsplane.serve(port=0)
                    _op_stop = _op_threading.Event()
                    _op_thread = _op_threading.Thread(
                        target=_op_scraper,
                        args=(f"http://127.0.0.1:{_op_port}/metrics", _op_stop),
                    )
                    _op_thread.start()
                    try:
                        if _op_off:
                            overheads.append(
                                100.0 * (1.0 - _op_chain_rate() / _op_off)
                            )
                    finally:
                        _op_stop.set()
                        _op_thread.join(timeout=30)
            if overheads:
                record["ops_overhead_pct"] = round(_op_median(overheads), 1)
            # one warm /metrics GET against the registry the rounds above
            # populated — registry fold + exposition render + HTTP roundtrip
            _op_port = _opsplane.serve(port=0)
            _op_url = f"http://127.0.0.1:{_op_port}/metrics"
            with _op_request.urlopen(_op_url, timeout=10) as r:
                r.read()  # warm: first GET pays one-time route setup
            start = time.perf_counter()
            with _op_request.urlopen(_op_url, timeout=10) as r:
                r.read()
            record["metrics_scrape_ms"] = round(
                (time.perf_counter() - start) * 1e3, 2
            )
            _opsplane.shutdown()
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["opsplane"] = repr(exc)

    # autoscale leg (core/autoscale.py, ISSUE 18): the overload-protection loop
    # under a bursty 8-tenant mixed-tier storm with the controller armed and the
    # burn alert lit — p99 dispatch latency the 4 interactive sessions pay while
    # the 4 batch tiers are being shed (interactive_p99_ms_overload: the whole
    # point of tiered shedding is that this stays flat), the fraction of batch
    # dispatches refused while shedding was active (batch_shed_pct), and the
    # wall time from the last overload dispatch until the controller walks shed
    # back off and reports state "ok" (overload_recovery_ms: drain window +
    # hysteresis cooldown).
    try:
        import threading as _as_threading

        from heat_tpu.core import autoscale as _autoscale
        from heat_tpu.core import fusion as _as_fusion
        from heat_tpu.core import health_runtime as _as_health
        from heat_tpu.core import opsplane as _as_ops
        from heat_tpu.core import serving as _as_serving

        if chain_fused and _as_fusion.active():

            def _as_input(seed):
                _k = jax.random.PRNGKey(seed)
                _n = (4096 // comm.size) * comm.size
                return ht.array(
                    jax.device_put(
                        jax.random.normal(_k, (_n,), dtype=jnp.float32),
                        comm.sharding(1, 0),
                    ),
                    is_split=0,
                )

            def _as_p99(lats):
                xs = sorted(lats)
                return 1e3 * xs[min(len(xs) - 1, int(0.99 * len(xs)))]

            # warm the chain shape before the storm so the measured window
            # is dispatch latency, not first-call compiles
            with _as_serving.Session("as-warm"):
                _as_arr = _as_input(60)
                for _i in range(3):
                    float(ht.sum(_as_arr * (1.0 + _i) + 1.0))

            _as_prev_slo = _as_health.set_slo(dispatch_ms=1.0)
            _as_prev_burn = _as_ops.set_burn(
                target=0.9, fast_s=1.0, slow_s=4.0,
                threshold=1.0, min_samples=4,
            )
            try:
                # no mesh moves in-bench: shrink_after_s parks the shrink arm
                # so recovery measures the shed hysteresis, not a mesh reform
                _autoscale.arm(
                    interval_s=60.0, cooldown_s=0.3, shrink_after_s=3600.0,
                )
                for _ in range(16):  # light the burn alert deterministically
                    _as_health._slo_observe("dispatch", 0.05)
                _as_ops.sample()
                if _autoscale.poll() != "shed_on":
                    raise RuntimeError("controller refused to shed")

                _as_barrier = _as_threading.Barrier(8)
                _as_lats = [[] for _ in range(4)]
                _as_ifail = []
                _as_shed = [0]
                _as_tries = [0]
                _as_tally = _as_threading.Lock()

                def _as_interactive(idx):
                    with _as_serving.Session(
                        f"as-fg{idx}", tier="interactive", deadline_ms=100.0
                    ):
                        arr = _as_input(70 + idx)
                        _as_barrier.wait(timeout=60)
                        for i in range(8):
                            t0 = time.perf_counter()
                            try:
                                float(ht.sum(arr * (1.0 + i * 0.25) + 1.0))
                            except Exception as exc:  # noqa: BLE001
                                _as_ifail.append(exc)
                            _as_lats[idx].append(time.perf_counter() - t0)

                def _as_batch(idx):
                    with _as_serving.Session(f"as-bg{idx}", tier="batch"):
                        arr = _as_input(80 + idx)
                        _as_barrier.wait(timeout=60)
                        for i in range(8):
                            with _as_tally:
                                _as_tries[0] += 1
                            try:
                                float(ht.sum(arr * (1.0 + i * 0.25) + 1.0))
                            except _as_serving.ShedError:
                                with _as_tally:
                                    _as_shed[0] += 1

                _as_threads = [
                    _as_threading.Thread(target=_as_interactive, args=(i,))
                    for i in range(4)
                ] + [
                    _as_threading.Thread(target=_as_batch, args=(i,))
                    for i in range(4)
                ]
                for _t in _as_threads:
                    _t.start()
                for _t in _as_threads:
                    _t.join()
                if not _as_ifail:
                    record["interactive_p99_ms_overload"] = round(
                        _as_p99([v for lats in _as_lats for v in lats]), 3
                    )
                if _as_tries[0]:
                    record["batch_shed_pct"] = round(
                        100.0 * _as_shed[0] / _as_tries[0], 1
                    )

                # recovery: stop injecting breaches, let the fast window
                # drain, and time until the controller reports "ok" again
                _as_t0 = time.perf_counter()
                while (
                    _autoscale.stats().get("state") != "ok"
                    and time.perf_counter() - _as_t0 < 30.0
                ):
                    _as_ops.sample()
                    _autoscale.poll()
                    time.sleep(0.05)
                if _autoscale.stats().get("state") == "ok":
                    record["overload_recovery_ms"] = round(
                        (time.perf_counter() - _as_t0) * 1e3, 1
                    )
            finally:
                _autoscale.disarm(restore=True)
                _as_serving.shed(())
                _as_health.set_slo(
                    dispatch_ms=None
                    if _as_prev_slo.get("dispatch") is None
                    else _as_prev_slo["dispatch"] * 1e3
                )
                _as_ops.set_burn(**_as_prev_burn)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["autoscale"] = repr(exc)

    # multi-process runtime leg (core/multihost.py, ISSUE 19): REAL spawned
    # worker processes joined into one process-spanning mesh over loopback gloo,
    # driven by scripts/multiproc_trainer.py. Two gauges: multiproc_weak_scaling
    # — aggregate row throughput of the 2-process world over the 1-process world
    # with rows-per-process held constant (on one box the workers SHARE physical
    # cores, so per-process step rate halving is core contention, not runtime
    # cost; aggregate rows/s isolates what the runtime itself adds: dual
    # controllers, the gloo psum, lease beats — target >= 0.9x).
    # peer_loss_recovery_ms — SIGKILL one worker mid-step and time from the kill
    # to the reformed generation's first progress beacon (detection + drain +
    # respawn + re-init + checkpoint restore: the whole recovery bill).
    try:
        import glob as _mp_glob
        import shutil as _mp_shutil
        import subprocess as _mp_subprocess
        import tempfile as _mp_tempfile

        from heat_tpu.core import multihost as _multihost

        _mp_trainer = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "scripts", "multiproc_trainer.py",
        )

        def _mp_run(n, root, rows, steps, **kw):
            cmd = [
                sys.executable, _mp_trainer,
                "--steps", str(steps), "--checkpoint-every", "2",
                "--rows", str(rows), "--dim", "256",
                "--ckpt-dir", os.path.join(root, "ckpt"),
                "--out", os.path.join(root, "out"),
            ]
            return _multihost.spawn_local(
                n, cmd, timeout_s=180.0, stdout=_mp_subprocess.DEVNULL, **kw
            )

        def _mp_rate(root):
            best = 0.0
            for p in _mp_glob.glob(os.path.join(root, "out", "result-*.json")):
                with open(p) as fh:
                    d = json.load(fh)
                if d.get("status") == "done" and d.get("rate_steps_per_s"):
                    best = max(best, float(d["rate_steps_per_s"]))
            return best

        _mp_root = _mp_tempfile.mkdtemp(prefix="heat_tpu_bench_mp_")
        try:
            _MP_ROWS = 32768  # rows PER PROCESS (weak scaling)
            _mp_r1 = _mp_run(1, os.path.join(_mp_root, "w1"), _MP_ROWS, 12)
            _mp_r2 = _mp_run(2, os.path.join(_mp_root, "w2"), 2 * _MP_ROWS, 12)
            _mp_rate1 = _mp_rate(os.path.join(_mp_root, "w1"))
            _mp_rate2 = _mp_rate(os.path.join(_mp_root, "w2"))
            if not (_mp_r1["ok"] and _mp_r2["ok"] and _mp_rate1 > 0 and _mp_rate2 > 0):
                raise RuntimeError(
                    f"weak-scaling worlds did not complete: 1-proc ok={_mp_r1['ok']} "
                    f"rate={_mp_rate1}, 2-proc ok={_mp_r2['ok']} rate={_mp_rate2}"
                )
            record["multiproc_weak_scaling"] = round(
                (_mp_rate2 * 2.0 * _MP_ROWS) / (_mp_rate1 * _MP_ROWS), 2
            )
            # 200 steps: the launcher polls the progress beacon every 50 ms,
            # and a short run can finish before the kill lands (the survivor
            # then exits 0 and no reform happens)
            _mp_rk = _mp_run(
                2, os.path.join(_mp_root, "wkill"), 64, 200,
                max_reforms=1, kill={"rank": 1, "at_step": 3},
            )
            if not (_mp_rk["ok"] and _mp_rk["reforms"] == 1 and _mp_rk["t_kill"]):
                raise RuntimeError(
                    f"peer-loss world did not reform once: ok={_mp_rk['ok']} "
                    f"reforms={_mp_rk['reforms']}"
                )
            _mp_g1 = _mp_rk["generations"][1]
            if _mp_g1.get("t_first_progress"):
                record["peer_loss_recovery_ms"] = round(
                    (_mp_g1["t_first_progress"] - _mp_rk["t_kill"]) * 1e3, 1
                )
        finally:
            _mp_shutil.rmtree(_mp_root, ignore_errors=True)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["multiproc"] = repr(exc)

    # static-analysis leg (heat_tpu/analysis, ISSUE 7): the AST lint's wall time
    # over the library (the pre-commit budget a CI hook would pay) and the AOT
    # program auditor's finding count over the program cache the measurements
    # above just warmed — a nonzero audit_findings means a measured workload
    # replicated a split input or broke collective parity.
    try:
        from heat_tpu import analysis as _analysis

        _repo = os.path.dirname(os.path.abspath(__file__))
        start = time.perf_counter()
        _lint = _analysis.lint_paths([os.path.join(_repo, "heat_tpu")])
        record["lint_ms"] = round((time.perf_counter() - start) * 1e3, 1)
        record["lint_findings"] = sum(
            1 for f in _lint if not f.suppressed and not f.baselined
        )
        _audit = _analysis.audit_programs(top=24)
        record["audit_findings"] = len(_audit)
        if _audit:  # name the worst offender so the artifact is actionable
            record["audit_worst"] = _audit[0].as_dict()
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["analysis"] = repr(exc)

    # distribution-flow verifier leg (heat_tpu/analysis/dataflow, ISSUE 9):
    # the interprocedural abstract interpreter's wall time over the library +
    # examples (the pre-merge budget a CI verify hook pays), its active
    # finding count, and the static cost model's worst drift against
    # telemetry-observed collective bytes on the drift workloads at the live
    # mesh — the pin that keeps the op-table byte formulas honest against
    # the runtime's declared schedules.
    try:
        from heat_tpu.analysis import dataflow as _dataflow

        _repo = os.path.dirname(os.path.abspath(__file__))
        start = time.perf_counter()
        _vfind, _vstats = _dataflow.verify_paths(
            [os.path.join(_repo, "heat_tpu"), os.path.join(_repo, "examples")],
            mesh_size=ht.get_comm().size,
        )
        record["verify_ms"] = round((time.perf_counter() - start) * 1e3, 1)
        record["verify_findings"] = sum(
            1 for f in _vfind if not f.suppressed and not f.baselined
        )
        _drift = _dataflow.drift_report()
        _pcts = [
            rec["drift_pct"]
            for rec in _drift["workloads"].values()
            if rec["drift_pct"] is not None
        ]
        if _pcts and len(_pcts) == len(_drift["workloads"]):
            record["verify_bytes_drift_pct"] = round(max(_pcts), 1)
        if not all(rec["within_bound"] for rec in _drift["workloads"].values()):
            # withheld-rather-than-mislabelled: name the drifting workloads
            record["verify_drift_exceeded"] = sorted(
                name
                for name, rec in _drift["workloads"].items()
                if not rec["within_bound"]
            )
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["verify"] = repr(exc)

    # checkpoint subsystem (utils/checkpoint.py): manifest-based sharded save +
    # verified restore of a trainer-shaped pytree (a split DNDarray riding
    # per-shard files + replicated param/opt leaves + scalars).
    try:
        import shutil as _shutil
        import tempfile as _tempfile

        from heat_tpu.utils import checkpoint as _ckpt

        ck_tree = {
            "params": {
                "w": jnp.ones((512, 256), jnp.float32),
                "b": jnp.zeros((256,), jnp.float32),
            },
            "data": ht.ones((4096 * max(1, ht.get_comm().size), 64), split=0),
            "schedule": {"epoch": 3, "lr": 0.125},
        }
        ck_dir = _tempfile.mkdtemp(prefix="heat_tpu_bench_ckpt_")
        try:
            _ckpt.save_checkpoint(ck_dir, ck_tree, step=0, keep=2)  # warm/compile
            save_best = float("inf")
            for i in range(1, 4):
                start = time.perf_counter()
                manifest = _ckpt.save_checkpoint(ck_dir, ck_tree, step=i, keep=2)
                save_best = min(save_best, time.perf_counter() - start)
            with open(manifest) as _fh:
                _doc = json.load(_fh)
            record["checkpoint_bytes_written"] = sum(
                frag["bytes"] or 0
                for entry in _doc["leaves"]
                for frag in entry.get("files", ())
            )
            restore_best = float("inf")
            for _ in range(3):
                start = time.perf_counter()
                _ckpt.load_checkpoint(ck_dir, ck_tree)
                restore_best = min(restore_best, time.perf_counter() - start)
            record["checkpoint_save_ms"] = round(save_best * 1e3, 2)
            record["checkpoint_restore_ms"] = round(restore_best * 1e3, 2)
        finally:
            _shutil.rmtree(ck_dir, ignore_errors=True)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["checkpoint"] = repr(exc)

    # live-elasticity leg (core/elastic.py, ISSUE 12): a small DASO training run
    # with one injected elastic.preempt — the full detect -> drain -> commit ->
    # reform -> resume cycle, shedding half the mesh mid-run. Banks the
    # per-reform downtime (preempt_recovery_ms: preemption observed to training
    # resumed on the shrunk world, recompiles included — that IS the recovery
    # bill) and the replay bill (steps_replayed_per_preempt, bounded by
    # checkpoint_every).
    try:
        import math as _math
        import shutil as _shutil
        import tempfile as _tempfile

        from heat_tpu.core import communication as _communication
        from heat_tpu.core import elastic as _elastic
        from heat_tpu.core import resilience as _resilience

        if comm.size > 1:
            _lose = comm.size // 2
            # batch rows must tile BOTH worlds (full and survivors)
            _ebs = _math.lcm(comm.size, comm.size - _lose) * 2
            _erng = np.random.default_rng(11)
            _ebatches = [
                (
                    _erng.standard_normal((_ebs, 6)).astype(np.float32),
                    _erng.integers(0, 4, _ebs).astype(np.int32),
                )
                for _ in range(8)
            ]
            _daso = ht.optim.DASO(
                local_optimizer=ht.optim.SGD(0.05),
                total_epochs=4, warmup_epochs=0, cooldown_epochs=0,
            )
            _daso.add_model(ht.nn.MLP(features=(8, 4)), 0, _ebatches[0][0][:2])
            _edir = _tempfile.mkdtemp(prefix="heat_tpu_bench_elastic_")
            try:
                _elastic.reset()
                with _resilience.inject("elastic.preempt", every=5, times=1):
                    _eres = _elastic.fit(
                        _daso, _ebatches, directory=_edir,
                        checkpoint_every=3, max_reforms=1, lose=_lose,
                        install_signals=False,
                    )
                _est = _eres["elastic"]
                if _est["reforms"]:
                    record["preempt_recovery_ms"] = round(
                        _est["downtime_ms"] / _est["reforms"], 1
                    )
                    record["steps_replayed_per_preempt"] = round(
                        _est["steps_replayed"] / _est["reforms"], 2
                    )
            finally:
                _shutil.rmtree(_edir, ignore_errors=True)
                _elastic.reset()
                _communication.reform()  # the full world back for later legs
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["elastic"] = repr(exc)

    # lloyd two-point marginal: a 10x-iteration program's time spread cancels
    # the per-program fixed cost (dispatch + the host read), yielding the
    # steady-state rate. The 1.2x acceptance floor keeps timing noise from
    # inflating the marginal unboundedly (a near-zero delta would imply an
    # arbitrarily high rate); rejected marginals leave the wall rate as the
    # record's only number.
    try:
        _, _, _, shift10 = _primary_run(10 * ITERS)
        float(shift10)  # compile
        best10 = float("inf")
        for _ in range(2):
            start = time.perf_counter()
            _, _, _, shift10 = _primary_run(10 * ITERS)
            float(shift10)
            best10 = min(best10, time.perf_counter() - start)
        marg = _marginal_sec(best, best10, 9 * ITERS)
        if marg:
            record["lloyd_iters_per_sec_marginal"] = round(1.0 / marg, 3)
            record["lloyd_fixed_ms"] = round((best - ITERS * marg) * 1e3, 1)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["lloyd_marginal"] = repr(exc)

    # dispatch round-trip floor: every measurement above synchronized via one
    # host scalar read — a fixed cost that dominates small configs; measure
    # it so the artifact is interpretable on its own
    try:
        tiny = jax.jit(lambda a: a.sum())
        tv = jnp.ones(8)
        float(tiny(tv))
        rtt = float("inf")
        for _ in range(3):
            start = time.perf_counter()
            float(tiny(tv))
            rtt = min(rtt, time.perf_counter() - start)
        record["dispatch_rtt_ms"] = round(rtt * 1e3, 2)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["dispatch_floor"] = repr(exc)

    # two-point marginal rates for cdist and moments: K chained evaluations
    # inside ONE program vs 1, cancelling the fixed per-dispatch cost. Each
    # chain step feeds a value
    # derived from the previous step's FULL result back into the operand, so
    # XLA can neither hoist the body out of the loop nor dead-code-eliminate
    # any part of the computation. Billed bytes describe the program as
    # written: the distance tile fuses into the carry add (carry read+write =
    # 2n² per step, loop carries are HBM-resident), and the moments chain
    # pays the 2-pass mean/std reduction plus the operand-update read+write.
    def _two_point(run1, runk, steps):
        float(run1())  # compile
        float(runk())
        b1 = bk = float("inf")
        for _ in range(2):
            start = time.perf_counter()
            float(run1())
            b1 = min(b1, time.perf_counter() - start)
            start = time.perf_counter()
            float(runk())
            bk = min(bk, time.perf_counter() - start)
        # the shared acceptance rule (same floor as every other marginal)
        return _marginal_sec(b1, bk, steps - 1)

    try:
        def _cdist_chain(steps):
            @jax.jit
            def run(t):
                def body(i, carry):
                    t, acc = carry
                    acc = acc + _euclidian_fast(t, t)
                    return (t + acc[0, 0] * 1e-30, acc)

                nloc = t.shape[0]
                acc0 = jnp.zeros((nloc, nloc), t.dtype)
                _, acc = jax.lax.fori_loop(0, steps, body, (t, acc0))
                return jnp.sum(acc)  # every element live: no DCE

            return run

        r1, r4 = _cdist_chain(1), _cdist_chain(4)
        sec = _two_point(lambda: r1(x), lambda: r4(x), 4)
        if sec:
            step_bytes = 2 * cd_n * CDIST_F * 4 + 2 * cd_n * cd_n * 4
            record["cdist_gbps_per_chip_marginal"] = round(
                step_bytes / sec / 1e9 / comm.size, 2
            )
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["cdist_marginal"] = repr(exc)

    try:
        def _moments_chain(steps):
            @jax.jit
            def run(t):
                def body(i, carry):
                    t, acc = carry
                    acc = acc + t.mean() + t.std()
                    return (t + acc * 1e-30, acc)

                _, acc = jax.lax.fori_loop(
                    0, steps, body, (t, jnp.zeros((), t.dtype))
                )
                return acc

            return run

        # 2048 steps: a single mean+std over 4 MB is ~tens of µs on-device,
        # so a short chain cannot clear the acceptance floor against the
        # per-dispatch fixed cost
        m1, mN = _moments_chain(1), _moments_chain(2048)
        mop = mom.larray
        sec = _two_point(lambda: m1(mop), lambda: mN(mop), 2048)
        if sec:
            # 2 reduction passes (mean, then centered squares) + the chained
            # operand update's read+write = 4 passes over the 1M f32 operand
            record["moments_device_us_marginal"] = round(sec * 1e6, 2)
            record["moments_gbps_marginal"] = round(
                4 * MOMENTS_N * 4 / sec / 1e9, 2
            )
        # attribution of the measured wall: with collective-aware fusion the
        # mean+std chain is ONE multi-output program dispatch and one host
        # scalar read (the second read finds its value in flight) — one
        # dispatch round trip accounts for the fixed cost
        if record.get("dispatch_rtt_ms"):
            record["moments_rtt_share_pct"] = round(
                min(100.0, 100.0 * record["dispatch_rtt_ms"] / record["moments_ms_1M"]),
                1,
            )
            record["moments_attribution"] = (
                "wall = 1 host scalar read (mean+std fused into one "
                "multi-output program, psums inside) x dispatch RTT + device "
                "compute; device compute is moments_device_us_marginal"
            )
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["moments_marginal"] = repr(exc)

    # CholeskyQR2 (all-matmul tall-skinny QR, MXU-native) vs the Householder
    # TSQR the headline qr_tflops uses — measured side by side
    try:
        qq2, qr2 = ht.linalg.qr(qa, method="cholqr2")
        float(qr2.larray[0, 0])  # compile + sync
        cq_best = float("inf")
        for _ in range(2):
            start = time.perf_counter()
            qq2, qr2 = ht.linalg.qr(qa, method="cholqr2")
            float(qr2.larray[0, 0])
            cq_best = min(cq_best, time.perf_counter() - start)
        record["qr_cholqr2_tflops"] = round(2.0 * qr_m * QR_N * QR_N / cq_best / 1e12, 3)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["qr_cholqr2"] = repr(exc)

    # -- external comparison baselines (reference benchmarks/*/{numpy,torch}-*.py:
    # every tracked config gets a vs_* field, not just kmeans). All run on
    # the host CPU, size-capped. moments_vs_numpy is measured up front in the
    # moments section itself — alternating heat/numpy best-of rounds on the
    # same data, wall-vs-wall.

    try:
        import numpy as _np

        nb = min(cd_n, 8192)  # the nb x nb f32 result caps host memory
        xb_np = _np.asarray(rng.standard_normal((nb, CDIST_F)), dtype=_np.float32)

        def _np_cdist(a):  # quadratic expansion, the reference's fast form
            sq = (a * a).sum(1)
            d2 = sq[:, None] + sq[None, :] - 2.0 * (a @ a.T)
            return _np.sqrt(_np.maximum(d2, 0.0))

        _np_cdist(xb_np)
        cb_best = float("inf")
        for _ in range(3):
            start = time.perf_counter()
            _np_cdist(xb_np)
            cb_best = min(cb_best, time.perf_counter() - start)
        np_gbps = (2 * nb * CDIST_F * 4 + nb * nb * 4) / cb_best / 1e9
        record["cdist_numpy_gbps"] = round(np_gbps, 2)
        record["cdist_numpy_n"] = nb
        best_cd = record.get("cdist_gbps_per_chip_marginal") or record.get(
            "cdist_gbps_per_chip"
        )
        if best_cd:
            record["cdist_vs_numpy"] = round(best_cd / np_gbps, 2)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["cdist_numpy"] = repr(exc)

    try:
        import torch as _torch

        tm = min(qr_m, 1 << 17)  # torch CPU QR at 2M rows would blow the budget
        ta = _torch.randn(tm, QR_N)
        _torch.linalg.qr(ta, mode="reduced")
        tq_best = float("inf")
        for _ in range(2):
            start = time.perf_counter()
            _torch.linalg.qr(ta, mode="reduced")
            tq_best = min(tq_best, time.perf_counter() - start)
        t_tflops = 2.0 * tm * QR_N * QR_N / tq_best / 1e12
        record["qr_torch_tflops"] = round(t_tflops, 3)
        record["qr_torch_shape"] = [tm, QR_N]
        best_qr = record.get("qr_cholqr2_tflops") or record.get("qr_tflops")
        if best_qr:
            record["qr_vs_torch"] = round(best_qr / t_tflops, 2)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["qr_torch"] = repr(exc)

    # the non-default Lloyd path, measured side by side: when the fused
    # pallas kernel is the primary (TPU), the jnp oracle path rides along so
    # the artifact shows the product dispatch's margin (and would expose a
    # regression if the gate ever picked the slower path)
    try:
        if use_fused:
            _, _, _, jshift = _lloyd_run(data, centers, K, ITERS)
            float(jshift)  # compile
            jbest = float("inf")
            for _ in range(3):
                start = time.perf_counter()
                _, _, _, jshift = _lloyd_run(data, centers, K, ITERS)
                float(jshift)
                jbest = min(jbest, time.perf_counter() - start)
            record["lloyd_jnp_iters_per_sec"] = round(ITERS / jbest, 3)
            record["lloyd_fused_vs_jnp"] = round(iters_per_sec / (ITERS / jbest), 2)
    except Exception as exc:  # noqa: BLE001 - leg boundary: recorded, fails the run
        errors["lloyd_jnp"] = repr(exc)

    # annotate last so the roofline fields see the marginal-rate diagnostics
    annotate_roofline(record)
    record["errors"] = errors
    return record


def _torch_cpu_iters_per_sec(n: int, iters: int = 2) -> float:
    import torch

    torch.manual_seed(1)
    data = torch.randn(n, F)
    centers = torch.randn(K, F) * 3

    def step(data, centers):
        d2 = torch.cdist(data, centers) ** 2
        labels = d2.argmin(dim=1)
        onehot = torch.nn.functional.one_hot(labels, K).to(data.dtype)
        counts = onehot.sum(0)
        sums = onehot.T @ data
        return torch.where(counts[:, None] > 0, sums / counts.clamp(min=1.0)[:, None], centers)

    step(data, centers)  # warmup
    start = time.perf_counter()
    for _ in range(iters):
        centers = step(data, centers)
    return iters / (time.perf_counter() - start)


# ---- regression sentinel (ISSUE 11) ----------------------------------------
# ``bench.py --against BENCH_rXX.json`` compares a fresh record against a
# banked round artifact and exits nonzero on regression, so CI can gate on
# "did this PR slow the runtime down / bloat an overhead / add findings".
# With ``--record PATH`` the fresh side is read from a file instead of
# measured (pure file-vs-file compare, no jax import — the test-matrix
# smoke path).

#: higher-is-better throughput fields, compared only when both records came
#: from the same platform (a CPU-fallback number is not a TPU regression)
_RATE_KEYS = (
    "lloyd_tflops",
    "qr_tflops",
    "qr_cholqr2_tflops",
    "cdist_gbps_per_chip",
    "lloyd_hbm_gbps",
    "moments_hbm_gbps",
    "lloyd_iters_per_sec_marginal",
    "lasso_sweeps_per_sec",
)

#: overhead percentages with absolute ceilings (the subsystem contracts);
#: fresh regresses when it exceeds BOTH the ceiling and banked*1.5+2.0 —
#: the banked term absorbs measurement noise on already-near-zero values
_OVERHEAD_CEILINGS = {
    "telemetry_overhead_pct": 10.0,
    "flight_overhead_pct": 2.0,
    "memory_ledger_overhead_pct": 5.0,
    "guarded_dispatch_overhead_pct": 10.0,
    "numlens_overhead_pct": 2.0,
    "ops_overhead_pct": 2.0,
}

#: static-analysis counters that must never grow between rounds
_MONOTONE_KEYS = ("lint_findings", "audit_findings", "verify_findings")

#: tracelens costs/shares with absolute ceilings (analyzer wall time on the
#: reduction-chain window; critical-path device-wait share) — same
#: ``max(ceiling, banked*1.5+2.0)`` noise logic as the overhead gauges
_TRACELENS_CEILINGS = {
    "analyze_ms": 500.0,
    "critical_path_sync_pct": 90.0,
}

#: monotone-QUALITY metrics: attribution coverage must stay near-total. The
#: −30% rate slack deliberately does NOT apply — fresh regresses past BOTH
#: the absolute ceiling and banked + 2 points (the small additive term is
#: scheduler noise on sub-ms segments, not license to decay)
_QUALITY_CEILINGS = {
    "unattributed_time_pct": 5.0,
}

#: numerics-lens gauges with absolute ceilings: the shadow-replay drift of
#: the reduction battery (ULPs of fused-vs-eager reassociation — a compiler
#: property, stable per box; a jump means XLA started reordering harder or
#: the replay broke) and the SDC canary's warm wall time; same
#: ``max(ceiling, banked*1.5+2.0)`` noise logic as the overhead gauges
_NUMLENS_CEILINGS = {
    "drift_max_ulp": 4096.0,
    "sdc_canary_ms": 2000.0,
}

#: elastic-recovery costs with absolute ceilings (lower is better; the
#: recovery bill of one preempt -> drain -> reform -> resume cycle); fresh
#: regresses when it exceeds BOTH the ceiling and banked*1.5+2.0 — same
#: noise logic as the overhead gauges, in ms / steps instead of percent
_ELASTIC_CEILINGS = {
    "preempt_recovery_ms": 60000.0,
    "steps_replayed_per_preempt": 5.0,
}

#: serving latency gauges with absolute ceilings (p99 ms of one warm client
#: and of 8 concurrent session threads under cross-session batching); same
#: ``max(ceiling, banked*1.5+2.0)`` noise logic as the overhead gauges
_SERVING_CEILINGS = {
    "serving_p99_ms_n1": 10.0,
    "serving_p99_ms_n8": 25.0,
}

#: ops-plane scrape cost with an absolute ceiling (wall time of one warm
#: /metrics GET: registry fold + exposition render + local HTTP roundtrip);
#: same ``max(ceiling, banked*1.5+2.0)`` noise logic as the overhead gauges
_OPS_CEILINGS = {
    "metrics_scrape_ms": 250.0,
}

#: autoscale overload-loop ceilings: interactive p99 while batch tiers shed
#: (tiered shedding exists to keep this flat), wall time from drain start
#: until the controller reports "ok" (fast burn window + hysteresis
#: cooldown), and the batch shed fraction (a percentage, hard-capped at 100);
#: same ``max(ceiling, banked*1.5+2.0)`` noise logic as the overhead gauges
_AUTOSCALE_CEILINGS = {
    "interactive_p99_ms_overload": 50.0,
    "overload_recovery_ms": 30000.0,
    "batch_shed_pct": 100.0,
}

#: multi-process runtime gauges (core/multihost.py). Weak scaling is a
#: RATIO with an ABSOLUTE floor — aggregate row throughput of the
#: 2-process world over the 1-process world at fixed rows-per-process must
#: stay >= 0.9x (higher is better: the rate slack and overhead noise logic
#: both invert, and a hard target beats a banked-relative one here).
_MULTIPROC_FLOORS = {
    "multiproc_weak_scaling": 0.9,
}
#: ...and the recovery bill of one SIGKILL -> detect -> drain -> respawn ->
#: restore cycle in ms, with the elastic-style cost-ceiling noise logic
_MULTIPROC_CEILINGS = {
    "peer_loss_recovery_ms": 30000.0,
}

#: whole-algorithm estimator gauge (ISSUE 20): blocking syncs of one warm
#: reduce->matmul estimator iteration. The collective-DAG contract is <= 1
#: (the worker withholds the gauge rather than bank a broken value when
#: collectives are active); same ``max(ceiling, banked*1.5+2.0)`` noise
#: logic as the overhead gauges for collectives-off records
_ESTIMATOR_CEILINGS = {
    "estimator_syncs_per_iter": 1.0,
}

#: serving counters that must be EXACTLY zero — steady-state traffic never
#: recompiles and a warm process against a populated cache dir never
#: compiles; no noise slack applies (a retrace is a bug, not jitter)
_SERVING_ZERO_KEYS = (
    "serving_steady_state_retraces",
    "serving_warm_start_compiles",
)


def _load_record(path: str) -> dict:
    """A bench record from disk — unwraps the round-artifact envelope
    (``{"n", "cmd", "rc", "tail", "parsed"}``) down to the parsed record."""
    with open(path) as fh:
        doc = json.load(fh)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: no parseable bench record (parsed is null)")
    return doc


def compare_records(fresh: dict, banked: dict, slack: float = 0.30) -> dict:
    """Noise-robust fresh-vs-banked comparison.

    Returns ``{"regressions": [...], "notes": [...], "ok": bool}``. Rate
    metrics regress below ``(1 - slack) * banked`` and only on matching
    platform; the headline ``value`` additionally requires the same
    ``metric`` name (problem sizes differ across rounds). Overheads regress
    above ``max(ceiling, banked * 1.5 + 2.0)``; analysis finding counts must
    not increase. Keys absent on either side are notes, never failures —
    round artifacts legitimately differ in shape.
    """
    regressions, notes = [], []
    same_platform = fresh.get("platform") == banked.get("platform")
    if not same_platform:
        notes.append(
            f"platform mismatch (fresh={fresh.get('platform')} vs "
            f"banked={banked.get('platform')}): throughput comparison skipped"
        )

    def _num(rec, key):
        v = rec.get(key)
        return float(v) if isinstance(v, (int, float)) and not isinstance(v, bool) else None

    rate_keys = _RATE_KEYS
    if fresh.get("metric") == banked.get("metric"):
        rate_keys = rate_keys + ("value",)
    elif same_platform:
        notes.append("headline metric names differ: 'value' comparison skipped")
    for key in rate_keys if same_platform else ():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None or b is None or b <= 0:
            if b is not None and f is None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        floor = (1.0 - slack) * b
        if f < floor:
            regressions.append(
                f"{key}: fresh {f:g} < {floor:g} (banked {b:g} - {slack:.0%} slack)"
            )
    for key, ceiling in _OVERHEAD_CEILINGS.items():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None:
            if b is not None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        limit = ceiling if b is None else max(ceiling, b * 1.5 + 2.0)
        if f > limit:
            regressions.append(
                f"{key}: fresh {f:g}% > limit {limit:g}% "
                f"(ceiling {ceiling:g}%, banked {b if b is not None else 'n/a'})"
            )
    for key, ceiling in _ELASTIC_CEILINGS.items():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None:
            if b is not None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        limit = ceiling if b is None else max(ceiling, b * 1.5 + 2.0)
        if f > limit:
            regressions.append(
                f"{key}: fresh {f:g} > limit {limit:g} "
                f"(ceiling {ceiling:g}, banked {b if b is not None else 'n/a'})"
            )
    for key, ceiling in _NUMLENS_CEILINGS.items():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None:
            if b is not None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        limit = ceiling if b is None else max(ceiling, b * 1.5 + 2.0)
        if f > limit:
            regressions.append(
                f"{key}: fresh {f:g} > limit {limit:g} "
                f"(ceiling {ceiling:g}, banked {b if b is not None else 'n/a'})"
            )
    for key, ceiling in _TRACELENS_CEILINGS.items():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None:
            if b is not None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        limit = ceiling if b is None else max(ceiling, b * 1.5 + 2.0)
        if f > limit:
            regressions.append(
                f"{key}: fresh {f:g} > limit {limit:g} "
                f"(ceiling {ceiling:g}, banked {b if b is not None else 'n/a'})"
            )
    for key, ceiling in _OPS_CEILINGS.items():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None:
            if b is not None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        limit = ceiling if b is None else max(ceiling, b * 1.5 + 2.0)
        if f > limit:
            regressions.append(
                f"{key}: fresh {f:g} > limit {limit:g} "
                f"(ceiling {ceiling:g}, banked {b if b is not None else 'n/a'})"
            )
    for key, ceiling in _QUALITY_CEILINGS.items():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None:
            if b is not None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        limit = ceiling if b is None else max(ceiling, b + 2.0)
        if f > limit:
            regressions.append(
                f"{key}: fresh {f:g} > limit {limit:g} (monotone-quality metric: "
                f"ceiling {ceiling:g}, banked {b if b is not None else 'n/a'} "
                "+ 2pt noise — the rate slack does not apply)"
            )
    for key, ceiling in _SERVING_CEILINGS.items():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None:
            if b is not None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        limit = ceiling if b is None else max(ceiling, b * 1.5 + 2.0)
        if f > limit:
            regressions.append(
                f"{key}: fresh {f:g} > limit {limit:g} "
                f"(ceiling {ceiling:g}, banked {b if b is not None else 'n/a'})"
            )
    for key, ceiling in _AUTOSCALE_CEILINGS.items():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None:
            if b is not None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        limit = ceiling if b is None else max(ceiling, b * 1.5 + 2.0)
        if key == "batch_shed_pct":
            limit = min(limit, 100.0)  # a percentage cannot regress past 100
        if f > limit:
            regressions.append(
                f"{key}: fresh {f:g} > limit {limit:g} "
                f"(ceiling {ceiling:g}, banked {b if b is not None else 'n/a'})"
            )
    for key, floor in _MULTIPROC_FLOORS.items():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None:
            if b is not None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        if f < floor:
            regressions.append(
                f"{key}: fresh {f:g} < floor {floor:g} (absolute weak-scaling "
                f"target; banked {b if b is not None else 'n/a'})"
            )
    for key, ceiling in _MULTIPROC_CEILINGS.items():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None:
            if b is not None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        limit = ceiling if b is None else max(ceiling, b * 1.5 + 2.0)
        if f > limit:
            regressions.append(
                f"{key}: fresh {f:g} > limit {limit:g} "
                f"(ceiling {ceiling:g}, banked {b if b is not None else 'n/a'})"
            )
    for key, ceiling in _ESTIMATOR_CEILINGS.items():
        f, b = _num(fresh, key), _num(banked, key)
        if f is None:
            if b is not None:
                notes.append(f"{key}: banked={b:g} but missing from fresh record")
            continue
        limit = ceiling if b is None else max(ceiling, b * 1.5 + 2.0)
        if f > limit:
            regressions.append(
                f"{key}: fresh {f:g} > limit {limit:g} "
                f"(ceiling {ceiling:g}, banked {b if b is not None else 'n/a'})"
            )
    for key in _SERVING_ZERO_KEYS:
        f = _num(fresh, key)
        if f is not None and f != 0:
            regressions.append(
                f"{key}: fresh {f:g} != 0 (strict-zero serving invariant: "
                "steady state never retraces, warm starts never compile)"
            )
    for key in _MONOTONE_KEYS:
        f, b = _num(fresh, key), _num(banked, key)
        if f is None or b is None:
            continue
        if f > b:
            regressions.append(f"{key}: fresh {f:g} > banked {b:g} (must not grow)")
    return {"regressions": regressions, "notes": notes, "ok": not regressions}


def _sentinel_main(against_path: str, record_path=None) -> int:
    """The ``--against`` entry: obtain a fresh record (from ``--record`` or
    by measuring), compare, print a verdict line, and return the process
    exit code (0 clean / 1 regression or leg errors)."""
    banked = _load_record(against_path)
    if record_path is not None:
        fresh = _load_record(record_path)
    else:
        fresh = worker()
        print(json.dumps(fresh), flush=True)
    verdict = compare_records(fresh, banked)
    verdict["sentinel"] = "ok" if verdict["ok"] else "regression"
    verdict["against"] = os.path.basename(against_path)
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] and not fresh.get("errors") else 1


def main() -> int:
    args = sys.argv[1:]
    if "--against" in args:
        against = args[args.index("--against") + 1]
        rec_path = args[args.index("--record") + 1] if "--record" in args else None
        return _sentinel_main(against, rec_path)
    record = worker()
    print(json.dumps(record), flush=True)
    return 1 if record["errors"] else 0


if __name__ == "__main__":
    sys.exit(main())
