"""``python -m heat_tpu.telemetry`` — the observability CLI.

Pretty-prints and diffs ``ht.telemetry.report_json`` artifacts and validates
exported Chrome/Perfetto trace files without writing any analysis code:

.. code-block:: console

    $ python -m heat_tpu.telemetry show telemetry.json
    $ python -m heat_tpu.telemetry diff before.json after.json
    $ python -m heat_tpu.telemetry validate-trace trace.json
    $ python -m heat_tpu.telemetry analyze trace.json           # tracelens verdict
    $ python -m heat_tpu.telemetry analyze new.json --against old.json --json
    $ python -m heat_tpu.telemetry gaps /tmp/profile_dir       # device idle on ONE clock: host by span, launch + completion
    $ python -m heat_tpu.telemetry memory                 # live process ledger
    $ python -m heat_tpu.telemetry memory report.json --json
    $ python -m heat_tpu.telemetry health                 # flight/watchdog/SLO
    $ python -m heat_tpu.telemetry health flight_dump.json
    $ python -m heat_tpu.telemetry numerics               # stats/drift/SDC lens
    $ python -m heat_tpu.telemetry numerics report.json --json
    $ python -m heat_tpu.telemetry ops scrape --port 9464       # GET /metrics
    $ python -m heat_tpu.telemetry ops check --port 9464        # strict exposition + /healthz
    $ python -m heat_tpu.telemetry ops serve --port 9464        # serve this process

The implementation (and all state) lives in :mod:`heat_tpu.core.telemetry`;
this module is a thin proxy (``heat_tpu.telemetry.report`` etc. delegate
there live), existing so the CLI has a stable ``-m`` entry point.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional

import jax

from heat_tpu.core import telemetry as _core


def __getattr__(name):
    # live proxy: heat_tpu.telemetry.<anything> == heat_tpu.core.telemetry.<anything>
    return getattr(_core, name)


def __dir__():
    return sorted(set(globals()) | set(dir(_core)))


# ----------------------------------------------------------------------
# show
# ----------------------------------------------------------------------
def _load(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def _fmt_bytes(n) -> str:
    try:
        n = float(n)
    except (TypeError, ValueError):
        return str(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} TiB"  # pragma: no cover - loop always returns


def _show(doc: Dict[str, Any], out) -> None:
    print(f"mode: {doc.get('mode', '?')}  enabled: {doc.get('enabled')}", file=out)
    colls = doc.get("collectives") or {}
    if colls:
        print("collectives:", file=out)
        for op, rec in sorted(colls.items(), key=lambda kv: -kv[1].get("count", 0)):
            print(
                f"  {op:<20} x{rec.get('count', 0):<8} {_fmt_bytes(rec.get('bytes', 0))}",
                file=out,
            )
    fused = doc.get("fused_collectives") or {}
    if fused:
        print("fused collective nodes:", file=out)
        for op, n in sorted(fused.items(), key=lambda kv: -kv[1]):
            print(f"  {op:<28} x{n}", file=out)
    asyncf = doc.get("async_forcing") or {}
    if asyncf:
        print(
            f"async forcing: {asyncf.get('dispatches', 0)} dispatches "
            f"({asyncf.get('roots_dispatched', 0)} roots, "
            f"{asyncf.get('multi_root_batches', 0)} batched) / "
            f"{asyncf.get('blocking_total', 0)} blocking syncs "
            f"{asyncf.get('blocking_syncs', {})}",
            file=out,
        )
    forces = doc.get("forcing_points") or {}
    if forces:
        print("forcing points:", file=out)
        for trig, rec in sorted(forces.items(), key=lambda kv: -kv[1].get("count", 0)):
            print(
                f"  {trig:<12} x{rec.get('count', 0):<7} mean depth "
                f"{rec.get('mean_depth', 0)} (max {rec.get('max_depth', 0)}, "
                f"{rec.get('compiles', 0)} compiles)",
                file=out,
            )
    progs = (doc.get("programs") or {}).get("top") or []
    if progs:
        print(f"top programs (of {doc.get('programs', {}).get('cached', 0)} cached):", file=out)
        for rec in progs:
            line = (
                f"  {rec.get('key', '?'):<18} x{rec.get('dispatches', 0):<6} "
                f"{rec.get('family', '')[:60]}"
            )
            cost = rec.get("cost") or {}
            if cost.get("flops") is not None:
                line += f"  [{cost['flops']:.0f} flops, {_fmt_bytes(cost.get('bytes_accessed'))}]"
            print(line, file=out)
    spans = doc.get("spans") or {}
    if spans:
        print("spans:", file=out)
        for path, rec in sorted(spans.items(), key=lambda kv: -kv[1].get("total_s", 0.0)):
            print(
                f"  {path:<28} x{rec.get('calls', 0):<5} {rec.get('total_s', 0.0):.4f}s",
                file=out,
            )
    scopes = doc.get("scopes") or {}
    if scopes:
        print("scopes:", file=out)
        for path, rec in sorted(scopes.items()):
            blk = rec.get("async_forcing") or {}
            print(
                f"  {path:<24} x{rec.get('calls', 0):<4} {rec.get('wall_s', 0.0):.4f}s  "
                f"{blk.get('dispatches', 0)} dispatches / "
                f"{blk.get('blocking_total', 0)} syncs  "
                f"collectives {rec.get('collective_counts', {})}",
                file=out,
            )
    tl = doc.get("timeline") or {}
    if tl:
        dropped = tl.get("events_dropped", 0)
        note = f" ({dropped} DROPPED past cap {tl.get('cap')})" if dropped else ""
        print(f"timeline: {tl.get('events', 0)} events{note}", file=out)
    for key in ("degraded", "faults", "io_retries", "checkpoint", "nonfinite", "retraces"):
        block = doc.get(key) or {}
        if block:
            print(f"{key}: {json.dumps(block, sort_keys=True)}", file=out)


# ----------------------------------------------------------------------
# memory: live ledger + watermark + per-program static peaks
# ----------------------------------------------------------------------
def _memory_doc(report_path: Optional[str], top: int) -> Dict[str, Any]:
    """The memory picture to render: a saved report's ``memory``/``programs``
    blocks when a path is given, else THIS process's live ledger (brings up
    the mesh and computes per-program costs — the interactive debug mode)."""
    if report_path is not None:
        doc = _load(report_path)
        return {
            "source": report_path,
            "memory": doc.get("memory") or {},
            "programs": doc.get("programs") or {},
        }
    import heat_tpu as ht  # noqa: F401 - the mesh must exist for a live ledger

    ht.get_comm()
    from heat_tpu.core import fusion, memledger

    return {
        "source": "<live>",
        "memory": {
            "ledger": memledger.ledger(top=top),
            "watermark": memledger.watermark(),
            "budget": memledger.budget_info(resolve=True),  # mesh is up here
            "last_oom": memledger.last_oom(),
        },
        "programs": {
            "cached": len(fusion.cache_stats()["program_keys"]),
            "cost_errors": fusion.cost_error_count(),
            "top": [
                dict(rec, key=key)
                for key, rec in fusion.program_costs(top=top).items()
            ],
        },
    }


def _show_memory(doc: Dict[str, Any], out) -> None:
    mem = doc.get("memory") or {}
    led = mem.get("ledger") or {}
    print(f"memory ({doc.get('source', '?')}):", file=out)
    if led:
        print(
            f"  live: {_fmt_bytes(led.get('total_bytes', 0))} over "
            f"{led.get('buffers', led.get('buffer_count', 0))} buffer(s)",
            file=out,
        )
        for owner, nbytes in sorted(
            (led.get("by_owner") or {}).items(), key=lambda kv: -kv[1]
        ):
            print(f"    {owner:<14} {_fmt_bytes(nbytes)}", file=out)
        for rec in led.get("top") or []:
            print(
                f"    top: {_fmt_bytes(rec.get('nbytes', 0)):<10} "
                f"{rec.get('owner', '?'):<14} {rec.get('dtype', '?')}"
                f"{rec.get('shape', [])}",
                file=out,
            )
    wm = mem.get("watermark") or {}
    if wm:
        print(
            f"  watermark: {_fmt_bytes(wm.get('bytes', 0))} "
            f"(event {wm.get('event')}, {wm.get('samples', 0)} samples) "
            f"{wm.get('by_owner', {})}",
            file=out,
        )
    budget = mem.get("budget") or {}
    if budget.get("budget") is not None:
        print(
            f"  budget: {_fmt_bytes(budget.get('budget_bytes'))} "
            f"policy={budget.get('policy')} checks={budget.get('checks', 0)} "
            f"exceeded={budget.get('exceeded', 0)} drains={budget.get('drains', 0)}",
            file=out,
        )
    oom = mem.get("last_oom")
    if oom:
        print(
            f"  LAST OOM: program {oom.get('program')} ({oom.get('family')}) "
            f"static peak {_fmt_bytes(oom.get('static_peak_bytes'))}, live "
            f"{_fmt_bytes(oom.get('live_total_bytes', 0))} by owner "
            f"{oom.get('by_owner', {})}",
            file=out,
        )
    dev = mem.get("device") or {}
    for name, stats in sorted(dev.items()):
        line = ", ".join(f"{k}={_fmt_bytes(v)}" for k, v in sorted(stats.items()))
        print(f"  {name}: {line}", file=out)
    progs = doc.get("programs") or {}
    top_progs = progs.get("top") or []
    if top_progs:
        print(
            f"per-program static peaks (of {progs.get('cached', 0)} cached, "
            f"{progs.get('cost_errors', 0)} cost error(s)):",
            file=out,
        )
        for rec in top_progs:
            memrec = (rec.get("cost") or rec).get("memory") or {}
            peak = memrec.get("peak_bytes")
            line = (
                f"  {rec.get('key', '?'):<18} x{rec.get('dispatches', 0):<6} "
                f"{str(rec.get('family', ''))[:48]:<48} "
            )
            if peak is not None:
                line += (
                    f"peak {_fmt_bytes(peak)} (args {_fmt_bytes(memrec.get('argument_bytes', 0))}"
                    f" + out {_fmt_bytes(memrec.get('output_bytes', 0))}"
                    f" + temp {_fmt_bytes(memrec.get('temp_bytes', 0))})"
                )
            else:
                line += "peak n/a"
            print(line, file=out)


# ----------------------------------------------------------------------
# health: flight recorder + watchdog + latency/SLO picture
# ----------------------------------------------------------------------
def _health_doc(report_path: Optional[str]) -> Dict[str, Any]:
    """The health picture to render: a saved report's (or flight-dump
    bundle's) ``health`` block when a path is given, else THIS process's
    live block — pure module state, no mesh bring-up (the never-initialize
    contract: asking for health must not pin a backend)."""
    if report_path is not None:
        doc = _load(report_path)
        blk = doc.get("health") or {}
        if not blk and "watchdog" in doc:  # a bare bundle without the block
            blk = {"watchdog": doc.get("watchdog") or {}}
        return {"source": report_path, "health": blk, "stalls": doc.get("stalls") or []}
    from heat_tpu.core import health_runtime

    return {
        "source": "<live>",
        "health": health_runtime.health_block(global_view=True),
        "stalls": health_runtime.stalls(),
    }


def _ms(v) -> str:
    try:
        return f"{float(v) * 1e3:.2f}ms"
    except (TypeError, ValueError):
        return "?"


def _show_health(doc: Dict[str, Any], out) -> None:
    blk = doc.get("health") or {}
    print(f"health ({doc.get('source', '?')}):", file=out)
    fl = blk.get("flight") or {}
    if fl:
        state = "armed" if fl.get("enabled") else "DISARMED"
        dropped = f", {fl['dropped']} dropped" if fl.get("dropped") else ""
        last = f"  last dump: {fl['last_dump']}" if fl.get("last_dump") else ""
        print(
            f"  flight: {state}, {fl.get('events', 0)}/{fl.get('cap', 0)} "
            f"events{dropped}, {fl.get('dumps', 0)} dump(s){last}",
            file=out,
        )
    wd = blk.get("watchdog") or {}
    if wd:
        state = "armed" if wd.get("enabled") else "DISARMED"
        print(
            f"  watchdog: {state}, deadline {wd.get('deadline_ms', 0)}ms "
            f"policy={wd.get('policy')} arms={wd.get('arms', 0)} "
            f"trips={wd.get('trips', 0)}",
            file=out,
        )
    for st in (doc.get("stalls") or [])[-3:]:
        print(
            f"  STALL: {st.get('site')} waited {st.get('waited_s')}s "
            f"(deadline {st.get('deadline_s')}s) program={st.get('program')} "
            f"pending={[r.get('cid') for r in st.get('pending_roots') or []]}",
            file=out,
        )
    for metric, title in (
        ("sync", "blocking-sync host wait"),
        ("dispatch", "dispatch→done"),
        ("compile", "compile time"),
    ):
        table = blk.get(metric) or {}
        rows = [(k, r) for k, r in table.items() if r.get("count")]
        if not rows:
            continue
        print(f"  {title}:", file=out)
        rows.sort(key=lambda kv: (kv[0] != "*", -kv[1].get("count", 0)))
        for key, rec in rows[:12]:
            print(
                f"    {key:<20} x{rec.get('count', 0):<6} "
                f"p50 {_ms(rec.get('p50_s'))}  p90 {_ms(rec.get('p90_s'))}  "
                f"p99 {_ms(rec.get('p99_s'))}  max {_ms(rec.get('max_s'))}",
                file=out,
            )
    slo = blk.get("slo") or {}
    for metric in ("sync", "dispatch", "compile"):
        rec = slo.get(metric) or {}
        if rec.get("limit_ms") is None:
            continue
        ratio = rec.get("ok_ratio")
        print(
            f"  SLO {metric}: limit {rec['limit_ms']}ms, {rec.get('recent', 0)} in "
            f"window, {rec.get('window_breaches', 0)} breach(es)"
            + (f", ok_ratio {ratio}" if ratio is not None else "")
            + f", {rec.get('breaches_total', 0)} total",
            file=out,
        )


# ----------------------------------------------------------------------
# diff
# ----------------------------------------------------------------------
def _flatten_numeric(doc, prefix="") -> Dict[str, float]:
    out: Dict[str, float] = {}
    if isinstance(doc, dict):
        for k, v in doc.items():
            out.update(_flatten_numeric(v, f"{prefix}{k}/" if prefix else f"{k}/"))
    elif isinstance(doc, bool) or doc is None or isinstance(doc, str):
        pass
    elif isinstance(doc, (int, float)):
        out[prefix.rstrip("/")] = float(doc)
    return out


def _diff(a: Dict[str, Any], b: Dict[str, Any], out, top: int = 40) -> int:
    """Print per-counter deltas b - a, largest absolute change first.
    Returns the number of changed counters."""
    fa, fb = _flatten_numeric(a), _flatten_numeric(b)
    deltas = []
    for key in sorted(set(fa) | set(fb)):
        if key.startswith("events/") or key.endswith("/ts"):
            continue  # raw timeline entries are not counters
        va, vb = fa.get(key, 0.0), fb.get(key, 0.0)
        if va != vb:
            deltas.append((abs(vb - va), key, va, vb))
    deltas.sort(reverse=True)
    for _, key, va, vb in deltas[:top]:
        sign = "+" if vb >= va else ""
        print(f"  {key:<64} {va:g} -> {vb:g} ({sign}{vb - va:g})", file=out)
    if len(deltas) > top:
        print(f"  ... and {len(deltas) - top} more changed counters", file=out)
    if not deltas:
        print("  no counter differences", file=out)
    return len(deltas)


# ----------------------------------------------------------------------
# entry point
# ----------------------------------------------------------------------
# ----------------------------------------------------------------------
# numerics: tensor stats + drift ledger + SDC canary + training streams
# ----------------------------------------------------------------------
def _sessions_doc(report_path: Optional[str]) -> Dict[str, Any]:
    """The serving picture to render: a saved report's ``serving`` block
    when a path is given, else THIS process's live block — pure module
    state, no mesh bring-up (the same never-initialize contract as
    ``health``/``numerics``)."""
    if report_path is not None:
        doc = _load(report_path)
        return {"source": report_path, "serving": doc.get("serving") or {}}
    from heat_tpu.core import serving

    return {"source": "<live>", "serving": serving.sessions_block()}


def _show_sessions(doc: Dict[str, Any], out) -> None:
    blk = doc.get("serving") or {}
    print(f"serving ({doc.get('source', '?')}):", file=out)
    sessions = blk.get("sessions") or []
    if not sessions:
        print("  no sessions recorded", file=out)
    adm = blk.get("admission") or {}
    gbl = adm.get("global")
    if gbl:
        print(
            f"  admission: policy {adm.get('policy', 'wait')}, global bucket "
            f"{gbl.get('rate')}/s burst {gbl.get('burst')} — "
            f"{gbl.get('admitted', 0)} admitted, {gbl.get('refused', 0)} "
            f"refused, {gbl.get('waited_s', 0)}s waited",
            file=out,
        )
    cache = blk.get("cache") or {}
    if cache.get("persistent_dir"):
        print(
            f"  persistent cache: {cache['persistent_dir']} "
            f"({cache.get('index_keys', 0)} indexed keys, "
            f"{cache.get('disk_hits', 0)} disk hits)",
            file=out,
        )
    for sess in sessions:
        st = sess.get("stats") or {}
        state = "active" if sess.get("active") else "exited"
        print(
            f"  {sess.get('name', '?')} ({state}): "
            f"{st.get('dispatches', 0)} dispatches "
            f"({st.get('roots', 0)} roots, {st.get('compiles', 0)} compiles), "
            f"errstate {sess.get('errstate', 'inherit')}, "
            f"numlens {sess.get('numlens', 'inherit')}",
            file=out,
        )
        trouble = {
            k: st.get(k, 0)
            for k in ("degraded", "quarantine_hits", "mem_refused",
                      "admission_refused", "admission_waits")
            if st.get(k)
        }
        if trouble:
            print(f"    incidents: {trouble}", file=out)
        if sess.get("quarantine"):
            print(f"    quarantine view: {sess['quarantine']}", file=out)
        bucket = sess.get("bucket")
        if bucket:
            print(
                f"    bucket: {bucket.get('rate')}/s burst {bucket.get('burst')} "
                f"— {bucket.get('admitted', 0)} admitted, "
                f"{bucket.get('refused', 0)} refused",
                file=out,
            )


# ----------------------------------------------------------------------
# ops: scrape / check / serve against a live ops-plane endpoint
# ----------------------------------------------------------------------
def _ops_base(args) -> str:
    if args.url:
        return args.url.rstrip("/")
    if args.port is None:
        raise SystemExit("ops: pass --url or --port to reach a live endpoint")
    return f"http://{args.host}:{int(args.port)}"


def _ops_get(url: str, timeout: float):
    """One GET: ``(status_code, body_text)`` — an HTTP error status is a
    result to report, not an exception."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read().decode("utf-8", "replace")
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read().decode("utf-8", "replace")


def _ops_scrape(args, out) -> int:
    url = _ops_base(args) + args.path
    try:
        code, body = _ops_get(url, args.timeout)
    except OSError as exc:
        print(f"ERROR: {url}: {exc}", file=out)
        return 1
    print(body, end="" if body.endswith("\n") else "\n", file=out)
    return 0 if code == 200 else 1


def _ops_check(args, out) -> int:
    """The strict endpoint check the test matrix runs mid-traffic: the
    ``/metrics`` exposition must validate (types, HELP lines, no duplicate
    samples, schema'd names only) and ``/healthz`` must answer 200."""
    from heat_tpu.core import opsplane

    base = _ops_base(args)
    rc = 0
    try:
        code, text = _ops_get(base + "/metrics", args.timeout)
    except OSError as exc:
        print(f"ERROR: {base}/metrics: {exc}", file=out)
        return 1
    if code != 200:
        print(f"FAIL: /metrics answered {code}", file=out)
        return 1
    problems = opsplane.validate_exposition(text)
    names = {
        line.split("{")[0].split()[0]
        for line in text.splitlines()
        if line and not line.startswith("#")
    }
    known = set(opsplane.SCHEMA)
    for mtype in ("histogram",):
        for name, spec in opsplane.SCHEMA.items():
            if spec[0] == mtype:
                known.update({name + s for s in ("_bucket", "_sum", "_count")})
    for name in sorted(names - known):
        problems.append(f"unschema'd metric name {name!r} (doc/metrics_schema.json)")
    if problems:
        for p in problems[:20]:
            print(f"INVALID: {p}", file=out)
        rc = 1
    else:
        samples = sum(
            1 for ln in text.splitlines() if ln and not ln.startswith("#")
        )
        print(
            f"OK: /metrics parses as Prometheus exposition "
            f"({len(names)} families, {samples} samples)",
            file=out,
        )
    try:
        code, body = _ops_get(base + "/healthz", args.timeout)
    except OSError as exc:
        print(f"ERROR: {base}/healthz: {exc}", file=out)
        return 1
    if code == 200:
        print("OK: /healthz answers 200", file=out)
    else:
        print(f"FAIL: /healthz answered {code}: {body.strip()[:200]}", file=out)
        rc = 1
    return rc


def _ops_serve(args, out) -> int:
    """Arm THIS process's ops plane and block — the sidecar-inspection
    entry (live module state; an idle CLI process exports mostly zeros,
    which is still a scrape target for wiring checks)."""
    import time as _time

    from heat_tpu.core import opsplane

    try:
        port = opsplane.serve(port=args.port, host=args.host)
    except ValueError as exc:
        print(f"ERROR: {exc}", file=out)
        return 2
    print(f"ops plane listening on http://{args.host}:{port}", file=out, flush=True)
    try:
        while True:
            _time.sleep(3600)
    except KeyboardInterrupt:
        opsplane.shutdown()
    return 0


def _numerics_doc(report_path: Optional[str]) -> Dict[str, Any]:
    """The numerics picture to render: a saved report's (or flight-dump
    bundle's) ``numerics`` block when a path is given, else THIS process's
    live block — pure module state, no mesh bring-up (the same
    never-initialize contract as ``health``)."""
    if report_path is not None:
        doc = _load(report_path)
        return {"source": report_path, "numerics": doc.get("numerics") or {}}
    from heat_tpu.core import numlens

    return {"source": "<live>", "numerics": numlens.numerics_block()}


def _show_numerics(doc: Dict[str, Any], out) -> None:
    blk = doc.get("numerics") or {}
    print(f"numerics ({doc.get('source', '?')}):", file=out)
    print(
        f"  lens: {blk.get('mode', 'off')}, sampled "
        f"{blk.get('dispatches_sampled', 0)}/{blk.get('dispatches_seen', 0)} "
        f"dispatches (every {blk.get('sample_every', '?')})",
        file=out,
    )
    stats = blk.get("tensor_stats") or {}
    if stats:
        print("  tensor stats:", file=out)
        rows = sorted(stats.items(), key=lambda kv: -kv[1].get("samples", 0))
        for key, rec in rows[:8]:
            for i, rr in sorted((rec.get("roots") or {}).items()):
                flags = []
                if rr.get("nonfinite"):
                    flags.append(f"NONFINITE x{rr['nonfinite']}")
                if rr.get("subnormal"):
                    flags.append(f"subnormal {rr.get('subnormal_pct', 0)}%")
                if rr.get("edge_high"):
                    flags.append(f"edge_high {rr['edge_high']}")
                print(
                    f"    {key}[{i}] {rr.get('dtype')}  rms {rr.get('rms', 0):.4g}  "
                    f"absmax {rr.get('absmax', 0):.4g}  x{rr.get('samples', 0)}"
                    + ("  " + " ".join(flags) if flags else ""),
                    file=out,
                )
    drift = blk.get("drift") or {}
    progs = drift.get("programs") or {}
    if progs:
        print(
            f"  drift ledger (max {drift.get('max_ulp', 0)} ULP, worst family "
            f"{drift.get('worst_family')}):",
            file=out,
        )
        for key, rec in sorted(progs.items(), key=lambda kv: -kv[1].get("max_ulp", 0))[:8]:
            print(
                f"    {key}  p50 {rec.get('p50_ulp', 0)} ULP  max "
                f"{rec.get('max_ulp', 0)} ULP  x{rec.get('samples', 0)}",
                file=out,
            )
    canary = blk.get("canary") or {}
    if canary.get("runs"):
        sick = canary.get("last_sick") or []
        print(
            f"  sdc canary: {canary['runs']} run(s) over "
            f"{canary.get('devices', '?')} device(s), "
            f"{canary.get('mismatches', 0)} mismatch(es), last "
            f"{canary.get('last_ms', '?')}ms"
            + (f"  SICK: {', '.join(sick)}" if sick else ""),
            file=out,
        )
    for tag, rec in (blk.get("training") or {}).items():
        extras = []
        if rec.get("overflows"):
            extras.append(f"OVERFLOWS x{rec['overflows']}")
        if rec.get("plateau"):
            extras.append("PLATEAU")
        ratio = rec.get("last_update_ratio")
        print(
            f"  train[{tag}]: {rec.get('steps', 0)} step(s), loss "
            f"{rec.get('last_loss')}"
            + (f", update_ratio {ratio:.3g}" if ratio is not None else "")
            + ("  " + " ".join(extras) if extras else ""),
            file=out,
        )
    for f in (blk.get("findings") or [])[-5:]:
        print(f"  {f.get('severity', '?').upper()}: {f.get('message')}", file=out)


# ----------------------------------------------------------------------
# gaps: the device's idle time on ONE clock, from a profiler trace alone
# ----------------------------------------------------------------------
_ENQUEUE, _DONE = "DoEnqueueProgram", "tpu::System::Execute=>Done"  # the runtime's own host events
_TRIP = ("host_before_us", "launch_plus_completion_us", "device_us", "host_after_us")  # a round trip


def _innermost(spans) -> List[tuple]:
    """Nested ``(start, end, name)`` spans as sorted disjoint pieces, each
    named by the innermost span over it (of spans that overlap without
    nesting, as two forcing threads' do: by the one opened last)."""
    out, stack, cursor = [], [], 0.0  # stack of (end, name), outermost first
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, inner = stack.pop()
            out.append((cursor, end, inner))
            cursor = max(cursor, end)
        if stack:
            out.append((cursor, s, stack[-1][1]))
        cursor = s
        stack.append((e, name))
    for end, inner in reversed(stack):
        out.append((cursor, end, inner))
        cursor = max(cursor, end)
    return [p for p in out if p[1] > p[0]]


def _overlap(pieces, gaps) -> Dict[str, float]:
    """Per name, the length of the sorted disjoint ``pieces`` inside the
    sorted disjoint ``gaps``."""
    by: Dict[str, float] = {}
    j = 0
    for s, e, name in pieces:
        while j < len(gaps) and gaps[j][1] <= s:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < e:
            by[name] = by.get(name, 0.0) + min(e, gaps[k][1]) - max(s, gaps[k][0])
            k += 1
    return by


def _shift_window(modules, enqueues, dones) -> Dict[str, Any]:
    """The shifts that put one device's ``modules`` (its clock) on the clock of
    the runtime's host events, sorted ``(start, end)`` seconds, the k-th with
    the k-th: no program starts before its enqueue ended, none ends after its
    ``Done`` began. An empty window: the shift moved inside the session (or they
    do not pair); ``by_part_us``, the same window over each of up to ten equal
    parts of the programs (32 at least in each), shows where."""
    n = min(len(modules), len(enqueues), len(dones))
    lows = [1e6 * (q[1] - m[0]) for q, m in zip(enqueues, modules[:n])]
    highs = [1e6 * (d[0] - m[1]) for d, m in zip(dones, modules[:n])]
    k = max(1, min(10, n // 32))
    cuts = [t * n // k for t in range(k + 1)]
    parts = [[max(lows[a:b], default=0.0), min(highs[a:b], default=0.0)] for a, b in zip(cuts, cuts[1:])]
    lo, hi, unmatched = max(lows, default=0.0), min(highs, default=0.0), max(len(modules), len(enqueues), len(dones)) - n
    return dict(programs=n, unmatched=unmatched, shift_lo_us=lo, shift_hi_us=hi, consistent=lo <= hi, by_part_us=parts)


def _cut(gaps, modules, enqueues, dones, spans, shifts=((0.0, 0.0),)) -> Dict[str, Any]:
    """One device's idle ``gaps`` (sorted, its clock) cut on the host's clock.
    A gap lies between a program P that ended and a program N that starts (the
    k-th module, enqueue and ``Done`` belong together). From P's ``Done`` start
    to N's enqueue end the time is the HOST's and needs no shift: it goes to
    the innermost of ``spans`` over it, ``outside`` under none (the caller). The
    rest is P's ``completion`` (last operation's end -> ``Done``) plus N's
    ``launch`` (enqueue's end -> first operation): the sum is exact, each a pair,
    at the low and the high shift of N's part of the session (``shifts``:
    :func:`_shift_window`'s ``by_part_us``). N enqueued before P was done:
    ``queued``. Between one program's operations: ``inside_program``. Where P or
    N is missing (the window's edges, a CPU backend) the gap's own end stands
    for its event, the host's part stays inside the gap and no pair grows."""
    starts, host, launch, completion = [m[0] for m in modules], [], [0.0, 0.0], [0.0, 0.0]
    out = dict(launch_plus_completion_s=0.0, launch_s=launch, completion_s=completion, queued_s=0.0, inside_program_s=0.0)
    for gs, ge in gaps:
        p, nx = bisect.bisect_right(starts, gs) - 1, bisect.bisect_right(starts, ge) - 1
        both = nx > p >= 0
        h0 = dones[p][0] if p >= 0 else gs
        h1 = max(h0, enqueues[nx][1] if nx > p else ge)
        if p >= 0 and ge <= modules[p][1]:
            out["inside_program_s"] += ge - gs
        elif both and h1 <= h0:
            out["queued_s"] += ge - gs
        else:
            h1 = h1 if both else min(h1, h0 + ge - gs)
            host.append((h0, h1))
            out["launch_plus_completion_s"] += (ge - gs) - (h1 - h0)
            for i in (0, 1) if both else ():
                shift = 1e-6 * shifts[nx * len(shifts) // len(starts)][i]
                launch[i] += ge + shift - h1
                completion[i] += h0 - gs - shift
    by_span = _overlap(_innermost(spans), host)
    by_span["outside"] = sum(e - s for s, e in host) - sum(by_span.values())
    return {**out, "idle_by_span_s": dict(sorted(by_span.items(), key=lambda kv: -kv[1]))}


def _round_trips(modules, enqueues, dones, forces) -> Dict[str, Any]:
    """Per program and over ``all``: the count and ``[mean, p95]`` microseconds of
    the host time before the enqueue (from the last ``Done``, or from where the
    program's span opened if later), ``launch + completion`` (enqueue end ->
    ``Done`` start less the device's time: exact whatever the shift; not of a
    program enqueued before the last was done), the device's time, the host time
    after ``Done`` (until the next span opens). A program goes by the ``program``
    of the ``heat.force`` (``forces``: sorted ``(start, end, program)``) opened last
    before its enqueue ended and after the one before, else ``-``."""
    groups, opens, row = {}, [f[0] for f in forces], None
    for k, (m, q, d) in enumerate(zip(modules, enqueues, dones)):
        i = bisect.bisect_right(opens, q[1]) - 1
        force = forces[i] if i >= 0 and (not k or forces[i][0] > enqueues[k - 1][1]) else None
        free = dones[k - 1][0] if k else force[0] if force else q[0]  # the host has the last result
        opened = max(free, force[0]) if force else free
        if row:
            row[3] = opened - free
        row = [max(0.0, q[1] - opened), d[0] - q[1] - (m[1] - m[0]) if q[1] >= free else None, m[1] - m[0], 0.0]
        for key in ("all", force[2] if force else "-"):
            groups.setdefault(key, []).append(row)

    def stat(values):
        values = sorted(v for v in values if v is not None) or [0.0]
        return [1e6 * sum(values) / len(values), 1e6 * values[-(-95 * len(values) // 100) - 1]]

    return {k: {"n": len(rs), **{nm: stat(r[j] for r in rs) for j, nm in enumerate(_TRIP)}} for k, rs in groups.items()}


def _trace_events(path: str):
    """Of an ``.xplane.pb``: per device its ``ops`` and ``modules`` (a
    ``/device:*:<n>`` plane's ``XLA Ops`` / ``XLA Modules`` lines) and the runtime's
    ``enqueues`` and ``dones`` for it (host events, by their ``device_ordinal`` /
    ``core_id`` stat), sorted ``(start, end)`` seconds; the ``heat.*`` spans ``(start,
    end, name)``; the ``heat.force`` ones ``(start, end, program)``. A CPU backend's
    operations are the host events with an ``hlo_op``, by ``device_ordinal``."""
    devices, spans, forces, ordinal = {}, [], [], {}
    kinds = {"XLA Ops": "ops", "XLA Modules": "modules", _ENQUEUE: "enqueues", _DONE: "dones"}

    def add(name, kind, e):
        lists = devices.setdefault(name, {k: [] for k in kinds.values()})
        lists[kind].append((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))

    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    for plane in planes:
        if plane.name.startswith("/device:") and plane.name.rsplit(":", 1)[1].isdigit():
            ordinal[int(plane.name.rsplit(":", 1)[1])] = plane.name
            for kind, e in ((kinds[ln.name], e) for ln in plane.lines if ln.name in kinds for e in ln.events):
                add(plane.name, kind, e)
    for e in (e for pl in planes if pl.name == "/host:CPU" for ln in pl.lines for e in ln.events):
        stats = dict(e.stats) if e.name in (_ENQUEUE, _DONE, "heat.force") or not ordinal else {}
        at = ordinal.get(stats.get("device_ordinal", stats.get("core_id", 0)))
        if e.name.startswith("heat."):
            spans.append((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name))
            if e.name == "heat.force":
                forces.append((*spans[-1][:2], str(stats.get("program", "-"))))
        elif e.name in (_ENQUEUE, _DONE) and at is not None:
            add(at, kinds[e.name], e)
        elif "hlo_op" in stats:  # a CPU backend: no device plane
            add(f"cpu:{stats.get('device_ordinal', 0)}", "ops", e)
    return {n: {kind: sorted(events) for kind, events in d.items()} for n, d in devices.items()}, spans, sorted(forces)


def _gaps_doc(path: str) -> Dict[str, Any]:
    """Busy and idle seconds of the busiest device in a profiler trace inside
    the window from the first ``heat.*`` span's start to the last one's end (the
    device's lines where the profiler put them), and the idle time cut on ONE
    clock (:func:`_cut`, at the shifts of :func:`_shift_window`; ``shifts_us``: every
    device's), with :func:`_round_trips`. A CPU backend's gaps are all the host's."""
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)) if os.path.isdir(path) else [path]
    if not found:
        raise ValueError(f"no .xplane.pb under {path}")
    path = found[-1]
    devices, spans, forces = _trace_events(path)
    if not any(d["ops"] for d in devices.values()):
        raise ValueError(f"{path} holds no device operation")
    every = spans or [op for d in devices.values() for op in d["ops"]]
    lo, hi = min(iv[0] for iv in every), max(iv[1] for iv in every)
    busy = {n: _innermost((max(s, lo), min(e, hi), "") for s, e in d["ops"] if e > lo and s < hi) for n, d in devices.items()}
    busy_s = {n: sum(e - s for s, e, _ in pieces) for n, pieces in busy.items()}  # the union of a device's operations
    device = max(busy_s, key=busy_s.get)
    edges = [lo] + [t for piece in busy[device] for t in piece[:2]] + [hi]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    windows = {n: _shift_window(d["modules"], d["enqueues"], d["dones"]) for n, d in devices.items()}
    window, idle = windows[device], (hi - lo) - busy_s[device]
    matched = [devices[device][k][: window["programs"]] for k in ("modules", "enqueues", "dones")]
    return {
        "source": path, "device": device, "devices": len(devices), "window_s": hi - lo,
        "busy_s": busy_s[device], "idle_s": idle, "idle_pct": 100.0 * idle / (hi - lo),
        "aligned": "by the runtime's events" if window["programs"] else "none needed", **window,
        "shifts_us": {name: [w["shift_lo_us"], w["shift_hi_us"]] for name, w in windows.items()},
        **_cut(gaps, *matched, spans, window["by_part_us"]),
        "round_trips": _round_trips(*matched, forces),
    }


def _show_gaps(doc: Dict[str, Any], out) -> None:
    def row(name, secs):
        print(f"  {name:<26} {secs:.6f} s  {100.0 * secs / (doc['idle_s'] or 1.0):5.1f} %", file=out)

    print(
        f"gaps ({doc['source']}): {doc['device']} (busiest of {doc['devices']}), window "
        f"{doc['window_s']:.6f} s, busy {doc['busy_s']:.6f} s, idle {doc['idle_s']:.6f} s ({doc['idle_pct']:.2f} %)\n"
        f"device lines aligned: {doc['aligned']}; {doc['programs']} program(s) matched, {doc['unmatched']} "
        f"event(s) unmatched; shift {doc['shift_lo_us']:+.1f} .. {doc['shift_hi_us']:+.1f} us"
        + ("" if doc["consistent"] else "  INCONSISTENT: no one shift fits the session")
        + "; by part of the session: " + ", ".join("{:+.0f} .. {:+.0f}".format(*w) for w in doc["by_part_us"])
        + "\nidle time, cut on the host's clock:",
        file=out,
    )
    row("launch + completion", doc["launch_plus_completion_s"])
    for name in ("launch", "completion"):  # a pair each: at the low shift of each part, at the high
        print("    {:<24} {:.6f} s at the low shifts, {:.6f} s at the high".format(name, *doc[name + "_s"]), file=out)
    row("queued behind a program", doc["queued_s"])
    row("inside a program", doc["inside_program_s"])
    print("the host's part, idle by innermost heat.* span:", file=out)
    for name, secs in doc["idle_by_span_s"].items():
        row(name, secs)
    print("round trips, mean / p95 us: " + "; ".join(_TRIP), file=out)
    for key, trip in doc["round_trips"].items():
        print(f"  {key:<18} x{trip['n']:<6} " + "; ".join("{:9.1f} /{:9.1f}".format(*trip[nm]) for nm in _TRIP), file=out)


def main(argv: Optional[List[str]] = None, out=None) -> int:
    out = out if out is not None else sys.stdout
    parser = argparse.ArgumentParser(
        prog="python -m heat_tpu.telemetry",
        description="Pretty-print/diff heat_tpu telemetry reports and validate trace files.",
    )
    sub = parser.add_subparsers(dest="cmd", required=True)
    p_show = sub.add_parser("show", help="pretty-print a report_json artifact")
    p_show.add_argument("report", help="path to a telemetry report_json file")
    p_show.add_argument("--raw", action="store_true", help="re-emit the parsed JSON instead")
    p_diff = sub.add_parser("diff", help="diff two report_json artifacts (b - a)")
    p_diff.add_argument("a")
    p_diff.add_argument("b")
    p_mem = sub.add_parser(
        "memory",
        help="live-buffer ledger + watermark + per-program static peaks "
        "(from a report_json artifact, or live from this process)",
    )
    p_mem.add_argument(
        "report",
        nargs="?",
        default=None,
        help="a report_json artifact; omitted = sample THIS process live "
        "(brings up the mesh)",
    )
    p_mem.add_argument("--json", action="store_true", help="emit JSON instead of text")
    p_mem.add_argument("--top", type=int, default=5, help="top-K buffers/programs shown")
    p_health = sub.add_parser(
        "health",
        help="runtime health: flight recorder, watchdog/stalls, latency "
        "p50/p90/p99 and SLO gauges (from a report_json artifact or a "
        "flight-dump bundle, or live from this process)",
    )
    p_health.add_argument(
        "report",
        nargs="?",
        default=None,
        help="a report_json artifact or flight-dump bundle; omitted = THIS "
        "process's live health block (pure module state, no mesh bring-up)",
    )
    p_health.add_argument("--json", action="store_true", help="emit JSON instead of text")
    p_num = sub.add_parser(
        "numerics",
        help="numerics lens: streaming tensor stats, shadow-replay drift "
        "ledger, SDC canary summary and training-signal streams (from a "
        "report_json artifact or a flight-dump bundle, or live from this "
        "process)",
    )
    p_num.add_argument(
        "report",
        nargs="?",
        default=None,
        help="a report_json artifact or flight-dump bundle; omitted = THIS "
        "process's live numerics block (pure module state, no mesh bring-up)",
    )
    p_num.add_argument("--json", action="store_true", help="emit JSON instead of text")
    p_sess = sub.add_parser(
        "sessions",
        help="serving layer: per-session billing/incident blocks, admission "
        "buckets and the persistent program cache (from a report_json "
        "artifact, or live from this process)",
    )
    p_sess.add_argument(
        "report",
        nargs="?",
        default=None,
        help="a report_json artifact; omitted = THIS process's live serving "
        "block (pure module state, no mesh bring-up)",
    )
    p_sess.add_argument("--json", action="store_true", help="emit JSON instead of text")
    p_ana = sub.add_parser(
        "analyze",
        help="tracelens diagnosis of a trace: time attribution per bucket, "
        "critical path, cross-host straggler attribution, anti-pattern "
        "findings; nonzero exit on warning/error findings or on regression "
        "vs --against",
    )
    p_ana.add_argument(
        "trace",
        nargs="?",
        default=None,
        help="an export_trace/merge_traces file; omitted = THIS process's "
        "live verbose timeline",
    )
    p_ana.add_argument(
        "--against",
        default=None,
        help="baseline to diff against: a saved `analyze --json` output or "
        "another trace file (bucket shifts, new findings, critical-path "
        "growth; regressions exit 1)",
    )
    p_ana.add_argument("--json", action="store_true", help="emit JSON instead of text")
    p_ana.add_argument(
        "--allow-partial",
        action="store_true",
        help="analyze a window with dropped events anyway (attribution "
        "undercounts the evicted prefix; refused with exit 2 otherwise)",
    )
    p_gaps = sub.add_parser(
        "gaps",
        help="a jax.profiler trace on one clock: busy and idle seconds of the "
        "busiest device, the window of the shift between its lines and the "
        "host's (from the runtime's enqueue and Done events), and every idle "
        "gap cut into the host's part by innermost heat.* span, launch + "
        "completion, queued and inside-program time; round trips by program",
    )
    p_gaps.add_argument("trace", help="a profiler trace directory or an .xplane.pb file")
    p_gaps.add_argument("--json", action="store_true", help="emit JSON instead of text")
    p_ops = sub.add_parser(
        "ops",
        help="live ops plane: scrape an endpoint, strict-check its "
        "/metrics exposition + /healthz, or serve this process's plane",
    )
    p_ops.add_argument("action", choices=("scrape", "check", "serve"))
    p_ops.add_argument("--url", default=None, help="endpoint base URL (overrides --host/--port)")
    p_ops.add_argument("--host", default="127.0.0.1")
    p_ops.add_argument("--port", type=int, default=None)
    p_ops.add_argument(
        "--path", default="/metrics", help="route for 'scrape' (default /metrics)"
    )
    p_ops.add_argument("--timeout", type=float, default=10.0)
    p_val = sub.add_parser(
        "validate-trace", help="check a Chrome/Perfetto trace-event JSON file"
    )
    p_val.add_argument("trace", help="path to an export_trace/merge_traces output")
    p_val.add_argument(
        "--cross-host",
        action="store_true",
        help="also require cross-host collective parity (per-cid collective event "
        "counts equal on every process row — the runtime signature of an H001 "
        "deadlock when violated)",
    )
    args = parser.parse_args(argv)

    if args.cmd == "show":
        doc = _load(args.report)
        if args.raw:
            print(json.dumps(doc, indent=2, sort_keys=True), file=out)
        else:
            _show(doc, out)
        return 0
    if args.cmd == "diff":
        _diff(_load(args.a), _load(args.b), out)
        return 0
    if args.cmd == "memory":
        doc = _memory_doc(args.report, top=args.top)
        if args.json:
            print(json.dumps(_core._jsonable(doc), indent=2, sort_keys=True), file=out)
        else:
            _show_memory(doc, out)
        return 0
    if args.cmd == "health":
        doc = _health_doc(args.report)
        if args.json:
            print(json.dumps(_core._jsonable(doc), indent=2, sort_keys=True), file=out)
        else:
            _show_health(doc, out)
        return 0
    if args.cmd == "numerics":
        doc = _numerics_doc(args.report)
        if args.json:
            print(json.dumps(_core._jsonable(doc), indent=2, sort_keys=True), file=out)
        else:
            _show_numerics(doc, out)
        return 0
    if args.cmd == "sessions":
        doc = _sessions_doc(args.report)
        if args.json:
            print(json.dumps(_core._jsonable(doc), indent=2, sort_keys=True), file=out)
        else:
            _show_sessions(doc, out)
        return 0
    if args.cmd == "gaps":
        try:
            doc = _gaps_doc(args.trace)
        except (ValueError, OSError) as exc:
            print(f"ERROR: {exc}", file=out)
            return 2
        if args.json:
            print(json.dumps(doc, indent=2), file=out)
        else:
            _show_gaps(doc, out)
        return 0
    if args.cmd == "ops":
        if args.action == "scrape":
            return _ops_scrape(args, out)
        if args.action == "check":
            return _ops_check(args, out)
        return _ops_serve(args, out)
    if args.cmd == "analyze":
        from heat_tpu.core import tracelens

        try:
            analysis = tracelens.analyze(args.trace, allow_partial=args.allow_partial)
        except tracelens.TraceIncompleteError as exc:
            print(f"REFUSED: {exc}", file=out)
            return 2
        except (ValueError, OSError) as exc:
            print(f"ERROR: {exc}", file=out)
            return 2
        delta = None
        if args.against is not None:
            try:
                baseline = tracelens.load_analysis(args.against)
            except (ValueError, OSError) as exc:
                print(f"ERROR: cannot load baseline: {exc}", file=out)
                return 2
            delta = tracelens.diff(baseline, analysis)
        if args.json:
            doc = dict(analysis)
            if delta is not None:
                doc["against"] = delta
            print(json.dumps(_core._jsonable(doc), indent=2, sort_keys=True), file=out)
        else:
            print(tracelens.render(analysis), file=out)
            if delta is not None:
                shifts = delta["bucket_shifts_pts"]
                if shifts:
                    print("vs baseline (bucket shifts, pts):", file=out)
                    for bucket, pts in sorted(shifts.items(), key=lambda kv: -abs(kv[1])):
                        print(f"  {bucket:<16} {pts:+.2f}", file=out)
                for f in delta["new_findings"]:
                    print(
                        f"NEW [{f.get('severity', '?')}] {f.get('rule')}: "
                        f"{f.get('message')}",
                        file=out,
                    )
                for r in delta["regressions"]:
                    print(f"REGRESSION: {r}", file=out)
        gate = any(
            f.get("severity") in ("error", "warning") for f in analysis["findings"]
        )
        if delta is not None and not delta["ok"]:
            gate = True
        return 1 if gate else 0
    if args.cmd == "validate-trace":
        problems = _core.validate_trace(args.trace, cross_host=args.cross_host)
        if problems:
            for p in problems[:20]:
                print(f"INVALID: {p}", file=out)
            return 1
        with open(args.trace) as fh:
            n = len(json.load(fh).get("traceEvents", []))
        parity = " + cross-host collective parity" if args.cross_host else ""
        print(
            f"OK: {args.trace} parses as trace-event JSON ({n} events){parity}",
            file=out,
        )
        return 0
    return 2  # pragma: no cover - argparse enforces the subcommands


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess in CI
    sys.exit(main())
