"""chipbench runner: one cell, one window, one result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell from the data files its ``BENCHMARK.json`` entry names, makes
the inputs on the device from ``--seed``, warms the cell's own shapes (set-up),
drives one closed-loop window through the program's public API, reads the
device's peak memory, checks a seeded sample of the window's answers against
the configuration's plain reference, and prints the contract's last line.
No accelerator, or another number of chips than the cell asks for: exit 3 and
no line. Nothing here knows a cell, configuration, mix or metric by name.
"""

from __future__ import annotations

import os
import sys
import time

_T0 = time.time()  # as near to the start of the process as Python lets us read


def _pin_hash_seed() -> None:
    """``PYTHONHASHSEED`` is read when the interpreter starts, so a run that
    was started without it replaces itself (exec: the same process, no child)."""
    if os.environ.get("PYTHONHASHSEED") != "0":
        env = dict(os.environ, PYTHONHASHSEED="0", CHIPBENCH_T0=repr(_T0))
        os.execve(sys.executable, [sys.executable, *sys.argv], env)


import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


EXIT_NO_CHIP = 3
_NULL = contextlib.nullcontext()
# The TPU runtime maps and touches a 4 GiB host staging buffer when it starts:
# 5.5-6.5 s of every run on a machine without transparent hugepages, and most of
# the run-to-run spread of ``setup_s``. No cell moves more than a few MB between
# host and device, so the benchmark starts the runtime with a small one (an
# outer setting wins). Measured in PERF.md, section 2.
PREMAPPED_BUFFER_BYTES = 256 << 20


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def find_chips(chips: int):
    """The cell's devices as JAX reports them, or exit: a measurement path
    that finds no chip fails, it does not fall back."""
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(PREMAPPED_BUFFER_BYTES))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != chips:
        sys.stderr.write(
            f"chipbench: the cell asks for {chips} TPU chip(s); JAX reports "
            f"{len(devices)} x {devices[0].platform}. No result.\n"
        )
        raise SystemExit(EXIT_NO_CHIP)
    return devices


class Counters:
    """Counts the readers take as deltas across the window: the fusion
    engine's own (``fusion.cache_stats``) and every executable XLA builds or
    loads (``jax.monitoring`` backend-compile events)."""

    def __init__(self):
        import jax

        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def read(self) -> dict:
        from heat_tpu.core import fusion

        stats = {k: v for k, v in fusion.cache_stats().items() if isinstance(v, (int, float))}
        return {"fusion": stats, "backend_compiles": self.backend_compiles}


class Tracer:
    """The profiler around the first ``seconds`` of the window (``--trace 1``).
    Host spans are the benchmark's own ``TraceAnnotation``s; Python call
    tracing is off, so the traced ops run as the untraced ones do."""

    def __init__(self, on: bool, seconds: float, directory: str):
        self.on, self.seconds, self.directory = on, seconds, directory
        self.active = False

    def span(self, name: str):
        if not self.on:
            return _NULL
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        if not self.on:
            return
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.active = True

    def stop(self) -> None:
        if self.active:
            import jax

            jax.profiler.stop_trace()
            self.active = False


class Reservoir:
    """A uniform sample of ``k`` of the window's answers, drawn from the seed,
    and the last answer besides: what the reference is run against."""

    def __init__(self, k: int, seed: int):
        self.k, self.rng, self.kept, self.last, self.seen = k, random.Random(seed), [], None, 0

    def offer(self, index: int, answer) -> None:
        self.last = (index, answer)
        if len(self.kept) < self.k:
            self.kept.append((index, answer))
        else:
            j = self.rng.randrange(self.seen + 1)
            if j < self.k:
                self.kept[j] = (index, answer)
        self.seen += 1

    def sample(self) -> list:
        picked = dict(self.kept)
        if self.last is not None:
            picked[self.last[0]] = self.last[1]
        return sorted(picked.items())


def drive(op, seconds: float, tracer: Tracer, sampler: Reservoir) -> types.SimpleNamespace:
    """One closed loop, one client: the next op starts when the last one's
    result is on the host. An op that is running when the time is up finishes
    and counts, and the window ends with it."""
    op_s, failed, index = [], 0, 0
    now = time.perf_counter
    tracer.start()
    begin = now()
    deadline = begin + seconds
    while True:
        t0 = now()
        if t0 >= deadline:
            break
        if tracer.active and t0 - begin >= tracer.seconds:
            tracer.stop()
            t0 = now()
        try:
            with tracer.span("bench.op"):
                answer = op.run(index)
        except Exception:  # the op failed; the window goes on and counts it
            failed += 1
            if failed <= 3:
                traceback.print_exc(file=sys.stderr)
        else:
            op_s.append(now() - t0)
            sampler.offer(index, answer)
        index += 1
    end = now()
    tracer.stop()
    return types.SimpleNamespace(op_s=op_s, failed=failed, attempted=index, window_s=end - begin, begin=begin)


def build_op(cell, seed: int, span):
    """The cell's op kind, built on what an op kind may know: the
    configuration, the mix, the chips, the seed, the span factory and the
    configuration's plain reference."""
    ctx = types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, chips=cell.chips,
        seed=seed, span=span, reference=cell.reference_module(),
    )
    return cell.op_module().build(ctx)


def read_metrics(cell, kind: str, entries: list, run) -> dict:
    """Every metric of the cell by its own reader, ``chipbench/<kind>/<name>.py``.
    A reader that finds nothing to read returns ``None`` and is left out."""
    out = {}
    for m in entries:
        value = cell.reader(kind, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, bench: dict | None = None, devices=None) -> int:
    """``bench`` and ``devices`` are for the tests: a stand-in for
    ``BENCHMARK.json`` (tiny sizes) and the devices to use without the look
    for a chip. A run of the benchmark passes neither."""
    args = parse_args(argv)
    t0 = float(os.environ.get("CHIPBENCH_T0", _T0))
    marks = [("start", t0)]

    def mark(name: str) -> None:
        marks.append((name, time.time()))

    from chipbench import spec

    cell = spec.Cell(args.workload, bench)
    devices = devices or find_chips(cell.chips)
    mark("chip_found")

    import jax
    import heat_tpu  # noqa: F401  (a checkout without the program: ImportError, no line)
    from heat_tpu.core import resilience, serving

    cache_dir = serving.use_entry_point_compile_cache()
    import warnings

    warnings.simplefilter("error", resilience.DegradedDispatchWarning)
    counters = Counters()
    mark("program_imported")
    tracer = Tracer(
        bool(args.trace),
        float(cell.traffic.get("trace_seconds", 3.0)),
        os.path.join(ROOT, ".chipbench_trace", f"{cell.name}-{args.seed}"),
    )

    # -- set-up: inputs from the seed, then every shape the window will use
    op = build_op(cell, args.seed, tracer.span)
    mark("inputs_made")
    t_warm = time.time()
    for i in range(int(cell.traffic.get("warm_up_ops", 2))):
        op.run(-1 - i)
    warm_up_s = time.time() - t_warm
    gc.collect()
    gc.freeze()
    before = counters.read()
    sampler = Reservoir(int(cell.traffic.get("check_answers", 3)), args.seed)

    # -- the measured window
    mark("warmed_up")
    setup_s = time.time() - t0
    run = drive(op, args.seconds, tracer, sampler)
    mark("window_closed")
    after = counters.read()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    run.setup_s, run.warm_up_s, run.memory_peak_bytes = setup_s, warm_up_s, memory_peak
    run.counters = {"before": before, "after": after}
    run.config, run.traffic, run.chips = cell.config, cell.traffic, cell.chips
    run.device_kind = devices[0].device_kind
    run.results_per_op = getattr(op, "results_per_op", 1)

    # -- correctness: the reference against what the window itself produced
    answers = sampler.sample()
    del sampler
    compared = op.check(answers)  # {name: [value, limit]}, value <= limit passes
    mark("checked")
    compared["answers_unchecked"] = [int(not answers), 0]
    compared["ops_failed"] = [run.failed, 0]
    compared["fallbacks_degraded"] = [after["fusion"]["degraded"] - before["fusion"]["degraded"], 0]
    correct = all(v == v and v <= lim for v, lim in compared.values())

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": int(memory_peak),
    }
    result = {"correct": correct, "attempted": run.attempted, "failed": run.failed}
    if args.trace:
        from chipbench import trace as trace_mod

        run.trace = trace_mod.load(tracer.directory)
        shutil.rmtree(tracer.directory, ignore_errors=True)
        device["busy_s"], device["window_s"] = run.trace.busy_s, run.trace.window_s
        result.update(metrics=read_metrics(cell, "layer_metrics", cell.per_layer, run), device=device,
                      breakdown=run.trace.breakdown())
    else:
        result.update(metrics=read_metrics(cell, "end_to_end", cell.end_to_end, run), device=device)
    result["ops_timed"] = len(run.op_s)
    result["window_s"] = run.window_s
    result["compile_cache"] = os.path.relpath(cache_dir, ROOT)
    result["compared"] = compared

    sys.stdout.flush()
    phases = {name: round(t - marks[i][1], 3) for i, (name, t) in enumerate(marks[1:])}
    sys.stderr.write(f"phases_s {json.dumps(phases)}\n")
    for name, (value, limit) in compared.items():
        sys.stderr.write(f"compared {name} = {value!r} limit {limit!r} {'ok' if value <= limit else 'FAIL'}\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    _pin_hash_seed()
    sys.exit(main())
