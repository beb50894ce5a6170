"""Training-stack throughput: DP and DASO samples/s (BASELINE config 5).

The reference's training raison d'être is nn.DataParallel + optim.DASO
(reference heat/optim/dp_optimizer.py:432-475,592-650 — the skip-schedule
cadence is the whole point of DASO); BASELINE.md tracks it as config 5
(ResNet/CIFAR). This harness measures both trainers on CIFAR-shaped
synthetic data (32x32x3, 10 classes) and reports:

  * dp_samples_per_sec        — nn.DataParallel fused jitted step
  * daso_sweep                — samples/s at each (global_skip, local_skip)
                                cadence point, incl. full-sync (0, 0): the
                                ici/dcn sweep showing what skipping buys
  * step-time breakdown       — device placement vs compiled compute vs
                                host overhead, so a dispatch-dominated
                                number is diagnosable from the artifact

Runs on the default backend and records its platform; ``JAX_PLATFORMS=cpu``
selects the forced-host CPU mesh (``--devices`` virtual devices, the CI
topology). Usage:

    python benchmarks/train_throughput.py [--devices 8]
        [--batch 64] [--steps 6] [--model resnet18|cnn] [--out FILE]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--devices", type=int, default=8, help="virtual CPU devices")
    parser.add_argument("--batch", type=int, default=64, help="global batch size")
    parser.add_argument("--steps", type=int, default=6, help="timed steps per config")
    parser.add_argument("--model", default="resnet18", choices=["resnet18", "cnn"])
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    # shapes the host platform only; an accelerator backend ignores it
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.devices}".strip()
        )

    import jax.numpy as jnp
    import numpy as np
    import optax

    import heat_tpu as ht
    from heat_tpu.core import serving

    serving.use_entry_point_compile_cache()
    from heat_tpu.nn import DataParallel, ResNet18, SimpleCNN
    from heat_tpu.optim import DASO

    comm = ht.get_comm()
    n_dev = comm.size
    doc = {
        "config": "BASELINE.md config 5 (synthetic CIFAR-shaped data)",
        "platform": comm.devices[0].platform,
        "devices": n_dev,
        "model": args.model,
        "global_batch": args.batch,
        "timed_steps": args.steps,
        "captured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }

    rng = np.random.default_rng(0)
    batch = args.batch // n_dev * n_dev or n_dev
    x_np = rng.standard_normal((batch, 32, 32, 3)).astype(np.float32)
    y_np = rng.integers(0, 10, size=batch).astype(np.int32)

    module = ResNet18(num_classes=10) if args.model == "resnet18" else SimpleCNN(num_classes=10)

    def timed_steps(step_fn, n):
        """Best-of-two mean step time over n steps (first call outside)."""
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(n):
                step_fn()
            best = min(best, (time.perf_counter() - t0) / n)
        return best

    # ---- DataParallel ----------------------------------------------------
    dp = DataParallel(module, comm=comm, optimizer=optax.sgd(0.05))
    dp.init(0, x_np[: max(n_dev, 2)])
    dp.train_step(x_np, y_np)  # compile
    dp_step_s = timed_steps(lambda: dp.train_step(x_np, y_np), args.steps)
    doc["dp_samples_per_sec"] = round(batch / dp_step_s, 1)
    doc["dp_step_ms"] = round(dp_step_s * 1e3, 2)

    # breakdown: placement cost vs compiled compute. The product step calls
    # _ensure_split (a device_put) then the jitted program; timing the jitted
    # program on pre-placed operands isolates the compute.
    from heat_tpu.core.dndarray import _ensure_split

    xb = _ensure_split(jnp.asarray(x_np), 0, comm)
    yb = _ensure_split(jnp.asarray(y_np), 0, comm)
    t_place = timed_steps(
        lambda: (_ensure_split(jnp.asarray(x_np), 0, comm), _ensure_split(jnp.asarray(y_np), 0, comm)),
        args.steps,
    )

    def compute_only():
        if dp._stateful:
            p, s, o, loss = dp._train_step(dp.params, dp.state, dp.opt_state, xb, yb)
        else:
            p, o, loss = dp._train_step(dp.params, dp.opt_state, xb, yb)
        float(loss)

    compute_only()
    t_compute = timed_steps(compute_only, args.steps)
    doc["dp_breakdown_ms"] = {
        "placement": round(t_place * 1e3, 2),
        "compiled_step": round(t_compute * 1e3, 2),
        "host_overhead": round(max(dp_step_s - t_place - t_compute, 0.0) * 1e3, 2),
    }

    # ---- DASO cadence sweep ---------------------------------------------
    # (global_skip, local_skip) points: (0,0) is full synchronization (every
    # batch: ICI grad allreduce + DCN merge); (4,1) is the reference's
    # post-warmup operating point; (8,2) the max-skip steady state.
    sweep = []
    for gs, ls in ((0, 0), (2, 1), (4, 1), (8, 2)):
        daso = DASO(
            optax.sgd(0.05),
            total_epochs=10,
            comm=comm,
            warmup_epochs=0,
            cooldown_epochs=0,
            verbose=False,
        )
        daso.add_model(module, 0, x_np[: max(n_dev, 2)])
        daso.global_skip = gs
        daso.local_skip = ls
        daso.batches_to_wait = 1 if gs else 0
        daso.step(x_np, y_np)  # compile both solo and synced programs
        daso.step(x_np, y_np)
        step_s = timed_steps(lambda: daso.step(x_np, y_np), args.steps)
        sweep.append(
            {
                "global_skip": gs,
                "local_skip": ls,
                "samples_per_sec": round(batch / step_s, 1),
                "step_ms": round(step_s * 1e3, 2),
                "solo_steps_seen": daso._solo_steps,
            }
        )
    doc["daso_sweep"] = sweep
    full_sync = sweep[0]["samples_per_sec"]
    best_pt = max(sweep, key=lambda r: r["samples_per_sec"])
    doc["daso_best"] = {
        "point": [best_pt["global_skip"], best_pt["local_skip"]],
        "samples_per_sec": best_pt["samples_per_sec"],
        "speedup_vs_full_sync": round(best_pt["samples_per_sec"] / full_sync, 2),
    }

    out = json.dumps(doc, indent=1)
    print(out)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(out + "\n")


if __name__ == "__main__":
    main()
