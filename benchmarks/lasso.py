"""Lasso benchmark (reference: benchmarks/lasso/config.json protocol).

``--torch-baseline`` also runs the same coordinate-descent sweep in torch on
CPU (the reference's comparison baseline, benchmarks/lasso/torch-cpu.py) and
reports ``torch_time_s`` + ``vs_torch``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import time


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--n", type=int, default=100_000)
    parser.add_argument("--f", type=int, default=64)
    parser.add_argument("--iterations", type=int, default=20)
    parser.add_argument("--trials", type=int, default=3)
    parser.add_argument("--torch-baseline", action="store_true")
    args = parser.parse_args()

    import heat_tpu as ht
    from heat_tpu.core import serving

    serving.use_entry_point_compile_cache()

    ht.random.seed(0)
    x = ht.random.randn(args.n, args.f, split=0)
    y = ht.random.randn(args.n, split=0)

    times = []
    for _ in range(args.trials):
        lasso = ht.regression.Lasso(lam=0.1, max_iter=args.iterations, tol=None)
        start = time.perf_counter()
        lasso.fit(x, y)
        float(lasso.theta.larray[0, 0])
        times.append(time.perf_counter() - start)

    rec = {
        "benchmark": "lasso",
        "n": args.n,
        "f": args.f,
        "devices": ht.get_comm().size,
        "time_s": round(min(times), 4),
    }
    if args.torch_baseline:
        t = _torch_lasso_sec(args.n, args.f, args.iterations, args.trials)
        rec["torch_time_s"] = round(t, 4)
        rec["vs_torch"] = round(t / rec["time_s"], 2)
    print(json.dumps(rec))


def _torch_lasso_sec(n: int, f: int, iters: int, trials: int) -> float:
    """The same coordinate-descent sweep in torch on CPU — the reference's
    single-node comparison baseline (benchmarks/lasso/torch-cpu.py): per
    feature, rho from the current residual, soft threshold, intercept free."""
    import torch

    torch.manual_seed(0)
    X = torch.randn(n, f)
    y = torch.randn(n, 1)
    lam = 0.1

    def fit():
        theta = torch.zeros(f, 1)
        for _ in range(iters):
            for j in range(f):
                X_j = X[:, j]
                y_est = X @ theta
                rho = (X_j @ (y.ravel() - y_est.ravel() + theta[j, 0] * X_j)) / n
                if j == 0:
                    theta[j, 0] = rho
                else:
                    theta[j, 0] = torch.sign(rho) * torch.clamp(rho.abs() - lam, min=0.0)
        return theta

    fit()  # warmup
    best = float("inf")
    for _ in range(trials):
        start = time.perf_counter()
        fit()
        best = min(best, time.perf_counter() - start)
    return best


if __name__ == "__main__":
    main()
