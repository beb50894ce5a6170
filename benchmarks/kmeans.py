"""KMeans benchmark (reference: benchmarks/kmeans/heat-cpu.py:20-26 protocol:
k=8, 30 iterations, timed over multiple trials, split=0)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import time

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--n", type=int, default=1_000_000, help="number of points")
    parser.add_argument("--f", type=int, default=16, help="features")
    parser.add_argument("--k", type=int, default=8)
    parser.add_argument("--iterations", type=int, default=30)
    parser.add_argument("--trials", type=int, default=3)
    parser.add_argument("--path", type=str, default=None, help="optional HDF5 input")
    parser.add_argument("--dataset", type=str, default="data")
    args = parser.parse_args()

    import jax

    import heat_tpu as ht
    from heat_tpu.core import serving

    serving.use_entry_point_compile_cache()

    if args.path:
        x = ht.load_hdf5(args.path, args.dataset, split=0)
    else:
        ht.random.seed(0)
        x = ht.random.randn(args.n, args.f, split=0)

    times = []
    for trial in range(args.trials):
        km = ht.cluster.KMeans(n_clusters=args.k, init="random", max_iter=args.iterations, tol=0.0, random_state=trial)
        start = time.perf_counter()
        km.fit(x)
        _ = km.inertia_  # host-read sync
        times.append(time.perf_counter() - start)
    print(
        json.dumps(
            {
                "benchmark": "kmeans",
                "n": args.n,
                "f": args.f,
                "k": args.k,
                "devices": ht.get_comm().size,
                "iters_per_sec": args.iterations / min(times),
                "times_s": [round(t, 4) for t in times],
            }
        )
    )


if __name__ == "__main__":
    main()
