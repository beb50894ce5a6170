"""cdist benchmark (reference: benchmarks/distance_matrix/heat-gpu.py:20-34:
quadratic_expansion on/off, timed trials, split=0)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import time


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--n", type=int, default=40_000)
    parser.add_argument("--f", type=int, default=64)
    parser.add_argument("--trials", type=int, default=3)
    parser.add_argument("--quadratic-expansion", action="store_true")
    args = parser.parse_args()

    import heat_tpu as ht
    from heat_tpu.core import serving

    serving.use_entry_point_compile_cache()

    ht.random.seed(0)
    n = (args.n // ht.get_comm().size) * ht.get_comm().size
    x = ht.random.randn(n, args.f, split=0)

    times = []
    for _ in range(args.trials):
        start = time.perf_counter()
        d = ht.spatial.cdist(x, quadratic_expansion=args.quadratic_expansion)
        float(d.larray[0, 0])  # sync
        times.append(time.perf_counter() - start)
    best = min(times)
    # bytes written for the (n, n) result per chip
    gb = (n * n * 4) / 1e9 / ht.get_comm().size
    print(
        json.dumps(
            {
                "benchmark": "distance_matrix",
                "n": n,
                "f": args.f,
                "quadratic_expansion": args.quadratic_expansion,
                "devices": ht.get_comm().size,
                "time_s": round(best, 4),
                "gb_per_sec_per_chip": round(gb / best, 2),
            }
        )
    )


if __name__ == "__main__":
    main()
