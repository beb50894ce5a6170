"""Statistical moments benchmark (reference:
benchmarks/statistical_moments/heat-cpu.py:21-28: mean and std over
axis in {None, 0, 1}, timed trials)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import time


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=1000)
    parser.add_argument("--cols", type=int, default=1000)
    parser.add_argument("--trials", type=int, default=10)
    args = parser.parse_args()

    import heat_tpu as ht
    from heat_tpu.core import serving

    serving.use_entry_point_compile_cache()

    ht.random.seed(0)
    x = ht.random.randn(args.rows, args.cols, split=0)

    results = {}
    for name, fn in (("mean", ht.mean), ("std", ht.std)):
        for axis in (None, 0, 1):
            fn(x, axis)  # warmup
            times = []
            for _ in range(args.trials):
                start = time.perf_counter()
                r = fn(x, axis)
                r.numpy() if r.ndim else float(r.larray)
                times.append(time.perf_counter() - start)
            results[f"{name}_axis{axis}"] = round(min(times) * 1000, 3)
    print(
        json.dumps(
            {
                "benchmark": "statistical_moments",
                "shape": [args.rows, args.cols],
                "devices": ht.get_comm().size,
                "ms": results,
            }
        )
    )


if __name__ == "__main__":
    main()
