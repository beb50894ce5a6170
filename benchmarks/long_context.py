"""Long-context attention throughput: ring / Ulysses / dense side by side.

The long-context story (SURVEY.md: sequence/context parallelism is
first-class) needs a measured artifact, not just oracle tests: this harness
times the attention kernels at growing sequence lengths on the mesh and
reports tokens/s plus the dense kernel's memory ceiling — the point of ring
attention is that the S x S score matrix never materializes, so it keeps
scaling after dense OOMs.

Runs on the default backend and records its platform; ``JAX_PLATFORMS=cpu``
selects the forced-host CPU mesh (``--devices`` virtual devices, the CI
topology). Usage:

    python benchmarks/long_context.py [--devices 8]
        [--seqs 2048 8192] [--dim 256] [--heads 8] [--out FILE]
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
    parser.add_argument("--seqs", type=int, nargs="+", default=[2048, 8192])
    parser.add_argument("--dim", type=int, default=256)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--trials", type=int, default=3)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    # an explicit --devices always wins: strip any pre-set count rather than
    # silently running on a different topology than requested (the flag only
    # shapes the host platform; an accelerator backend ignores it)
    import re

    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        "",
        os.environ.get("XLA_FLAGS", ""),
    ).strip()
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={args.devices}".strip()
    )

    import jax
    import jax.numpy as jnp
    import numpy as np

    import heat_tpu as ht
    from heat_tpu.core import serving

    serving.use_entry_point_compile_cache()
    from heat_tpu.nn.attention import (
        dot_product_attention,
        ring_attention,
        ulysses_attention,
    )

    comm = ht.get_comm()
    p = comm.size
    head_dim = args.dim // args.heads
    doc = {
        "platform": comm.devices[0].platform,
        "devices": p,
        "heads": args.heads,
        "head_dim": head_dim,
        "causal": True,
        "captured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "series": [],
    }

    def timed(fn, *ops):
        out = fn(*ops)
        float(jnp.sum(out[..., 0]))  # compile + sync
        best = float("inf")
        for _ in range(args.trials):
            t0 = time.perf_counter()
            out = fn(*ops)
            float(jnp.sum(out[..., 0]))
            best = min(best, time.perf_counter() - t0)
        return best

    for S in args.seqs:
        S = S // p * p
        key = jax.random.PRNGKey(S)
        q, k, v = (
            jax.random.normal(kk, (1, S, args.heads, head_dim), jnp.float32)
            for kk in jax.random.split(key, 3)
        )
        qs, ks, vs = (jax.device_put(t, comm.sharding(4, 1)) for t in (q, k, v))
        rec = {"seq": S}

        t_ring = timed(
            lambda a, b, c: ring_attention(a, b, c, causal=True, comm=comm), qs, ks, vs
        )
        rec["ring_tokens_per_sec"] = round(S / t_ring, 1)
        rec["ring_ms"] = round(t_ring * 1e3, 2)

        t_uly = timed(
            lambda a, b, c: ulysses_attention(a, b, c, causal=True, comm=comm), qs, ks, vs
        )
        rec["ulysses_tokens_per_sec"] = round(S / t_uly, 1)
        rec["ulysses_ms"] = round(t_uly * 1e3, 2)

        # dense reference: materializes the (S, S) score matrix per head —
        # measured while it fits; recorded as the ceiling it is
        score_bytes = args.heads * S * S * 4
        if score_bytes <= 2 << 30:  # keep the CI box sane
            t_dense = timed(
                lambda a, b, c: dot_product_attention(a, b, c, causal=True), q, k, v
            )
            rec["dense_tokens_per_sec"] = round(S / t_dense, 1)
            rec["dense_ms"] = round(t_dense * 1e3, 2)
        else:
            rec["dense_tokens_per_sec"] = None
            rec["dense_skipped"] = f"score matrix would be {score_bytes / 1e9:.1f} GB"
        rec["score_matrix_gb_if_dense"] = round(score_bytes / 1e9, 3)
        doc["series"].append(rec)
        # bank incrementally: an OOM at the NEXT (bigger) seq must not lose
        # this one's measurements
        if args.out:
            with open(args.out, "w") as fh:
                fh.write(json.dumps(doc, indent=1) + "\n")

    print(json.dumps(doc, indent=1))


if __name__ == "__main__":
    main()
