"""A stand-in for ``BENCHMARK.json`` at sizes a CPU test can hold: the same
entries, each configuration's file replaced by a tiny copy."""

from __future__ import annotations

import copy
import json
import os

from chipbench import spec

TINY = {"moments_f32": {"resident_shape": [2048, 256], "small_shape": [100, 100]}}


def bench(tmp_path, chips: int = 4) -> dict:
    """Every cell on ``chips`` virtual devices (the CPU mesh the tests run on)."""
    out = copy.deepcopy(spec.benchmark())
    for cfg in out["configs"]:
        with open(os.path.join(spec.ROOT, cfg["file"])) as fh:
            data = json.load(fh)
        data.update(TINY[cfg["name"]])
        path = os.path.join(str(tmp_path), cfg["name"] + ".json")
        with open(path, "w") as fh:
            json.dump(data, fh)
        cfg["file"] = path
    for w in out["workloads"]:
        w["chips"] = chips
    return out
