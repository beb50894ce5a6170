"""The control of a cell: the same set-up and the same comparison as a run,
with the answers computed one precision below the one the configuration
states (each op kind's ``control_run``). It has to come out NOT correct: at
least one compared number over its limit. Run on the chip at the cell's own
size on three seeds or more when a limit is set or moved; the tests run it at
a CPU size. A run of the benchmark never runs it.

    python3 chipbench/control.py --workload <name> --seed <n> [--ops 2]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def control(workload: str, seed: int, ops: int = 2, bench: dict | None = None, devices=None) -> dict:
    from chipbench import run, spec

    cell = spec.Cell(workload, bench)
    devices = devices or run.find_chips(cell.chips)
    from heat_tpu.core import serving

    serving.use_entry_point_compile_cache()
    op = run.build_op(cell, seed, lambda name: run._NULL)
    answers = [(i, op.control_run(i)) for i in range(ops)]
    compared = op.check(answers)
    return {
        "workload": workload, "seed": seed, "control": cell.config["check"]["control_cast"],
        "correct": all(v == v and v <= lim for v, lim in compared.values()), "compared": compared,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--ops", type=int, default=2)
    args = p.parse_args(argv)
    out = control(args.workload, args.seed, args.ops)
    print(json.dumps(out), flush=True)
    return 0 if not out["correct"] else 1  # a control that passes is the failure


if __name__ == "__main__":
    sys.exit(main())
