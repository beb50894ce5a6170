"""Time per op that the busiest device spends in collective operations,
milliseconds: the union of its ``XLA Ops`` events named collective-permute,
all-to-all, all-gather or all-reduce (start and done halves of an
asynchronous one both count: what lies between them is the device's other
work) inside the traced window, over the ops traced. ``None`` where the
trace holds no such event (one chip)."""

import re

from chipbench import trace

COLLECTIVE = re.compile(r"collective-permute|all-to-all|all-gather|all-reduce")


def read(run):
    starts, ends, names = run.trace.devices[run.trace.busiest]
    keep = [i for i, name in enumerate(names) if COLLECTIVE.search(name)]
    if not keep:
        return None
    return 1e3 * trace.union_length(starts[keep], ends[keep]) / run.trace.n_ops
