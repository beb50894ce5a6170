"""Device time of one coordinate step, microseconds: the busiest device's time
in the sweep programs per fit over the steps of a fit, ``phase_lasso_sweeps``
per ``phase_lasso_fits`` times the configuration's features. A sweep program
(``lasso_cd_sweep``) is all but a ``while`` of as many steps as there are
features, and the ``XLA Ops`` line names an operation by its instruction, its
result's type included. The sweep's loop is found by what it carries: the
step's index, the m-vector ``c`` and theta (m, 1), in that order, ahead of
whatever else the compiler keeps in the tuple: ``(s32[], f32[m], f32[m,1],
...) while(``. No other loop is taken for it: the Gram's carries its (m, m)
sums first, and a loop a later PR adds is not counted until this reader is
told of it. The time is the union of those operations (each holds its body).
A program without the counters, or a trace without such an operation, reads
``None``."""

import re

from chipbench import spec, trace

_phases = spec.load_module("layer_metrics", "_phases.py")
_LAYOUT = re.compile(r"\{[^}]*\}")


def is_sweep_loop(name: str, features: int) -> bool:
    """Whether the instruction ``name`` is a ``while`` whose tuple starts with
    the sweep's carry (layouts aside)."""
    _, sep, rhs = name.partition(" = ")
    carry = f"(s32[], f32[{features}], f32[{features},1],"
    return bool(sep) and trace.short_name(name).endswith(":while") and _LAYOUT.sub("", rhs).startswith(carry)


def read(run):
    fits, sweeps = _phases.delta(run, "phase_lasso_fits"), _phases.delta(run, "phase_lasso_sweeps")
    if not fits or not sweeps:
        return None
    features = int(run.config["features"])
    starts, ends, names = run.trace.devices[run.trace.busiest]
    loops = [i for i, name in enumerate(names) if is_sweep_loop(name, features)]
    if not loops:
        return None
    in_sweeps = trace.union_length(starts[loops], ends[loops]) / run.trace.n_ops
    return 1e6 * in_sweeps / ((sweeps / fits) * features)
