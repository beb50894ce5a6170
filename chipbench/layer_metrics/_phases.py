"""What the phase readers share. The program times the phases of a forced
result while a profiler session records (``fusion.cache_stats()``'s
``phase_*`` keys, nanoseconds, beside its ``heat.force`` / ``heat.place`` /
``heat.read`` spans), so over the window the counters grow by the traced
part alone. A program without these counters reads ``None`` everywhere."""

FORCE_PHASES = ("admit", "walk", "lookup", "dispatch", "install")


def delta(run, key):
    """Growth of one ``fusion.cache_stats()`` key over the window, ``None``
    where the program has no such key."""
    before, after = run.counters["before"]["fusion"], run.counters["after"]["fusion"]
    if key not in before or key not in after:
        return None
    return after[key] - before[key]


def mean_us(run, ns_keys, count_key):
    """Sum of the ``ns_keys`` deltas over the ``count_key`` delta, in
    microseconds; ``None`` when the count did not move."""
    count = delta(run, count_key)
    total = [delta(run, k) for k in ns_keys]
    if not count or None in total:
        return None
    return 1e-3 * sum(total) / count


HOST_NS = [f"phase_{p}_ns" for p in FORCE_PHASES] + ["phase_place_ns"]  # the five force phases and heat.place


def reader(ns_keys, count_key="phase_forces"):
    """A metric's ``read(run)``: ``mean_us`` of these keys."""
    return lambda run: mean_us(run, ns_keys, count_key)
