"""Programs built or loaded inside the window: the fusion engine's compiles
plus every XLA backend-compile event. Expected 0."""


def read(run):
    before, after = run.counters["before"], run.counters["after"]
    fusion = after["fusion"]["compiles"] - before["fusion"]["compiles"]
    return float(fusion + after["backend_compiles"] - before["backend_compiles"])
