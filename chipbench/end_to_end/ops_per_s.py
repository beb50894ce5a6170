"""All the ops completed in the window over the whole window (an op that is
running when the time is up finishes, counts, and the window ends with it)."""


def read(run):
    return len(run.op_s) / run.window_s
