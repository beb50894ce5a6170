"""Process start to the first timed op: interpreter, JAX and the TPU runtime,
the program's import, the inputs, the warm-up ops (compilation in a first run)."""


def read(run):
    return run.setup_s
