"""Seconds of the warm-up spent tracing, lowering and compiling (or
loading compiled programs from the persistent cache): the union of
JAX's own compile events."""


def read(run):
    return run.compile_s if run.compile_s > 0 else None
