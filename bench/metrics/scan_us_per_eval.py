"""Device time of the compiled NSGA scan segments (variation,
evaluation, selection) per exact evaluation completed in the traced
window, in microseconds."""


def read(run):
    s = run.trace["layer_s"].get("scan", 0.0) if run.trace else 0.0
    if s <= 0 or run.evals <= 0:
        return None
    return 1e6 * s / run.evals
