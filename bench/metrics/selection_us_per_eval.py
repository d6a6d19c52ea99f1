"""Device self time of the scan's operations under the named scope
``selection`` (selection: penalized objectives, dominance counts (the
``pareto_rank`` kernel), crowding, survivor choice and the telemetry)
per exact evaluation completed in the traced window, in microseconds
(``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.reading(run, "selection_us_per_eval")
