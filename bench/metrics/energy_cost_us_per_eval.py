"""Device self time of the scan's operations under the named scope
``energy_cost`` (energy, area and the Eq.-1 cost) per exact evaluation
completed in the traced window, in microseconds (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.reading(run, "energy_cost_us_per_eval")
