"""Device self time of the scan's operations under the named scope
``variation`` (variation: crossover, mutation and the immigrant splice)
per exact evaluation completed in the traced window, in microseconds
(``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.reading(run, "variation_us_per_eval")
