"""Device self time of the scan's operations under the named scope
``network`` (the package network: flows, the next-hop table gather, both
``evaluate_network`` calls, the contention fixed point, transfer stages
and the longest-path relax) per exact evaluation completed in the traced
window, in microseconds (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.reading(run, "network_us_per_eval")
