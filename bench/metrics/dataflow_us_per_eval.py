"""Device self time of the scan's operations under the named scope
``dataflow`` (the per-chiplet dataflow analysis (both
``analyze_chiplet`` passes)) per exact evaluation completed in the
traced window, in microseconds (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.reading(run, "dataflow_us_per_eval")
