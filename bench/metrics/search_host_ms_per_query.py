"""Host self time of the search loop's program spans
(``explore.open_group``, ``explore.init_population``, ``explore.seed``,
``explore.dispatch``, ``archive.insert``, ``explore.project`` and the
refinement's epilogue ``explore.book``; spans nested in them excluded)
per query served in the traced window, in milliseconds, read from the
trace's host plane (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.reading(run, "search_host_ms_per_query")
