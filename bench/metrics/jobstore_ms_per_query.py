"""Host time in the program span ``serve.store`` (job record create,
claim and update, store lock and atomic replace included) per job
served in the traced window, in milliseconds, read from the trace's
host plane (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.reading(run, "jobstore_ms_per_query")
