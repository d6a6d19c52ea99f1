"""Host time in the program span ``explore.fetch`` (the device-to-host
reads of a scan segment's results, where the host waits for the device)
per query served in the traced window, in milliseconds, read from the
trace's host plane (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.reading(run, "fetch_wait_ms_per_query")
