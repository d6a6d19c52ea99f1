"""Host time of building each query's workload graph, the program span
``presets.moe_layer`` (routing draws nested in it included), per query
served in the traced window, in milliseconds, read from the trace's
host plane (``harness/scopes.py``); none where the program opens no
such span.  Reading it also logs the trace's layer split (the scan by
scope, the program spans per query), as the other per-layer readers
do, for cells that report none of them."""

from harness import scopes


def read(run):
    scopes.readings(run)
    ms = scopes.span_self(scopes.rows_of(run), ("presets.moe_layer",))
    if not ms or run.queries <= 0:
        return None
    return 1e3 * ms["presets.moe_layer"] / run.queries
