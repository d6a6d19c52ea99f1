"""Device time of the archive insert program (``_archive_update`` and
its dominance kernel) per scan segment inserted in the traced window, in
milliseconds."""


def read(run):
    s = run.trace["layer_s"].get("insert", 0.0) if run.trace else 0.0
    if s <= 0 or run.segments <= 0:
        return None
    return 1e3 * s / run.segments
