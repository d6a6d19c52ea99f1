"""Host time of the service's archive and manifest I/O and resume
checkpoints (the ``repro.obs`` spans ``archive.save``, ``archive.load``,
``manifest.reload`` and ``explore.checkpoint``) per query served in the
traced window, in milliseconds."""


def read(run):
    total = sum(total for _count, total in run.spans.values())
    if total <= 0 or run.queries <= 0:
        return None
    return 1e3 * total / run.queries
