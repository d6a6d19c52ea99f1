"""Share of the traced window in which no operation ran on the device,
in percent; the mean over the devices a cell uses."""


def read(run):
    t = run.trace
    if t is None or t["devices"] == 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
