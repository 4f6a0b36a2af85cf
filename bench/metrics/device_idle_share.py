"""Share of the traced window in which no operation ran on the device,
in percent, averaged over the chips."""


def read(run):
    t = run.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100 * (1 - t["busy_s"] / t["window_s"])
