"""Share of the traced window in which no operation ran on the device, in
percent, in a decode cell."""


def read(run):
    if run["trace"] is None:
        return None
    return 100.0 * (1.0 - run["busy_s"] / run["window_s"])
