"""Device idle share of the traced window of an analytics cell, in
percent: 1 - (union of the device operations' intervals) / window."""


def read(run):
    tr = run.trace
    if tr is None or not tr.devices or not run.jobs or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
