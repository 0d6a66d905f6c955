"""Share of the traced window in which no op ran on the device (averaged
over the chips): 1 - union of op intervals / window."""


def read(m):
    r = m.reading
    return 100.0 * (1.0 - r.busy_s / r.window_s)
