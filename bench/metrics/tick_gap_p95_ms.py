"""95th percentile of the gaps between the starts of consecutive
executions of the tick source (``tick_op``, the jitted function that makes
one tick on the device) in the traced window, on the device's clock: the
cadence a user's stream gets, the wait for other tenants and for the host
included.  A tick lasts tens of milliseconds, too short for the host's
clock to time one by one."""

import statistics


def read(m):
    if not m.tick_op:
        return None
    r = m.reading
    gaps = []
    for d in r.devices.values():
        starts = sorted(s for n, s, _ in d["modules"] if n == m.tick_op and r.lo <= s < r.hi)
        gaps += [(b - a) / 1e6 for a, b in zip(starts, starts[1:])]
    if len(gaps) < 2:
        return None
    # "inclusive": with few gaps the tail stays among the gaps read.
    return statistics.quantiles(gaps, n=20, method="inclusive")[-1]
