"""The backbone decode's share of its roofline: the least time its needed
operations and bytes take at the chip's peaks, over its device time."""

from bench import costs


def read(m):
    t = m.reading.module_s(["bench_decode"])
    if not t or not m.work.get("decode"):
        return None
    return 100.0 * sum(costs.min_time(f, b, m.peaks) for f, b in m.work["decode"]) / t
