"""The heads' share of their roofline: the least time the plan (x, x
alpha, beta) and the learn (P and beta of the rows that received labels)
need at the chip's peaks, over the device time of the plan and learn
executables."""

from bench import costs
from bench.metrics.head_ms import HEAD_MODULES


def read(m):
    t = m.reading.module_s(HEAD_MODULES)
    if not t or not m.work.get("head"):
        return None
    return 100.0 * sum(costs.min_time(f, b, m.peaks) for f, b in m.work["head"]) / t
