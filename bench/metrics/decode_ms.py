"""Device time per tick of the backbone decode (the ``bench_decode``
executable)."""


def read(m):
    n = m.reading.module_count(["bench_decode"])
    if not n:
        return None
    return 1e3 * m.reading.module_s(["bench_decode"]) / n
