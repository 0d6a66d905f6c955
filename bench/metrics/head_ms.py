"""Device time per tick of the heads' plan and learn executables."""

HEAD_MODULES = ("run_plan", "run_learn", "run_learn_plan", "run_patch")


def read(m):
    if not m.ticks:
        return None
    t = m.reading.module_s(HEAD_MODULES)
    return 1e3 * t / m.ticks if t else None
