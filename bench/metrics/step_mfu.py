"""The whole step's share of the chip's peak: the model operations every
piece of work of the traced ticks needed (backbone decode, head plan and
learn, from ``costs``) over the traced window times chips times the bf16
peak."""


def read(m):
    flops = sum(f for work in m.work.values() for f, _ in work)
    if not flops:
        return None
    return 100.0 * flops / (m.reading.window_s * m.chips * m.peaks["bf16_flops_per_s"])
