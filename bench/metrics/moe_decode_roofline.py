"""The latent-attention, expert-share decode's share of its roofline: the
least time its needed operations and bytes (``costs_moe``: the absorbed
latent attention, the dense layer, router, shared and held experts, the
output head) take at the chip's peaks, over its device time."""

from bench.metrics.decode_roofline import read  # noqa: F401
