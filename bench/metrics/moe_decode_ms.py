"""Device time per tick of the latent-attention, expert-share backbone
decode (the ``bench_decode`` executable of a ``decode_moe`` cell)."""

from bench.metrics.decode_ms import read  # noqa: F401
