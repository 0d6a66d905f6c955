"""The HAR surrogate the benchmark's HAR traffic draws from.

A copy of the generator in ``repro.data.har`` (same formula, same
parameters, same seeds), kept here so that no change to the program can
move the benchmark's inputs.  It mirrors the published structure of UCI
HAR: 30 subjects, 6 activities, 561 features in [-1, 1], samples clustered
per (subject, activity), subjects 9/14/16/19/25 held out as the drift set.

Unlike the program's copy it keeps the clusters apart: ``pool[c]`` with
``c = subject * 6 + activity`` holds that cluster's samples, which is what
a per-stream generator that follows one subject through activity bouts
needs.  ``init_x``/``init_y`` are the known subjects' training split, the
batch a head starts from.
"""

from __future__ import annotations

import dataclasses

import numpy as np

N_SUBJECTS = 30
N_CLASSES = 6
N_FEATURES = 561
DRIFT_SUBJECTS = (9, 14, 16, 19, 25)


@dataclasses.dataclass
class Surrogate:
    pool: np.ndarray  # (30 * 6, K, 561) float32, cluster = subject * 6 + class
    init_x: np.ndarray  # (n, 561) float32, known subjects' training split
    init_y: np.ndarray  # (n,) int32


def generate(seed: int, samples_per_subject_class: int = 56,
             subject_sigma: float = 0.17, class_sep: float = 0.13,
             noise_sigma: float = 0.35, hard_frac: float = 0.15,
             hard_scale: float = 1.8) -> Surrogate:
    """x[s, c, i] = tanh(mu[c] + scale_s * delta[s, c] + sigma_i * eps_i),
    with held-out subjects at scale 1.45 and a ``hard_frac`` minority of
    samples at ``hard_scale`` times the noise."""
    rng = np.random.default_rng(seed)
    mu = rng.normal(0.0, class_sep, size=(N_CLASSES, N_FEATURES))
    mu[4] = mu[3] + rng.normal(0.0, 0.35 * class_sep, size=N_FEATURES)
    k = samples_per_subject_class
    pool = np.empty((N_SUBJECTS * N_CLASSES, k, N_FEATURES), np.float32)
    for s in range(N_SUBJECTS):
        srng = np.random.default_rng(seed * 1009 + 7 * s + 1)
        scale = 1.45 if s in DRIFT_SUBJECTS else 1.0
        delta = srng.normal(0.0, subject_sigma, size=(N_CLASSES, N_FEATURES))
        for c in range(N_CLASSES):
            center = mu[c] + scale * delta[c]
            eps = srng.normal(0.0, noise_sigma, size=(k, N_FEATURES))
            hard = (srng.uniform(size=k) < hard_frac).astype(np.float64)
            sigma = (0.35 + hard * (hard_scale - 0.35))[:, None]
            pool[s * N_CLASSES + c] = np.tanh(center[None, :] + sigma * eps)
    x = pool.reshape(-1, N_FEATURES)
    y = np.repeat(np.tile(np.arange(N_CLASSES, dtype=np.int32), N_SUBJECTS), k)
    sub = np.repeat(np.arange(N_SUBJECTS, dtype=np.int32), N_CLASSES * k)
    perm = rng.permutation(len(x))
    x, y, sub = x[perm], y[perm], sub[perm]
    keep = ~np.isin(sub, DRIFT_SUBJECTS)
    n_train = int(0.7 * int(keep.sum()))
    return Surrogate(pool=pool, init_x=x[keep][:n_train],
                     init_y=y[keep][:n_train])
