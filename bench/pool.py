"""The seeded image source the benchmark hands the program.

Images follow the program's synthetic ImageNet-like formula (a rank-8
smooth template per class, normalised, plus Gaussian noise of scale 0.5;
``data/synthetic.py:SyntheticImageData``), with each template normalised
by its own standard deviation so that only the classes drawn are built.
The whole pool is drawn on the device in one jitted call at set-up and
kept on the host; ``batch_at`` hands out views of it, so the measured
window pays for what a user's training run pays for (the program's
augmentation, feed and step), not for drawing random numbers.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("n", "size", "channels",
                                              "classes", "rank"))
def _draw(key, *, n, size, channels, classes, rank, noise=0.5):
    kl, ku, kw, kn = jax.random.split(key, 4)
    labels = jax.random.randint(kl, (n,), 0, classes, dtype=jnp.int32)
    u = jax.random.normal(ku, (classes, size, rank), jnp.float32)
    w = jax.random.normal(kw, (classes, rank, size * channels), jnp.float32)
    t = jnp.einsum("nir,nrj->nij", u[labels], w[labels],
                   precision=jax.lax.Precision.HIGHEST)
    t = t / (jnp.std(t, axis=(1, 2), keepdims=True) + 1e-6)
    x = t.reshape(n, size, size, channels)
    x = x + noise * jax.random.normal(kn, x.shape, jnp.float32)
    return x, labels


class ImagePool:
    """``n_batches`` distinct batches of ``batch`` images and labels.

    ``batch_at(step)`` returns batch ``(step + offset) % n_batches``;
    ``offset`` lets a later run of the trainer, whose steps count from
    0 again, carry on through the pool."""

    def __init__(self, key, *, batch: int, n_batches: int, size: int,
                 channels: int, classes: int, rank: int = 8):
        x, y = _draw(key, n=batch * n_batches, size=size,
                     channels=channels, classes=classes, rank=rank)
        self.images = np.asarray(x)
        self.labels = np.asarray(y)
        self.batch = batch
        self.n_batches = n_batches
        self.offset = 0

    def rows(self, i: int) -> Dict[str, np.ndarray]:
        sl = slice(i * self.batch, (i + 1) * self.batch)
        return {"images": self.images[sl], "labels": self.labels[sl]}

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        return self.rows((step + self.offset) % self.n_batches)
