"""Seeded training traffic: a ring of distinct batches held in host memory.

The arithmetic is the repository's synthetic classification set (a class
prototype plus 0.5 x normal noise per row, labels uniform), made here so
that the benchmark owns it. It is drawn on the device in one jitted call,
with each row's prototype drawn from its label's key, so no table of all
classes' prototypes is ever held, and then copied to host memory once:
the window feeds every step a batch from the host, as a training loop
does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NOISE = 0.5


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole ``seed`` below 2**64.

    ``jax.random.key`` keeps only the low 32 bits of its argument, so
    seeds 2**32 apart would collide; the high bits are folded in.
    """
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames=("ring", "batch", "image", "n_classes"))
def _ring(key, *, ring, batch, image, n_classes):
    k_lab, k_proto, k_noise = jax.random.split(key, 3)
    labels = jax.random.randint(k_lab, (ring, batch), 0, n_classes, jnp.int32)

    def row(label, r):
        proto = jax.random.normal(jax.random.fold_in(k_proto, label), image, jnp.float32)
        noise = jax.random.normal(jax.random.fold_in(k_noise, r), image, jnp.float32)
        return proto + NOISE * noise

    rows = jnp.arange(ring * batch, dtype=jnp.int32)
    images = jax.vmap(row)(labels.reshape(-1), rows)
    return images.reshape(ring, batch, *image), labels


def batch_ring(
    seed: int, *, ring: int, batch: int, image, n_classes: int
) -> tuple[np.ndarray, np.ndarray]:
    """``ring`` distinct batches: images ``[R, B, C, H, W]`` float32 and
    labels ``[R, B]`` int32, in host memory. A pure function of ``seed``."""
    key = jax.random.fold_in(seed_key(seed), 0x5EED)
    images, labels = _ring(
        key, ring=ring, batch=batch, image=tuple(image), n_classes=n_classes
    )
    return np.asarray(images), np.asarray(labels)


def param_key(seed: int) -> jax.Array:
    """The key that the program's and the reference's weights come from."""
    return jax.random.fold_in(seed_key(seed), 0x9A7A)
