"""Data of the Knowledge Bank cells, made from the run's seed.

Everything here belongs to the yardstick, not to the system under test:
the bank contents (a copy of the clustered Gaussian mixture that IVF
targets), queries drawn from the same mixture, and YCSB's scrambled
zipfian key chooser. The same seed always gives the same data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# YCSB's 64-bit FNV-1a constants (site.ycsb.Utils.fnvhash64)
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)


def seed32(seed: int, stream: int = 0) -> int:
    """A 32-bit key for ``jax.random.key`` from a seed of any size."""
    return int(np.random.SeedSequence([int(seed), stream])
               .generate_state(1, np.uint32)[0])


@functools.partial(jax.jit, static_argnames=("n", "dim", "n_centers",
                                             "noise"))
def _mixture(kc, ka, kn, *, n: int, dim: int, n_centers: int, noise: float):
    centers = 2.0 * jax.random.normal(kc, (n_centers, dim))
    assign = jax.random.randint(ka, (n,), 0, n_centers)
    return centers[assign] + noise * jax.random.normal(kn, (n, dim))


def clustered_bank(n: int, dim: int, n_centers: int, noise: float,
                   seed: int) -> jax.Array:
    """(n, dim) fp32 mixture of ``n_centers`` Gaussians, made on the device
    in one jitted call (the layout of ``repro.core.ann_index.
    clustered_bank``, copied so that the yardstick cannot move)."""
    kc, ka, kn = jax.random.split(jax.random.key(seed32(seed, 1)), 3)
    return _mixture(kc, ka, kn, n=n, dim=dim, n_centers=n_centers,
                    noise=noise)


def mixture_queries(n: int, dim: int, n_centers: int, noise: float,
                    seed: int, stream: int) -> jax.Array:
    """(n, dim) queries from the bank's own mixture: the same centers
    (same center key), fresh assignments and noise."""
    kc = jax.random.split(jax.random.key(seed32(seed, 1)), 3)[0]
    _, ka, kn = jax.random.split(jax.random.key(seed32(seed, 100 + stream)),
                                 3)
    return _mixture(kc, ka, kn, n=n, dim=dim, n_centers=n_centers,
                    noise=noise)


def fnv1a64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``fnvhash64`` over the 8 little-endian bytes of each value."""
    v = values.astype(np.uint64)
    h = np.full(v.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            h *= _FNV_PRIME
            v >>= np.uint64(8)
    return h


class ScrambledZipf:
    """YCSB ``ScrambledZipfianGenerator`` over ``n`` keys: a zipfian rank
    (exponent ``theta``) hashed with FNV-1a and taken modulo ``n``, so the
    hot keys are spread over the key space instead of packed at its
    start. ``theta=0`` gives uniform keys."""

    def __init__(self, n: int, theta: float):
        self.n, self.theta = n, theta
        if theta > 0:
            w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
            self._cdf = np.cumsum(w) / w.sum()
            self._key_of_rank = (fnv1a64(np.arange(n)) %
                                 np.uint64(n)).astype(np.int32)

    def sample(self, rng: np.random.Generator, size) -> np.ndarray:
        if self.theta <= 0:
            return rng.integers(0, self.n, size, dtype=np.int32)
        ranks = np.searchsorted(self._cdf, rng.random(size), side="right")
        return self._key_of_rank[np.minimum(ranks, self.n - 1)]
