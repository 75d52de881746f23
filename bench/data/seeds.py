"""Seed handling shared by the generators.

``--seed`` is any whole number, larger than 32 bits may hold. It is hashed
by numpy's SeedSequence into the words that key both numpy's generator (for
host-side draws) and JAX's (for on-device draws), so every seed gives a
distinct, reproducible stream on either side.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int, stream: str, n_words: int = 2) -> np.ndarray:
    """`n_words` uint32 words for the named `stream` of `seed`."""
    tag = [ord(c) for c in stream]
    ss = np.random.SeedSequence([int(seed) % (1 << 64)] + tag)
    return ss.generate_state(n_words, np.uint32)


def host_rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, stream, 4))


def device_key(seed: int, stream: str):
    import jax
    w = seed_words(seed, stream, 2)
    return jax.random.fold_in(jax.random.key(int(w[0])), int(w[1]))
