"""Seeds: the driver's `--seed` to blocks of u32 simulation seeds, and a
lane's base key as a reference recomputes it."""

from __future__ import annotations

import numpy as np

_M64 = (1 << 64) - 1
SEED_MASK = (1 << 31) - 1


def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


def block(seed: int, index: int, size: int) -> np.ndarray:
    """The `index`-th block of `size` simulation seeds for run seed `seed`.
    Blocks of one run never overlap (index -1 is the warm-up); any whole
    number is a valid run seed. Simulation seeds stay below 2**31 and wrap
    there: `repro.replay_device` cannot take a seed at or above 2**31
    (PERF.md, Open questions)."""
    base = _splitmix64(int(seed) & _M64) & SEED_MASK
    start = base + (int(index) + 1) * int(size)
    return ((start + np.arange(size, dtype=np.uint64)) & SEED_MASK).astype(
        np.uint32)


def rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for the run's samples (lanes to check, bundles to
    replay), independent of the simulation seeds."""
    return np.random.default_rng([int(seed) & _M64, int(stream)])


def mix(x):
    """murmur3 fmix32 on uint32 (numpy)."""
    x = np.asarray(x, np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def fold(h, word):
    """One more word mixed into a 32-bit hash: fmix32(h ^ word * golden),
    the engine's documented fold (numpy, wrapping)."""
    with np.errstate(over="ignore"):
        return mix(np.asarray(h, np.uint32) ^ (
            np.asarray(word).astype(np.uint32) * np.uint32(0x9E3779B9)))


def key_from_seed(seeds) -> np.ndarray:
    """A lane's base key from its simulation seed: the fold of the seed
    into 0x2545F491 (the engine's documented key schedule), in numpy."""
    return fold(np.uint32(0x2545F491), np.asarray(seeds, np.uint32))
