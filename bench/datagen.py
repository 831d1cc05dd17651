"""Helpers the configurations' generators share: seeded columns drawn in
parallel, and ranks drawn from a truncated power law.

The tables are made on the host, where the engine keeps them: a generator
that ran on the device would leave its columns in the device's peak memory,
which the result line reports as the engine's."""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict

import numpy as np

Draw = Callable[[np.random.Generator], np.ndarray]
THREADS = 8


def draw(seed: int, columns: Dict[str, Draw]) -> Dict[str, np.ndarray]:
    """Each column drawn from a stream of its own, spawned from ``seed`` (of
    any size, also past 2**31) in the order of ``columns``; numpy
    fills large arrays without the GIL, so the draws run side by side."""
    rngs = [np.random.default_rng(s) for s in np.random.SeedSequence(int(seed)).spawn(len(columns))]
    with ThreadPoolExecutor(max_workers=THREADS) as ex:
        arrays = list(ex.map(lambda fr: fr[0](fr[1]), zip(columns.values(), rngs)))
    return dict(zip(columns, arrays))


def ints(lo: int, hi: int, size: int) -> Draw:
    """Uniform int32 in ``[lo, hi)``."""
    return lambda rng: rng.integers(lo, hi, size, dtype=np.int32)


def permutation(size: int) -> Draw:
    return lambda rng: rng.permutation(size).astype(np.int32)


def powerlaw_ranks(n: int, support: int, s: float) -> Draw:
    """``n`` ranks in ``[1, support]`` with P(rank = k) close to k**-s: the
    inverse CDF of the continuous power law on ``[1, support + 1)``, floored.
    Stands in for Zipf(s) over a finite set of ids."""
    def ranks(rng: np.random.Generator) -> np.ndarray:
        u = rng.random(n)
        a = 1.0 - s
        top = float(support + 1) ** a
        x = (1.0 - u * (1.0 - top)) ** (1.0 / a)
        return np.clip(np.floor(x), 1, support).astype(np.int32)
    return ranks
