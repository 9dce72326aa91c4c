"""Traffic generation: the seeded sources every loop draws from.

A mix's data file ``bench/traffic/<mix>.json`` names its ``loop``, and
the harness runs the loop of that name, ``bench/loops/<loop>.py``, with
the file's other keys as its parameters. A new kind of arrivals is a new
loop file; a new mix of a known kind is a new data file.

Audio is a seeded pool of N(0, 1) samples (``pool`` long). A packet or a
clip is a slice of it at a seeded offset, as the repo's
``benchmarks/load_gen.py`` slices its pool.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, for any whole seed >= 0."""
    return np.random.default_rng([int(seed), sum(map(ord, stream))])


def pool(seed: int, mix: dict) -> np.ndarray:
    return rng(seed, "pool").standard_normal(int(mix["pool"])) \
        .astype(np.float32)
