"""The bytes of every object a cell writes, from the seed alone.

The reference of the write cells: what object `index` of a run with
`seed` holds is what was drawn here, whatever the program stored.
Incompressible (uniform random) bytes, as `rados bench` writes a
pattern that no compressor is configured for.
"""

from __future__ import annotations

import numpy as np


def payload(seed: int, index: int, size: int) -> bytes:
    return np.random.default_rng((seed, index)).bytes(size)


def object_name(seed: int, index: int) -> str:
    return f"perfbench_{seed}_{index}"
