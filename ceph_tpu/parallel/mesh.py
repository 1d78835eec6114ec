"""Device-mesh construction.

One function builds every mesh in the framework so axis naming stays consistent:
``dp`` (data/placement parallel) x ``ec`` (erasure-shard parallel).  On a v5e pod
slice the mesh should be laid out so ``ec`` rides the minor (fastest ICI) axis —
`mesh_utils.create_device_mesh` handles the physical layout when available.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh


def factor_devices(n: int, ec_max: int = 4, ec_divides: int | None = None) -> tuple[int, int]:
    """Split n devices into (dp, ec).

    Without ``ec_divides`` the split is pure data parallelism (ec=1): an
    ec axis only helps when the k+m chunk count is KNOWN to divide it —
    otherwise chunk rows split unevenly across the ec axis and shard_map
    callers fail on the ragged block.  (The old default picked the
    largest ec <= ec_max whenever it divided n, handing ec=4 meshes to
    callers that never promised any chunk-axis divisibility.)  With
    ``ec_divides`` (the k+m chunk count), ec is the largest divisor of n
    that is <= ec_max and divides it, so chunk rows split evenly; ec=1
    remains the fallback for awkward n.
    """
    if ec_divides is None:
        return n, 1
    best = 1
    for d in range(1, n + 1):
        if n % d or d > ec_max:
            continue
        if ec_divides % d:
            continue
        best = d
    return n // best, best


def make_mesh(n_devices: int | None = None, *, ec: int | None = None,
              ec_divides: int | None = None) -> Mesh:
    """Build a ("dp", "ec") mesh over the first n_devices jax devices."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if n > len(devices):
        raise ValueError(f"asked for {n} devices, only {len(devices)} present")
    if ec is None:
        dp, ec = factor_devices(n, ec_divides=ec_divides)
    else:
        if n % ec:
            raise ValueError(f"ec={ec} does not divide n={n}")
        dp = n // ec
    from jax.experimental import mesh_utils
    dev_array = mesh_utils.create_device_mesh((dp, ec), devices=devices[:n])
    return Mesh(dev_array, axis_names=("dp", "ec"))


