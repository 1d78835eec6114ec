"""Map-epoch consumption at the source's own pool size: the deployment
and the epochs of osdmap_churn.py on a pool of 1,048,576 PGs, where an
epoch's delta is a device diff of the packed tables and a CRUSH batch is
a million lanes.

Two things differ from the parent module.  The reference's table of the
whole map comes from perfbench/reference/crush_plain_bulk.py, which
takes a block of PGs at a time (one PG at a time is a quarter of an hour
here); per-PG answers of the followed epochs are still compared with
`crush_plain.up_of`.  And the run is held to the device diff: an epoch
whose delta the host computed fails it (`device_diffs_short`).  The
counters that say so are new in the program, so `setup` looks for them
first: a program without them fails at once, before any map is built.
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np

from perfbench.harness.cell import Check
from perfbench.reference import crush_plain, crush_plain_bulk
from perfbench.systems import osdmap_churn

SPANS = osdmap_churn.SPANS
TRAFFIC_KIND = osdmap_churn.TRAFFIC_KIND
#: the program's counters of who diffed an epoch's tables
DELTA_COUNTERS = ("delta_device_diffs", "delta_host_diffs",
                  "delta_upload_bytes")


class System(osdmap_churn.System):
    def setup(self) -> None:
        from ceph_tpu.ops import telemetry
        diffs = telemetry.mapping_summary().get("delta_device_diffs")
        if diffs is None:
            raise SystemExit(
                f"perfbench: {self.cell.config_name} needs a program that "
                f"counts its epochs' table diffs: mapping_summary() has no "
                f"delta_device_diffs")
        self._diffs_at_setup = diffs
        super().setup()

    def counters(self) -> dict:
        from ceph_tpu.ops import telemetry
        c = super().counters()
        mapping = telemetry.mapping_summary()
        for key in DELTA_COUNTERS:
            c[f"mapping.{key}"] = mapping[key]
        return c

    def _base_table(self) -> np.ndarray:
        """The reference's `up` of every PG while the map is whole,
        from the bulk reference; kept from run to run of one checkout
        under a name made of the deployment and of both reference
        files' source."""
        key = hashlib.sha256()
        for module in (crush_plain, crush_plain_bulk):
            with open(module.__file__, "rb") as f:
                key.update(f.read())
        key.update(json.dumps(self.dep, sort_keys=True).encode())
        # the directory is read from the parent module at the time of
        # the call: the tests point it elsewhere
        cache_dir = osdmap_churn.CACHE_DIR
        path = os.path.join(cache_dir,
                            f"up_table_bulk_{key.hexdigest()[:24]}.npy")
        try:
            table = np.load(path)
            if table.shape == (self.plain.pg_num, self.plain.size):
                return table
        except (OSError, ValueError):
            pass
        table = crush_plain_bulk.up_table(self.plain)
        os.makedirs(cache_dir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            np.save(f, table)
        os.replace(tmp, path)
        return table

    def verify(self) -> list[Check]:
        checks = super().verify()
        # every epoch of every phase has a previous table to be diffed
        # with (the first build is not an epoch of `_applied`), and one
        # pool: one device diff an epoch
        diffs = (self.counters()["mapping.delta_device_diffs"]
                 - self._diffs_at_setup)
        checks.append(Check("device_diffs_short",
                            max(0, self._applied - diffs), 0))
        return checks
