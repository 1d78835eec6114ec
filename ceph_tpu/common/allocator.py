"""glibc malloc's thresholds, fixed once for the process.

glibc moves two of its thresholds by what a process happens to free:
freeing a chunk that came from ``mmap`` raises M_MMAP_THRESHOLD to that
chunk's size (up to 32 MiB) and M_TRIM_THRESHOLD to twice that.  An OSD
handles object-sized buffers all the time — a 4 MiB op is copied about
ten times between the socket, the stripe arrays and the reply — and
where the thresholds end up early in a process's life decides whether
each of those buffers is mapped and page-faulted anew or reused from
the heap, for the rest of that life.  On the v5e host the same pool
read a lone 4 MiB degraded read at 210 ms in one process and 232 ms in
the next, same seed, same machine (PERF.md, PR 31); with the thresholds
fixed every process is the fast one.  A Ceph OSD fixes its allocator's
behaviour from its environment too (tcmalloc's thread cache, in
/etc/default/ceph).

The PG mapping service pins them as well: every epoch of a large pool
it builds and drops tables of the pool's size (five ladder operands of
4-12 MB for a million PGs), and with glibc's moving thresholds each
epoch's 54 MB came from the top of the heap, was trimmed back to the
system when freed and page-faulted anew.  On the v5e host that build
took 31-46 ms of an epoch in one process and 4 ms in the next
(PERF.md, PR 38); pinned, it is 4 ms in every one.

Setting either threshold turns glibc's adjustment off.  No option: one
value serves every daemon, and a libc without ``mallopt`` is left as it
is.
"""

from __future__ import annotations

import ctypes

_M_TRIM_THRESHOLD = -1
_M_MMAP_THRESHOLD = -3
#: the ceiling of glibc's own dynamic threshold on 64-bit
MMAP_THRESHOLD = 32 << 20
#: free memory kept at the top of the heap before it goes back to the
#: system: more than the buffers of the ops in flight ever add up to
TRIM_THRESHOLD = 1 << 30

_pinned = False


def pin_malloc_thresholds() -> bool:
    """Fix the two thresholds; True if this process has them fixed.
    Cheap to call again."""
    global _pinned
    if _pinned:
        return True
    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt.restype = ctypes.c_int
    _pinned = bool(mallopt(_M_MMAP_THRESHOLD, MMAP_THRESHOLD)
                   and mallopt(_M_TRIM_THRESHOLD, TRIM_THRESHOLD))
    return _pinned
