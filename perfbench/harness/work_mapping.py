"""What the mapping service's device work has to do, counted from
shapes alone (as harness/work.py counts the kernels').

None of these functions knows which program does the work.
"""

from __future__ import annotations


def table_diff_work(rows: int, row_bytes: int) -> dict:
    """Which rows of two tables of `rows` rows of `row_bytes` bytes
    differ: both tables are read once and one byte of answer is written
    for each row.  No arithmetic worth counting."""
    return {"ops": 0, "bytes": 2 * rows * row_bytes + rows}
