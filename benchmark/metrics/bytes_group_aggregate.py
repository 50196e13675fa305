"""The least bytes the grouped filter-and-sum kernel has to move for a
query, from the shapes alone, as `bytes_aggregate.py` counts for the
ungrouped one: what `group_agg_roofline` divides by the chip's bandwidth.
The published widths of the columns count (TPC-H: a date is 4 bytes, a
decimal(15,2) 8, a char(1) 1), not the lanes the program keeps, which hold
a flag as a 4-byte dictionary code and a byte of validity a value.
"""
from __future__ import annotations

from typing import Sequence

PARTIAL_BYTES = 8  # one int64 partial


def group_aggregate_least_bytes(file_rows: Sequence[int],
                                column_bytes: Sequence[int], aggregates: int,
                                groups: int) -> int:
    """Every column the query reads, of every file planning leaves, read
    once at its published width; written, one partial of each aggregate a
    group and a file (a file's groups cannot be merged with another's
    before the dictionaries are read back, on the host)."""
    return (sum(file_rows) * sum(column_bytes)
            + PARTIAL_BYTES * aggregates * groups * len(file_rows))
