"""The least bytes the filter-and-sum kernel has to move for a query, from
the shapes alone, as `bytes.py` counts for the probe and the mask: what
`agg_roofline` divides by the chip's bandwidth. The published widths of the
columns count (TPC-H: a date is 4 bytes, a decimal(15,2) 8), not the lanes
the program keeps, which also hold a byte of validity a value.
"""
from __future__ import annotations

from typing import Sequence

PARTIAL_BYTES = 8  # one int64 partial sum


def aggregate_least_bytes(file_rows: Sequence[int],
                          column_bytes: Sequence[int], sums: int = 1) -> int:
    """Every column the query reads, of every file planning leaves, read
    once at its published width; written, one partial of each sum a file."""
    return (sum(file_rows) * sum(column_bytes)
            + PARTIAL_BYTES * sums * len(file_rows))
