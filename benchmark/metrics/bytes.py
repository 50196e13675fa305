"""The least bytes a kernel has to move for a call, from the shapes of the
call alone: what a roofline share divides by the chip's bandwidth. They
count the published widths of the data, not the lanes the program happens to
keep, so a program that narrows its lanes gains and one that pads them
loses.
"""
from __future__ import annotations

from typing import Sequence

KEY_BYTES = 8  # (ss_item_sk, ss_ticket_number) packed into one int64


def probe_least_bytes(slab_rows: int, source_rows: int, matched: int) -> int:
    """The resident probe: every key the table holds and every key of the
    source read once; written, one bit a source row (matched or not) and a
    (slab row, source row) pair of int32 for each match."""
    return (KEY_BYTES * (slab_rows + source_rows)
            + (source_rows + 7) // 8 + 8 * matched)


def mask_least_bytes(file_rows: Sequence[int], column_bytes: Sequence[int]) -> int:
    """The residual mask over the files a scan has to look into: each
    predicate column of each such file read once at its published width,
    and one byte a row of mask written."""
    return sum(file_rows) * (sum(column_bytes) + 1)
