"""Operations and bytes of one exact top-k scan, from the scanned
buffer's shape alone, so the count is the same whatever implements it.

The scan reads every row of the ``(rows, cols)`` float32 buffer
(embedding plus flag columns) once, reads the ``(b, cols)`` query
block, and writes ``(b, k)`` scores and indices; it does one
multiply-add per query, row and column.
"""
from __future__ import annotations


def ops(b: int, rows: int, cols: int) -> int:
    return 2 * b * rows * cols


def bytes_moved(b: int, rows: int, cols: int, k: int) -> int:
    return 4 * rows * cols + 4 * b * cols + 8 * b * k


def least_seconds(b: int, rows: int, cols: int, k: int,
                  peaks: dict) -> tuple:
    """(seconds, bound): the larger of the operation and byte times at
    the chip's peaks, and which of the two it is."""
    t_ops = ops(b, rows, cols) / peaks["bf16_flops_per_s"]
    t_bytes = bytes_moved(b, rows, cols, k) / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
