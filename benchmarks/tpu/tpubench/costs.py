"""Analytic operation and byte counts the per-layer metrics divide by.

``train_flops``: the model FLOPs of training on ``tokens`` samples or
tokens, ``6 x parameters x tokens`` (forward 2, backward 4 per parameter
and token; rematerialised forwards are not counted, nor are the
attention-like products of an SSM's scan).

``aggregate_bytes``: the least HBM traffic of one Eq. 2 aggregate call
over a ``(rows, d)`` client matrix: the ``(d,)`` float32 result written
once, plus every row the call must read at ``d x itemsize`` bytes.  A call
that also yields the clients' norms must read all its rows; a pure masked
aggregate needs only the rows whose scale is non-zero.
"""

from __future__ import annotations


def train_flops(params: int, tokens: int) -> float:
    return 6.0 * params * tokens


def aggregate_bytes(d: int, rows_read: int, itemsize: int) -> float:
    return 4.0 * d + float(rows_read) * d * itemsize
