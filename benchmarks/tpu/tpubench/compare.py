"""The comparison that decides ``correct``: numbers, each with its limit.

Every number is a worst case over what the timed path produced, measured
against the plain reference:

* ``loss_gap`` — ``max_k |L_k - L*_k| / |L*_k|`` over the checked rounds;
* ``norm_gap`` — ``max_{k,i} |u_ki - u*_ki| / max(u*_ki, median_i u*_ki)``,
  the per-client norms the sampling plan was drawn from;
* ``mask_mismatch`` — participation draws that differ (exact: limit 0);
* ``agg_gap`` / ``update_gap`` — by the worst leaf, the gap between the
  program's and the reference's norm of the first round's server update
  (the Eq. 2 aggregate as the server applied it; only where the entry reads
  it) and of the params' change after the checked rounds, each over
  ``max(ref leaf norm, median leaf norm)``.  Leaves whose reference change
  is under a thousandth of the median leaf's are left out: they move by
  round-off alone.
"""

from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE = 1e-3


def _pair(prog, ref, dtype=np.float64):
    prog, ref = np.asarray(prog, dtype), np.asarray(ref, dtype)
    if prog.shape != ref.shape:
        raise ValueError(f"program read {prog.shape}, the reference {ref.shape}")
    return prog, ref


def loss_gap(prog, ref) -> float:
    prog, ref = _pair(prog, ref)
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def norm_gap(prog, ref) -> float:
    prog, ref = _pair(prog, ref)
    floor = np.maximum(ref, np.median(ref, axis=-1, keepdims=True))
    return float(np.max(np.abs(prog - ref) / floor))


def mask_mismatch(prog, ref) -> int:
    prog, ref = _pair(prog, ref, bool)
    return int(np.sum(prog != ref))


def counted_leaves(ref_change: dict) -> list:
    """The leaves the reference moves by more than round-off: at least a
    thousandth of the median leaf's change."""
    med = float(np.median(list(ref_change.values())))
    return [k for k, v in ref_change.items() if v >= NEGLIGIBLE * med]


def leaf_gap(prog: dict, ref: dict, leaves: list) -> float:
    """Worst leaf of ``|prog - ref| / max(ref, median ref)``.  Where the
    reference did not move at all (a round in which no client was sent), the
    program must not either: any movement of its own reads 1, all of it
    wrong."""
    med = float(np.median([ref[k] for k in leaves]))
    gaps = []
    for k in leaves:
        floor = max(ref[k], med)
        if floor > 0.0:
            gaps.append(abs(prog[k] - ref[k]) / floor)
        else:
            gaps.append(0.0 if prog[k] == 0.0 else 1.0)
    return max(gaps)


def numbers(prog: dict, ref: dict) -> dict:
    """Every number of the comparison, from the program's readings and the
    reference's (each a dict of ``losses``, ``norms``, ``masks``,
    ``change`` and, where read, ``first``)."""
    leaves = counted_leaves(ref["change"])
    out = {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
           "norm_gap": norm_gap(prog["norms"], ref["norms"]),
           "mask_mismatch": float(mask_mismatch(prog["masks"], ref["masks"]))}
    if prog.get("first") is not None:
        out["agg_gap"] = leaf_gap(prog["first"], ref["first"], leaves)
    out["update_gap"] = leaf_gap(prog["change"], ref["change"], leaves)
    return out


def check(entry, state: dict) -> dict:
    """The entry's program state released, then its readings against the
    plain reference's."""
    entry.release(state)
    return numbers(state["program"], entry.reference(state))


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, [(name, value, limit)])``: every number at or under its
    limit, and finite; a number without a limit is an error."""
    rows, ok = [], True
    for name, value in numbers.items():
        limit = limits[name]
        ok = ok and not math.isnan(value) and value <= limit
        rows.append((name, value, limit))
    return ok, rows
