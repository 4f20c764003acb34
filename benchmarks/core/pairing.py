"""Which request an output answers, and whether it answers it correctly.

The output contract carries no record id and the bolt tasks finish out of
order, so an output is known only by its value: it belongs to the pool row
whose reference prediction is nearest, and to the oldest request for that row
that has no answer yet. Two requests for the same row may thereby exchange
their answers; that exchanges two latencies of one multiset and changes no
quantile.
"""

from __future__ import annotations

import numpy as np


def match_rows(outputs: np.ndarray, reference: np.ndarray, tol: float):
    """For each output row the index of the nearest reference row (by
    Euclidean distance) and its distance from it as a share of that row's
    length. An output further than ``tol`` from its nearest row answers
    nothing: its index is -1.

    The distance is taken over all classes and not at the worst one: the
    rounding of a bfloat16 forward moves every probability by a few percent
    of itself, independently, so its sum over a thousand classes is steady
    from row to row and seed to seed, while its largest single value has a
    tail that no tolerance short of another row's answer covers."""
    outputs = np.asarray(outputs, np.float64)
    reference = np.asarray(reference, np.float64)
    if outputs.ndim != 2 or outputs.shape[1] != reference.shape[1]:
        raise ValueError(f"outputs {outputs.shape} against reference "
                         f"{reference.shape}")
    idx = np.empty(len(outputs), np.int64)
    err = np.empty(len(outputs), np.float64)
    ref_sq = (reference ** 2).sum(1)
    for a in range(0, len(outputs), 4096):
        got = outputs[a:a + 4096]
        d2 = ref_sq[None, :] - 2.0 * got @ reference.T
        near = d2.argmin(1)
        idx[a:a + 4096] = near
        err[a:a + 4096] = np.sqrt(((got - reference[near]) ** 2).sum(1)
                                  / ref_sq[near])
    idx[~(err <= tol)] = -1  # also catches NaN
    return idx, err


def row_separation(reference: np.ndarray) -> float:
    """Smallest distance between two reference rows, as a share of the
    longer row's length."""
    reference = np.asarray(reference, np.float64)
    length = np.sqrt((reference ** 2).sum(1))
    apart = np.sqrt(((reference[:, None, :] - reference[None, :, :]) ** 2)
                    .sum(-1)) / np.maximum(length[:, None], length[None, :])
    return float((apart + np.eye(len(reference)) * 1e9).min())


def pair_latencies(req_due, req_row, out_ts, out_row):
    """Delivery time of each request, NaN where none came: outputs of one
    pool row go to that row's requests in order of their due times, oldest
    first. Returns ``(delivered_at, n_unclaimed)`` where the second counts
    outputs that matched a row with no request left to answer (duplicates
    under at-least-once delivery)."""
    req_due = np.asarray(req_due, np.float64)
    req_row = np.asarray(req_row, np.int64)
    out_ts = np.asarray(out_ts, np.float64)
    out_row = np.asarray(out_row, np.int64)
    delivered = np.full(len(req_due), np.nan)
    unclaimed = 0
    for row in np.unique(out_row[out_row >= 0]):
        reqs = np.flatnonzero(req_row == row)
        reqs = reqs[np.argsort(req_due[reqs], kind="stable")]
        outs = np.sort(out_ts[out_row == row])
        n = min(len(reqs), len(outs))
        delivered[reqs[:n]] = outs[:n]
        unclaimed += len(outs) - n
    return delivered, unclaimed


def quantile(values, q: float) -> float:
    """The ``q`` quantile (0..1) by linear interpolation; NaN when empty."""
    values = np.asarray(values, np.float64)
    return float(np.quantile(values, q)) if len(values) else float("nan")
