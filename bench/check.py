"""The comparison that decides ``correct``.

One number is compared, ``max_rel_err``: over every cell of every compared
viz result, ``|served - reference| / max(|reference|, 1)``.  A cell empty in
one and not in the other (``-inf`` against a finite MAX), or a result that
never came, reads ``inf``.  Its limit is kept per cell in
``bench/limits/<workload>.json`` with the readings it was set from.
"""

from __future__ import annotations

import numpy as np


def cell_errors(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest relative error of one result against its reference."""
    got = np.asarray(got, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    fin = np.isfinite(ref)
    if not np.array_equal(np.isfinite(got), fin) or not np.array_equal(got[~fin], ref[~fin]):
        return float("inf")
    err = np.abs(got[fin] - ref[fin]) / np.maximum(np.abs(ref[fin]), 1.0)
    return float(err.max(initial=0.0))


def compare(answers, reference) -> dict:
    """Compare served results with the reference.

    ``answers`` is a list of ``(filters, {viz: served array or None})``;
    ``reference`` answers ``(viz, filters)``.  Returns the largest error,
    overall and per ring, and how many results were compared.
    """
    worst = {"max_rel_err": 0.0}
    n = 0
    for filters, screen in answers:
        for viz, got in screen.items():
            ring = reference.vizzes[viz]["ring"]
            err = float("inf") if got is None else cell_errors(got, reference.answer(viz, filters))
            worst["max_rel_err"] = max(worst["max_rel_err"], err)
            worst[f"worst_{ring}"] = max(worst.get(f"worst_{ring}", 0.0), err)
            n += 1
    worst["results"] = n
    return worst
