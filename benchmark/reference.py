"""The plain reference: float64 arithmetic on the benchmark's own copy of
each matrix, with nothing taken from the program.

Each right-hand side is b = A_k·x_true for an x_true drawn from the seed,
so every answer x the program returns is judged against x_true itself:

- ``ferr``: forward error, max |x - x_true| / max |x_true|;
- ``berr``: normwise backward error in the max norm,
  max |b - A_k x| / (‖A_k‖∞ max |x| + max |b|).

A run is correct when the largest of each over the answers checked stays
at or under the configuration's limit (``check`` in its file)."""

import numpy as np

NUMBERS = ("ferr", "berr")


def matvec(m, values, x):
    """A·x in float64 for A = (m's pattern, values)."""
    x = np.asarray(x, dtype=np.float64)
    return np.add.reduceat(values * x[m.indices], m.indptr[:-1])


def errors(m, values, x, x_true, b) -> dict:
    x = np.asarray(x, dtype=np.float64)
    if not np.all(np.isfinite(x)):
        return {"ferr": float("inf"), "berr": float("inf")}
    anorm = float(np.max(np.add.reduceat(np.abs(values), m.indptr[:-1])))
    r = b - matvec(m, values, x)
    return {
        "ferr": float(np.max(np.abs(x - x_true)) / np.max(np.abs(x_true))),
        "berr": float(np.max(np.abs(r))
                      / (anorm * np.max(np.abs(x)) + np.max(np.abs(b)))),
    }
