"""The one traffic generator.  A mix is a data file, ``traffic/<mix>.json``:

- ``fact``: the ``superlu_dist_tpu.Fact`` mode of every call in the window
  (``SamePattern_SameRowPerm`` refactors new values on the analysed
  pattern);
- ``shift``: ``{"lo", "hi", "strata"}``: call k factors
  A_k = A + s_k·diag(A).  Every seed gets the same set of shifts, the
  midpoints of ``strata`` equal slices of [lo, hi], in an order drawn from
  the seed, so seeds change the order of the work and not its amount.

One caller drives the calls in a closed loop: the next call starts when
the last one returned.  Each call solves one right-hand side: its
solution x_k is drawn from the seed (standard normal, one stream per call)
and its right-hand side is b_k = A_k·x_k in float64."""

import dataclasses

import numpy as np

from benchmark import reference
from benchmark.matrices import Matrix


@dataclasses.dataclass
class Request:
    index: int
    shift: float
    values: np.ndarray      # A_k's values on A's pattern
    x_true: np.ndarray
    b: np.ndarray


class Mix:
    def __init__(self, params: dict, matrix: Matrix, seed: int):
        self.fact = params["fact"]
        self.matrix = matrix
        self.seed = int(seed)
        shift = params["shift"]
        k = int(shift["strata"])
        mids = shift["lo"] + (np.arange(k) + 0.5) * (
            (shift["hi"] - shift["lo"]) / k)
        order = np.random.default_rng([self.seed, 0]).permutation(k)
        self.shifts = mids[order]
        rows = np.repeat(np.arange(matrix.n), np.diff(matrix.indptr))
        self._diag = np.flatnonzero(rows == matrix.indices)

    def values(self, shift: float) -> np.ndarray:
        vals = self.matrix.data.copy()
        vals[self._diag] *= 1.0 + shift
        return vals

    def request(self, index: int) -> Request:
        """Call ``index`` of the run (0 is the set-up's factorization)."""
        shift = float(self.shifts[index % len(self.shifts)])
        vals = self.values(shift)
        rng = np.random.default_rng([self.seed, 1, index])
        x_true = rng.standard_normal(self.matrix.n)
        b = reference.matvec(self.matrix, vals, x_true)
        return Request(index, shift, vals, x_true, b)
