"""Matrix generators, one module per generator named in a configuration's
``generator`` key.  Each module's ``build(**params)`` returns a
:class:`benchmark.matrices.Matrix`.  They are the benchmark's own copies of
the stencils, so that no change to the program can change the matrix."""

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class Matrix:
    """A square CSR matrix in float64: row pointers (int64), sorted column
    indices (int32) and values, with the grid it was stamped on."""

    n: int
    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray
    grid_shape: tuple

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])


def stencil(grid_shape, diag, offsets):
    """Stamp a constant-coefficient stencil on a grid with Dirichlet
    boundaries.  ``offsets`` holds ``(axis, step, value)``: row ``i`` gets
    ``value`` in the column of its neighbour ``step`` cells along ``axis``."""
    idx = np.arange(int(np.prod(grid_shape))).reshape(grid_shape)
    rows, cols, vals = [idx.ravel()], [idx.ravel()], [np.full(idx.size, diag)]
    for axis, step, value in offsets:
        src = [slice(None)] * idx.ndim
        dst = [slice(None)] * idx.ndim
        src[axis] = slice(max(step, 0), idx.shape[axis] + min(step, 0))
        dst[axis] = slice(max(-step, 0), idx.shape[axis] + min(-step, 0))
        r = idx[tuple(dst)].ravel()
        rows.append(r)
        cols.append(idx[tuple(src)].ravel())
        vals.append(np.full(r.size, value))
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    vals = np.concatenate(vals).astype(np.float64)
    n = idx.size
    order = np.lexsort((cols, rows))
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return Matrix(n=n, indptr=indptr, indices=cols[order].astype(np.int32),
                  data=vals[order], grid_shape=tuple(grid_shape))


def build(generator: str, params: dict) -> Matrix:
    """The matrix of ``benchmark/matrices/<generator>.py`` at ``params``."""
    return importlib.import_module(
        f"benchmark.matrices.{generator}").build(**params)
