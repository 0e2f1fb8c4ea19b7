"""Distributed row-block matrix format + sharded SpMV.

Analog of the reference's ``NRformat_loc`` (SRC/supermatrix.h:175-188) — the
distributed CSR each MPI rank holds — and of the distributed SpMV used by
iterative refinement (pdgsmv_init/pdgsmv, SRC/pdgsmv.c:31,234).

TPU-first redesign: the "ranks" are positions along the mesh's "snode"
axis.  Row blocks are the contiguous block-row partition the reference's
example drivers create (EXAMPLE/dcreate_matrix.c:239: read on rank 0,
scatter block rows).  For the SpMV, where the reference exchanges only the
needed x-entries via precomputed index lists (ind_tosend/ind_torecv), here
x is replicated across the mesh and each device computes its row block —
the gather that the reference does by point-to-point messages becomes an
XLA all-gather over ICI, which is both simpler and faster at TPU
interconnect bandwidths for the n·nrhs vectors involved.

CSR padding makes the local blocks static-shape so one jitted kernel
serves every shard.

Where this sits in the SPMD-first stack: these row blocks are the
INPUT/OUTPUT distribution only (matrix assembly, refinement SpMV).  The
factor/solve numeric path no longer walks a per-rank host dispatch
loop over them — on a single-controller mesh it is one shard_map
program per factor/solve (parallel/spmd.py) and on multi-process
meshes the GSPMD streamed kernels; the TreeComm host-lockstep tier
that used to carry this traffic is the A/B reference and recovery
fallback (parallel/pgssvx.py).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from superlu_dist_tpu.sparse.formats import SparseCSR


@dataclasses.dataclass
class DistributedCSR:
    """One rank's row block (NRformat_loc analog).

    Attributes mirror the reference fields: m_loc (local rows), fst_row
    (first global row), nnz_loc implicit in indptr.
    """

    n: int                 # global dimension
    m_loc: int
    fst_row: int
    indptr: np.ndarray     # (m_loc+1,) local row pointers
    indices: np.ndarray    # global column indices
    data: np.ndarray

    @property
    def nnz_loc(self) -> int:
        return int(self.indptr[-1])

    def matvec_local(self, x_global: np.ndarray) -> np.ndarray:
        """Local rows of A·x given the full x, (n,) or (n, nrhs)
        (pdgsmv's compute phase)."""
        rows = np.repeat(np.arange(self.m_loc), np.diff(self.indptr))
        x = np.asarray(x_global)
        if x.ndim > 1:
            contrib = self.data[:, None] * x[self.indices]
            out = np.zeros((self.m_loc, x.shape[1]),
                           dtype=np.result_type(self.data, x))
            np.add.at(out, rows, contrib)
            return out
        contrib = self.data * x[self.indices]
        if np.iscomplexobj(contrib):
            return (np.bincount(rows, weights=contrib.real,
                                minlength=self.m_loc)
                    + 1j * np.bincount(rows, weights=contrib.imag,
                                       minlength=self.m_loc))
        return np.bincount(rows, weights=contrib, minlength=self.m_loc)

    def abs_matvec_local(self, x: np.ndarray) -> np.ndarray:
        """Local rows of |A|·x (the berr denominator in refinement)."""
        rows = np.repeat(np.arange(self.m_loc), np.diff(self.indptr))
        contrib = np.abs(self.data) * np.asarray(x)[self.indices]
        return np.bincount(rows, weights=contrib, minlength=self.m_loc)

    def matvec_trans_local(self, x_global: np.ndarray,
                           conj: bool = False) -> np.ndarray:
        """This rank's full-length contribution to op(A)·x, op = Aᵀ/Aᴴ:
        out[j] += v̄·x[i] over local entries (i, j, v).  Sum the ranks'
        returns (tree all-reduce) to get op(A)·x — block rows of A are
        block *columns* of op(A), so every rank touches all of out."""
        rows = np.repeat(np.arange(self.m_loc), np.diff(self.indptr))
        vals = np.conj(self.data) if conj else self.data
        contrib = vals * np.asarray(x_global)[self.fst_row + rows]
        out = np.zeros(self.n, dtype=np.result_type(contrib, np.float64))
        np.add.at(out, self.indices, contrib)
        return out

    def abs_matvec_trans_local(self, x: np.ndarray) -> np.ndarray:
        """Full-length contribution to |op(A)|·x (|Aᵀ| = |A|ᵀ = |Aᴴ|)."""
        rows = np.repeat(np.arange(self.m_loc), np.diff(self.indptr))
        contrib = np.abs(self.data) * np.asarray(x)[self.fst_row + rows]
        out = np.zeros(self.n)
        np.add.at(out, self.indices, contrib)
        return out


def distribute_rows(a: SparseCSR, nparts: int) -> list[DistributedCSR]:
    """Block-row partition of A (the dcreate_matrix scatter,
    EXAMPLE/dcreate_matrix.c:66): part p gets rows [p·⌈n/P⌉, ...)."""
    n = a.n_rows
    step = -(-n // nparts)
    out = []
    for p in range(nparts):
        lo = min(p * step, n)
        hi = min(lo + step, n)
        indptr = a.indptr[lo:hi + 1].astype(np.int64)
        s, e = int(indptr[0]), int(indptr[-1])
        out.append(DistributedCSR(
            n=n, m_loc=hi - lo, fst_row=lo,
            indptr=indptr - s,
            indices=a.indices[s:e].copy(),
            data=a.data[s:e].copy()))
    return out


def gather_rows(parts: list[DistributedCSR]) -> SparseCSR:
    """Inverse of distribute_rows (pdCompRow_loc_to_CompCol_global analog,
    SRC/pdutil.c)."""
    parts = sorted(parts, key=lambda p: p.fst_row)
    n = parts[0].n
    indptr = [np.zeros(1, dtype=np.int64)]
    indices, data = [], []
    base = 0
    for p in parts:
        indptr.append(p.indptr[1:].astype(np.int64) + base)
        base += p.nnz_loc
        indices.append(p.indices)
        data.append(p.data)
    return SparseCSR(n, n, np.concatenate(indptr),
                     np.concatenate(indices), np.concatenate(data))


@functools.lru_cache(maxsize=16)
def _refine_spmv(n):
    """y = A·x over a resident pattern of ``n`` rows, the values, pattern
    and x as arguments: every operator with ``n`` rows shares the one
    program, so a refactor's new matrix does not rebuild it."""
    import jax
    import jax.numpy as jnp

    def refine_spmv(vals, rows, cols, x):
        contrib = vals[:, None] * x[cols]
        y = jnp.zeros((n, x.shape[1]), dtype=contrib.dtype)
        return y.at[rows].add(contrib)

    return jax.jit(refine_spmv)


class DeviceSpMV:
    """Single-device y = A·x with the pattern resident in HBM — the
    pdgsmv analog (SRC/pdgsmv.c:234) used by iterative refinement when
    the backend is an accelerator: the residual SpMV runs next to the
    factors instead of round-tripping A through host numpy each step.

    Setup cost (uploading rows/cols/vals once) is amortized across all
    refinement steps and repeated solves, exactly the pdgsmv_init /
    SOLVEstruct caching discipline (SRC/pdgsmv.c:31).  Computation is in
    the value dtype as uploaded (f64 residuals stay f64 — XLA emulates
    f64 on the TPU VPU; the SpMV is O(nnz), negligible next to solves).

    Presents the same matvec/abs_matvec/nnz surface the refinement loop
    uses, so it can stand in for SparseCSR there.
    """

    def __init__(self, a: SparseCSR, dtype=None):
        import jax
        import jax.numpy as jnp

        self.n_rows, self.n_cols = a.n_rows, a.n_cols
        self._nnz = a.nnz
        dtype = np.dtype(dtype or np.result_type(a.data.dtype, np.float64))
        real_width = np.dtype(dtype).type(0).real.dtype.itemsize
        if real_width >= 8 and not jax.config.read("jax_enable_x64"):
            # without x64, jnp silently downcasts f64 -> f32 and the
            # refinement residual loses exactly the digits it exists to
            # recover — refuse, so the caller falls back to the host SpMV
            raise RuntimeError(
                "DeviceSpMV needs jax_enable_x64 for a 64-bit residual")
        rows = np.repeat(np.arange(a.n_rows, dtype=np.int64),
                         np.diff(a.indptr))
        self._rows = jnp.asarray(rows)
        self._cols = jnp.asarray(a.indices.astype(np.int64))
        self._vals = jnp.asarray(a.data.astype(dtype))
        self._avals = jnp.asarray(np.abs(a.data).astype(
            dtype if not np.issubdtype(dtype, np.complexfloating)
            else np.dtype(dtype).type(0).real.dtype))
        self._fn = _refine_spmv(self.n_rows)

    @property
    def nnz(self) -> int:
        return self._nnz

    def _apply(self, vals, x):
        import jax.numpy as jnp
        x = np.asarray(x)
        squeeze = x.ndim == 1
        x2 = x[:, None] if squeeze else x
        # a new size, x width or dtype is a build: a census record
        from superlu_dist_tpu.obs.compilestats import call
        y = np.asarray(call("spmv", f"refine_spmv n{self.n_rows} "
                            f"nnz{self._nnz} k{x2.shape[1]}", self._fn,
                            vals, self._rows, self._cols, jnp.asarray(x2)))
        return y[:, 0] if squeeze else y

    def matvec(self, x: np.ndarray) -> np.ndarray:
        return self._apply(self._vals, x)

    def abs_matvec(self, x: np.ndarray) -> np.ndarray:
        # |A|·x, NOT |A|·|x| — same contract as SparseCSR.abs_matvec
        return self._apply(self._avals, x)


class ShardedSpMV:
    """Mesh-sharded y = A·x — the pdgsmv analog for refinement at scale.

    Rows are sharded along the mesh's "snode" axis (padded to equal block
    sizes so shapes are static); x is replicated, so XLA inserts no
    communication for the gather and one all-gather-free elementwise for
    the result.  Built once per pattern, reused across solves — the
    pdgsmv_init / SOLVEstruct caching discipline (SRC/pdgsmv.c:31).
    """

    def __init__(self, a: SparseCSR, mesh):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.n = a.n_rows
        nshards = int(np.prod(mesh.devices.shape))
        rows_all = np.repeat(np.arange(self.n), np.diff(a.indptr))
        nnz = a.nnz
        pad_nnz = -(-nnz // nshards) * nshards
        # pad entries: row n-1? No — use a dump row == n (result sliced off)
        rows_p = np.full(pad_nnz, self.n, dtype=np.int64)
        cols_p = np.zeros(pad_nnz, dtype=np.int64)
        vals_p = np.zeros(pad_nnz, dtype=a.data.dtype)
        rows_p[:nnz] = rows_all
        cols_p[:nnz] = a.indices
        vals_p[:nnz] = a.data
        flat = NamedSharding(mesh, P(("snode", "panel")))
        rep = NamedSharding(mesh, P())
        self._rows = jax.device_put(jnp.asarray(rows_p), flat)
        self._cols = jax.device_put(jnp.asarray(cols_p), flat)
        self._vals = jax.device_put(jnp.asarray(vals_p), flat)
        self._rep = rep
        n1 = self.n + 1

        @jax.jit
        def spmv(rows, cols, vals, x):
            contrib = vals * x[cols]
            y = jnp.zeros(n1, dtype=contrib.dtype)
            return y.at[rows].add(contrib)[:-1]

        self._fn = spmv

    def __call__(self, x: np.ndarray) -> np.ndarray:
        import jax
        import jax.numpy as jnp
        xd = jax.device_put(jnp.asarray(x), self._rep)
        return np.asarray(self._fn(self._rows, self._cols, self._vals, xd))
