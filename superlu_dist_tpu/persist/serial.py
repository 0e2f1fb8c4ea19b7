"""Versioned, integrity-checked serialization of solver state.

Bundle layout (one directory per bundle)::

    <dir>/
      MANIFEST.json            # written LAST, atomically — the commit point
      <name>.npy               # one file per array, each written tmp+rename
      structure.pkl            # pickled host structures (symbolic + plan)

``MANIFEST.json`` carries ``{format, version, kind, meta, arrays}`` where
``arrays[name]`` records the file name, byte length and sha256 digest of
every artifact.  A bundle is readable iff the manifest parses, the
version is known, and every artifact matches its digest — anything else
raises a structured :class:`CheckpointError` subclass instead of handing
back garbage factors.  Because the manifest is replaced last and every
artifact is written to a temp name first, an interrupted writer always
leaves either the previous consistent bundle or no manifest at all
(crash consistency by construction — the same tmp+rename discipline the
obs tracer uses for its artifacts).

Versioning rule (docs/RELIABILITY.md): readers accept exactly the
versions they know how to decode; ``version`` bumps on any layout or
semantic change, and unknown versions raise
:class:`CheckpointVersionError` rather than guessing.

Int-width / precision portability: every array is stored with its exact
dtype (``.npy`` self-describes), so a bundle saved under
``SLU_TPU_INT64=0`` loads bit-identically under ``SLU_TPU_INT64=1`` and
vice versa — the plan's index maps are int64 on every config, and the
factors' dtype travels in the meta block (f32/f64/c128 and the df64
path's recombined f64 factors all round-trip bitwise;
tests/test_persist.py pins this).
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import pickle

import numpy as np

from superlu_dist_tpu.utils.errors import (
    CheckpointCorruptError, CheckpointError, CheckpointVersionError)

FORMAT = "slu-tpu-persist"
FORMAT_VERSION = 2   # 2: sorted A-entry maps, one child per slot per set
MANIFEST = "MANIFEST.json"


# ---------------------------------------------------------------------------
# bundle primitives
# ---------------------------------------------------------------------------

def _sha256(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _atomic_write(path: str, data: bytes) -> None:
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _npy_bytes(arr: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, np.ascontiguousarray(arr), allow_pickle=False)
    return buf.getvalue()


def write_array(dirpath: str, name: str, arr: np.ndarray,
                entries: dict, skip_existing: bool = False) -> None:
    """Write one ``.npy`` artifact (tmp+rename) and record it in the
    manifest's ``entries`` dict.  ``skip_existing`` lets an advancing
    checkpoint reuse immutable artifacts already on disk (the digest in
    ``entries`` must then come from the previous manifest entry)."""
    fname = f"{name}.npy"
    path = os.path.join(dirpath, fname)
    if skip_existing and name in entries and os.path.exists(path):
        return
    data = _npy_bytes(arr)
    _atomic_write(path, data)
    entries[name] = {"file": fname, "bytes": len(data),
                     "sha256": _sha256(data),
                     "dtype": str(arr.dtype), "shape": list(arr.shape)}


def write_blob(dirpath: str, name: str, data: bytes, entries: dict) -> None:
    path = os.path.join(dirpath, name)
    _atomic_write(path, data)
    entries[name] = {"file": name, "bytes": len(data),
                     "sha256": _sha256(data)}


def write_manifest(dirpath: str, kind: str, meta: dict,
                   entries: dict) -> str:
    doc = {"format": FORMAT, "version": FORMAT_VERSION, "kind": kind,
           "meta": meta, "arrays": entries}
    _atomic_write(os.path.join(dirpath, MANIFEST),
                  json.dumps(doc, sort_keys=True).encode())
    return dirpath


def write_bundle(dirpath: str, kind: str, meta: dict,
                 arrays: dict, blobs: dict | None = None) -> str:
    """Write a whole bundle: every array, every blob, then the manifest
    (the commit point).  Returns ``dirpath``."""
    os.makedirs(dirpath, exist_ok=True)
    entries: dict = {}
    for name, arr in arrays.items():
        write_array(dirpath, name, np.asarray(arr), entries)
    for name, data in (blobs or {}).items():
        write_blob(dirpath, name, data, entries)
    return write_manifest(dirpath, kind, meta, entries)


def read_manifest(dirpath: str, kind: str | None = None) -> dict:
    mpath = os.path.join(dirpath, MANIFEST)
    if not os.path.isdir(dirpath) or not os.path.exists(mpath):
        raise CheckpointError(
            f"no persisted bundle at {dirpath!r} (missing {MANIFEST} — "
            "either the path is wrong or a writer died before its first "
            "commit point)")
    try:
        doc = json.loads(open(mpath, "rb").read().decode())
    except Exception as e:
        raise CheckpointCorruptError(
            f"unreadable manifest {mpath!r}: {type(e).__name__}: {e}")
    if doc.get("format") != FORMAT:
        raise CheckpointError(
            f"{mpath!r} is not a {FORMAT} bundle (format="
            f"{doc.get('format')!r})")
    if doc.get("version") != FORMAT_VERSION:
        raise CheckpointVersionError(
            f"bundle version {doc.get('version')!r} at {dirpath!r} is not "
            f"readable by this build (expected {FORMAT_VERSION}) — see the "
            "versioning rules in docs/RELIABILITY.md")
    if kind is not None and doc.get("kind") != kind:
        raise CheckpointError(
            f"bundle at {dirpath!r} is kind={doc.get('kind')!r}, "
            f"expected {kind!r}")
    return doc


def _read_artifact(dirpath: str, name: str, ent: dict) -> bytes:
    path = os.path.join(dirpath, ent["file"])
    try:
        data = open(path, "rb").read()
    except OSError as e:
        raise CheckpointCorruptError(
            f"artifact {name!r} missing/unreadable at {path!r}: {e}")
    if len(data) != ent["bytes"]:
        raise CheckpointCorruptError(
            f"artifact {name!r} at {path!r} is truncated: "
            f"{len(data)} bytes on disk vs {ent['bytes']} in the manifest")
    if _sha256(data) != ent["sha256"]:
        raise CheckpointCorruptError(
            f"artifact {name!r} at {path!r} failed its sha256 digest "
            "check — the bundle is corrupt (refusing to return garbage "
            "factors)")
    return data


def read_array(dirpath: str, name: str, doc: dict) -> np.ndarray:
    ent = doc["arrays"].get(name)
    if ent is None:
        raise CheckpointCorruptError(
            f"manifest at {dirpath!r} has no artifact named {name!r}")
    data = _read_artifact(dirpath, name, ent)
    try:
        return np.load(io.BytesIO(data), allow_pickle=False)
    except Exception as e:
        raise CheckpointCorruptError(
            f"artifact {name!r} at {dirpath!r} is not a valid .npy "
            f"payload: {type(e).__name__}: {e}")


def read_blob(dirpath: str, name: str, doc: dict) -> bytes:
    ent = doc["arrays"].get(name)
    if ent is None:
        raise CheckpointCorruptError(
            f"manifest at {dirpath!r} has no artifact named {name!r}")
    return _read_artifact(dirpath, name, ent)


def read_bundle(dirpath: str, kind: str | None = None):
    """Read and fully verify a bundle.  Returns ``(doc, arrays)`` where
    ``arrays`` maps each ``.npy`` artifact name to its ndarray (blobs are
    left to :func:`read_blob` — callers decide whether to unpickle)."""
    doc = read_manifest(dirpath, kind=kind)
    arrays = {name: read_array(dirpath, name, doc)
              for name, ent in doc["arrays"].items()
              if ent["file"].endswith(".npy")}
    return doc, arrays


# ---------------------------------------------------------------------------
# identity fingerprints
# ---------------------------------------------------------------------------

def plan_fingerprint(plan) -> str:
    """Structural identity of a FactorPlan: the dispatch-group geometry,
    batch membership, pool layout and assembly maps.  Two plans with the
    same fingerprint run the identical kernel/dispatch sequence, which is
    the precondition for splicing a checkpointed frontier into a fresh
    run (resume) — the schedule knobs, bucket geometry and amalgamation
    all fold into these arrays, so they need no separate encoding."""
    h = hashlib.sha256()
    h.update(f"n={plan.n};pool={plan.pool_size};"
             f"sched={plan.schedule};groups={len(plan.groups)};".encode())
    for grp in plan.groups:
        h.update(np.int64([grp.level, grp.m, grp.w, grp.u,
                           grp.batch]).tobytes())
        h.update(np.ascontiguousarray(grp.sns, dtype=np.int64).tobytes())
        h.update(np.ascontiguousarray(grp.ws, dtype=np.int64).tobytes())
        h.update(np.ascontiguousarray(grp.off, dtype=np.int64).tobytes())
        h.update(np.ascontiguousarray(grp.a_src, dtype=np.int64).tobytes())
        for cs in grp.children:
            h.update(np.int64([cs.ub]).tobytes())
            h.update(np.ascontiguousarray(cs.child_off,
                                          dtype=np.int64).tobytes())
    return h.hexdigest()


def pattern_digest(indptr, indices) -> str:
    """Identity of a symmetrized-permuted sparsity pattern: sha256 over
    the CSR structure arrays (widths canonicalized to int64, so the
    digest is int-width portable like the bundles themselves).  This is
    the refactor pipeline's pattern key (``drivers/gssvx.refactor``):
    two handles/bundles with equal digests were analyzed on the SAME
    structure and may share symbolic + plan + compiled programs, paying
    only the numeric phase — drift raises ``PatternMismatchError``
    instead of silently re-running symbolic."""
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(indptr, dtype=np.int64).tobytes())
    h.update(np.ascontiguousarray(indices, dtype=np.int64).tobytes())
    return h.hexdigest()


def dtype_str(dtype) -> str:
    """Canonical dtype name, tolerating extension dtypes (bfloat16)
    numpy's constructor rejects."""
    try:
        return str(np.dtype(dtype))
    except TypeError:
        return str(dtype)


def values_digest(pattern_values, dtype, thresh, gemm_prec: str = "") -> str:
    """Identity of the NUMERIC inputs a frontier was computed from: the
    structurally-permuted value array, factor dtype, GESP threshold and
    the GEMM-precision ladder tier (``gemm_prec``; "" = unspecified —
    callers on the driver path pass the resolved tier, since a bf16
    frontier spliced under highest arithmetic is exactly the stale-
    arithmetic splice this digest exists to refuse).  A resume against
    different values is refused via CheckpointMismatchError."""
    h = hashlib.sha256()
    v = np.ascontiguousarray(np.asarray(pattern_values))
    h.update(str(v.dtype).encode())
    h.update(v.tobytes())
    h.update(dtype_str(dtype).encode())
    h.update(np.float64(float(np.real(thresh))).tobytes())
    if gemm_prec:
        # appended only when specified, so tier-less callers (tests,
        # tooling) keep their historical digests
        h.update(f";gemm={gemm_prec}".encode())
    return h.hexdigest()


def front_digest(arr) -> str:
    """sha256 of one front panel's canonical ``.npy`` payload — the SAME
    digest ``save_lu`` records in a bundle manifest, computable from a
    live (device-resident) panel stack via one D2H pull.  This is the
    unit the serving tier's factor-integrity scrubber compares
    (serve/server.py ``scrub_now``): byte-for-byte, so any bit flip in
    the resident factors — not just NaN-producing ones — mismatches."""
    return _sha256(_npy_bytes(np.asarray(arr)))


def front_digests(fronts) -> list:
    """Per-front ``(sha256_L, sha256_U)`` digests of a live handle's
    panel stacks, in group order — the construction-time ground truth
    for scrubbing a handle that was never persisted."""
    return [(front_digest(lp), front_digest(up)) for lp, up in fronts]


def bundle_front_digests(dirpath: str) -> list:
    """Per-front ``(sha256_L, sha256_U)`` digests straight from a
    persisted LU bundle's manifest — no array reads, no digest work:
    the DURABLE ground truth a scrubber verifies resident factors
    against (a corrupted manifest already fails ``read_manifest``)."""
    doc = read_manifest(dirpath, kind="lu_handle")
    ent = doc["arrays"]
    out = []
    for g in range(int(doc["meta"]["n_groups"])):
        try:
            out.append((ent[f"front_{g:05d}_l"]["sha256"],
                        ent[f"front_{g:05d}_u"]["sha256"]))
        except KeyError:
            raise CheckpointCorruptError(
                f"bundle at {dirpath!r} is missing the manifest entry "
                f"for front group {g} — cannot establish a scrub "
                "baseline")
    return out


# ---------------------------------------------------------------------------
# LU handle save / load
# ---------------------------------------------------------------------------

def _host_fronts(numeric):
    return [(np.asarray(lp), np.asarray(up)) for lp, up in numeric.fronts]


def save_lu(lu, dirpath: str) -> str:
    """Persist a factored :class:`LUFactorization` handle.

    Saved: the scaling/permutation transforms, the symbolic fact + plan
    (one digest-checked pickle blob — they are already the structures
    the distributed tier ships over ``bcast_obj``), and every numeric
    front as its own digest-checked ``.npy`` pair.  NOT saved: the
    original matrix ``a`` (refinement needs a fresh one anyway — pass it
    to ``gssvx(Fact.FACTORED, a, b, lu=loaded)``) and the volatile
    device-side caches, which rebuild lazily.
    """
    if lu.numeric is None:
        raise CheckpointError("save_lu requires a factored handle "
                              "(lu.numeric is None — run the "
                              "factorization first)")
    numeric = lu.numeric
    fronts = _host_fronts(numeric)
    os.makedirs(dirpath, exist_ok=True)
    entries: dict = {}
    arrays = {"dr": lu.dr, "dc": lu.dc, "r1": lu.r1, "c1": lu.c1,
              "row_order": lu.row_order}
    if lu.col_order is not None:
        arrays["col_order"] = lu.col_order
    if lu.a_sym_indptr is not None:
        arrays["a_sym_indptr"] = lu.a_sym_indptr
        arrays["a_sym_indices"] = lu.a_sym_indices
    for name, arr in arrays.items():
        write_array(dirpath, name, np.asarray(arr), entries)
    for g, (lp, up) in enumerate(fronts):
        write_array(dirpath, f"front_{g:05d}_l", lp, entries)
        write_array(dirpath, f"front_{g:05d}_u", up, entries)
    blob = pickle.dumps((lu.sf, lu.plan),
                        protocol=pickle.HIGHEST_PROTOCOL)
    write_blob(dirpath, "structure.pkl", blob, entries)
    meta = {
        "n": int(lu.n),
        "equed": lu.equed,
        "anorm": float(lu.anorm),
        "factor_dtype": str(numeric.dtype),
        "tiny_pivots": int(numeric.tiny_pivots),
        "finite": bool(numeric.finite),
        "info_col": int(numeric.info_col),
        "n_groups": len(fronts),
        "plan_fingerprint": plan_fingerprint(lu.plan),
        "has_col_order": lu.col_order is not None,
        "has_sym_pattern": lu.a_sym_indptr is not None,
        # which GEMM-precision ladder tier the persisted factors were
        # computed at — a reloaded handle must not claim a higher tier
        # than it ran (the escalation rung and SolveReport read this)
        "gemm_precision": getattr(numeric, "gemm_prec", "highest"),
    }
    if lu.a_sym_indptr is not None:
        # pattern-keyed plan sharing (docs/RELIABILITY.md): bundles with
        # equal digests were analyzed on the same structure — a refactor
        # or a same-pattern sibling may reuse this bundle's symbolic +
        # plan + compiled programs wholesale, paying only numeric
        meta["pattern_digest"] = pattern_digest(lu.a_sym_indptr,
                                                lu.a_sym_indices)
    return write_manifest(dirpath, "lu_handle", meta, entries)


def lu_meta(dirpath: str) -> dict:
    """Manifest meta block of a persisted LU handle — a cheap peek (no
    array reads, no digest work) so a serving process can size queues
    and validate n/dtype before paying the full load (serve/server.py's
    from_bundle path).  Adds a computed ``nbytes`` key (the sum of
    every artifact's manifest byte length) so the fleet's handle cache
    (serve/handlecache.py) can budget residency BEFORE paying the
    load."""
    doc = read_manifest(dirpath, kind="lu_handle")
    meta = dict(doc["meta"])
    meta["nbytes"] = sum(int(e.get("bytes", 0))
                         for e in doc["arrays"].values())
    return meta


def load_lu(dirpath: str):
    """Load a persisted handle: verify every digest, rebuild the
    :class:`LUFactorization` with host-resident factors, and return it
    ready to solve (no refactorization; ``lu.a`` is None — supply the
    matrix when refinement is wanted)."""
    from superlu_dist_tpu.drivers.gssvx import LUFactorization
    from superlu_dist_tpu.numeric.factor import NumericFactorization
    from superlu_dist_tpu.utils.options import Options

    doc = read_manifest(dirpath, kind="lu_handle")
    meta = doc["meta"]
    try:
        sf, plan = pickle.loads(read_blob(dirpath, "structure.pkl", doc))
    except CheckpointError:
        raise
    except Exception as e:
        raise CheckpointCorruptError(
            f"structure blob at {dirpath!r} failed to unpickle: "
            f"{type(e).__name__}: {e}")
    if plan_fingerprint(plan) != meta["plan_fingerprint"]:
        raise CheckpointCorruptError(
            f"structure blob at {dirpath!r} does not match the "
            "manifest's plan fingerprint")
    n_groups = int(meta["n_groups"])
    if n_groups != len(plan.groups):
        raise CheckpointCorruptError(
            f"bundle at {dirpath!r} has {n_groups} front pairs for a "
            f"{len(plan.groups)}-group plan")
    fronts = [(read_array(dirpath, f"front_{g:05d}_l", doc),
               read_array(dirpath, f"front_{g:05d}_u", doc))
              for g in range(n_groups)]
    dtype = meta["factor_dtype"]
    numeric = NumericFactorization(
        plan=plan, fronts=fronts, tiny_pivots=int(meta["tiny_pivots"]),
        dtype=np.dtype(dtype), finite=bool(meta["finite"]),
        info_col=int(meta["info_col"]),
        gemm_prec=str(meta.get("gemm_precision", "highest")))
    arr = lambda name: read_array(dirpath, name, doc)   # noqa: E731
    return LUFactorization(
        n=int(meta["n"]), options=Options(), equed=meta["equed"],
        dr=arr("dr"), dc=arr("dc"), r1=arr("r1"), c1=arr("c1"),
        row_order=arr("row_order"),
        col_order=arr("col_order") if meta.get("has_col_order") else None,
        sf=sf, plan=plan, numeric=numeric, anorm=float(meta["anorm"]),
        a=None,
        a_sym_indptr=(arr("a_sym_indptr")
                      if meta.get("has_sym_pattern") else None),
        a_sym_indices=(arr("a_sym_indices")
                       if meta.get("has_sym_pattern") else None))
