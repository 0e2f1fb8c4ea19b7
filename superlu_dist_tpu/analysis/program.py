"""Program-level IR audit — slulint v4's jaxpr tier.

slulint v1-v3 analyze Python SOURCE, but the artifacts that actually run
are jaxprs/HLO: the failure modes of compiled programs — un-donated
device buffers doubling peak memory, per-matrix constants baked into a
program that was supposed to be bucket-closed, shard-divergent
collective sequences that deadlock an SPMD mesh — are invisible to AST
rules.  This module walks CLOSED JAXPRS of the actual jitted programs
(stream/mega factor kernels, the fused ``make_factor_fn`` program, the
``solve/device.py`` sweep kernels, any ``shard_map``-wrapped program)
and checks them against the SLU111/SLU112/SLU114 rules in
``rules_program.py`` — the "verify the SCHEDULED program, not the
source" discipline of the dataflow-scheduling literature
(arXiv:2406.10511, arXiv:2506.05793) and the same statically-before-it-
deadlocks/OOMs bet SLU106/SLU109 already won at runtime.

Layering: this module is the only analysis file that touches jax, and
only LAZILY (inside :func:`trace_spec`) — the slulint CLI never imports
it, so source scans stay jax-free.  The rule functions themselves
(rules_program.py) are duck-typed over jaxpr objects and import no jax
either, so they are unit-testable on stubs.

The runtime twin lives in ``utils/programaudit.py``
(``SLU_TPU_VERIFY_PROGRAMS=1``): executors submit each program once at
construction/AOT-stage time and a finding raises a structured
``ProgramAuditError`` before the program ever runs.
"""

from __future__ import annotations

import dataclasses

#: jaxpr primitives that move data BETWEEN shards.  Under jax 0.9's
#: ``jax.shard_map`` a ``psum`` lowers to ``psum_invariant`` when the
#: varying-axes check is on and to plain ``psum`` with
#: ``check_vma=False``.  ``pvary`` is excluded deliberately — shard_map
#: inserts it as varying-axes BOOKKEEPING around ordinary math (a
#: type cast, no data movement), so counting it would make every branch
#: look collective-bearing.
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmax", "pmin", "ppermute", "pshuffle",
    "all_gather", "all_gather_invariant", "all_to_all", "reduce_scatter",
    "psum_scatter",
})

#: control-flow primitives whose branch sub-jaxprs execute ALTERNATIVELY
#: (every other sub-jaxpr — scan/while/pjit/closed_call bodies — executes
#: unconditionally and is walked inline)
BRANCHING_PRIMS = frozenset({"cond", "switch"})


@dataclasses.dataclass
class ProgramSpec:
    """One traced program plus the call-site facts the rules need.

    ``donated`` are the argument positions jit will alias/overwrite;
    ``dead`` are the positions the CALL SITE treats as dead after the
    call (the submitter knows its own liveness — the jaxpr cannot).
    A dead-but-not-donated large input is exactly the SLU111 bug."""

    label: str                 # program identity, e.g. "lu b8 m24 w8 u16"
    site: str                  # build site, e.g. "stream._kernel"
    jaxpr: object              # jax.core.ClosedJaxpr (duck-typed)
    donated: tuple = ()        # argnums jit donates
    dead: tuple = ()           # argnums the call site discards after use
    mesh_axes: tuple = ()      # mesh axis names the program runs under

    @property
    def in_avals(self):
        return tuple(self.jaxpr.in_avals)


# --------------------------------------------------------------------------
# duck-typed jaxpr walking (no jax import — works on test stubs)
# --------------------------------------------------------------------------

def aval_bytes(aval) -> int:
    """Size of one input/output aval in bytes (0 when unknown)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(getattr(dtype, "itemsize", 0) or 0)


def const_bytes(const) -> int:
    """Bytes held by one baked constant (jax array, numpy array or
    scalar)."""
    nb = getattr(const, "nbytes", None)
    if nb is not None:
        return int(nb)
    return 0


def open_jaxpr(j):
    """The open jaxpr of a ClosedJaxpr, or ``j`` itself if already open."""
    inner = getattr(j, "jaxpr", None)
    return inner if inner is not None and hasattr(inner, "eqns") else j


def sub_jaxprs(eqn, branches_too: bool = True):
    """Sub-jaxprs referenced by one equation's params (scan/while/pjit
    bodies, cond branches...).  ``branches_too=False`` skips params named
    'branches' so callers can treat alternative execution specially."""
    for name, v in getattr(eqn, "params", {}).items():
        if not branches_too and name == "branches":
            continue
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for s in vs:
            s = open_jaxpr(s)
            if hasattr(s, "eqns"):
                yield s


def iter_eqns(jaxpr):
    """Every equation, recursively through all sub-jaxprs (branches
    included)."""
    stack = [open_jaxpr(jaxpr)]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            stack.extend(sub_jaxprs(eqn))


def eqn_axes(eqn) -> tuple:
    """Mesh axis NAMES a collective equation reduces/permutes over
    (positional integer axes are filtered out)."""
    params = getattr(eqn, "params", {})
    axes = params.get("axes", params.get("axis_name", ()))
    if not isinstance(axes, (list, tuple)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def collective_sequence(jaxpr) -> list:
    """The ordered (primitive, axes) sequence of collectives a program
    executes, sub-jaxprs inlined IN ORDER.  For branching primitives the
    first branch's sequence is inlined (branch DISAGREEMENT is SLU114's
    separate check — for a lockstep-clean program all branches agree, so
    any branch represents the sequence)."""
    out = []
    j = open_jaxpr(jaxpr)
    for eqn in j.eqns:
        name = getattr(eqn.primitive, "name", str(eqn.primitive))
        if name in COLLECTIVE_PRIMS:
            out.append((name, eqn_axes(eqn)))
            continue
        if name in BRANCHING_PRIMS:
            branches = [open_jaxpr(b)
                        for b in eqn.params.get("branches", ())]
            if branches:
                out.extend(collective_sequence(branches[0]))
            continue
        for s in sub_jaxprs(eqn):
            out.extend(collective_sequence(s))
    return out


def branch_divergences(jaxpr) -> list:
    """Branching equations whose branches execute DIFFERENT collective
    sequences — the static shard-divergence witness: under shard_map a
    traced predicate can differ per shard, so a collective present in
    one branch and absent (or reordered) in another is the in-program
    analog of ranks entering different TreeComm collectives (runtime
    SLU106).  Returns [(eqn, [per-branch sequences])]."""
    out = []
    for eqn in iter_eqns(jaxpr):
        name = getattr(eqn.primitive, "name", str(eqn.primitive))
        if name not in BRANCHING_PRIMS:
            continue
        seqs = [collective_sequence(b)
                for b in eqn.params.get("branches", ())]
        if seqs and any(s != seqs[0] for s in seqs[1:]):
            out.append((eqn, seqs))
    return out


def bound_axis_names(jaxpr) -> set:
    """Axis names bound INSIDE the program by nested shard_map/pmap
    equations (valid targets for collectives even when the outer mesh
    contributes none)."""
    names: set = set()
    for eqn in iter_eqns(jaxpr):
        params = getattr(eqn, "params", {})
        mesh = params.get("mesh")
        if mesh is not None:
            names.update(str(a) for a in getattr(mesh, "axis_names", ()))
        an = params.get("axis_name")
        if isinstance(an, str) and getattr(
                eqn.primitive, "name", "") not in COLLECTIVE_PRIMS:
            names.add(an)
    return names


# --------------------------------------------------------------------------
# tracing (the ONLY place this module touches jax — lazily)
# --------------------------------------------------------------------------

def _shape_structs(args):
    """Per-argument ShapeDtypeStruct PYTREES mirroring ``args`` (the
    fused solve programs take lists/tuples of arrays)."""
    import numpy as np
    import jax
    from jax.tree_util import tree_map

    def to_sds(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
        a = np.asarray(leaf)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return tuple(tree_map(to_sds, a) for a in args)


def _flat_argnums(sds, argnums) -> tuple:
    """Translate TOP-LEVEL argument positions into flat invar positions
    of the traced program (pytree args span several invars)."""
    from jax.tree_util import tree_leaves
    counts = [len(tree_leaves(a)) for a in sds]
    starts = [0]
    for c in counts[:-1]:
        starts.append(starts[-1] + c)
    out = []
    for i in argnums:
        if i < len(counts):
            out.extend(range(starts[i], starts[i] + counts[i]))
    return tuple(out)


def _auto_donated(traced) -> tuple:
    """Donated argnums read off jax.stages.Traced.args_info (flat
    positional programs: leaf order == argnum order)."""
    try:
        from jax.tree_util import tree_leaves
        leaves = tree_leaves(traced.args_info,
                             is_leaf=lambda x: hasattr(x, "donated"))
        return tuple(i for i, l in enumerate(leaves)
                     if getattr(l, "donated", False))
    except Exception:
        return ()


def trace_spec(fn, args, *, label: str, site: str, dead=(),
               donated=None, mesh_axes=()) -> ProgramSpec:
    """Trace ``fn`` abstractly (ShapeDtypeStructs — no device work, no
    compile) and package the closed jaxpr with the call-site facts.

    ``fn`` is usually a ``jax.jit`` object: its ``.trace`` (jax >=
    0.4.31) yields the closed jaxpr AND the per-arg donation flags, so
    donation never has to be restated at the submit site.  Plain
    callables fall back to ``jax.make_jaxpr`` (donated=()).
    """
    import jax
    sds = _shape_structs(args)
    closed = None
    if donated is None:
        auto = ()
    else:
        auto = _flat_argnums(sds, tuple(donated))
    if hasattr(fn, "trace"):
        traced = fn.trace(*sds)
        closed = traced.jaxpr
        if donated is None:
            auto = _auto_donated(traced)
    if closed is None:
        closed = jax.make_jaxpr(fn)(*sds)
    return ProgramSpec(label=label, site=site, jaxpr=closed,
                       donated=tuple(auto), dead=_flat_argnums(sds, dead),
                       mesh_axes=tuple(mesh_axes))


def audit_spec(spec: ProgramSpec, donate_min_bytes: int,
               const_max_bytes: int):
    """Run the SLU111/SLU112/SLU114 program rules over one spec.

    Returns ``(findings, stats)`` — findings are
    :class:`~superlu_dist_tpu.analysis.core.Finding` records anchored at
    ``<program:label>``; stats carry the per-program donation coverage
    and baked-const byte totals the compile census and bench row report.
    """
    from superlu_dist_tpu.analysis import rules_program as rp
    findings = []
    f1, don_stats = rp.audit_donation(spec, donate_min_bytes)
    f2, const_stats = rp.audit_baked_consts(spec, const_max_bytes)
    f3 = rp.audit_collective_lockstep(spec)
    findings = f1 + f2 + f3
    stats = {"label": spec.label, "site": spec.site,
             "findings": len(findings)}
    stats.update(don_stats)
    stats.update(const_stats)
    return findings, stats


def audit_sharding(spec: ProgramSpec, reshard_min_bytes: int,
                   budget_bytes: int = 0):
    """Run the v6 sharding/memory rules (SLU119 implicit replication/
    reshard blowup / SLU121 static peak-memory model) over one spec —
    the jaxpr half of the ``SLU_TPU_VERIFY_SHARDING=1`` /
    ``SLU_TPU_MEM_BUDGET_BYTES`` runtime twin (utils/programaudit.py).

    Returns ``(findings, stats)`` like :func:`audit_spec`; stats carry
    ``peak_bytes_est``/``replicated_bytes`` — the census memory column.
    """
    from superlu_dist_tpu.analysis import rules_sharding as rs
    f1, reshard_stats = rs.audit_resharding(spec, reshard_min_bytes)
    f2, mem_stats = rs.audit_peak_memory(spec, budget_bytes)
    findings = f1 + f2
    stats = {"label": spec.label, "site": spec.site,
             "findings": len(findings)}
    stats.update(reshard_stats)
    stats.update(mem_stats)
    return findings, stats


def audit_dtypes(spec: ProgramSpec):
    """Run the v5 precision rules (SLU115 narrowing converts / SLU116
    accumulation dtypes) over one spec — the jaxpr half of the
    ``SLU_TPU_VERIFY_DTYPES=1`` runtime twin (utils/programaudit.py).

    Returns ``(findings, stats)`` like :func:`audit_spec`; stats carry
    the convert/dot_general census the precision audit notes report.
    """
    from superlu_dist_tpu.analysis import rules_precision as rp
    f1, narrow_stats = rp.audit_narrowing(spec)
    f2, accum_stats = rp.audit_accumulation(spec)
    findings = f1 + f2
    stats = {"label": spec.label, "site": spec.site,
             "findings": len(findings)}
    stats.update(narrow_stats)
    stats.update(accum_stats)
    return findings, stats
