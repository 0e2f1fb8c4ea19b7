"""SPMD shard_map tier: one compiled program per factor group / solve sweep.

The distributed execution model the reference's pdgstrf look-ahead
pipeline (SRC/pdgstrf.c:624-697) exists to approximate by hand: instead
of a per-rank host dispatch loop whose communication is host-mediated
lockstep (parallel/treecomm.py — kept as the A/B reference and recovery
fallback), each (level, bucket) group of the numeric factorization is
one ``shard_map``-wrapped jitted program over a real ``jax.Mesh`` (axes
registered in utils/meshreg.py), dispatched asynchronously with the
Schur pool resident and replicated on the devices, and each
triangular-solve sweep bucket is one more program.  Panels are sharded
BLOCK-CYCLICALLY over the flattened device order — slot j of a group
lives on device ``j % nd`` (the reference's 2-D block-cyclic
process-to-panel map, SURVEY.md §2.4) — and every extend-add / Schur /
lsum exchange is an in-program ``all_gather`` / ``psum`` leg derived
from the FactorPlan dataflow schedule, so XLA sees the communication
and can overlap it with the surrounding GEMMs.

Bitwise contract (the PR 5 pattern, gated by scripts/check_spmd_equiv.py
and tests/test_spmd.py): L, U and X are bitwise-identical to the
lockstep/host path.  Two mechanisms carry it:

* per-slot independence — the batched partial factor and the batched
  GEMMs compute slot s's result from slot s's data alone, so
  re-batching the slots across devices cannot change any slot's bits
  (the same invariant that keeps fused/stream/mega bitwise-equal under
  different batch compositions).  The batched TRSM does NOT have this
  property — XLA:CPU's batched triangular_solve picks a strategy per
  TOTAL batch size, so a slot's bits change when the stack is split —
  which is why SpmdSolver runs the pivot TRSM replicated on the full
  batch (identical HLO + identical operands as the single-device
  sweep) and shards only the contribution GEMMs;
* full-order replay — every scatter whose ORDER matters (the Schur pool
  write, the solve's x/lsum updates) is NOT performed on the local
  shard: the per-slot values are all-gathered, un-permuted back to the
  original slot order (``g[j] = (j % nd)·B_loc + j//nd``), and the
  exact scatter the single-device executors run is replayed redundantly
  on every device.  Identical scatter HLO on identical inputs ==
  identical bits, and the redundant copies keep the pool/x replicated
  without any varying-axes machinery (shard_map runs with
  ``check_vma=False``; replication is by construction).

Padding sentinels follow the streamed executor's conventions
(numeric/stream.py): OOB scatter slots == local batch (dropped), OOB
gather sources == array length (filled 0), rel sentinel == m, padded
batch slots are identity fronts (ws == 0).
"""

from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
from superlu_dist_tpu.obs.trace import get_tracer


def spmd_mode(value: str | None = None) -> bool:
    """Resolve SLU_TPU_SPMD: ""/"auto" enables the shard_map tier on
    single-process meshes (where one controller addresses every mesh
    device); "0"/"off" forces the GSPMD stream/fused tier; anything
    else forces it on.  Read OUTSIDE traced code only (slulint
    SLU102)."""
    if value is None:
        from superlu_dist_tpu.utils.options import env_str
        value = env_str("SLU_TPU_SPMD")
    v = str(value).strip().lower()
    if v in ("", "auto"):
        return jax.process_count() == 1
    return v not in ("0", "off", "false", "no")


def _cyclic_layout(batch: int, nd: int):
    """Block-cyclic slot partition over ``nd`` devices.

    Returns (B_loc, B_pad, src, valid, g): position p of the device-major
    padded order (device d = p // B_loc, local l = p % B_loc) holds slot
    ``src[p] = l·nd + d`` when ``valid[p]``; ``g[j]`` is the padded
    position of slot j, so ``take(gathered, g)`` restores slot order."""
    b_loc = max(1, -(-batch // nd))
    b_pad = b_loc * nd
    pos = np.arange(b_pad)
    src = (pos % b_loc) * nd + pos // b_loc
    valid = src < batch
    j = np.arange(batch)
    g = (j % nd) * b_loc + j // nd
    return b_loc, b_pad, src, valid, g


def _partition_rows(owner: np.ndarray, nd: int, pads: list, cols: list):
    """Stable partition of table rows by owning device: row i goes to
    device ``owner[i]``, original order preserved within a device (the
    scatter-add sequence INTO one slot is the bitwise contract).  Each
    column array in ``cols`` is repacked to (nd·C_max, ...) device-major
    with its ``pads`` sentinel filling the tail — sharding the leading
    axis over the mesh hands each device exactly its (C_max, ...)
    block."""
    per_dev = [np.nonzero(owner == d)[0] for d in range(nd)]
    c_max = max((len(ix) for ix in per_dev), default=0)
    out = []
    for col, pad in zip(cols, pads):
        col = np.asarray(col)
        shaped = np.full((nd * c_max,) + col.shape[1:], pad,
                         dtype=col.dtype)
        for d, ix in enumerate(per_dev):
            shaped[d * c_max:d * c_max + len(ix)] = col[ix]
        out.append(shaped)
    return c_max, out


def _group_program(mesh, dims, ubs, pivot, gemm_prec, specs):
    """One group's step as a jitted shard_map program: each device
    assembles and factors its block-cyclic slot partition, then the
    panels and Schur blocks are all-gathered, un-permuted to slot order,
    and the pool write is replayed in full order on every device."""
    from jax.sharding import PartitionSpec as P
    from superlu_dist_tpu.numeric.factor import group_step, pool_write
    axes = tuple(mesh.axis_names)
    b_loc, m, w, u = dims

    def step(avals, pool, thresh, a_slot, a_flat, a_src, ws_l, off_full, g,
             *child):
        children = [(ub, child[3 * i], child[3 * i + 1], child[3 * i + 2])
                    for i, ub in enumerate(ubs)]
        # off=None: write_back=False never reaches the pool scatter — the
        # replay below IS the pool write
        (lp_l, up_l), schur, t = group_step(
            (b_loc, m, w, u), avals, pool, thresh, a_slot, a_flat, a_src,
            ws_l, None, children, pivot=pivot, gemm_prec=gemm_prec,
            write_back=False)
        lp = jnp.take(jax.lax.all_gather(lp_l, axes, axis=0, tiled=True),
                      g, axis=0)
        up = jnp.take(jax.lax.all_gather(up_l, axes, axis=0, tiled=True),
                      g, axis=0)
        if u > 0:
            sv = jnp.take(jax.lax.all_gather(schur, axes, axis=0,
                                             tiled=True), g, axis=0)
            pool = pool_write(pool, off_full, sv)
        return (lp, up), pool, jax.lax.psum(t, axes)

    rep = P()
    smapped = jax.shard_map(step, mesh=mesh, in_specs=(rep, rep, rep) + specs,
                            out_specs=rep, check_vma=False)
    # the pool is threaded linearly through the groups: donate it
    return jax.jit(smapped, donate_argnums=(1,))


class SpmdFactorExecutor:
    """The numeric factorization as one shard_map program per group.

    Per (level, bucket) group, each device assembles and factors only
    its block-cyclic slot partition (``group_step`` with
    ``write_back=False`` — identical per-slot arithmetic to every other
    executor), then the panels and Schur values are all-gathered,
    un-permuted to slot order, and the pool write is replayed in full
    order on every device (``_group_program``).  The pool stays
    replicated on the devices between programs, and the programs are
    compiled ahead of the first factorization in parallel threads
    (stream.compile_all): a whole-factorization program at n=110,592
    did not finish compiling for a 2x2 v5e mesh in minutes, while its
    89 group programs build independently.

    Same call surface as the other executors: ``fn(avals, thresh) ->
    (fronts_tuple, tiny)``.  Checkpointing forces the streamed executor
    (numeric_factorize).
    """

    _census_site = "spmd.factor"

    def __init__(self, plan, dtype="float64", mesh=None, gemm_prec=None):
        if mesh is None:
            raise ValueError("SpmdFactorExecutor needs a mesh")
        from jax.sharding import NamedSharding, PartitionSpec as P
        from superlu_dist_tpu.ops.dense import gemm_precision, pivot_kernel
        from superlu_dist_tpu.symbolic.symbfact import _front_flops
        plan.check_index_width()
        self.plan = plan
        self.mesh = mesh
        self.dtype = jnp.dtype(dtype)
        self._axes = tuple(mesh.axis_names)
        self.nd = nd = int(np.prod(mesh.devices.shape))
        # env knobs resolved HERE, in the uncached constructor, and baked
        # into the compiled programs (slulint SLU102/SLU105)
        self.gemm_prec = gemm_precision(gemm_prec)
        self._pivot = pivot_kernel()
        n_avals = len(plan.pattern_indices)
        sh, rep = P(self._axes), P()
        self._rep = NamedSharding(mesh, rep)
        # per group: (program key, host index arrays) — placed on the
        # mesh at the first call (``_place``), so a described mesh with
        # no devices attached can still lower and compile the programs
        self._steps = []
        self._programs = {}     # program key -> jitted shard_map program
        executed = 0.0
        for grp in plan.groups:
            b = grp.batch
            b_loc, b_pad, src, valid, g = _cyclic_layout(b, nd)
            executed += b_pad * _front_flops(grp.w, grp.u)
            # assembly triples partitioned by the owning slot's device;
            # sentinels: slot == b_loc drops, src == len(avals) fills 0
            a_slot = np.asarray(grp.a_slot)
            _, (as_s, af_s, asrc_s) = _partition_rows(
                a_slot % nd, nd, [b_loc, 0, n_avals],
                [a_slot // nd, np.asarray(grp.a_flat),
                 np.asarray(grp.a_src)])
            ws = np.asarray(grp.ws)
            srcc = np.minimum(src, max(b - 1, 0))
            ws_s = np.where(valid, ws[srcc], 0).astype(ws.dtype)
            arrs = [(as_s, sh), (af_s, sh), (asrc_s, sh), (ws_s, sh),
                    (np.asarray(grp.off), rep), (g, rep)]
            ubs = []
            for cs in grp.children:
                child_slot = np.asarray(cs.child_slot)
                _, (co_s, cs_s, rel_s) = _partition_rows(
                    child_slot % nd, nd,
                    [plan.pool_size, b_loc, grp.m],
                    [np.asarray(cs.child_off), child_slot // nd,
                     np.asarray(cs.rel)])
                arrs += [(co_s, sh), (cs_s, sh), (rel_s, sh)]
                ubs.append(cs.ub)
            args = tuple(np.asarray(x) for x, _ in arrs)
            specs = tuple(spec for _, spec in arrs)
            dims = (b_loc, grp.m, grp.w, grp.u)
            key = (dims, tuple(ubs), specs,
                   tuple((x.shape, x.dtype) for x in args))
            if key not in self._programs:
                self._programs[key] = _group_program(
                    mesh, dims, tuple(ubs), self._pivot, self.gemm_prec,
                    specs)
            self._steps.append((key, args))
        self._placed = None     # per group: arguments on the mesh
        # XLA:CPU runs every device's share of a program on one shared
        # thread pool, and a collective holds its thread until all
        # devices arrive: with two group programs in flight, devices
        # waiting in the later one can take every thread from a device
        # still in the earlier one (a rendezvous deadlock).  There, each
        # group finishes before the next is issued.
        self._serial = mesh.devices.flat[0].platform == "cpu"
        self._compiled = {}     # program key -> compiled executable
        self.executed_flops = float(executed)
        # executor telemetry surface (bench.py / drivers read these)
        self.offload = 0.0
        self.granularity = "group"
        self.n_kernels = len(self._programs)
        self.last_dispatch_seconds = 0.0

    def _label(self, key) -> str:
        (b, m, w, u) = key[0]
        return (f"spmd nd{self.nd} b{b} m{m} w{w} u{u} {self.dtype} "
                f"{self.gemm_prec}")

    def _place(self) -> list:
        if self._placed is None:
            from jax.sharding import NamedSharding
            self._placed = [
                tuple(jax.device_put(x, NamedSharding(self.mesh, spec))
                      for x, spec in zip(args, key[2]))
                for key, args in self._steps]
        return self._placed

    def _build(self, avals, pool, thresh) -> None:
        """Compile every group program not yet built, in parallel; each
        build lands in the compile census and, when armed, the runtime
        program auditors."""
        from superlu_dist_tpu.numeric.stream import compile_all
        from superlu_dist_tpu.utils.programaudit import maybe_audit
        todo = {}
        for (key, _), args in zip(self._steps, self._place()):
            if key not in self._compiled and key not in todo:
                todo[key] = (avals, pool, thresh, *args)
        if not todo:
            return
        keys, lowered, t_lower = list(todo), [], []
        for key in keys:
            fn, args = self._programs[key], todo[key]
            maybe_audit(self._census_site, self._label(key), fn, args,
                        dead=(1,), mesh_axes=self._axes)
            t0 = time.perf_counter()
            lowered.append(fn.lower(*args))
            t_lower.append(time.perf_counter() - t0)
        for i, exe, _ in compile_all(
                lowered, label=lambda i: self._label(keys[i]),
                build=lambda i: COMPILE_STATS.build(
                    self._census_site, self._label(keys[i]),
                    n_args=len(todo[keys[i]]), before=t_lower[i])):
            self._compiled[keys[i]] = exe

    def __call__(self, avals, thresh):
        tracer = get_tracer()
        avals = jax.device_put(jnp.asarray(avals, self.dtype), self._rep)
        thresh = jax.device_put(thresh, self._rep)
        pool = jnp.zeros(self.plan.pool_size, self.dtype, device=self._rep)
        self._build(avals, pool, thresh)
        fronts = []
        tiny = jnp.zeros((), jnp.int32)
        t0 = time.perf_counter()
        for (key, _), args in zip(self._steps, self._place()):
            (lp, up), pool, t = self._compiled[key](avals, pool, thresh,
                                                    *args)
            if self._serial:
                jax.block_until_ready(pool)
            fronts.append((lp, up))
            tiny = tiny + t
        t_issue = time.perf_counter() - t0
        self.last_dispatch_seconds = t_issue
        if tracer.enabled:
            tracer.complete("issue spmd", "dispatch", t0, t_issue,
                            groups=len(self.plan.groups), n_devices=self.nd)
            if tracer.profiling:
                jax.block_until_ready(fronts)
                tracer.complete("factor-spmd", "kernel", t0,
                                time.perf_counter() - t0,
                                n_groups=len(self.plan.groups),
                                aggregate=True,
                                executed_flops=self.executed_flops,
                                structural_flops=float(self.plan.flops))
        return tuple(fronts), tiny


from superlu_dist_tpu.solve.device import DeviceSolver, _trsm


class SpmdSolver(DeviceSolver):
    """Triangular sweeps as one shard_map program per nrhs bucket.

    Subclasses DeviceSolver for its plan/panel machinery — built with
    ``mesh=None`` so the DATAFLOW solve schedule applies (the factor-
    schedule pin is a multi-process constraint only; solve/plan.py) —
    and fuses the forward AND backward sweeps into ONE jitted shard_map
    program per nrhs bucket.  Work split per group (the reference's
    pdgstrs shape — the diagonal solve is latency-bound on the pivot
    owner while the lsum updates carry the flops, SRC/pdgstrs.c):

    * pivot TRSM — runs REPLICATED on the full slot-ordered batch.
      XLA:CPU's batched triangular_solve is not batch-size invariant
      (slot bits change when the stack is split; module docstring), so
      the only way to keep y bitwise-identical to DeviceSolver is to
      issue the exact same full-batch solve on every device.  The pivot
      stack is (B, w, w) — tiny next to the off-diagonal panels — so
      replicating it costs little memory and no communication.
    * contribution GEMMs (L21·y forward, U12·x backward — where the
      flops are) — sharded block-cyclically: each device multiplies
      only its slots' L21/U12 panels (batched matmul IS per-slot
      independent), the per-slot blocks are all-gathered and
      un-permuted, and the x/lsum scatters are replayed in full slot
      order on every device (replicated x — the bitwise contract).

    Padded slots exist only in the sharded arrays: zero L21/U12 (their
    contributions vanish), gather rows pinned to the dump row."""

    def __init__(self, fact, mesh, fused=True, schedule=None,
                 window=None, align=None, trsm_leaf=None, nrhs_max=None,
                 nrhs_growth=None, gemm_prec=None):
        if mesh is None:
            raise ValueError("SpmdSolver needs a mesh")
        super().__init__(fact, diag_inv=False, fused=True, mesh=None,
                         schedule=schedule, window=window, align=align,
                         trsm_leaf=trsm_leaf, nrhs_max=nrhs_max,
                         nrhs_growth=nrhs_growth, gemm_prec=gemm_prec)
        # the shard_map programs capture this mesh and these panels'
        # layout: one set per solver
        self._fused_cache = {}
        self.spmd_mesh = mesh
        self._axes = tuple(mesh.axis_names)
        self.nd = nd = int(np.prod(mesh.devices.shape))
        from jax.sharding import PartitionSpec as P
        sh, rep = P(self._axes), P()
        sf = fact.plan.sf
        first = sf.sn_start[:-1]
        n = self.n
        dt = jnp.dtype(fact.dtype)
        flat, specs, meta = [], [], []
        for (sg, _, _, _), (lp, up) in zip(self._groups, self.fronts):
            b, m, w, u = sg.batch, lp.shape[1], sg.w, sg.u
            b_loc, b_pad, src, valid, g = _cyclic_layout(b, nd)
            srcc = np.minimum(src, max(b - 1, 0))
            lp, up = jnp.asarray(lp), jnp.asarray(up)
            # replicated pivot stack (full slot order, no padding) for
            # the full-batch TRSM; sharded off-diagonal panels for the
            # contribution GEMMs (pad slots zeroed — no contribution)
            piv = lp[:, :w, :w]
            l21_s, up_s = lp[srcc][:, w:, :], up[srcc]
            if not valid.all():
                mask = jnp.asarray(valid)[:, None, None]
                l21_s = jnp.where(mask, l21_s,
                                  jnp.zeros((m - w, w), dt)[None])
                up_s = jnp.where(mask, up_s, jnp.zeros((w, u), dt)[None])
            firsts = first[sg.sns]
            rows = np.full((b, u), n, dtype=np.int64)
            for slot, s in enumerate(sg.sns):
                r = sf.sn_rows[s]
                rows[slot, :len(r)] = r
            ws = np.asarray(sg.ws)
            # sel: which full-order y row each local GEMM slot reads
            # (pad slots read slot 0 — harmless, zero panels)
            sel = srcc.astype(np.int64)
            rows_l = np.where(valid[:, None], rows[srcc], n)
            flat += [piv, l21_s, up_s, jnp.asarray(sel),
                     jnp.asarray(rows_l), jnp.asarray(firsts),
                     jnp.asarray(ws), jnp.asarray(rows), jnp.asarray(g)]
            specs += [rep, sh, sh, sh, sh, rep, rep, rep, rep]
            meta.append((w, u))
        self._spmd_flat = tuple(flat)
        self._spmd_specs = tuple(specs)
        self._spmd_meta = meta

    def _spmd_program(self, conj=None):
        """Build one fwd+bwd shard_map program (notrans when conj is
        None, else the transpose pair with optional conjugation)."""
        from jax.sharding import PartitionSpec as P
        meta = self._spmd_meta
        axes = self._axes
        n1 = self.n + 1
        leaf, prec = self.trsm_leaf, self.gemm_prec
        hp = jax.lax.Precision.HIGHEST

        def sweep(x, lsum, *args):
            per_group = [args[i * 9:(i + 1) * 9] for i in range(len(meta))]
            # forward sweep, groups ascending (L·y = d; Uᵀ leads when
            # transposed).  The TRSM runs on the FULL slot-ordered batch
            # on every device — same HLO, same operands as the
            # single-device _fwd_body, hence the same bits; only the
            # contribution GEMM is sharded (per-slot exact).
            for (w, u), ga in zip(meta, per_group):
                (piv, l21_s, up_s, sel, rows_l, f_f, ws_f, rows_f, g) = ga
                k = jnp.arange(w)
                cols_f = jnp.where(k[None, :] < ws_f[:, None],
                                   f_f[:, None] + k, n1 - 1)
                rhs = (x.at[cols_f].get(mode="fill", fill_value=0)
                       - lsum.at[cols_f].get(mode="fill", fill_value=0))
                if conj is None:
                    y = _trsm(piv, rhs, lower=True, unit=True,
                              trans=0, leaf=leaf, prec=prec)
                    mat = l21_s
                else:
                    u11 = piv.conj() if conj else piv
                    y = _trsm(u11, rhs, lower=False, unit=False, trans=1,
                              leaf=leaf, prec=prec)
                    u12 = up_s.conj() if conj else up_s
                    mat = jnp.swapaxes(u12, 1, 2)
                x = x.at[cols_f].set(y, mode="drop")
                if u:
                    y_l = jnp.take(y, sel, axis=0)
                    contrib = jnp.matmul(mat, y_l, precision=hp,
                                         preferred_element_type=y.dtype)
                    c_f = jnp.take(jax.lax.all_gather(
                        contrib, axes, axis=0, tiled=True), g, axis=0)
                    lsum = lsum.at[rows_f].add(c_f, mode="drop")
            # backward sweep, descending: the correction GEMM reads the
            # replicated x at each device's own row slots, the gathered
            # full-order corrections are subtracted, then the full-batch
            # TRSM replays _bwd_body exactly
            for (w, u), ga in zip(reversed(meta), reversed(per_group)):
                (piv, l21_s, up_s, sel, rows_l, f_f, ws_f, rows_f, g) = ga
                k = jnp.arange(w)
                cols_f = jnp.where(k[None, :] < ws_f[:, None],
                                   f_f[:, None] + k, n1 - 1)
                rhs = x.at[cols_f].get(mode="fill", fill_value=0)
                if u:
                    xr = x.at[rows_l].get(mode="fill", fill_value=0)
                    if conj is None:
                        mat = up_s
                    else:
                        l21 = l21_s.conj() if conj else l21_s
                        mat = jnp.swapaxes(l21, 1, 2)
                    mm = jnp.matmul(mat, xr, precision=hp,
                                    preferred_element_type=xr.dtype)
                    mm_f = jnp.take(jax.lax.all_gather(
                        mm, axes, axis=0, tiled=True), g, axis=0)
                    rhs = rhs - mm_f
                if conj is None:
                    y = _trsm(piv, rhs, lower=False, unit=False,
                              trans=0, leaf=leaf, prec=prec)
                else:
                    l11 = piv.conj() if conj else piv
                    y = _trsm(l11, rhs, lower=True, unit=True, trans=1,
                              leaf=leaf, prec=prec)
                x = x.at[cols_f].set(y, mode="drop")
            return x

        rep = P()
        smapped = jax.shard_map(sweep, mesh=self.spmd_mesh,
                                in_specs=(rep, rep) + self._spmd_specs,
                                out_specs=rep, check_vma=False)
        return jax.jit(smapped, donate_argnums=(0, 1))

    def _spmd_fns(self, kb, conj=None):
        key = ("S", kb, conj)
        fn = self._fused_cache.get(key)
        if fn is None:
            fn = self._fused_cache[key] = self._spmd_program(conj)
        return fn

    def _sweeps_for(self, conj=None):
        def sweeps(x, lsum, kb):
            fn = self._spmd_fns(kb, conj)
            args = (x, lsum, *self._spmd_flat)
            from superlu_dist_tpu.utils.programaudit import maybe_audit
            t = "" if conj is None else ("H" if conj else "T")
            maybe_audit("solve.spmd", f"spmd{t}-sweep n{self.n} k{kb}",
                        fn, args, dead=(0, 1), mesh_axes=self._axes)
            return fn(*args)
        return sweeps

    def solve(self, rhs):
        return self._run_sweeps(rhs, self._sweeps_for(None))

    def solve_trans(self, rhs, conj: bool = False):
        return self._run_sweeps(rhs, self._sweeps_for(bool(conj)))
