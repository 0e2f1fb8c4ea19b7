"""Double-float ("df64") arithmetic: ~2^-48 precision from f32 pairs.

TPUs have no fp64 MXU (SURVEY.md §7 hard part 1).  This module provides the
emulated-double building blocks the full-precision path is built from: a
value is an (hi, lo) pair of float32 arrays with value = hi + lo and
|lo| <= ulp(hi)/2, giving ~48 significant bits — enough for the reference's
residual targets (≤1e-10) without iterative refinement, at ~20-30 f32 flops
per MAC.

Algorithms are the classical error-free transformations (Dekker/Knuth):
two_sum, Dekker splitting (2^12+1 factor for f32), two_prod without FMA.
The matmul accumulates in df64 via a fori_loop of rank-1 exact outer
products — VPU-bound by design (the MXU's f32 accumulation would round at
2^-24 and destroy the low words).  Use it where accuracy is worth 20-30x
flops: diagonal-block factors of nearly-singular fronts, high-precision
residuals on device.  The default pipeline (f32 factor + f64 host IR)
remains the fast path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_SPLIT = jnp.float32(4097.0)      # 2^12 + 1 (Dekker split factor for f32)

# Error-free transformations depend on every intermediate being rounded
# exactly once to f32 and on each HLO value being computed exactly once.
# CAVEAT (XLA:CPU, jax 0.9): the CPU pipeline strips optimization_barrier
# (33 in the StableHLO, 0 after compile) and its instruction fusion
# recomputes broadcast-fed subexpressions with LLVM contraction freedoms,
# perturbing the compensation terms toward plain-f32 accuracy.  Running
# with XLA_FLAGS=--xla_disable_hlo_passes=fusion,cpu-instruction-fusion
# restores df64-class accuracy under jit on CPU (tests verify this in a
# subprocess); eager mode is always exact.  The barriers below are kept
# for backends that honor them.
_bar = jax.lax.optimization_barrier

def two_sum(a, b):
    """Exact sum: returns (s, err) with s + err == a + b exactly."""
    s = _bar(a + b)
    bb = _bar(s - a)
    err = _bar(_bar(a - _bar(s - bb)) + _bar(b - bb))
    return s, err


def quick_two_sum(a, b):
    """Exact sum assuming |a| >= |b|."""
    s = _bar(a + b)
    return s, _bar(b - _bar(s - a))


def _split(a):
    t = _bar(_SPLIT * a)
    hi = _bar(t - _bar(t - a))
    return hi, _bar(a - hi)


def two_prod(a, b):
    """Exact product: (p, err) with p + err == a·b exactly (Dekker)."""
    p = _bar(a * b)
    ahi, alo = _split(a)
    bhi, blo = _split(b)
    err = _bar(_bar(_bar(_bar(ahi * bhi) - p) + _bar(ahi * blo))
               + _bar(alo * bhi))
    err = _bar(err + _bar(alo * blo))
    return p, err


def _bcast(x, y):
    """Materialize (and barrier-pin) operands at the common output shape.

    XLA sinks broadcasts below elementwise chains; on mixed-shape df64
    operands (e.g. a rank-1-update's (m,1) x (1,n)) that rewrite reorders
    the EFT arithmetic and destroys the low-word compensation (observed:
    jit result degrades to plain f32).  Broadcasting first, pinned by a
    barrier, keeps every transform at one shape.
    """
    xh, xl = x
    yh, yl = y
    shape = jnp.broadcast_shapes(xh.shape, yh.shape)
    if xh.shape == shape and yh.shape == shape:
        return xh, xl, yh, yl
    return (_bar(jnp.broadcast_to(xh, shape)),
            _bar(jnp.broadcast_to(xl, shape)),
            _bar(jnp.broadcast_to(yh, shape)),
            _bar(jnp.broadcast_to(yl, shape)))


def df64_add(x, y):
    """(hi, lo) + (hi, lo) -> normalized (hi, lo)."""
    xh, xl, yh, yl = _bcast(x, y)
    s, e = two_sum(xh, yh)
    e = e + xl + yl
    return quick_two_sum(s, e)


def df64_mul(x, y):
    xh, xl, yh, yl = _bcast(x, y)
    p, e = two_prod(xh, yh)
    e = e + xh * yl + xl * yh
    return quick_two_sum(p, e)


def df64_neg(x):
    return -x[0], -x[1]


def df64_sub(x, y):
    return df64_add(x, df64_neg(y))


def df64_div(x, y):
    """df64 division (long division with one correction): ~2^-47."""
    xh, xl, yh, yl = _bcast(x, y)
    q1 = _bar(xh / yh)
    r = df64_sub((xh, xl), df64_mul((q1, jnp.zeros_like(q1)), (yh, yl)))
    q2 = _bar(r[0] / yh)
    r2 = df64_sub(r, df64_mul((q2, jnp.zeros_like(q2)), (yh, yl)))
    q3 = _bar(r2[0] / yh)
    s, e = two_sum(q1, q2)
    return quick_two_sum(s, e + q3)


def df64_from_f64(a):
    """Split a float64 array into a df64 pair of f32 device arrays.

    The split is computed host-side in numpy so it is exact regardless of
    jax_enable_x64 (with x64 off, a device-side `a - hi` would silently
    canonicalize to f32 and zero the low word).
    """
    import numpy as np
    a64 = np.asarray(a, dtype=np.float64)
    hi = np.asarray(a64, dtype=np.float32)
    lo = np.asarray(a64 - hi.astype(np.float64), dtype=np.float32)
    return jnp.asarray(hi), jnp.asarray(lo)


def df64_to_f64(x):
    """Recombine to a host numpy float64 array (exact under any x64
    setting — device f64 may not exist on TPU)."""
    import numpy as np
    hi, lo = x
    return np.asarray(hi, dtype=np.float64) + np.asarray(lo, np.float64)


# ---- complex double-float ("zdf64"): re/im each an (hi, lo) pair ---------
# The z-twin discipline of the reference (pzgstrf.c:243 et al.) without
# twin files: a complex value is the 4-tuple (re_hi, re_lo, im_hi, im_lo)
# and the arithmetic is composed from the real error-free transforms.

def zdf64_add(x, y):
    r = df64_add((x[0], x[1]), (y[0], y[1]))
    i = df64_add((x[2], x[3]), (y[2], y[3]))
    return (*r, *i)


def zdf64_sub(x, y):
    r = df64_sub((x[0], x[1]), (y[0], y[1]))
    i = df64_sub((x[2], x[3]), (y[2], y[3]))
    return (*r, *i)


def zdf64_neg(x):
    return (-x[0], -x[1], -x[2], -x[3])


def zdf64_mul(x, y):
    """(a+bi)(c+di) = (ac - bd) + (ad + bc)i, every product/sum in df64."""
    a, b = (x[0], x[1]), (x[2], x[3])
    c, d = (y[0], y[1]), (y[2], y[3])
    re = df64_sub(df64_mul(a, c), df64_mul(b, d))
    im = df64_add(df64_mul(a, d), df64_mul(b, c))
    return (*re, *im)


def zdf64_div(x, y):
    """Scaled complex division — Smith's algorithm in df64 components.

    The naive x·conj(y)/|y|² squares the denominator magnitude and
    overflows/underflows the f32 hi words at ~1.9e19 / ~1e-19, silently
    halving the usable exponent range; Smith's form keeps every
    intermediate within a constant factor of the operands (the
    reference's scaled slud_z_div discipline, SRC/dcomplex_dist.c).
    Branchless: operands are component-swapped so the larger-magnitude
    denominator part leads, and the imaginary part's sign is fixed up.
    """
    swap = jnp.abs(y[2]) > jnp.abs(y[0])

    def sel(p, q):
        return tuple(jnp.where(swap, pi, qi) for pi, qi in zip(p, q))

    c = sel((y[2], y[3]), (y[0], y[1]))     # larger |.| denominator part
    d = sel((y[0], y[1]), (y[2], y[3]))
    a = sel((x[2], x[3]), (x[0], x[1]))
    b = sel((x[0], x[1]), (x[2], x[3]))
    t = df64_div(d, c)                      # |t| <= 1 by construction
    den = df64_add(c, df64_mul(d, t))
    re = df64_div(df64_add(a, df64_mul(b, t)), den)
    im = df64_div(df64_sub(b, df64_mul(a, t)), den)
    im = tuple(jnp.where(swap, -i, i) for i in im)
    return (*re, *im)


def zdf64_from_c128(a):
    """Split a complex128 array into the (re_hi, re_lo, im_hi, im_lo)
    f32 quadruple (exact host-side splits, see df64_from_f64)."""
    import numpy as np
    a = np.asarray(a, dtype=np.complex128)
    rh, rl = df64_from_f64(a.real)
    ih, il = df64_from_f64(a.imag)
    return rh, rl, ih, il


def zdf64_to_c128(x):
    """Recombine to host complex128 (exact)."""
    import numpy as np
    return (df64_to_f64((x[0], x[1]))
            + 1j * df64_to_f64((x[2], x[3]))).astype(np.complex128)


def df64_matmul(ah, al, bh, bl):
    """df64 GEMM: (m,k) x (k,n) pairs -> (m,n) pair, ~2^-48 accurate.

    A fori_loop of exact rank-1 outer products accumulated in df64.
    Deliberately NOT an MXU matmul: f32 accumulation inside the MXU rounds
    every partial sum to 2^-24, which is exactly what this path exists to
    avoid; the elementwise error-free transforms vectorize on the VPU.
    """
    m, k = ah.shape
    n = bh.shape[1]

    def step(i, acc):
        ch, cl = acc
        a_i = (ah[:, i][:, None], al[:, i][:, None])
        b_i = (bh[i, :][None, :], bl[i, :][None, :])
        return df64_add((ch, cl), df64_mul(a_i, b_i))

    zero = jnp.zeros((m, n), dtype=jnp.float32)
    ch, cl = jax.lax.fori_loop(0, k, step, (zero, zero))
    return ch, cl
