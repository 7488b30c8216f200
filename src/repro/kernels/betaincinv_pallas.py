"""Pallas TPU kernel for the batched Beta quantile (§7.5 numerics).

``core.betainc.betaincinv`` is a bracketed Halley iteration on
``jax.scipy.special.betainc`` — purely elementwise over the row axis, so
it is a natural Pallas fit: tile the (N,) axis into ``block_n`` lanes and
run the fixed-count iteration entirely inside the kernel.  BENCH_fleet
shows the §7.5 credible-bound path at ~8x vs the no-bound path's ~66x —
this inversion is the dominant remaining cost of every gated path.

The kernel mirrors ``core.betainc._invert`` step for step: the same
Numerical-Recipes initial guess, the same 64 bracketed Halley iterations
with bisection fallback, the same special-value handling.  The one
difference is the ``I_x(a, b)`` evaluation itself: ``jax.scipy``'s
``betainc`` is an XLA custom call that Mosaic cannot lower, so the kernel
carries its own evaluator — the Lentz continued fraction (NR §6.4
``betacf``, fixed iteration count, FPMIN clamps, the symmetry switch at
``x >= (a + 1)/(a + b + 2)``) with a Lanczos ``lgamma`` for the log-Beta
front factor.  Consequence for parity: results agree with the
``jax.scipy``-based path (and scipy's ``beta.ppf``) to <= 1e-10 relative
— the established betaincinv tier — but not bitwise; the fused online
tick keeps its bitwise contract on the mean path, where no inversion
runs.

Inert padding lanes use (a=1, b=1, q=0.5): ``I_x(1,1) = x``, so every
step is benign and the pad result (0.5) is sliced off by the wrapper.

Validated under interpret=True on CPU against ``core.betainc.betaincinv``
and ``scipy.stats.beta.ppf`` (tests/test_kernels.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["betaincinv_kernel_call", "betainc_in_kernel", "lbeta_in_kernel",
           "check_native_dtype"]

# Same fixed Halley budget as core.betainc (the bisection-fallback lanes
# need the headroom to reach ~1e-16 interval width at float64).
_N_ITER = 64
# Lentz continued-fraction budget: NR quotes <~50 double-steps for
# convergence at double precision over the symmetry-reduced domain; the
# fixed 100 keeps deep-tail a, b ~ 150 lanes converged without a
# data-dependent exit (which would shear the SIMD lanes apart).
_CF_ITER = 100

# Lanczos g=7, n=9 coefficients (Godfrey/Boost; standard double-precision
# set, ~1e-13 relative on lgamma over the positive axis).
_LANCZOS_G = 7.0
_LANCZOS = (
    0.99999999999980993,
    676.5203681218851,
    -1259.1392167224028,
    771.32342877765313,
    -176.61502916214059,
    12.507343278686905,
    -0.13857109526572012,
    9.9843695780195716e-6,
    1.5056327351493116e-7,
)
_HALF_LOG_2PI = 0.9189385332046727417803297364056176


def check_native_dtype(dtype, interpret: bool, kernel: str) -> None:
    """Refuse f64 operands on a native (Mosaic) lowering: the TPU has no
    f64 vector unit, and a silent cast down would change the result the
    caller asked for.  Interpret mode (the CPU parity path) takes f64."""
    if not interpret and jnp.dtype(dtype) == jnp.float64:
        raise TypeError(
            f"{kernel}: Pallas TPU kernels have no float64; got float64 "
            "operands (turn off jax_enable_x64 or use the XLA path)")


def _lgamma(z):
    """Lanczos log-gamma for z > 0 (elementwise, Mosaic-lowerable).

    Evaluated at z + 1 (the approximation's sweet spot) and stepped down
    via ``lgamma(z) = lgamma(z + 1) - log(z)``, so a, b << 1 lanes stay
    accurate without a reflection branch.
    """
    w = z + 1.0
    x = _LANCZOS[0]
    for i, c in enumerate(_LANCZOS[1:]):
        x = x + c / (w + i)
    t = w + (_LANCZOS_G - 0.5)
    return (_HALF_LOG_2PI + (w - 0.5) * jnp.log(t) - t + jnp.log(x)
            - jnp.log(z))


def lbeta_in_kernel(a, b):
    """log B(a, b) from the in-kernel Lanczos ``lgamma``."""
    return _lgamma(a) + _lgamma(b) - _lgamma(a + b)


def _betacf(a, b, x, dt):
    """Lentz continued fraction for ``I_x`` (NR §6.4 betacf): fixed
    iteration count, FPMIN clamps on near-zero denominators."""
    fpmin = jnp.finfo(dt).tiny / jnp.finfo(dt).eps
    qab = a + b
    qap = a + 1.0
    qam = a - 1.0
    c = jnp.ones_like(x)
    d = 1.0 - qab * x / qap
    d = jnp.where(jnp.abs(d) < fpmin, fpmin, d)
    d = 1.0 / d
    h = d

    def body(mf, cdh):
        c, d, h = cdh
        m2 = 2.0 * mf
        # even step
        aa = mf * (b - mf) * x / ((qam + m2) * (a + m2))
        d = 1.0 + aa * d
        d = jnp.where(jnp.abs(d) < fpmin, fpmin, d)
        c = 1.0 + aa / c
        c = jnp.where(jnp.abs(c) < fpmin, fpmin, c)
        d = 1.0 / d
        h = h * d * c
        # odd step
        aa = -(a + mf) * (qab + mf) * x / ((a + m2) * (qap + m2))
        d = 1.0 + aa * d
        d = jnp.where(jnp.abs(d) < fpmin, fpmin, d)
        c = 1.0 + aa / c
        c = jnp.where(jnp.abs(c) < fpmin, fpmin, c)
        d = 1.0 / d
        h = h * d * c
        return c, d, h

    def step(m, cdh):
        # float, not int, arithmetic on the loop index: an int literal
        # would lower as i64 under jax_enable_x64
        return body(m.astype(dt) + 1.0, cdh)

    _, _, h = jax.lax.fori_loop(np.int32(0), np.int32(_CF_ITER), step,
                                (c, d, h))
    return h


def betainc_in_kernel(a, b, x):
    """Regularized incomplete beta ``I_x(a, b)`` for x in (0, 1), a, b > 0
    — the kernel-resident replacement for ``jax.scipy.special.betainc``
    (agreement ~1e-13 relative; see module docstring)."""
    dt = x.dtype
    # front factor; symmetric under (a, b, x) -> (b, a, 1 - x)
    lnfront = (a * jnp.log(x) + b * jnp.log1p(-x)
               - lbeta_in_kernel(a, b))
    bt = jnp.exp(lnfront)
    # symmetry switch keeps the continued fraction in its fast region
    swap = x >= (a + 1.0) / (a + b + 2.0)
    aa = jnp.where(swap, b, a)
    bb = jnp.where(swap, a, b)
    xx = jnp.where(swap, 1.0 - x, x)
    res = bt * _betacf(aa, bb, xx, dt) / aa
    return jnp.where(swap, 1.0 - res, res)


def _initial_guess(a, b, q):
    """NR 3rd ed. §6.4 ``invbetai`` starting point — identical to
    ``core.betainc._initial_guess`` (all ops Mosaic-lowerable already)."""
    dt = q.dtype
    eps = jnp.finfo(dt).eps
    tiny = jnp.finfo(dt).tiny
    pp = jnp.maximum(jnp.where(q < 0.5, q, 1.0 - q), tiny)
    t = jnp.sqrt(-2.0 * jnp.log(pp))
    x = (2.30753 + t * 0.27061) / (1.0 + t * (0.99229 + t * 0.04481)) - t
    x = jnp.where(q < 0.5, -x, x)
    al = (x * x - 3.0) / 6.0
    h = 2.0 / (1.0 / (2.0 * a - 1.0) + 1.0 / (2.0 * b - 1.0))
    w = (
        x * jnp.sqrt(al + h) / h
        - (1.0 / (2.0 * b - 1.0) - 1.0 / (2.0 * a - 1.0))
        * (al + 5.0 / 6.0 - 2.0 / (3.0 * h))
    )
    guess_large = a / (a + b * jnp.exp(2.0 * w))
    lna = jnp.log(a / (a + b))
    lnb = jnp.log(b / (a + b))
    t_a = jnp.exp(a * lna) / a
    t_b = jnp.exp(b * lnb) / b
    s = t_a + t_b
    guess_small = jnp.where(
        q < t_a / s,
        (a * s * q) ** (1.0 / a),
        1.0 - (b * s * (1.0 - q)) ** (1.0 / b),
    )
    guess = jnp.where((a >= 1.0) & (b >= 1.0), guess_large, guess_small)
    return jnp.clip(guess, tiny, 1.0 - eps)


def betaincinv_in_kernel(a, b, q):
    """The bracketed Halley inversion, kernel-resident: mirrors
    ``core.betainc._invert`` line for line with ``betainc_in_kernel``
    as the evaluator.  Shared by the betaincinv kernel and the fused
    online-tick kernel's lower-bound / drift paths."""
    dt = q.dtype
    tiny = jnp.finfo(dt).tiny
    a1 = a - 1.0
    b1 = b - 1.0
    lbeta = lbeta_in_kernel(a, b)
    x0 = _initial_guess(a, b, q)
    lo0 = jnp.zeros_like(q)
    hi0 = jnp.ones_like(q)

    def body(_, state):
        x, lo, hi = state
        err = betainc_in_kernel(a, b, x) - q
        lo = jnp.where(err < 0.0, jnp.maximum(lo, x), lo)
        hi = jnp.where(err > 0.0, jnp.minimum(hi, x), hi)
        logpdf = a1 * jnp.log(x) + b1 * jnp.log1p(-x) - lbeta
        u = err / jnp.maximum(jnp.exp(logpdf), tiny)
        halley = 1.0 - 0.5 * jnp.minimum(1.0, u * (a1 / x - b1 / (1.0 - x)))
        xn = x - u / halley
        bad = ~jnp.isfinite(xn) | (xn < lo) | (xn > hi)
        xn = jnp.where(bad, 0.5 * (lo + hi), xn)
        return xn, lo, hi

    x, _, _ = jax.lax.fori_loop(np.int32(0), np.int32(_N_ITER), body,
                                (x0, lo0, hi0))
    x = jnp.where(q <= 0.0, 0.0, jnp.where(q >= 1.0, 1.0, x))
    valid = (a > 0.0) & (b > 0.0) & (q >= 0.0) & (q <= 1.0)
    return jnp.where(valid, x, jnp.nan)


def _betaincinv_kernel(a_ref, b_ref, q_ref, out_ref):
    out_ref[...] = betaincinv_in_kernel(a_ref[...], b_ref[...], q_ref[...])


def betaincinv_kernel_call(
    a: jax.Array,       # (n,) Beta alpha
    b: jax.Array,       # (n,) Beta beta
    q: jax.Array,       # (n,) quantile levels
    *,
    block_n: int = 1024,
    interpret: bool = True,
) -> jax.Array:
    """Batched Beta quantile ``I_x^{-1}(a, b) = q`` as one Pallas launch
    over ``block_n``-lane row tiles.  Returns (n,) in ``q``'s float dtype.

    ``block_n`` is the tunable row-tile width (sweep hook:
    ``benchmarks/kernels_bench.py``); padding lanes are inert
    (a=b=1, q=0.5) and sliced off.
    """
    n = q.shape[0]
    dtype = jnp.result_type(q.dtype, jnp.float32)
    check_native_dtype(dtype, interpret, "betaincinv")
    if n == 0:
        return jnp.zeros((0,), dtype)
    a = a.astype(dtype)
    b = b.astype(dtype)
    q = q.astype(dtype)

    block_n = min(block_n, max(n, 1))
    nb = -(-n // block_n)
    pad_n = nb * block_n - n
    if pad_n:
        a = jnp.pad(a, (0, pad_n), constant_values=1.0)
        b = jnp.pad(b, (0, pad_n), constant_values=1.0)
        q = jnp.pad(q, (0, pad_n), constant_values=0.5)

    out = pl.pallas_call(
        _betaincinv_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((nb * block_n,), dtype),
        interpret=interpret,
    )(a, b, q)
    return out[:n]
