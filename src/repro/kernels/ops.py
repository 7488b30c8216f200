"""jit'd public wrappers for the Pallas kernels.

Backend dispatch: on TPU the kernels compile natively; on the CPU they
run under interpret=True (Python evaluation of the kernel body —
correctness validation only); any other backend raises.
``flash_attention`` exposes a custom_vjp whose backward is the
rematerialized reference (fused bwd kernel is future work); the scan
kernels are forward-only ops used by serving paths.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref as _ref
from .betaincinv_pallas import betaincinv_kernel_call
from .decode_attention import decode_attention_kernel_call
from .flash_attention import flash_attention_fwd
from .online_tick import online_tick_kernel_call
from .replay_grid import replay_grid_kernel_call
from .rglru_scan import rglru_scan_kernel_call
from .ssd_scan import ssd_scan_kernel_call

__all__ = [
    "flash_attention",
    "decode_attention_op",
    "rglru_scan_op",
    "ssd_scan_op",
    "replay_grid_op",
    "betaincinv_op",
    "online_tick_op",
    "on_tpu",
]

def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """The Pallas interpret flag, from the default backend: interpret
    mode on the CPU, native Mosaic lowering on the TPU.  Any other
    backend raises: a kernel never drops to the interpreter where a
    device was expected.

    Resolved OUTSIDE jit by the ops below and passed as a static arg, so
    a backend change between calls is honored rather than baked into the
    first trace.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas lowering for backend {backend!r} (cpu: interpret, "
        "tpu: native)")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None):
    """Flash attention with Pallas fwd + reference-recompute bwd."""
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               interpret=_interpret())


def _fa_fwd(q, k, v, causal, window):
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              interpret=_interpret())
    return out, (q, k, v)


def _fa_bwd(causal, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref.reference_attention(
            q_, k_, v_, causal=causal, window=window),
        q, k, v,
    )
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@jax.jit
def decode_attention_op(q, k_cache, v_cache, cache_positions, current_pos):
    """(B,H,D) x cache -> (B,H,D)."""
    return decode_attention_kernel_call(
        q, k_cache, v_cache, cache_positions, current_pos,
        interpret=_interpret(),
    )


@jax.jit
def rglru_scan_op(a, b, h0=None):
    """Linear recurrence h_t = a_t h_{t-1} + b_t, all t.  (B,T,C)."""
    return rglru_scan_kernel_call(a, b, h0, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan_op(x, A, Bm, Cm, chunk: int = 128):
    """Mamba-2 SSD chunk scan.  Returns y (B,S,H,P)."""
    return ssd_scan_kernel_call(x, A, Bm, Cm, chunk=chunk,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("rho", "interpret"))
def _replay_grid_jit(P, lat, cost, alphas, lambdas, rho, interpret):
    return replay_grid_kernel_call(P, lat, cost, alphas, lambdas,
                                   rho=rho, interpret=interpret)


def replay_grid_op(P, lat, cost, alphas, lambdas, rho: float = 0.5):
    """§12.1 fused counterfactual (alpha, lambda) grid sweep: one kernel
    launch over all log rows x grid cells.  Returns (A, L) arrays
    (speculate_count, expected_latency_sum, expected_waste_sum)."""
    return _replay_grid_jit(P, lat, cost, alphas, lambdas, rho,
                            _interpret())


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _betaincinv_jit(a, b, q, block_n, interpret):
    return betaincinv_kernel_call(a, b, q, block_n=block_n,
                                  interpret=interpret)


def betaincinv_op(a, b, q, block_n: int = 1024):
    """Batched Beta quantile via the Pallas kernel: (n,) -> (n,).
    <=1e-10 relative vs the `jax.scipy`-based `core.betainc.betaincinv`
    (not bitwise — the kernel carries its own betainc evaluator)."""
    return _betaincinv_jit(a, b, q, block_n, _interpret())


@functools.partial(
    jax.jit,
    static_argnames=("use_lower_bound", "check_drift", "block_n",
                     "interpret"),
)
def _online_tick_jit(post, rowcfg, flags, zero, row, reqs, out_row, out_x,
                     consecutive_n, use_lower_bound, check_drift, block_n,
                     interpret):
    return online_tick_kernel_call(
        post, rowcfg, flags, zero, row, reqs, out_row, out_x,
        consecutive_n, use_lower_bound=use_lower_bound,
        check_drift=check_drift, block_n=block_n, interpret=interpret)


def online_tick_op(post, rowcfg, flags, zero, row, reqs, out_row, out_x,
                   consecutive_n, use_lower_bound: bool = False,
                   check_drift: bool = False, block_n: int = 1024):
    """Fused online-service tick (settle + D4 gate + drift) in one Pallas
    launch over the SoA row axis.  Mean-path outputs are bitwise-f64
    equal to `OnlineDecisionService._tick_impl`; the lower-bound / drift
    quantile paths sit at the <=1e-10 betaincinv tier."""
    return _online_tick_jit(
        post, rowcfg, flags, zero, row, reqs, out_row, out_x,
        consecutive_n, use_lower_bound, check_drift, block_n,
        _interpret())
