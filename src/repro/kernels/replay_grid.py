"""Pallas TPU kernel for the §12.1 counterfactual (alpha, lambda) grid.

The offline-replay calibration stage re-runs the D4 gate for every logged
decision row at every (alpha, lambda) grid point and aggregates per-cell
statistics (speculate fraction, expected latency, expected waste).  The
reduction axis is the log (millions of rows); the grid is small.  This
kernel fuses the whole sweep into one launch:

    grid = (num_row_blocks,)  — sequential on TPU, so each program
    accumulates its block's partial sums into the same (A, L) output
    block (the standard revisited-output accumulation pattern).

Per row i and cell (a, l):

    EV[a,l,i]  = P_i * lat_i * lam_l - (1 - P_i) * cost_i
    thr[a,i]   = (1 - alpha_a) * cost_i
    spec       = EV >= thr
    count     += spec
    lat_sum   += spec ? lat_i * (1 - P_i) : lat_i     (expected latency)
    waste_sum += spec * (1 - P_i) * cost_i * rho      (§9.3 expected waste)

Padded rows are encoded as (P=0, lat=0, cost=1) so they never speculate
and contribute zero to every sum; padded lambda cells use lam=0 and are
sliced off by the wrapper.  Layout: the log rows ride along lanes as
(1, block_n) tiles, lambdas down sublanes as an (Lp, 1) column, and the
alphas are SMEM scalars looped over in the kernel, so every broadcast is
a plain (Lp, block_n) one that Mosaic lays out without reshapes.

Validated under interpret=True on CPU against ``ref.reference_replay_grid``
(and transitively against ``batch_decision.counterfactual_grid``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .betaincinv_pallas import check_native_dtype

__all__ = ["replay_grid_kernel_call", "replay_grid_summary"]


def _replay_grid_kernel(alpha_ref, lam_ref, p_ref, lat_ref, cost_ref,
                        count_ref, lat_o_ref, waste_o_ref, *, rho: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)
        lat_o_ref[...] = jnp.zeros_like(lat_o_ref)
        waste_o_ref[...] = jnp.zeros_like(waste_o_ref)

    P = p_ref[...]        # (1, bn)
    lat = lat_ref[...]    # (1, bn)
    cost = cost_ref[...]  # (1, bn)
    lams = lam_ref[...]   # (Lp, 1)
    one = jnp.ones((), P.dtype)
    nil = jnp.zeros((), P.dtype)

    gain = lams * (P * lat)                            # (Lp, bn)
    lose = (1.0 - P) * cost                            # (1, bn)
    ev = gain - lose                                   # (Lp, bn)
    lat_spec = lat * (1.0 - P)                         # (1, bn)

    # one (Lp, bn) pass per alpha; alphas are SMEM scalars and each
    # alpha's per-lambda sums land in its own (Lp, 1) output slab
    def per_alpha(k, carry):
        thr = (1.0 - alpha_ref[k]) * cost              # (1, bn)
        spec = ev >= thr                               # (Lp, bn)
        count_ref[k] += jnp.where(spec, one, nil).sum(1, keepdims=True)
        lat_o_ref[k] += jnp.where(spec, lat_spec, lat).sum(1, keepdims=True)
        waste_o_ref[k] += jnp.where(spec, lose, nil).sum(
            1, keepdims=True) * rho
        return carry

    jax.lax.fori_loop(np.int32(0), np.int32(alpha_ref.shape[0]), per_alpha,
                      0)


def replay_grid_kernel_call(
    P: jax.Array,         # (n,) per-row success probability
    lat: jax.Array,       # (n,) latency savings per row (s)
    cost: jax.Array,      # (n,) C_spec per row (USD)
    alphas: jax.Array,    # (A,)
    lambdas: jax.Array,   # (L,)
    *,
    rho: float = 0.5,
    block_n: int = 4096,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused §12.1 grid sweep.  Returns per-cell (A, L) arrays:
    (speculate_count, expected_latency_sum, expected_waste_sum)."""
    n = P.shape[0]
    A = alphas.shape[0]
    L = lambdas.shape[0]
    dtype = jnp.result_type(P.dtype, jnp.float32)
    check_native_dtype(dtype, interpret, "replay_grid")
    if n == 0:
        zeros = jnp.zeros((A, L), dtype)
        return zeros, zeros, zeros
    P = P.astype(dtype)
    lat = lat.astype(dtype)
    cost = cost.astype(dtype)

    block_n = min(block_n, max(n, 1))
    nb = -(-n // block_n)
    pad_n = nb * block_n - n
    if pad_n:
        # inert rows: never speculate, zero latency/waste contribution
        P = jnp.pad(P, (0, pad_n))
        lat = jnp.pad(lat, (0, pad_n))
        cost = jnp.pad(cost, (0, pad_n), constant_values=1.0)

    # the lambda axis runs down sublanes: pad it to the 8-row tile
    Lp = -(-L // 8) * 8
    lambdas_p = jnp.pad(lambdas.astype(dtype), (0, Lp - L)).reshape(Lp, 1)

    kernel = functools.partial(_replay_grid_kernel, rho=float(rho))
    z = np.int32(0)       # a Python 0 would lower as i64 under x64
    row = pl.BlockSpec((1, block_n), lambda i: (z, i))
    acc = pl.BlockSpec((A, Lp, 1), lambda i: (z, z, z))
    count, lat_sum, waste_sum = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((A,), lambda i: (z,), memory_space=pltpu.SMEM),
            pl.BlockSpec((Lp, 1), lambda i: (z, z)),
            row, row, row,
        ],
        out_specs=[acc, acc, acc],
        out_shape=[jax.ShapeDtypeStruct((A, Lp, 1), dtype)] * 3,
        interpret=interpret,
    )(alphas.astype(dtype), lambdas_p, P.reshape(1, -1), lat.reshape(1, -1),
      cost.reshape(1, -1))
    return (count[:, :L, 0], lat_sum[:, :L, 0], waste_sum[:, :L, 0])


def replay_grid_summary(
    P: np.ndarray, lat: np.ndarray, cost: np.ndarray,
    alphas: np.ndarray, lambdas: np.ndarray,
    *, rho: float = 0.5, interpret: bool = True,
) -> dict:
    """Convenience wrapper matching ``batch_decision.counterfactual_grid``'s
    output dict, computed via the fused kernel."""
    n = np.shape(lat)[0]
    P = jnp.broadcast_to(jnp.asarray(P, jnp.float32), (n,))
    count, lat_sum, waste = replay_grid_kernel_call(
        P, jnp.asarray(lat, jnp.float32), jnp.asarray(cost, jnp.float32),
        jnp.asarray(alphas, jnp.float32), jnp.asarray(lambdas, jnp.float32),
        rho=rho, interpret=interpret,
    )
    total_cost = float(np.sum(cost))
    waste = np.asarray(waste)
    return {
        "speculate_fraction": np.asarray(count) / n,
        "expected_latency_s": np.asarray(lat_sum) / n,
        "expected_cost_usd": total_cost + waste,
        "expected_waste_usd": waste,
    }
