"""Pipeline parallelism via shard_map + lax.ppermute (GPipe schedule).

The decoder stack is split into S contiguous stages along a ``stage`` mesh
axis; a batch is split into M microbatches.  Each loop iteration every
stage processes one microbatch and ppermutes its activation to the next
stage — the standard (S + M - 1)-tick GPipe pipeline expressed as pure
collectives, so the same code runs on a 2-pod mesh with ``pod`` as the
stage axis (inter-pod pipelining: one ICI/DCN hop per microbatch).

This module is exercised by multi-device subprocess tests (8 host devices)
and available to the launcher as an alternative to pure DPxTP for very
deep models; the default production configs fit without PP (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["PipelineConfig", "pipeline_forward"]


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    num_stages: int
    num_microbatches: int
    stage_axis: str = "stage"


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,        # pytree whose leaves have leading dim = num_stages
    x: jax.Array,             # (B, ...) global batch
    mesh: Mesh,
    cfg: PipelineConfig,
) -> jax.Array:
    """Run x through num_stages stage_fn applications, GPipe-scheduled.

    stage_params leaves are sharded over the stage axis (leading dim = S);
    x is replicated along the stage axis and microbatched internally.
    stage_fn must preserve the activation shape (a decoder stage).
    """
    S, M = cfg.num_stages, cfg.num_microbatches
    axis = cfg.stage_axis
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")

    def local(params_s, x_full):
        params_s = jax.tree.map(lambda t: t[0], params_s)  # strip stage dim
        stage_id = lax.axis_index(axis)
        micro = x_full.reshape(M, B // M, *x_full.shape[1:])
        n_ticks = S + M - 1
        right = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 feeds microbatch t while t < M; other stages consume buf
            feed_idx = jnp.clip(t, 0, M - 1)
            take_input = (stage_id == 0) & (t < M)
            inp = jnp.where(take_input, micro[feed_idx], buf)
            # stage s is active for microbatches at ticks [s, s + M)
            active = (t - stage_id >= 0) & (t - stage_id < M)
            out = stage_fn(params_s, inp)
            out = jnp.where(active, out, buf)
            # last stage banks its finished microbatch
            mb_idx = jnp.clip(t - (S - 1), 0, M - 1)
            bank = (stage_id == S - 1) & (t - (S - 1) >= 0) & (t - (S - 1) < M)
            outputs = outputs.at[mb_idx].set(
                jnp.where(bank, out, outputs[mb_idx])
            )
            buf = lax.ppermute(out, axis, right)  # pass rightward
            return (buf, outputs), None

        buf0 = jnp.zeros_like(micro[0])
        outs0 = jnp.zeros(micro.shape, micro.dtype)
        (_, outputs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(n_ticks))
        # results live on the last stage only; broadcast via masked psum
        mask = (stage_id == S - 1).astype(outputs.dtype)
        outputs = lax.psum(outputs * mask, axis)
        return outputs.reshape(B, *x_full.shape[1:])

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)
