"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  The dry-run process
sets XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import; everything else sees the real device count.
"""
from __future__ import annotations

import jax

__all__ = [
    "make_production_mesh",
    "make_host_mesh",
    "make_fleet_mesh",
    "HardwareSpec",
    "V5E",
]

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Roofline constants for the target chip (TPU v5e)."""

    name: str
    peak_bf16_flops: float      # per chip, FLOP/s
    hbm_bandwidth: float        # bytes/s
    ici_link_bandwidth: float   # bytes/s per link
    hbm_bytes: float            # per-chip capacity


V5E = HardwareSpec(
    name="tpu-v5e",
    peak_bf16_flops=197e12,
    hbm_bandwidth=819e9,
    ici_link_bandwidth=50e9,
    hbm_bytes=16 * 1024**3,
)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (real or forced) host devices exist —
    used by multi-device CPU tests, not the dry-run."""
    return jax.make_mesh((data, model), ("data", "model"))


def make_fleet_mesh(n: int | None = None, *, axis: str = "fleet"):
    """1-D mesh for the fleet replay engines.  Two batch axes ride it:
    the ``tenants x grid`` axis of
    ``repro.core.fleet.multi_tenant_replay`` and the episode-segment
    axis of ``repro.core.fleet.episode_sharded_replay`` (one tenant's
    million-episode log as C independent scan segments) — both
    shard_map'd via ``sharding.rules.fleet_axis_spec``.  Defaults to
    every visible (real or XLA_FLAGS-forced) device.

    The axis is ``Auto``: the engines gather rows by dynamic index from
    tables sharded on it (``jax.make_mesh`` otherwise makes it
    ``Explicit``, where such gathers are sharding-type errors)."""
    n = len(jax.devices()) if n is None else n
    return jax.make_mesh((n,), (axis,),
                         axis_types=(jax.sharding.AxisType.Auto,))
