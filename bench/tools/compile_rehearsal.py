"""Compile each cell's executables at the cells' real sizes for a
described TPU v5e, without the chip, and print ``memory_analysis``.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_rehearsal.py

Nothing runs: it shows what the chip's compiler accepts and how much
memory each executable asks for.  It is not a chip run.
"""
from __future__ import annotations

import functools
import json
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "src"))


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")
        if hasattr(m, k)}


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    from bench import gen
    from bench.common import BENCH, load_json

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    s = functools.partial(lambda sh, shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=sh), one)
    f, i = jnp.float32, jnp.int32
    out = {}

    # -- the serving cells: the tick at every settle bucket, the store's
    # fault-in scatter and spill gather at every bucket
    from bench.serving import settle_buckets
    from repro.core import store as st
    from repro.core.online import ServiceState, _tick

    fleet = load_json(BENCH / "configs" / "fleet1m.json")
    N, B = fleet["resident_rows"], fleet["frontend"]["max_batch"]
    state = ServiceState(post=s((N, 2), f), rowcfg=s((N, 3), f),
                         flags=s((N, 2), i), roll=s((N, 6), i),
                         tel=s((4096, 10), f), counters=s((2,), i))
    for S in (0, B, *settle_buckets(fleet)):
        c = _tick.lower(
            state, s((), f), s((B,), i), s((B,), i), s((B, 7), f),
            s((0, 1), f), s((0,), i), s((S,), i), s((S,), f), s((), i),
            s((9,), i), use_lower_bound=False, check_drift=True,
            use_rollout=False, use_beam=False).compile()
        out[f"tick_settles_{S}"] = _mem(c)
    for k in (256, 512, N):
        c = st._scatter_rows.lower(
            s((N, 2), f), s((N, 3), f), s((N, 2), i), s((N, 6), i),
            s((k,), jnp.int32), s((k, 2), f), s((k, 3), f), s((k, 2), i),
            s((k, 6), i)).compile()
        out[f"store_scatter_{k}"] = _mem(c)

    # -- the replay cells
    from repro.core import fleet as fl

    ar = load_json(BENCH / "configs" / "autoreply.json")
    lowered = gen.autoreply_workflow(ar)
    alphas, lams = gen.grid(ar)
    G, V = alphas.shape[0], lowered.n_ops
    static = jax.tree.map(lambda x: s(x.shape, x.dtype),
                          fl._pack_static(lowered, np.zeros(V, bool)))
    for E in (65_536, 131_072, 262_144):
        c = fl._fleet_scan.lower(
            static, s((V,), f), s((V,), f), s((V,), f), s((G,), f),
            s((G,), f), s((), f), s((E, V), jnp.bool_), s((E, V), jnp.bool_),
            s((E, V, 1), f), s((E,), jnp.bool_), throttle_every=1, K=1,
            use_lower_bound=False).compile()
        out[f"fleet_replay_E{E}"] = _mem(c)

    # -- the four-chip replay left for a later cell (PERF.md): the
    # handoff scan on one chip, the segment stats pass shard_map'd over
    # the 2x2 fleet mesh
    E, C = 131_072, 8
    L = E // C
    mesh = Mesh(np.array(topo.devices), ("fleet",),
                axis_types=(jax.sharding.AxisType.Auto,))
    rep = NamedSharding(mesh, PartitionSpec())
    part = NamedSharding(mesh, PartitionSpec("fleet"))
    args1 = (s((V,), f), s((G,), f), s((G,), f), s((), f),
             s((C, L, V), jnp.bool_), s((C, L, V), jnp.bool_),
             s((C, L, V, 1), f), s((C, L), jnp.bool_))
    c = jax.jit(functools.partial(
        fl._boundary_scan, throttle_every=1, K=1,
        use_lower_bound=False)).lower(static, s((G, V, 2), f),
                                      *args1).compile()
    out[f"boundary_scan_E{E}_C{C}"] = _mem(c)
    m = functools.partial(lambda sh, shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=sh))
    static4 = jax.tree.map(lambda x: m(rep, x.shape, x.dtype), static)
    args4 = (m(rep, (V,), f), m(rep, (G,), f), m(rep, (G,), f),
             m(rep, (), f), m(part, (C, L, V), jnp.bool_),
             m(part, (C, L, V), jnp.bool_), m(part, (C, L, V, 1), f),
             m(part, (C, L), jnp.bool_))
    fn = fl._seg_executable(mesh, "fleet", 1, 1, False)
    c = fn.lower(static4, m(part, (C, G, V, 2), f), *args4).compile()
    out[f"segment_stats_E{E}_C{C}_4chips"] = _mem(c)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
