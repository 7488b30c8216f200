"""Multi-device tests (8 forced host devices via subprocess — the parent
pytest process must keep seeing 1 device, so each test spawns its own
python with XLA_FLAGS set before jax import)."""
import pytest
import subprocess
import sys
import textwrap
from pathlib import Path

pytestmark = pytest.mark.slow  # spawns 8-device subprocess per test

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_subprocess(body: str, devices: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import jax
        import jax.numpy as jnp
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=420,
        env={**__import__('os').environ, "PYTHONPATH": SRC},
    )
    assert proc.returncode == 0, f"subprocess failed:\n{proc.stderr[-4000:]}"
    return proc.stdout


def test_pipeline_parallel_matches_sequential():
    out = run_subprocess("""
        from repro.pipeline import PipelineConfig, pipeline_forward
        S, M = 4, 4
        mesh = jax.make_mesh((S,), ("stage",))
        w = jax.random.normal(jax.random.key(0), (S, 16, 16)) * 0.3
        x = jax.random.normal(jax.random.key(1), (8, 16))
        fn = lambda wi, h: jnp.tanh(h @ wi)
        out = pipeline_forward(fn, w, x, mesh, PipelineConfig(S, M))
        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ w[s])
        print("ERR", float(jnp.abs(out - ref).max()))
    """)
    assert float(out.split("ERR")[1]) < 1e-6


def test_sharded_train_step_matches_single_device():
    """The same train step, sharded 4x2 (data x model) vs unsharded, gives
    identical losses — the distribution layer is semantics-preserving."""
    out = run_subprocess("""
        import dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import REGISTRY
        from repro.models import build_model
        from repro.sharding import named_sharding_tree, param_rules
        cfg = dataclasses.replace(REGISTRY["llama3.2-1b"].reduced(),
                                  num_layers=2, remat=False)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (8, 64), 0, cfg.vocab_size)
        loss_fn = lambda p, t: model.loss(p, {"tokens": t})[0]
        base = float(jax.jit(loss_fn)(params, tokens))

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        pspecs = model.pspecs(param_rules(cfg, fsdp=True))
        psh = named_sharding_tree(model.abstract(), pspecs, mesh)
        with mesh:
            sharded = jax.jit(
                loss_fn,
                in_shardings=(psh, NamedSharding(mesh, P("data", None))),
            )
            dist = float(sharded(params, tokens))
        print("LOSSES", base, dist)
    """)
    base, dist = map(float, out.split("LOSSES")[1].split())
    assert abs(base - dist) < 5e-3


def test_elastic_reshard_dp1_to_dp2():
    """Checkpoint written on 1 device resumes on a 2x DP mesh — elastic
    scaling across restarts."""
    out = run_subprocess("""
        import tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.io import save_pytree, load_pytree
        tree = {"w": jnp.arange(32.0).reshape(8, 4),
                "m": {"v": jnp.ones((8, 4))}}
        d = tempfile.mkdtemp()
        save_pytree(d, 7, tree, extra={"next_step": 7})
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        sh = jax.tree.map(
            lambda _: NamedSharding(mesh, P("data", "model")), tree)
        loaded, extra = load_pytree(d, 7, tree, shardings=sh)
        ok = bool(jnp.array_equal(loaded["w"], tree["w"]))
        shards = len(loaded["w"].sharding.device_set)
        print("OK", ok, shards, extra["next_step"])
    """)
    _, ok, shards, step = out.split()
    assert ok == "True" and int(shards) == 8 and int(step) == 7


def test_multi_tenant_fleet_sharded_matches_unsharded():
    """The multi-tenant replay engine shard_map'd over 8 forced host
    devices (one tenant per device) returns the same report as the
    unsharded vmap — and the posterior carry really is partitioned
    8-ways (so repeated calibration rounds donate per-device buffers)."""
    out = run_subprocess("""
        import dataclasses
        import sys
        from pathlib import Path
        sys.path.insert(0, str(Path({src!r}).parent))
        import numpy as np
        from benchmarks.workflow_sim import DEFAULT_ALPHAS, LAMBDA_USD_PER_S, _mt_stack
        from repro.core.fleet import multi_tenant_replay
        from repro.launch.mesh import make_fleet_mesh
        stack = _mt_stack(tenants=8, episodes=40)
        alphas = np.asarray(DEFAULT_ALPHAS)
        base = multi_tenant_replay(stack, alphas, LAMBDA_USD_PER_S,
                                   donate=False)
        mesh = make_fleet_mesh()
        sharded = multi_tenant_replay(stack, alphas, LAMBDA_USD_PER_S,
                                      mesh=mesh)
        ok = True
        for f in dataclasses.fields(base):
            a, b = getattr(base, f.name), getattr(sharded, f.name)
            if isinstance(a, np.ndarray):
                ok = ok and bool(np.array_equal(a, b))
        ok = ok and bool(np.array_equal(np.asarray(base.post_final),
                                        np.asarray(sharded.post_final)))
        shards = len(sharded.post_final.sharding.device_set)
        # chained round: donate the sharded carry back in
        r2 = multi_tenant_replay(stack, alphas, LAMBDA_USD_PER_S, mesh=mesh,
                                 post0=sharded.post_final, donate=True)
        chained = int(np.asarray(r2.post_final).shape[0])
        print("OK", ok, shards, chained)
    """.format(src=SRC))
    _, ok, shards, chained = out.split()
    assert ok == "True" and int(shards) == 8 and int(chained) == 8


def test_episode_sharded_fleet_matches_unsharded():
    """The episode-sharded replay shard_map'd over 8 forced host devices
    (one segment per device) returns a report bitwise-equal (f64) to the
    unsharded sequential ``fleet_replay`` scan — and the segment-stats
    pass really is partitioned 8-ways."""
    out = run_subprocess("""
        import dataclasses
        import sys
        from pathlib import Path
        sys.path.insert(0, str(Path({src!r}).parent))
        import numpy as np
        from jax import enable_x64
        from benchmarks.workflow_sim import (
            DEFAULT_ALPHAS, LAMBDA_USD_PER_S, _autoreply_fleet,
            _episode_sharded_shards)
        from repro.core import episode_sharded_replay, fleet_replay
        from repro.launch.mesh import make_fleet_mesh
        alphas = np.asarray(DEFAULT_ALPHAS)
        mesh = make_fleet_mesh()
        with enable_x64():
            lowered, success, _ = _autoreply_fleet(episodes=64)
            base = fleet_replay(lowered, success, alphas, LAMBDA_USD_PER_S)
            sharded = episode_sharded_replay(
                lowered, success, alphas, LAMBDA_USD_PER_S,
                n_segments=8, mesh=mesh)
            ok = True
            for f in dataclasses.fields(base):
                a, b = getattr(base, f.name), getattr(sharded, f.name)
                ok = ok and bool(np.array_equal(a, b))
            shards = _episode_sharded_shards(
                lowered, success, alphas, mesh, 8)
        print("OK", ok, shards)
    """.format(src=SRC))
    _, ok, shards = out.split()
    assert ok == "True" and int(shards) == 8


def test_online_service_row_sharded_matches_unsharded():
    """The online decision service's posterior table shard_map'd over 8
    forced host devices (rows partitioned on the 1-D fleet mesh) answers a
    mixed tick sequence — decisions incl. §7.5, outcome settlement, drift
    checks, telemetry — bitwise-equal (f64) to the unsharded service, the
    table really is partitioned 8-ways after warm donated ticks, and an
    indivisible mesh extent (3 of 8 devices over 16 rows) falls back to
    the unsharded executable with identical results."""
    out = run_subprocess("""
        import numpy as np
        from jax import enable_x64
        from jax.sharding import Mesh
        from repro.core.online import OnlineDecisionService
        from repro.core.taxonomy import DependencyType
        from repro.launch.mesh import make_fleet_mesh

        with enable_x64():
            def build(mesh):
                svc = OnlineDecisionService(mesh=mesh,
                                            credible_consecutive_n=2)
                for i in range(16):
                    svc.register_edge(
                        ("u", f"v{i}"),
                        dep_type=DependencyType.ROUTER_K_WAY, k=2 + i % 5,
                        discount=(0.97 if i % 3 == 0 else 1.0),
                        floor_alpha=0.5, floor_C_spec_usd=0.01,
                        floor_L_value_usd=0.002 + 0.001 * i)
                return svc

            def run(svc, seed=42):
                rng = np.random.default_rng(seed)
                ticks = []
                for t in range(3):
                    B = 40
                    d = svc.tick(
                        rng.integers(0, 16, B),
                        alpha=rng.uniform(0, 1, B), lambda_usd_per_s=0.05,
                        latency_s=rng.uniform(0.1, 2, B), input_tokens=20,
                        output_tokens=rng.uniform(10, 200, B),
                        input_price=1e-6, output_price=1e-5,
                        outcomes=[(int(r), bool(s)) for r, s in zip(
                            rng.integers(0, 16, 9), rng.integers(0, 2, 9))],
                        use_lower_bound=(t == 1), check_drift=True)
                    ticks.append((d.EV_usd.copy(), d.margin_usd.copy(),
                                  d.speculate.copy(),
                                  d.drift_triggered.copy()))
                return (ticks, svc.posterior_snapshot(),
                        svc.enabled_snapshot(), svc.breach_runs(),
                        svc.drain_telemetry().fields["margin_usd"])

            base = run(build(None))
            sharded_svc = build(make_fleet_mesh())
            sharded = run(sharded_svc)
            ok = all(
                np.array_equal(a, b)
                for t0, t1 in zip(base[0], sharded[0])
                for a, b in zip(t0, t1)
            ) and all(np.array_equal(base[i], sharded[i])
                      for i in (1, 2, 3, 4))
            shards = len(sharded_svc.state.post.sharding.device_set)

            # indivisible fallback: 3-device fleet mesh over 16 rows
            mesh3 = Mesh(np.array(jax.devices()[:3]), ("fleet",))
            fb_svc = build(mesh3)
            fb = run(fb_svc)
            fb_ok = all(np.array_equal(base[i], fb[i]) for i in (1, 2, 3, 4))
            fb_shards = len(fb_svc.state.post.sharding.device_set)
        print("OK", ok, shards, fb_ok, fb_shards)
    """)
    _, ok, shards, fb_ok, fb_shards = out.split()
    assert ok == "True" and int(shards) == 8
    assert fb_ok == "True" and int(fb_shards) == 1


def test_gpipe_on_pod_axis_with_dp():
    """PP on one axis composed with DP on the other (2 stages x 4 dp)."""
    out = run_subprocess("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.pipeline import PipelineConfig, pipeline_forward
        mesh = jax.make_mesh((2, 4), ("stage", "data"))
        w = jax.random.normal(jax.random.key(0), (2, 8, 8)) * 0.3
        x = jax.random.normal(jax.random.key(1), (8, 8))
        fn = lambda wi, h: jnp.tanh(h @ wi)
        out = pipeline_forward(fn, w, x, mesh, PipelineConfig(2, 4))
        ref = jnp.tanh(jnp.tanh(x @ w[0]) @ w[1])
        print("ERR", float(jnp.abs(out - ref).max()))
    """)
    assert float(out.split("ERR")[1]) < 1e-6
