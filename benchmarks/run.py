"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one row per benchmark) and
persists every module's rows to ``BENCH_<module>.json`` at the repo root
so the perf trajectory is machine-readable across PRs (the fleet replay
additionally writes its own BENCH_fleet.json speedup record from
``workflow_sim.fleet_speedup``).

    PYTHONPATH=src python -m benchmarks.run            # full run
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI mode

``--smoke`` runs the fleet record at tiny episode counts: every parity
gate still executes (scalar<->fleet Pareto, bitwise multi-tenant) and
both the fresh record and the checked-in BENCH_*.json files are
schema-validated, but no timings are asserted and nothing is written —
tests/test_benchmarks_smoke.py keeps it in tier-1 so benchmark drift
breaks fast instead of rotting silently.
"""
from __future__ import annotations

import json
import pathlib
import platform
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]

# BENCH_fleet.json schema (see workflow_sim.fleet_speedup): required keys
# at each level of the record.
_FLEET_KEYS = {
    "benchmark", "alphas", "episodes", "grid_points", "scalar_total_s",
    "fleet_total_s", "speedup", "parity", "pareto_dtype", "pareto_fleet",
    "credible_bound", "multi_tenant", "episode_sharded", "online_service",
    "beam",
}
_CREDIBLE_KEYS = {"benchmark", "gamma", "speedup", "parity", "pareto_dtype",
                  "pareto_fleet"}
_OS_KEYS = {"benchmark", "rows", "reps", "rounds", "parity", "batches"}
_OS_BATCH_KEYS = {"B", "reps", "ticks_per_s", "us_per_decision",
                  "scalar_us_per_decision", "speedup"}
_MT_KEYS = {
    "benchmark", "tenants", "grid_points", "episodes", "one_call_s",
    "per_tenant_calls_s", "speedup", "parity", "scaling",
}
_ES_KEYS = {
    "benchmark", "episodes", "segments", "grid_points", "unsharded_s",
    "sharded_s", "speedup", "parity", "scaling", "pipelined",
}
_ES_PIPE_KEYS = {"pipelined_s", "speedup_vs_two_pass",
                 "speedup_vs_unsharded", "parity"}
_BEAM_KEYS = {
    "benchmark", "widths", "candidates", "confidences", "lambda_usd_per_s",
    "episodes", "grid_points", "one_call_s", "per_width_calls_s", "speedup",
    "parity", "pareto_dtype", "pareto",
}
_BEAM_PARETO_KEYS = {
    "latency_s", "cost_usd", "waste_usd", "launched", "committed",
    "launched_candidates", "cancelled_candidates",
}
_ROWS_KEYS = {"module", "rows"}

# BENCH_frontend.json schema (see frontend_load.frontend_record)
_FRONTEND_KEYS = {
    "benchmark", "seed", "offered_rate_hz", "duration_s", "requests",
    "config", "decisions_per_s", "shed_rate", "latency_ms", "ticks",
    "deadline_ticks", "full_ticks", "stats", "parity", "fault_matrix",
    "resilience_events", "usd_attribution",
}
_FRONTEND_FAULTS = {"exception_burst", "hung_tick", "tenant_flood",
                    "drift_flip"}

# BENCH_store.json schema (see store_scale.store_record)
_STORE_KEYS = {
    "benchmark", "seed", "logical_rows", "resident_capacity",
    "decisions_per_s", "parity", "zero_recompile", "register", "decide",
    "memory", "cold_start",
}

# BENCH_kernels.json schema (see kernels_bench.kernels_record)
_KERNELS_KEYS = {"benchmark", "backend", "interpret", "betaincinv",
                 "online_tick"}
_K_BII_KEYS = {"n", "parity", "sweep", "reference_us_per_call"}
_K_TICK_KEYS = {"rows", "batch", "settles", "parity", "sweep",
                "reference_us_per_tick"}

# BENCH_rollout.json schema (see rollout_fleet.rollout_record)
_ROLLOUT_KEYS = {
    "benchmark", "seed", "decisions_per_s", "determinism", "parity",
    "zero_recompile", "acceptance", "pareto",
}
_ROLLOUT_PARETO_KEYS = {
    "archetype", "p_mode", "speculate_rate", "success_rate",
    "final_phases", "promotes", "demotes", "demote_usd", "events",
}


def _require(present, required, what: str) -> None:
    missing = sorted(required - set(present))
    if missing:
        raise AssertionError(f"{what}: missing keys {missing}")


def validate_fleet_record(rec: dict, what: str = "fleet record") -> None:
    """Assert the BENCH_fleet.json shape (full and --smoke records)."""
    _require(rec, _FLEET_KEYS, what)
    _require(rec["credible_bound"], _CREDIBLE_KEYS, f"{what}.credible_bound")
    _require(rec["multi_tenant"], _MT_KEYS, f"{what}.multi_tenant")
    for row in rec["multi_tenant"]["scaling"]:
        _require(row, {"devices", "shards", "wall_s"},
                 f"{what}.multi_tenant.scaling")
    es = rec["episode_sharded"]
    _require(es, _ES_KEYS, f"{what}.episode_sharded")
    _require(es["parity"],
             {"bitwise_f64_vs_fleet_replay",
              "grid_reroute_fraction_bitwise",
              "grid_reroute_max_rel_error"},
             f"{what}.episode_sharded.parity")
    _require(es["pipelined"], _ES_PIPE_KEYS,
             f"{what}.episode_sharded.pipelined")
    if not es["pipelined"]["parity"].get("bitwise_f64_vs_fleet_replay"):
        raise AssertionError(
            f"{what}.episode_sharded.pipelined: parity gate recorded false")
    for row in es["scaling"]:
        _require(row, {"devices", "shards", "wall_s"},
                 f"{what}.episode_sharded.scaling")
    osvc = rec["online_service"]
    _require(osvc, _OS_KEYS, f"{what}.online_service")
    _require(osvc["parity"],
             {"bitwise_f64_vs_scalar_evaluate", "lower_bound_flags_match"},
             f"{what}.online_service.parity")
    if not osvc["batches"]:
        raise AssertionError(f"{what}.online_service: no batch rows")
    for row in osvc["batches"]:
        _require(row, _OS_BATCH_KEYS, f"{what}.online_service.batches")
    beam = rec["beam"]
    _require(beam, _BEAM_KEYS, f"{what}.beam")
    _require(beam["parity"],
             {"w1_bitwise_f64_vs_fleet_replay",
              "reference_decisions_bitwise", "reference_max_rel_error"},
             f"{what}.beam.parity")
    if not (beam["parity"]["w1_bitwise_f64_vs_fleet_replay"]
            and beam["parity"]["reference_decisions_bitwise"]):
        raise AssertionError(f"{what}.beam: parity gate recorded false")
    if not beam["widths"] or beam["widths"][0] != 1:
        raise AssertionError(
            f"{what}.beam: width sweep must start at the parity-gated "
            f"width 1, got {beam['widths']}")
    for w in beam["widths"]:
        rows = beam["pareto"].get(str(w))
        if not rows:
            raise AssertionError(f"{what}.beam: no pareto rows at w={w}")
        for a, row in rows.items():
            _require(row, _BEAM_PARETO_KEYS, f"{what}.beam.pareto[{w}][{a}]")


def validate_frontend_record(rec: dict, what: str = "frontend record") -> None:
    """Assert the BENCH_frontend.json shape (full and --smoke records)."""
    _require(rec, _FRONTEND_KEYS, what)
    _require(rec["latency_ms"], {"p50", "p99", "max"}, f"{what}.latency_ms")
    _require(rec["config"], {"max_batch", "deadline_s", "bulkhead_limit"},
             f"{what}.config")
    _require(rec["parity"],
             {"service_vs_scalar_bitwise_f64",
              "fallback_vs_scalar_bitwise_f64"},
             f"{what}.parity")
    if not (rec["parity"]["service_vs_scalar_bitwise_f64"]
            and rec["parity"]["fallback_vs_scalar_bitwise_f64"]):
        raise AssertionError(f"{what}: parity gate recorded false")
    _require(rec["fault_matrix"], _FRONTEND_FAULTS, f"{what}.fault_matrix")
    for name in _FRONTEND_FAULTS:
        _require(rec["fault_matrix"][name], {"events"},
                 f"{what}.fault_matrix.{name}")


def validate_store_record(rec: dict, what: str = "store record") -> None:
    """Assert the BENCH_store.json shape (full and --smoke records)."""
    _require(rec, _STORE_KEYS, what)
    par = rec["parity"]
    _require(par, {"paged_vs_dense_bitwise_f64",
                   "paged_vs_scalar_bitwise_f64", "rows_checked"},
             f"{what}.parity")
    if not (par["paged_vs_dense_bitwise_f64"]
            and par["paged_vs_scalar_bitwise_f64"]):
        raise AssertionError(f"{what}: parity gate recorded false")
    zr = rec["zero_recompile"]
    _require(zr, {"churn_steps", "logical_rows_end",
                  "host_capacity_doublings", "physical_capacity",
                  "rebuilds", "asserted"}, f"{what}.zero_recompile")
    if not zr["asserted"]:
        raise AssertionError(f"{what}: zero-recompile churn not asserted")
    _require(rec["register"], {"rows", "us_per_row"}, f"{what}.register")
    _require(rec["decide"], {"ticks", "batch", "us_per_decision",
                             "fault_ins", "spills"}, f"{what}.decide")
    _require(rec["memory"], {"logical_rows", "resident_rows",
                             "shelved_rows", "host_soa_bytes_per_row",
                             "device_table_bytes", "capacity"},
             f"{what}.memory")
    cs = rec["cold_start"]
    _require(cs, {"p_star", "bucket", "pooled_prior", "fixed_prior",
                  "curve", "pooled_tighter_at_birth"}, f"{what}.cold_start")
    if not cs["pooled_tighter_at_birth"]:
        raise AssertionError(
            f"{what}: pooled cold start not tighter than the fixed prior")
    if not cs["curve"]:
        raise AssertionError(f"{what}: empty cold-start curve")
    for row in cs["curve"]:
        _require(row, {"n_obs", "pooled_abs_err", "fixed_abs_err"},
                 f"{what}.cold_start.curve")


def validate_kernels_record(rec: dict, what: str = "kernels record") -> None:
    """Assert the BENCH_kernels.json shape (full and --smoke records).

    Both kernels must have recorded their parity gates as *passed*
    (parity is asserted in-process before any timing row is taken, so a
    record that exists at all implies the gates ran — this re-checks the
    recorded outcome so a hand-edited file can't smuggle a timing row
    past a failed gate)."""
    _require(rec, _KERNELS_KEYS, what)
    bii = rec["betaincinv"]
    _require(bii, _K_BII_KEYS, f"{what}.betaincinv")
    par = bii["parity"]
    _require(par, {"max_rel_vs_core", "max_rel_vs_scipy", "asserted_rtol"},
             f"{what}.betaincinv.parity")
    if not (par["max_rel_vs_core"] <= par["asserted_rtol"]
            and par["max_rel_vs_scipy"] <= par["asserted_rtol"]):
        raise AssertionError(
            f"{what}.betaincinv: recorded rel error exceeds asserted rtol")
    if not bii["sweep"]:
        raise AssertionError(f"{what}.betaincinv: empty block_n sweep")
    for row in bii["sweep"]:
        _require(row, {"block_n", "us_per_call"}, f"{what}.betaincinv.sweep")
    tick = rec["online_tick"]
    _require(tick, _K_TICK_KEYS, f"{what}.online_tick")
    tpar = tick["parity"]
    _require(tpar, {"mean_path_bitwise_f64", "lower_bound_max_rel"},
             f"{what}.online_tick.parity")
    if not tpar["mean_path_bitwise_f64"]:
        raise AssertionError(
            f"{what}.online_tick: mean-path parity gate recorded false")
    if not tick["sweep"]:
        raise AssertionError(f"{what}.online_tick: empty block_n sweep")
    for row in tick["sweep"]:
        _require(row, {"block_n", "us_per_tick"}, f"{what}.online_tick.sweep")


def validate_rollout_record(rec: dict, what: str = "rollout record") -> None:
    """Assert the BENCH_rollout.json shape (full and --smoke records)."""
    _require(rec, _ROLLOUT_KEYS, what)
    if not rec["determinism"].get("deterministic"):
        raise AssertionError(f"{what}: scenario determinism gate false")
    par = rec["parity"]
    _require(par, {"in_graph_vs_scalar_lifecycle", "ticks", "transitions",
                   "roll_state_bitwise"}, f"{what}.parity")
    if not (par["in_graph_vs_scalar_lifecycle"]
            and par["roll_state_bitwise"]):
        raise AssertionError(f"{what}: lifecycle parity gate false")
    zr = rec["zero_recompile"]
    _require(zr, {"asserted", "churn_ticks", "tick_executables",
                  "transition_kinds"}, f"{what}.zero_recompile")
    if not zr["asserted"]:
        raise AssertionError(f"{what}: zero-recompile churn not asserted")
    acc = rec["acceptance"]
    _require(acc, {"flip_at", "revert_at", "first_demote_tick",
                   "trigger_window_ticks", "demote_usd",
                   "re_promote_ticks", "final_phase", "events"},
             f"{what}.acceptance")
    if acc["final_phase"] != "FULL" or acc["demote_usd"] <= 0.0:
        raise AssertionError(f"{what}: acceptance scenario not met: {acc}")
    if not rec["pareto"]:
        raise AssertionError(f"{what}: empty Pareto table")
    for row in rec["pareto"]:
        _require(row, _ROLLOUT_PARETO_KEYS, f"{what}.pareto row")


def validate_bench_files() -> list[str]:
    """Schema-check every checked-in BENCH_*.json; returns the paths."""
    checked = []
    for path in sorted(ROOT.glob("BENCH_*.json")):
        obj = json.loads(path.read_text())
        if path.name == "BENCH_fleet.json":
            validate_fleet_record(obj, path.name)
        elif path.name == "BENCH_kernels.json":
            validate_kernels_record(obj, path.name)
        elif path.name == "BENCH_frontend.json":
            validate_frontend_record(obj, path.name)
        elif path.name == "BENCH_store.json":
            validate_store_record(obj, path.name)
        elif path.name == "BENCH_rollout.json":
            validate_rollout_record(obj, path.name)
        else:
            _require(obj, _ROWS_KEYS, path.name)
            for row in obj["rows"]:
                _require(row, {"name", "us_per_call", "derived"},
                         f"{path.name} row")
        checked.append(path.name)
    return checked


def smoke() -> dict:
    """Tiny-episode parity + schema gate (no timing claims, no writes).

    Runs the fleet record at tiny episode counts AND the serving
    front-end open-loop gate (deterministic seeded arrival trace on a
    virtual clock: parity, fault matrix, schema) AND the paged posterior
    store gate (dense/scalar bitwise parity, zero-recompile churn,
    pooled cold start) AND the staged-rollout lifecycle gate (scenario
    determinism, scalar lifecycle parity, zero-recompile phase churn,
    the acceptance flip) AND the Pallas hot-path kernel gate (interpret
    mode: betaincinv <=1e-10 vs the XLA inversion and scipy, fused tick
    bitwise vs the jitted reference tick) — all without touching any
    BENCH file."""
    from . import (frontend_load, kernels_bench, rollout_fleet, store_scale,
                   workflow_sim)

    rec = workflow_sim.smoke()
    validate_fleet_record(rec, "smoke record")
    k_rec = kernels_bench.smoke()
    validate_kernels_record(k_rec, "kernels smoke record")
    fe_rec = frontend_load.smoke()
    validate_frontend_record(fe_rec, "frontend smoke record")
    st_rec = store_scale.smoke()
    validate_store_record(st_rec, "store smoke record")
    ro_rec = rollout_fleet.smoke()
    validate_rollout_record(ro_rec, "rollout smoke record")
    checked = validate_bench_files()
    print(f"smoke ok: parity gates passed, schema ok for {checked}")
    return rec


def _persist(module_name: str, rows: list[tuple[str, float, str]]) -> None:
    import jax

    dev = jax.devices()[0]
    out = {
        "module": module_name,
        "host": platform.machine(),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "python": platform.python_version(),
        "unix_time": int(time.time()),
        "rows": [
            {"name": name, "us_per_call": us, "derived": derived}
            for name, us, derived in rows
        ],
    }
    path = ROOT / f"BENCH_{module_name}.json"
    path.write_text(json.dumps(out, indent=2) + "\n")


def main(only: list[str] | None = None) -> None:
    from . import (appendix_d, frontend_load, kernels_bench, paper_tables,
                   perf, rollout_fleet, roofline, store_scale, workflow_sim)

    modules = {
        "paper_tables": paper_tables,
        "appendix_d": appendix_d,
        "workflow_sim": workflow_sim,
        "perf": perf,
        "roofline": roofline,
        "frontend_load": frontend_load,
        "store_scale": store_scale,
        "rollout_fleet": rollout_fleet,
        "kernels_bench": kernels_bench,
    }
    if only:
        unknown = sorted(set(only) - set(modules))
        if unknown:
            raise SystemExit(
                f"unknown benchmark module(s) {unknown}; "
                f"known: {sorted(modules)}"
            )
        modules = {k: v for k, v in modules.items() if k in only}

    rows: list[tuple[str, float, str]] = []
    for name, mod in modules.items():
        mod_rows = mod.benchmarks()
        _persist(name, mod_rows)
        rows.extend(mod_rows)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    argv = sys.argv[1:]
    if "--smoke" in argv:
        smoke()
    else:
        main(only=argv or None)
