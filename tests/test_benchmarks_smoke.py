"""benchmarks/run.py --smoke wired into tier-1: tiny-episode parity
(scalar<->fleet Pareto, bitwise multi-tenant) plus the serving
front-end gate (bitwise parity, fault matrix on a virtual clock) and
schema validation of both the freshly-built records and every
checked-in BENCH_*.json — so benchmark or record-format drift breaks
fast tests instead of rotting until the next manual benchmark run."""
import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks import run as bench_run


def test_smoke_mode_parity_and_schema():
    rec = bench_run.smoke()
    # the smoke record is the full BENCH_fleet.json shape at tiny sizes
    assert rec["multi_tenant"]["parity"][
        "bitwise_f64_vs_independent_fleet_replay"] is True
    assert rec["parity"]["launched_match"] and rec["parity"]["committed_match"]
    assert rec["credible_bound"]["parity"]["launched_match"]
    # episode-sharded gate: the two-pass engine replayed the tiny log
    # bitwise-equal to the sequential scan, and the log-axis-sharded
    # §12.1 grid (the offline_replay reroute) kept decision fractions
    # bitwise with float sums inside reorder tolerance
    es = rec["episode_sharded"]
    assert es["parity"]["bitwise_f64_vs_fleet_replay"] is True
    assert es["parity"]["grid_reroute_fraction_bitwise"] is True
    assert es["parity"]["grid_reroute_max_rel_error"] <= 1e-12
    assert es["segments"] > 1
    # the pipelined replay (stats/handoff overlap) ran the same tiny log
    # through its host-loop dispatch and matched fleet_replay bitwise too
    assert es["pipelined"]["parity"]["bitwise_f64_vs_fleet_replay"] is True
    # online decision service gate: the batched tick must have passed the
    # bitwise-f64 decide parity (and §7.5 flag parity) before timing, and
    # the published pareto rows must carry the f64 dtype label matching
    # the parity tier
    osvc = rec["online_service"]
    assert osvc["parity"]["bitwise_f64_vs_scalar_evaluate"] is True
    assert osvc["parity"]["lower_bound_flags_match"] is True
    assert rec["pareto_dtype"] == "float64"
    assert rec["credible_bound"]["pareto_dtype"] == "float64"
    # beam gate: the top-k engine's width=1 slice replayed bitwise-f64
    # against fleet_replay, and the wide-beam sweep matched its pure-numpy
    # reference twin (decisions bitwise, USD stats inside 1-ULP FMA
    # tolerance), both before any timing was recorded
    beam = rec["beam"]
    assert beam["parity"]["w1_bitwise_f64_vs_fleet_replay"] is True
    assert beam["parity"]["reference_decisions_bitwise"] is True
    assert beam["parity"]["reference_max_rel_error"] <= 1e-12
    assert beam["widths"][0] == 1 and len(beam["widths"]) >= 2
    assert beam["pareto_dtype"] == "float64"
    # the width axis is live: some grid cell launches more candidates
    # (and bills more §9.3 waste) at the widest beam than at width 1
    w_lo, w_hi = str(beam["widths"][0]), str(beam["widths"][-1])
    assert any(
        beam["pareto"][w_hi][a]["launched_candidates"]
        > beam["pareto"][w_lo][a]["launched_candidates"]
        for a in beam["pareto"][w_lo])
    # tiny sizes: the smoke path must never masquerade as the real record
    assert rec["episodes"] < 100
    assert es["episodes"] < 100
    assert max(b["B"] for b in osvc["batches"]) < 64
    assert beam["episodes"] < 100


def test_frontend_smoke_gate_parity_and_fault_matrix():
    from benchmarks import frontend_load

    rec = frontend_load.smoke()
    bench_run.validate_frontend_record(rec, "frontend smoke record")
    # both parity stages ran: healthy batched tick bitwise-f64 vs the
    # scalar decision.evaluate path, and the breaker-open scalar
    # fallback answers bitwise vs the same reference
    assert rec["parity"]["service_vs_scalar_bitwise_f64"] is True
    assert rec["parity"]["fallback_vs_scalar_bitwise_f64"] is True
    # every fault-matrix scenario executed and recorded resilience events
    for name in sorted(bench_run._FRONTEND_FAULTS):
        events = rec["fault_matrix"][name]["events"]
        assert events, f"fault scenario {name} recorded no events"
    # drift_flip must have reached the §12.5 kill-switch on-device and
    # tenant_flood must have shed with USD attributed to the noisy tenant
    assert rec["fault_matrix"]["drift_flip"]["events"].get("drift_trip", 0) >= 1
    assert rec["fault_matrix"]["tenant_flood"]["events"].get("shed", 0) > 0
    # smoke never makes timing claims and never writes BENCH files
    assert rec["decisions_per_s"] == 0.0
    # the virtual-clock drive replayed deadline ticks deterministically
    assert rec["deadline_ticks"] >= 1
    assert rec["requests"] > 0 and rec["shed_rate"] == 0.0


def test_store_smoke_gate_parity_and_zero_recompile():
    from benchmarks import store_scale

    rec = store_scale.smoke()
    bench_run.validate_store_record(rec, "store smoke record")
    # both parity stages ran: the paged store answered churn ticks
    # bitwise-f64 equal to the dense identity-mode service, and its
    # batched decisions matched scalar decision.evaluate over the
    # composed (device + shelf + unborn) snapshot
    assert rec["parity"]["paged_vs_dense_bitwise_f64"] is True
    assert rec["parity"]["paged_vs_scalar_bitwise_f64"] is True
    assert rec["parity"]["rows_checked"] > 0
    assert rec["parity"]["dense_paged"]["spills"] > 0
    # capacity-doubling insert/evict churn left every jit cache where
    # warm-up put it and never rebuilt the physical table
    zr = rec["zero_recompile"]
    assert zr["asserted"] is True and zr["rebuilds"] == 1
    assert zr["host_capacity_doublings"] >= 1
    # empirical-Bayes pooling: the cold row born from the fitted bucket
    # hyperprior starts strictly tighter than the fixed taxonomy prior
    curve = rec["cold_start"]["curve"]
    assert rec["cold_start"]["pooled_tighter_at_birth"] is True
    assert curve[0]["pooled_abs_err"] < curve[0]["fixed_abs_err"]
    # smoke never makes timing claims and never writes BENCH files
    assert rec["decisions_per_s"] == 0.0
    assert rec["register"]["us_per_row"] == 0.0
    assert rec["logical_rows"] < 10_000


def test_checked_in_store_record_shape():
    checked = bench_run.validate_bench_files()
    assert "BENCH_store.json" in checked
    rec = json.loads((bench_run.ROOT / "BENCH_store.json").read_text())
    # acceptance shape: >= 1M logical rows served from a fixed physical
    # table a fraction of that size — every touched row beyond capacity
    # LRU-spilled to the host shelf (untouched rows stay unborn priors)
    # — with the bitwise scalar parity gate asserted before any timing
    assert rec["logical_rows"] >= 1_000_000
    assert rec["memory"]["capacity"] < rec["logical_rows"]
    assert rec["memory"]["resident_rows"] <= rec["memory"]["capacity"]
    assert rec["memory"]["shelved_rows"] > 0
    assert rec["decide"]["spills"] > 0 and rec["decide"]["fault_ins"] > 0
    assert rec["parity"]["paged_vs_scalar_bitwise_f64"] is True
    assert rec["decide"]["us_per_decision"] > 0.0
    assert rec["decisions_per_s"] > 0.0
    # zero recompiles across >= 3 host-capacity doublings
    assert rec["zero_recompile"]["host_capacity_doublings"] >= 3
    assert rec["zero_recompile"]["rebuilds"] == 1
    # cold-start recovery: pooled strictly tighter at birth
    curve = rec["cold_start"]["curve"]
    assert curve[0]["pooled_abs_err"] < curve[0]["fixed_abs_err"]


def test_checked_in_bench_files_carry_required_schema():
    checked = bench_run.validate_bench_files()
    assert "BENCH_fleet.json" in checked
    fleet = json.loads((bench_run.ROOT / "BENCH_fleet.json").read_text())
    mt = fleet["multi_tenant"]
    # acceptance shape: >= 8 tenants in one sharded call, with the
    # 1/2/4/8 forced-host-device scaling rows recorded
    assert mt["tenants"] >= 8
    assert mt["parity"]["bitwise_f64_vs_independent_fleet_replay"] is True
    assert [r["devices"] for r in mt["scaling"]] == [1, 2, 4, 8]
    assert all(r["shards"] == r["devices"] for r in mt["scaling"])
    # acceptance shape: the single-tenant 1M-episode sharded replay row,
    # bitwise parity asserted before timing, 1/2/4/8 device rows with the
    # segment axis really partitioned (shards == devices)
    es = fleet["episode_sharded"]
    assert es["episodes"] >= 1_000_000
    assert es["parity"]["bitwise_f64_vs_fleet_replay"] is True
    assert [r["devices"] for r in es["scaling"]] == [1, 2, 4, 8]
    assert all(r["shards"] == r["devices"] for r in es["scaling"])
    # the pipelined row is a timed measurement whose parity gate passed
    # at the full episode count before its clock started
    assert es["pipelined"]["pipelined_s"] > 0.0
    assert es["pipelined"]["parity"]["bitwise_f64_vs_fleet_replay"] is True
    # acceptance shape: the online decision service row — B up to 1024,
    # bitwise decide parity asserted pre-timing, and the warm B=1024 tick
    # >= 20x faster per decision than the scalar decide loop
    osvc = fleet["online_service"]
    assert osvc["parity"]["bitwise_f64_vs_scalar_evaluate"] is True
    assert [b["B"] for b in osvc["batches"]] == [1, 64, 1024]
    assert osvc["batches"][-1]["speedup"] >= 20.0
    # the published pareto rows carry the dtype of the parity tier
    assert fleet["pareto_dtype"] == "float64"
    assert fleet["credible_bound"]["pareto_dtype"] == "float64"
    # acceptance shape: the beam-width sweep — width 1 first (the
    # parity-gated slice), the w=1 bitwise gate and the reference twin
    # both asserted pre-timing, and the published per-width Pareto
    # attributing every launched/cancelled candidate in USD
    beam = fleet["beam"]
    assert beam["widths"] == [1, 2, 4]
    assert beam["parity"]["w1_bitwise_f64_vs_fleet_replay"] is True
    assert beam["parity"]["reference_decisions_bitwise"] is True
    assert beam["parity"]["reference_max_rel_error"] <= 1e-12
    assert beam["pareto_dtype"] == "float64"
    assert beam["one_call_s"] > 0.0 and beam["per_width_calls_s"] > 0.0
    # the checked-in record must show the width axis doing real work:
    # strictly more candidates launched (and more USD waste billed) at
    # the widest beam on at least one grid cell
    w_lo, w_hi = str(beam["widths"][0]), str(beam["widths"][-1])
    assert any(
        beam["pareto"][w_hi][a]["launched_candidates"]
        > beam["pareto"][w_lo][a]["launched_candidates"]
        and beam["pareto"][w_hi][a]["waste_usd"]
        > beam["pareto"][w_lo][a]["waste_usd"]
        for a in beam["pareto"][w_lo])


def test_checked_in_frontend_record_shape():
    checked = bench_run.validate_bench_files()
    assert "BENCH_frontend.json" in checked
    fe = json.loads((bench_run.ROOT / "BENCH_frontend.json").read_text())
    # acceptance shape: a timed open-loop run (not a smoke record) whose
    # parity gates passed before timing and whose fault matrix covers
    # all four injected-failure scenarios
    assert fe["decisions_per_s"] > 0.0
    assert fe["requests"] >= 1000
    assert 0.0 <= fe["shed_rate"] <= 1.0
    assert fe["latency_ms"]["p50"] <= fe["latency_ms"]["p99"] <= \
        fe["latency_ms"]["max"]
    assert set(fe["fault_matrix"]) >= bench_run._FRONTEND_FAULTS


def test_kernels_smoke_gate_parity_before_timing():
    from benchmarks import kernels_bench

    rec = kernels_bench.smoke()
    bench_run.validate_kernels_record(rec, "kernels smoke record")
    # the betaincinv kernel sat inside the same 1e-10 envelope tier-1
    # pins for the XLA inversion, against both references
    bii = rec["betaincinv"]
    assert bii["parity"]["max_rel_vs_core"] <= bii["parity"]["asserted_rtol"]
    assert bii["parity"]["max_rel_vs_scipy"] <= bii["parity"]["asserted_rtol"]
    assert [r["block_n"] for r in bii["sweep"]] == sorted(
        {r["block_n"] for r in bii["sweep"]})
    # the fused tick matched the default XLA tick bitwise-f64 on the
    # mean path through the real service dispatch, and the §7.5 tier
    # flag-matched with only betainc-implementation-level EV drift
    tick = rec["online_tick"]
    assert tick["parity"]["mean_path_bitwise_f64"] is True
    assert tick["parity"]["lower_bound_max_rel"] <= 1e-9
    # the kernels run in interpret mode on the CPU and natively on the
    # TPU (Mosaic only lowers there), and the record must say so
    assert rec["interpret"] == (rec["backend"] != "tpu")
    # tiny shapes: the smoke record never masquerades as the real one
    assert bii["n"] < 1024 and tick["rows"] < 64


def test_checked_in_kernels_record_shape():
    checked = bench_run.validate_bench_files()
    assert "BENCH_kernels.json" in checked
    rec = json.loads((bench_run.ROOT / "BENCH_kernels.json").read_text())
    # acceptance shape: a timed record (full batch, real sweeps) whose
    # parity gates passed before its clock started
    assert rec["betaincinv"]["n"] >= 1024
    assert all(r["us_per_call"] > 0.0 for r in rec["betaincinv"]["sweep"])
    assert rec["betaincinv"]["reference_us_per_call"] > 0.0
    assert rec["online_tick"]["parity"]["mean_path_bitwise_f64"] is True
    assert all(r["us_per_tick"] > 0.0 for r in rec["online_tick"]["sweep"])
    assert rec["online_tick"]["reference_us_per_tick"] > 0.0


def test_smoke_rejects_malformed_record():
    with pytest.raises(AssertionError, match="missing keys"):
        bench_run.validate_fleet_record({"benchmark": "x"})
    with pytest.raises(AssertionError, match="missing keys"):
        bench_run.validate_frontend_record({"benchmark": "x"})
    with pytest.raises(AssertionError, match="missing keys"):
        bench_run.validate_store_record({"benchmark": "x"})
    with pytest.raises(AssertionError, match="missing keys"):
        bench_run.validate_kernels_record({"benchmark": "x"})
    # a hand-edited kernels record can't smuggle timing past a failed
    # parity gate: the validator re-checks the recorded outcome
    bad = {
        "benchmark": "pallas_hot_path_kernels", "backend": "cpu",
        "interpret": True,
        "betaincinv": {
            "n": 8,
            "parity": {"max_rel_vs_core": 1e-3, "max_rel_vs_scipy": 0.0,
                       "asserted_rtol": 1e-10},
            "sweep": [{"block_n": 8, "us_per_call": 1.0}],
            "reference_us_per_call": 1.0,
        },
        "online_tick": {
            "rows": 8, "batch": 8, "settles": 8,
            "parity": {"mean_path_bitwise_f64": True,
                       "lower_bound_max_rel": 0.0},
            "sweep": [{"block_n": 8, "us_per_tick": 1.0}],
            "reference_us_per_tick": 1.0,
        },
    }
    with pytest.raises(AssertionError, match="exceeds asserted rtol"):
        bench_run.validate_kernels_record(bad)


def test_rollout_smoke_gate_determinism_parity_zero_recompile():
    from benchmarks import rollout_fleet

    rec = rollout_fleet.smoke()
    bench_run.validate_rollout_record(rec, "rollout smoke record")
    # seeded scenario fleet replays bit-identically (transition
    # signatures AND resilience event streams)
    det = rec["determinism"]
    assert det["deterministic"] is True and det["scenarios_checked"] >= 3
    # the in-graph phase machine matched the scalar ReferenceLifecycle
    # transition-for-transition and state-bitwise across the flip trace
    par = rec["parity"]
    assert par["in_graph_vs_scalar_lifecycle"] is True
    assert par["transitions"] >= 6 and par["roll_state_bitwise"] is True
    # promote/demote churn across paged spill/fault-in left every tick
    # executable where warm-up put it
    zr = rec["zero_recompile"]
    assert zr["asserted"] is True
    assert {"rollout_promote", "rollout_demote"} <= set(
        zr["transition_kinds"])
    # the acceptance flip demoted inside the trigger window, billed the
    # demotion in USD, and re-promoted to FULL through cooldown + probes
    acc = rec["acceptance"]
    assert acc["flip_at"] <= acc["first_demote_tick"] <= \
        acc["flip_at"] + acc["trigger_window_ticks"]
    assert acc["demote_usd"] > 0.0 and acc["final_phase"] == "FULL"
    assert all(t > acc["revert_at"] for t in acc["re_promote_ticks"])
    assert acc["events"].get("rollout_reenter", 0) >= 1
    assert acc["events"].get("drift_trip", 0) >= 1
    # the per-archetype pareto separates: confident archetypes reach
    # FULL with no demotes, flat ones never leave SHADOW
    top, bottom = rec["pareto"][0], rec["pareto"][-1]
    assert top["final_phases"].get("FULL", 0) >= 1 and top["demotes"] == 0
    assert bottom["final_phases"].get("FULL", 0) == 0
    assert bottom["promotes"] == 0
    # smoke never makes timing claims and never writes BENCH files
    assert rec["decisions_per_s"] == 0.0


def test_checked_in_rollout_record_shape():
    checked = bench_run.validate_bench_files()
    assert "BENCH_rollout.json" in checked
    rec = json.loads((bench_run.ROOT / "BENCH_rollout.json").read_text())
    # acceptance shape: a timed record with all four gates asserted and
    # the eight-archetype pareto table
    assert rec["decisions_per_s"] > 0.0
    assert rec["determinism"]["deterministic"] is True
    assert rec["parity"]["in_graph_vs_scalar_lifecycle"] is True
    assert rec["zero_recompile"]["asserted"] is True
    assert rec["acceptance"]["final_phase"] == "FULL"
    assert len(rec["pareto"]) >= 8
    archetypes = {r["archetype"] for r in rec["pareto"]}
    assert len(archetypes) >= 8


def test_rollout_smoke_rejects_malformed_record():
    with pytest.raises(AssertionError, match="missing keys"):
        bench_run.validate_rollout_record({"benchmark": "x"})
