"""Fleet-scale benchmark of the paged hierarchical posterior store.

tests/test_store.py pins the store's contracts at toy sizes; this module
exercises them at the scale §14.3 actually asks about — **a million
logical (tenant, edge) rows behind a few thousand device-resident
slots** — and records what an operator would ask of the subsystem:

* registration throughput (amortized-O(1) host insert, no device work),
* decide throughput under worst-case paging churn (every tick faults a
  random batch across the full logical range, LRU-spilling victims),
* memory per logical row, host SoA vs device table,
* the zero-recompile guarantee under capacity-doubling insert/evict
  churn, asserted via jit compile-cache sizes,
* the empirical-Bayes cold-start recovery curve: a cold row born from
  its bucket's pooled hyperprior vs the fixed taxonomy prior against a
  planted per-bucket p*.

The repo's standing discipline applies: **parity before timing**.
Under ``enable_x64`` a paged store must answer ticks bitwise-f64 equal
to the dense identity-mode service on the same rows, and the 1M-row
store's decisions must be bitwise-f64 equal to scalar
``decision.evaluate`` over the composed snapshot — only then is
anything timed.

Everything is persisted to ``BENCH_store.json`` (``smoke()`` returns
the same record shape at tiny sizes, makes no timing claims, and never
touches the file).
"""
from __future__ import annotations

import json
import pathlib
import time

import numpy as np

BENCH_PATH = pathlib.Path(__file__).resolve().parents[1] / "BENCH_store.json"

SEED = 0


# --------------------------------------------------------------------------
# registry + request helpers
# --------------------------------------------------------------------------
def _dep_mix():
    from repro.core.taxonomy import DependencyType

    return [
        (DependencyType.ALWAYS_PRODUCES_OUTPUT, None),
        (DependencyType.CONDITIONAL_OUTPUT, None),
        (DependencyType.LIST_OUTPUT_VARIABLE_LENGTH, None),
        (DependencyType.ROUTER_K_WAY, 2),
        (DependencyType.ROUTER_K_WAY, 3),
    ]


def _register_mixed(svc, n: int) -> None:
    """The tests' registry mix (router k spread, discounts, floors) so
    parity runs cover heterogeneous row configs."""
    from repro.core.taxonomy import DependencyType

    for i in range(n):
        svc.register_edge(
            ("u", f"v{i}"),
            dep_type=DependencyType.ROUTER_K_WAY,
            k=2 + i % 5,
            discount=(0.95 if i % 3 == 0 else 1.0),
            floor_C_spec_usd=0.01,
            floor_L_value_usd=0.05,
        )


def _requests(rng, B, rows):
    return dict(
        rows=rng.choice(rows, B),
        alpha=rng.uniform(0, 1, B),
        lam=rng.uniform(1e-4, 0.5, B),
        lat=rng.uniform(0.01, 5.0, B),
        in_tok=rng.integers(1, 2000, B).astype(float),
        out_tok=rng.uniform(1, 2000, B),
        in_price=rng.uniform(1e-8, 1e-4, B),
        out_price=rng.uniform(1e-8, 1e-4, B),
    )


def _tick(svc, req, **kw):
    return svc.tick(
        req["rows"], alpha=req["alpha"], lambda_usd_per_s=req["lam"],
        latency_s=req["lat"], input_tokens=req["in_tok"],
        output_tokens=req["out_tok"], input_price=req["in_price"],
        output_price=req["out_price"], **kw)


def _scalar_ref(snap, req, j, row):
    from repro.core.decision import DecisionInputs, evaluate
    from repro.core.posterior import BetaPosterior

    a, b = snap[row]
    return evaluate(DecisionInputs(
        P=BetaPosterior(alpha=float(a), beta=float(b)).mean,
        alpha=float(req["alpha"][j]),
        lambda_usd_per_s=float(req["lam"][j]),
        latency_seconds=float(req["lat"][j]),
        input_tokens=int(req["in_tok"][j]),
        output_tokens=float(req["out_tok"][j]),
        input_price=float(req["in_price"][j]),
        output_price=float(req["out_price"][j]),
    ))


# --------------------------------------------------------------------------
# parity gates (run before any timing — repo discipline)
# --------------------------------------------------------------------------
def dense_paged_parity(*, n_rows: int = 40, resident_rows: int = 8,
                       ticks: int = 12, batch: int = 6,
                       n_outcomes: int = 4, seed: int = 7) -> dict:
    """A paged store holding ``resident_rows`` of ``n_rows`` on device —
    ticks cycling every row force constant LRU spill / fault-in — must
    answer every decision, settle every outcome, and run every drift
    step bitwise-f64 identical to the dense identity-mode service."""
    from jax import enable_x64

    from repro.core.online import OnlineDecisionService

    with enable_x64():
        dense = OnlineDecisionService(use_lower_bound=True)
        paged = OnlineDecisionService(use_lower_bound=True,
                                      resident_rows=resident_rows,
                                      min_rows=resident_rows)
        _register_mixed(dense, n_rows)
        _register_mixed(paged, n_rows)
        rng_seq = np.random.default_rng(seed)
        for t in range(ticks):
            rows = np.arange((t * 7) % n_rows,
                             (t * 7) % n_rows + batch) % n_rows
            req = _requests(np.random.default_rng(100 + t), batch, rows)
            outcomes = [(int(r), bool(rng_seq.integers(2)))
                        for r in rng_seq.choice(rows, n_outcomes)]
            dd = _tick(dense, req, outcomes=outcomes, check_drift=True)
            dp = _tick(paged, req, outcomes=outcomes, check_drift=True)
            for field in ("speculate", "EV_usd", "threshold_usd",
                          "margin_usd", "P_used"):
                if not np.array_equal(getattr(dd, field),
                                      getattr(dp, field)):
                    raise AssertionError(
                        f"paged != dense on {field} at tick {t}")
            if not np.array_equal(dd.drift_triggered[:n_rows],
                                  dp.drift_triggered[:n_rows]):
                raise AssertionError(f"paged != dense drift at tick {t}")
        for name, a, b in (
            ("posterior_snapshot", dense.posterior_snapshot(),
             paged.posterior_snapshot()),
            ("breach_runs", dense.breach_runs(), paged.breach_runs()),
            ("enabled_snapshot", dense.enabled_snapshot(),
             paged.enabled_snapshot()),
        ):
            if not np.array_equal(a, b):
                raise AssertionError(f"paged != dense {name} after churn")
        if not paged.store.stats["spills"]:
            raise AssertionError("parity churn never spilled a row")
    return {
        "rows": n_rows,
        "resident_rows": resident_rows,
        "ticks": ticks,
        "spills": paged.store.stats["spills"],
        "fault_ins": paged.store.stats["fault_ins"],
    }


def scalar_parity(svc, rows: np.ndarray, *, group: int,
                  seed: int = SEED) -> int:
    """Assert the store-backed service's batched decisions are bitwise
    -f64 equal to scalar ``decision.evaluate`` over the composed
    snapshot (device + shelf + unborn tiers).  Returns rows checked."""
    snap = svc.posterior_snapshot()
    checked = 0
    for start in range(0, len(rows), group):
        chunk = np.asarray(rows[start:start + group])
        req = _requests(np.random.default_rng(seed + start), len(chunk),
                        chunk)
        req["rows"] = chunk
        d = _tick(svc, req)
        for j, i in enumerate(chunk):
            ref = _scalar_ref(snap, req, j, int(i))
            if not (d.EV_usd[j] == ref.EV_usd
                    and d.threshold_usd[j] == ref.threshold_usd
                    and d.P_used[j] == ref.P_used):
                raise AssertionError(
                    f"paged tick != scalar evaluate on logical row {i}")
            checked += 1
    return checked


# --------------------------------------------------------------------------
# zero recompiles across capacity-doubling insert/evict churn
# --------------------------------------------------------------------------
def zero_recompile_churn(*, base_rows: int = 256, resident_rows: int = 64,
                         steps: int = 120, per_step: int = 8,
                         batch: int = 16, evict_every: int = 3,
                         seed: int = 11) -> dict:
    """Insert/evict churn that doubles the logical registry capacity
    multiple times must leave every jit cache exactly where warm-up put
    it: the physical table shape is fixed, so growth is host-only.
    Asserted via compile-cache sizes (the acceptance mechanism)."""
    from jax import enable_x64

    from repro.core import online as online_mod
    from repro.core.online import OnlineDecisionService
    from repro.core.store import _bucket, _gather_rows, _scatter_rows
    from repro.core.taxonomy import DependencyType

    with enable_x64():
        svc = OnlineDecisionService(resident_rows=resident_rows,
                                    min_rows=resident_rows)
        # warm-up faults k = K, K/2, ..., 1 fresh rows through a full
        # table, so the registry needs resident_rows + (2K - 1) rows
        K = _bucket(max(batch, resident_rows))
        total0 = max(base_rows, resident_rows + 2 * K)
        _register_mixed(svc, total0)
        rng = np.random.default_rng(seed)
        _tick(svc, _requests(rng, batch, np.arange(batch)),
              outcomes=[(0, True)], check_drift=True)   # tick executables
        # warm every power-of-two scatter/gather pad bucket the churn can
        # reach: filling the table then faulting k fresh rows compiles
        # both the k-lane fault-in scatter and the k-victim spill gather
        svc.store.ensure_resident(np.arange(resident_rows))
        cursor = resident_rows
        k = K
        while k >= 1:
            svc.store.ensure_resident(np.arange(cursor, cursor + k))
            cursor += k
            k //= 2
        caches = lambda: (                               # noqa: E731
            online_mod._tick._cache_size(),
            _scatter_rows._cache_size(),
            _gather_rows._cache_size(),
        )
        warm = caches()
        cap0 = _bucket(max(total0, svc.store.min_rows, 16))
        live = list(range(total0))
        next_edge = total0
        evictions = 0
        for step in range(steps):
            for _ in range(per_step):
                live.append(svc.register_edge(
                    ("w", f"x{next_edge}"),
                    dep_type=DependencyType.ALWAYS_PRODUCES_OUTPUT))
                next_edge += 1
            if step % evict_every == 0:
                svc.store.evict_row(live.pop(int(rng.integers(len(live)))))
                evictions += 1
            rows = rng.choice(np.asarray(live), batch, replace=False)
            _tick(svc, _requests(rng, batch, rows),
                  outcomes=[(int(rows[0]), True)], check_drift=True)
        after = caches()
        doublings = (_bucket(svc.store.n_rows).bit_length()
                     - cap0.bit_length())
        if after != warm:
            raise AssertionError(
                f"churn recompiled: caches {warm} -> {after}")
        if svc.store.stats["rebuilds"] != 1:
            raise AssertionError(
                f"physical table rebuilt {svc.store.stats['rebuilds']}x")
        if doublings < 1:
            raise AssertionError("churn never doubled the logical capacity")
    return {
        "churn_steps": steps,
        "registered_per_step": per_step,
        "evictions": evictions,
        "logical_rows_end": svc.store.n_rows,
        "host_capacity_doublings": doublings,
        "physical_capacity": svc.store.capacity,
        "rebuilds": svc.store.stats["rebuilds"],
        "caches": {"tick": warm[0], "scatter": warm[1], "gather": warm[2]},
        "asserted": True,
    }


# --------------------------------------------------------------------------
# empirical-Bayes cold-start recovery curve (planted per-bucket p*)
# --------------------------------------------------------------------------
def cold_start_curve(*, p_star: float = 0.3, n_warm: int = 64,
                     trials: int = 200, seed: int = SEED,
                     checkpoints=(0, 1, 2, 5, 10, 20, 50, 100, 200,
                                  500)) -> dict:
    """Warm rows in one taxonomy bucket each see ``trials`` Bernoulli(p*)
    outcomes; after the jit'd EB fit a brand-new row is born from the
    bucket's pooled hyperprior.  The curve tracks |posterior mean - p*|
    for the pooled-born row vs a fixed-taxonomy-prior twin over the same
    outcome stream — pooled must start strictly tighter and both must
    converge (shrinkage fades under conjugate evidence)."""
    from jax import enable_x64

    from repro.core.posterior import BetaPosterior
    from repro.core.store import PosteriorStore
    from repro.core.taxonomy import DependencyType, prior_params

    dep = DependencyType.ALWAYS_PRODUCES_OUTPUT
    with enable_x64():
        store = PosteriorStore(resident_rows=256)
        rng = np.random.default_rng(seed)
        for i in range(n_warm):
            store.register(("op", f"w{i}"), dep_type=dep)
        store.device_tables("float64")
        store.ensure_resident(np.arange(n_warm))
        a0, b0 = prior_params(dep)
        succ = rng.binomial(trials, p_star, n_warm)
        store.set_rows(
            np.arange(n_warm),
            np.stack([a0 + succ, b0 + (trials - succ)], 1).astype(float))
        store.fit_hyperpriors(min_evidence=5.0, strength_cap=200.0)
        label = PosteriorStore.bucket_label(dep)
        hp = store.hyperpriors[label]
        cold = store.register(("op", "cold"), dep_type=dep)
        born = store.rows_snapshot([cold])[0]
        if tuple(born) != (hp.alpha, hp.beta):
            raise AssertionError("cold row not born from the pooled prior")
    pooled = BetaPosterior(alpha=hp.alpha, beta=hp.beta)
    fixed = BetaPosterior(alpha=a0, beta=b0)
    outcomes = np.random.default_rng(seed + 1).random(
        max(checkpoints)) < p_star
    curve, n_obs = [], 0
    for cp in sorted(checkpoints):
        while n_obs < cp:
            pooled.update(bool(outcomes[n_obs]))
            fixed.update(bool(outcomes[n_obs]))
            n_obs += 1
        curve.append({
            "n_obs": cp,
            "pooled_abs_err": round(abs(pooled.mean - p_star), 6),
            "fixed_abs_err": round(abs(fixed.mean - p_star), 6),
        })
    if not curve[0]["pooled_abs_err"] < curve[0]["fixed_abs_err"]:
        raise AssertionError(
            f"pooled cold start not tighter: {curve[0]}")
    if abs(pooled.mean - fixed.mean) > 0.05:
        raise AssertionError("pooled and fixed posteriors did not converge")
    return {
        "p_star": p_star,
        "bucket": label,
        "warm_rows": n_warm,
        "trials_per_warm_row": trials,
        "pooled_prior": {
            "alpha": round(hp.alpha, 6), "beta": round(hp.beta, 6),
            "mean": round(hp.mean, 6), "strength": round(hp.strength, 6),
            "fitted_rows": hp.n_rows,
        },
        "fixed_prior": {
            "alpha": a0, "beta": b0, "mean": round(a0 / (a0 + b0), 6),
        },
        "curve": curve,
        "pooled_tighter_at_birth": True,
    }


# --------------------------------------------------------------------------
# the million-row record
# --------------------------------------------------------------------------
def store_record(*, logical_rows: int = 1_000_000,
                 resident_rows: int = 4096, batch: int = 256,
                 n_outcomes: int = 32, timed_ticks: int = 32,
                 parity_sample: int = 256, seed: int = SEED,
                 write: bool = True) -> dict:
    """Parity gates → zero-recompile churn → cold-start curve → timed
    1M-row register + paged decide churn → BENCH_store.json."""
    from jax import enable_x64

    from repro.core.online import OnlineDecisionService

    parity = dense_paged_parity(n_rows=256, resident_rows=32, ticks=20,
                                batch=32, n_outcomes=8)
    zero_recompile = zero_recompile_churn()
    cold_start = cold_start_curve()

    with enable_x64():
        svc = OnlineDecisionService(resident_rows=resident_rows,
                                    min_rows=256)
        mix = _dep_mix()
        t0 = time.perf_counter()
        for i in range(logical_rows):
            dep, k = mix[i % len(mix)]
            svc.register_edge(("op", f"e{i}"), tenant=f"t{i & 1023}",
                              dep_type=dep, k=k)
        register_wall = time.perf_counter() - t0

        # fill + steady-state the resident set so every later tick pays
        # the worst case: a full batch of faults each spilling a victim
        rng = np.random.default_rng(seed + 2)

        def churn_tick(out: bool):
            rows = rng.choice(logical_rows, batch, replace=False)
            req = _requests(rng, batch, rows)
            req["rows"] = rows
            outcomes = ([(int(rows[j]), bool(j % 2))
                         for j in range(n_outcomes)] if out else None)
            return _tick(svc, req, outcomes=outcomes)

        while svc.store.n_resident < svc.store.capacity:
            churn_tick(True).speculate
        for _ in range(2):                      # warm the steady state
            churn_tick(True).speculate

        # acceptance gate at scale: the LRU-paged 1M-row store answers
        # batched ticks bitwise-f64 equal to scalar decision.evaluate
        sample = np.random.default_rng(seed + 3).choice(
            logical_rows, parity_sample, replace=False)
        rows_checked = scalar_parity(svc, sample, group=batch, seed=seed)

        spills0 = svc.store.stats["spills"]
        faults0 = svc.store.stats["fault_ins"]
        t0 = time.perf_counter()
        for _ in range(timed_ticks):
            churn_tick(True).speculate          # one host sync per tick
        decide_wall = time.perf_counter() - t0
        memory = svc.store.memory_stats()
        decide = {
            "ticks": timed_ticks,
            "batch": batch,
            "outcomes_per_tick": n_outcomes,
            "wall_s": round(decide_wall, 4),
            "us_per_decision": round(
                decide_wall / (timed_ticks * batch) * 1e6, 3),
            "fault_ins": svc.store.stats["fault_ins"] - faults0,
            "spills": svc.store.stats["spills"] - spills0,
        }

    record = {
        "benchmark": "posterior_store_scale",
        "seed": seed,
        "logical_rows": logical_rows,
        "resident_capacity": memory["capacity"],
        "decisions_per_s": round(timed_ticks * batch / decide_wall, 2),
        "parity": {
            "paged_vs_dense_bitwise_f64": True,
            "paged_vs_scalar_bitwise_f64": True,
            "rows_checked": rows_checked,
            "dense_paged": parity,
        },
        "zero_recompile": zero_recompile,
        "register": {
            "rows": logical_rows,
            "wall_s": round(register_wall, 4),
            "us_per_row": round(register_wall / logical_rows * 1e6, 3),
        },
        "decide": decide,
        "memory": memory,
        "cold_start": cold_start,
    }
    if write:
        BENCH_PATH.write_text(json.dumps(record, indent=2) + "\n")
    return record


def smoke() -> dict:
    """The --smoke gate: every parity / zero-recompile / cold-start
    assertion at tiny sizes (the same shapes tests/test_store.py
    compiles, so tier-1 shares the jit cache), no timing claims, nothing
    written.  The record keeps the full BENCH_store.json shape so schema
    drift breaks tier-1."""
    from jax import enable_x64

    from repro.core.online import OnlineDecisionService

    parity = dense_paged_parity()                # test_store's exact shapes
    zero_recompile = zero_recompile_churn(
        base_rows=16, resident_rows=8, steps=20, per_step=3, batch=4,
        evict_every=4)
    cold_start = cold_start_curve(n_warm=16, trials=80,
                                  checkpoints=(0, 1, 5, 20, 100))

    with enable_x64():
        svc = OnlineDecisionService(resident_rows=4, min_rows=4)
        _register_mixed(svc, 16)
        rng = np.random.default_rng(3)
        for start in range(0, 16, 4):           # spill every row once
            _tick(svc, _requests(rng, 4, np.arange(start, start + 4)),
                  outcomes=[(start, True), (start + 1, False)])
        rows_checked = scalar_parity(svc, np.arange(16), group=4, seed=40)
        memory = svc.store.memory_stats()
        stats = dict(svc.store.stats)

    return {
        "benchmark": "posterior_store_scale",
        "seed": SEED,
        "logical_rows": 16,
        "resident_capacity": memory["capacity"],
        "decisions_per_s": 0.0,                  # no timing claims in smoke
        "parity": {
            "paged_vs_dense_bitwise_f64": True,
            "paged_vs_scalar_bitwise_f64": True,
            "rows_checked": rows_checked,
            "dense_paged": parity,
        },
        "zero_recompile": zero_recompile,
        "register": {"rows": 16, "wall_s": 0.0, "us_per_row": 0.0},
        "decide": {
            "ticks": 8, "batch": 4, "outcomes_per_tick": 2, "wall_s": 0.0,
            "us_per_decision": 0.0,
            "fault_ins": stats["fault_ins"], "spills": stats["spills"],
        },
        "memory": memory,
        "cold_start": cold_start,
    }


def benchmarks() -> list[tuple[str, float, str]]:
    rec = store_record()
    zr = rec["zero_recompile"]
    return [(
        "store_paged_decide_1M",
        rec["decide"]["us_per_decision"],
        (f"{rec['logical_rows']} logical rows on "
         f"{rec['resident_capacity']} resident slots | "
         f"register {rec['register']['us_per_row']}us/row | "
         f"decide {rec['decisions_per_s']:.0f}/s under full-fault churn | "
         f"0 recompiles over {zr['host_capacity_doublings']} capacity "
         f"doublings"),
    )]


if __name__ == "__main__":
    print(json.dumps(store_record(), indent=2))
