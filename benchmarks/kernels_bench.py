"""Pallas hot-path kernel benchmarks: batched betaincinv + fused tick.

Two kernels, one record (BENCH_kernels.json):

* ``betaincinv`` — the tiled bracketed-Halley inverse regularized
  incomplete beta (repro.kernels.betaincinv_pallas) against the XLA
  fixed-iteration inversion in repro.core.betainc and against scipy.
  Parity (<= 1e-10 relative, the same RTOL tier-1 pins for the XLA
  path) is asserted under ``enable_x64`` *before* any timing row is
  taken.
* ``online_tick`` — the fused settle+gate+drift tick
  (repro.kernels.online_tick) through the real service dispatch
  (``OnlineDecisionService(use_fused_tick=True)``) against the default
  XLA tick, bitwise-f64 on the mean path, flag-matched with a recorded
  EV allowance on the §7.5 lower-bound path (the in-kernel betainc is
  not XLA's custom call, so 1-ULP-scale drift is expected there).

Timing sweeps the ``block_n`` tile tunable for both kernels.  On CPU
the kernels execute in Pallas interpret mode (Mosaic lowers only on
TPU), so the recorded ``backend`` / ``interpret`` fields say what was
measured: interpret-mode rows track dispatch + emulation cost and are a
correctness trajectory, not a TPU speed claim — re-measure on TPU
hardware before tuning block_n from this file (EXPERIMENTS.md
§Kernels).
"""
from __future__ import annotations

import json
import pathlib
import platform
import time

import numpy as np

from repro.core import DependencyType

ROOT = pathlib.Path(__file__).resolve().parents[1]
SEED = 20260531
RTOL = 1e-10


def _rand_abq(n: int, seed: int):
    """Log-uniform shape parameters over the tier-1 grid's span plus
    deep-tail q — the operating range of every §7.5 lower-bound call."""
    rng = np.random.default_rng(seed)
    a = np.exp(rng.uniform(np.log(0.05), np.log(150.0), n))
    b = np.exp(rng.uniform(np.log(0.05), np.log(150.0), n))
    q = np.concatenate([
        rng.uniform(1e-8, 1.0 - 1e-8, n - 2 * (n // 8)),
        np.exp(rng.uniform(np.log(1e-8), np.log(1e-3), n // 8)),
        1.0 - np.exp(rng.uniform(np.log(1e-8), np.log(1e-3), n // 8)),
    ])[:n]
    rng.shuffle(q)
    return a, b, q


def betaincinv_record(n: int = 4096, block_sweep=(256, 1024, 4096),
                      reps: int = 5, seed: int = SEED) -> dict:
    """Parity gate + block_n timing sweep for the betaincinv kernel."""
    import jax
    import jax.numpy as jnp
    from jax import enable_x64
    from scipy import special as sp

    from repro.core.betainc import betaincinv as core_betaincinv
    from repro.kernels.betaincinv_pallas import betaincinv_kernel_call
    from repro.kernels.ops import betaincinv_op

    a, b, q = _rand_abq(n, seed)

    # --- parity first (f64, interpret mode on CPU): the kernel must sit
    # inside the same 1e-10 envelope tier-1 pins for the XLA inversion.
    with enable_x64():
        got = np.asarray(betaincinv_kernel_call(
            jnp.asarray(a), jnp.asarray(b), jnp.asarray(q), interpret=True))
        ref_core = np.asarray(core_betaincinv(
            jnp.asarray(a), jnp.asarray(b), jnp.asarray(q)))
        ref_scipy = sp.betaincinv(a, b, q)

        def _max_rel(ref):
            denom = np.maximum(np.abs(ref), 1e-300)
            rel = np.abs(got - ref) / denom
            bad = rel > RTOL
            if bad.any():
                # scipy's own ppf carries >1e-10 error at a handful of
                # small-shape points; accept those via the round-trip
                # |I(a,b,x) - q| <= 1e-9 * q (same fallback tier-1 uses)
                rt = np.abs(sp.betainc(a[bad], b[bad], got[bad]) - q[bad])
                if (rt > 1e-9 * np.maximum(q[bad], 1e-300)).any():
                    worst = int(np.argmax(rel))
                    raise AssertionError(
                        f"betaincinv kernel parity broke: rel "
                        f"{rel[worst]:.3e} at a={a[worst]} b={b[worst]} "
                        f"q={q[worst]}")
                rel = np.where(bad, 0.0, rel)
            return float(rel.max())

        max_rel_core = _max_rel(ref_core)
        max_rel_scipy = _max_rel(ref_scipy)

    # --- then timing (working dtype) through the dispatch op, per tile
    # size.  Reference row: the jitted XLA inversion on the same batch.
    aj, bj, qj = jnp.asarray(a), jnp.asarray(b), jnp.asarray(q)
    core_jit = jax.jit(core_betaincinv)

    def _time(fn):
        fn().block_until_ready()                      # warm the executable
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn()
            out.block_until_ready()
            best = min(best, (time.perf_counter() - t0) / reps)
        return best * 1e6

    sweep = [{"block_n": int(bn),
              "us_per_call": _time(lambda bn=bn: betaincinv_op(
                  aj, bj, qj, block_n=int(bn)))}
             for bn in block_sweep]
    ref_us = _time(lambda: core_jit(aj, bj, qj))

    return {
        "n": n,
        "parity": {"max_rel_vs_core": max_rel_core,
                   "max_rel_vs_scipy": max_rel_scipy,
                   "asserted_rtol": RTOL},
        "sweep": sweep,
        "reference_us_per_call": ref_us,
    }


def _build_service(n_rows: int, **kw):
    from repro.core.online import OnlineDecisionService

    svc = OnlineDecisionService(**kw)
    for i in range(n_rows):
        svc.register_edge(("classifier", f"drafter{i}"),
                          dep_type=DependencyType.ROUTER_K_WAY,
                          k=2 + i % 7, gamma=0.1,
                          discount=(1.0, 0.97)[i % 2])
    return svc


def _tick_blocks(n_rows: int, batch: int, settles: int, seed: int, dtype):
    """One packed request block + settle bucket (tail -1 sentinels)."""
    rng = np.random.default_rng(seed)
    row = np.full(batch, -1, np.int32)
    nb = max(1, batch - batch // 8)
    row[:nb] = rng.integers(0, n_rows, nb)
    reqs = np.zeros((batch, 7), dtype)
    reqs[:, 0] = rng.uniform(0.0, 1.0, batch)
    reqs[:, 1] = rng.uniform(1e-3, 0.5, batch)
    reqs[:, 2] = rng.uniform(0.05, 4.0, batch)
    reqs[:, 3], reqs[:, 4] = 32, 160
    reqs[:, 5], reqs[:, 6] = 3e-6, 15e-6
    out_row = np.full(settles, -1, np.int32)
    ns = max(1, settles - settles // 8)
    out_row[:ns] = rng.integers(0, max(n_rows // 2, 1), ns)
    out_x = np.zeros(settles, dtype)
    out_x[:ns] = rng.integers(0, 2, ns).astype(dtype)
    return row, reqs, out_row, out_x


def online_tick_record(n_rows: int = 256, batch: int = 128,
                       settles: int = 64, block_sweep=(64, 256, 1024),
                       reps: int = 20, ticks: int = 4,
                       seed: int = SEED) -> dict:
    """Parity gate + block_n timing sweep for the fused tick kernel,
    driven through the real ``OnlineDecisionService`` dispatch."""
    import jax
    from jax import enable_x64

    # --- parity first (f64): fused vs default service, same tick
    # stream (requests + settles + drift checks), bitwise everywhere on
    # the mean path; lower-bound ticks must flag-match with the EV drift
    # recorded (in-kernel betainc vs XLA's betainc custom call).
    lb_max_rel = 0.0
    with enable_x64():
        svc0 = _build_service(n_rows)
        svc1 = _build_service(n_rows, use_fused_tick=True)
        for t in range(ticks):
            row, reqs, out_row, out_x = _tick_blocks(
                n_rows, batch, settles, seed + t, np.float64)
            d0 = svc0.tick_packed(row, reqs.copy(), out_row=out_row,
                                  out_x=out_x, check_drift=(t % 2 == 1))
            d1 = svc1.tick_packed(row, reqs.copy(), out_row=out_row,
                                  out_x=out_x, check_drift=(t % 2 == 1))
            for f in ("speculate", "EV_usd", "threshold_usd", "margin_usd"):
                if not np.array_equal(getattr(d0, f), getattr(d1, f)):
                    raise AssertionError(
                        f"fused tick parity broke: {f} at tick {t}")
        if not (np.array_equal(svc0.posterior_snapshot(),
                               svc1.posterior_snapshot())
                and np.array_equal(np.asarray(svc0._tel),
                                   np.asarray(svc1._tel))):
            raise AssertionError(
                "fused tick parity broke: posterior/telemetry state")
        # §7.5 lower-bound tier
        row, reqs, out_row, out_x = _tick_blocks(
            n_rows, batch, settles, seed + ticks, np.float64)
        d0 = svc0.tick_packed(row, reqs.copy(), out_row=out_row,
                              out_x=out_x, use_lower_bound=True)
        d1 = svc1.tick_packed(row, reqs.copy(), out_row=out_row,
                              out_x=out_x, use_lower_bound=True)
        if not np.array_equal(d0.speculate, d1.speculate):
            raise AssertionError("fused tick lower-bound flags diverged")
        denom = np.maximum(np.abs(d0.EV_usd), 1e-300)
        lb_max_rel = float(np.max(np.abs(d0.EV_usd - d1.EV_usd) / denom))
        if lb_max_rel > 1e-9:
            raise AssertionError(
                f"fused tick lower-bound EV drifted: {lb_max_rel:.3e}")

    # --- then timing (working dtype): per-tick wall time with the
    # honest per-tick host round-trip, best-of-rounds (2-core container).
    fdtype = np.dtype("float64" if jax.config.jax_enable_x64
                      else "float32")
    row, reqs, out_row, out_x = _tick_blocks(
        n_rows, batch, settles, seed, fdtype)

    def _time_service(svc):
        svc.tick_packed(row, reqs, out_row=out_row, out_x=out_x)
        svc.tick_packed(row, reqs, out_row=out_row, out_x=out_x)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                d = svc.tick_packed(row, reqs, out_row=out_row, out_x=out_x)
                d.speculate                       # per-tick host sync
            best = min(best, (time.perf_counter() - t0) / reps)
        return best * 1e6

    sweep = [{"block_n": int(bn),
              "us_per_tick": _time_service(_build_service(
                  n_rows, use_fused_tick=True, fused_block_n=int(bn)))}
             for bn in block_sweep]
    ref_us = _time_service(_build_service(n_rows))

    return {
        "rows": n_rows,
        "batch": batch,
        "settles": settles,
        "parity": {"mean_path_bitwise_f64": True,
                   "lower_bound_max_rel": lb_max_rel},
        "sweep": sweep,
        "reference_us_per_tick": ref_us,
    }


def kernels_record(bii_n: int = 4096, bii_sweep=(256, 1024, 4096),
                   tick_rows: int = 256, tick_batch: int = 128,
                   tick_settles: int = 64, tick_sweep=(64, 256, 1024),
                   reps: int = 10, seed: int = SEED) -> dict:
    """The full BENCH_kernels.json record (parity before every timing)."""
    import jax

    from repro.kernels.ops import _interpret

    interpret = _interpret()
    return {
        "benchmark": "pallas_hot_path_kernels",
        "backend": jax.default_backend(),
        "interpret": interpret,
        "betaincinv": betaincinv_record(bii_n, bii_sweep, reps=max(3, reps // 2),
                                        seed=seed),
        "online_tick": online_tick_record(tick_rows, tick_batch, tick_settles,
                                          tick_sweep, reps=reps, seed=seed),
    }


def smoke() -> dict:
    """Tiny-shape parity + schema gate (no timing claims, no writes).

    Every parity assertion in the full record still executes — the
    betaincinv <=1e-10 envelope and the fused tick's bitwise-f64 mean
    path — at shapes small enough for tier-1; timing rows exist only so
    the schema validator sees the real record shape."""
    return kernels_record(bii_n=96, bii_sweep=(16, 96), tick_rows=24,
                          tick_batch=8, tick_settles=8, tick_sweep=(8, 32),
                          reps=2)


def benchmarks() -> list[tuple[str, float, str]]:
    """Full record: persists BENCH_kernels.json, returns summary rows."""
    rec = kernels_record()
    rec["host"] = platform.machine()
    rec["unix_time"] = int(time.time())
    (ROOT / "BENCH_kernels.json").write_text(
        json.dumps(rec, indent=2) + "\n")

    bii = rec["betaincinv"]
    best_bii = min(bii["sweep"], key=lambda r: r["us_per_call"])
    tick = rec["online_tick"]
    best_tick = min(tick["sweep"], key=lambda r: r["us_per_tick"])
    mode = "interpret" if rec["interpret"] else "native"
    return [
        ("kernel_betaincinv", best_bii["us_per_call"],
         f"n={bii['n']} block_n={best_bii['block_n']} {mode} "
         f"xla_ref={bii['reference_us_per_call']:.1f}us "
         f"rel<={bii['parity']['max_rel_vs_core']:.1e}"),
        ("kernel_online_tick", best_tick["us_per_tick"],
         f"rows={tick['rows']} B={tick['batch']} "
         f"block_n={best_tick['block_n']} {mode} "
         f"xla_ref={tick['reference_us_per_tick']:.1f}us bitwise"),
    ]
