"""Pallas TPU kernel for the online decision service's fused tick.

``OnlineDecisionService._tick_impl`` is three XLA loops over the SoA row
table — the settle scan, the batched D4 gate, the drift breach step —
each reading and writing the same posterior rows.  This kernel fuses all
three into one launch over ``block_n``-row tiles:

    grid = (num_row_blocks,) — sequential on TPU.  Each program settles
    its rows (a masked elementwise replay of the settle scan over the
    settles held in SMEM, preserving per-row arrival order), gathers the
    settled rows of the requests whose (clamped) row lives in its tile
    into VMEM scratch (by select — no arithmetic touches another tile's
    values), and runs the trigger-2 breach step on its rows.  The last
    program runs the D4 gate once over the gathered rows.

Parity tiers (tests/test_kernels.py):

* mean-path ticks (``use_lower_bound=False``) are **bitwise-f64 equal**
  to ``_tick_impl`` — settled posteriors, decisions, drift runs and
  telemetry rows.  The traced-runtime-zero FMA pin survives: ``zero``
  arrives as an SMEM scalar operand, so ``x * d + zero`` inside the kernel
  contracts (or not) exactly as in the XLA lowering;
* ``use_lower_bound=True`` gates on the kernel-resident ``betaincinv``
  (see ``betaincinv_pallas`` — ``jax.scipy``'s betainc is a custom call
  Mosaic cannot lower), so P_used agrees with ``_tick_impl`` to the
  established <= 1e-10 betaincinv tier rather than bitwise; decision
  flags can differ only when EV - threshold sits inside that margin;
* ``check_drift`` breach *booleans* compare the same kernel-resident
  bound against the row floor: run counters and trigger bits are bitwise
  vs ``_tick_impl`` except when a bound sits within ~1e-12 of its floor
  (the same razor-edge caveat ``DriftMonitor.check_credible_bound_batch``
  documents for its scalar-vs-batch pairing).

The rollout lifecycle (3b) and beam gate are not fused — the service
falls back to the XLA tick for those statics (they are cold paths next
to the gate + settle + drift hot loop this kernel owns).

Padding: request and settle slots carry the -1 row sentinel (same
convention as the service's shape buckets); padded *table* rows (row-axis
tile alignment) are inert (a=b=1, enabled=0, floor=0) and, since no
request or settle row can index them, emerge unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.batch_decision import d4_gate
from .betaincinv_pallas import betaincinv_in_kernel, check_native_dtype

__all__ = ["online_tick_kernel_call"]


def _online_tick_kernel(
    # scalar operands (SMEM)
    zero_ref, cn_ref, orow_ref, ox_ref,
    # request columns, (Bk, 1)
    srow_ref, alpha_ref, lam_ref, lat_ref, itok_ref, otok_ref, iprice_ref,
    oprice_ref,
    # row-tiled table columns, (1, block_n)
    a_ref, b_ref, gam_ref, disc_ref, floor_ref, en_ref, run_ref,
    # row-tiled outputs
    a_out, b_out, en_out, run_out, trig_out,
    # revisited (Bk, 1) request outputs, written at the last program
    pused_out, pmean_out, ev_out, thr_out, cspec_out, lval_out,
    flag_out, enreq_out,
    # (Bk, 1) scratch: the gathered request rows, filled tile by tile
    ga_acc, gb_acc, ggam_acc, gen_acc,
    *, use_lower_bound: bool, check_drift: bool,
):
    i = pl.program_id(0)
    block_n = a_ref.shape[1]
    zero = zero_ref[0]
    base = i * block_n
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)

    @pl.when(i == 0)
    def _init():
        for ref in (ga_acc, gb_acc, ggam_acc, gen_acc):
            ref[...] = jnp.zeros_like(ref)

    a = a_ref[...]
    b = b_ref[...]
    disc = disc_ref[...]
    gam = gam_ref[...]
    dt = a.dtype

    # ---- 1. settle: sequential masked replay of the settle scan.  Each
    # entry (a scalar read from SMEM) updates exactly one lane with the
    # same ``(a*d + zero) + x`` recurrence as ``_tick_impl``; entries
    # hitting other tiles (or the -1 sentinel) are full-width no-ops, so
    # per-row arrival order is preserved and cross-tile order is
    # irrelevant.
    def settle_step(s, ab):
        a, b = ab
        r = orow_ref[s]
        x = ox_ref[s]
        m = (r >= 0) & (lane == r - base)
        a2 = (a * disc + zero) + x
        b2 = (b * disc + zero) + (1.0 - x)
        return jnp.where(m, a2, a), jnp.where(m, b2, b)

    a, b = jax.lax.fori_loop(np.int32(0), np.int32(orow_ref.shape[0]),
                             settle_step, (a, b))

    # ---- 2a. gather the settled rows of the requests this tile owns
    # (clamped row in [base, base + block_n)).  A one-hot select + lane
    # sum: every addend but the target lane is an exact 0.0, so the
    # gathered values are bitwise the table rows.
    srow = srow_ref[...]
    rl = jnp.maximum(srow, 0) - base
    own = (rl >= 0) & (rl < block_n)
    sel = lane == rl                                    # (Bk, block_n)

    def gather(acc, col):
        got = jnp.where(sel, col, 0.0).sum(1, keepdims=True)
        acc[...] = jnp.where(own, got, acc[...])

    gather(ga_acc, a)
    gather(gb_acc, b)
    gather(ggam_acc, gam)
    one = jnp.ones((), dt)
    nil = jnp.zeros((), dt)
    gather(gen_acc, jnp.where(en_ref[...] > 0, one, nil))

    # ---- 2b. D4 gate, once, after the last tile has been gathered.
    @pl.when(i == pl.num_programs(0) - 1)
    def _gate():
        ga = ga_acc[...]
        gb = gb_acc[...]
        P_mean = ga / (ga + gb)
        if use_lower_bound:
            # padding lanes inverted as an inert Beta(1, 1), as _tick_impl
            valid = srow_ref[...] >= 0
            P_used = betaincinv_in_kernel(
                jnp.where(valid, ga, one), jnp.where(valid, gb, one),
                jnp.where(valid, ggam_acc[...], 0.5 * one))
        else:
            P_used = P_mean
        EV, thr, flag, C_spec, L_value = d4_gate(
            P_used, alpha_ref[...], lam_ref[...], lat_ref[...],
            itok_ref[...], otok_ref[...], iprice_ref[...], oprice_ref[...],
            zero)
        pused_out[...] = P_used
        pmean_out[...] = P_mean
        ev_out[...] = EV
        thr_out[...] = thr
        cspec_out[...] = C_spec
        lval_out[...] = L_value
        flag_out[...] = flag.astype(jnp.int32)
        enreq_out[...] = (gen_acc[...] > 0).astype(jnp.int32)

    # ---- 3. trigger-2 drift over this tile's rows (post-settle table,
    # touched = any valid request landed on the row — the same mask
    # ``_tick_impl`` scatters).
    en = en_ref[...]
    run = run_ref[...]
    if check_drift:
        hit = sel & (srow >= 0)
        touched = jnp.where(hit, one, nil).sum(0, keepdims=True) > 0
        P_low = betaincinv_in_kernel(a, b, gam)
        breached = touched & (P_low < floor_ref[...])
        # int literals stay out of int selects: under jax_enable_x64
        # they would lower as i64, which Mosaic refuses
        run0 = jnp.zeros_like(run)
        run = jnp.where(touched, jnp.where(breached, run + 1, run0), run)
        triggered = touched & (run >= cn_ref[0])
        en = ((en > 0) & ~triggered).astype(jnp.int32)
        run = jnp.where(triggered, run0, run)
        trig_out[...] = triggered.astype(jnp.int32)
    else:
        trig_out[...] = jnp.zeros_like(trig_out)

    a_out[...] = a
    b_out[...] = b
    en_out[...] = en
    run_out[...] = run


def online_tick_kernel_call(
    post: jax.Array,     # (N, 2) posterior alpha/beta rows
    rowcfg: jax.Array,   # (N, 3) [gamma, discount, trigger-2 floor]
    flags: jax.Array,    # (N, 2) int32 [enabled, breach_run]
    zero: jax.Array,     # () traced runtime 0.0 (the FMA pin)
    row: jax.Array,      # (Bp,) int32 request rows, -1 padding
    reqs: jax.Array,     # (Bp, 7) [alpha, lam, lat, itok, otok, ipr, opr]
    out_row: jax.Array,  # (S,) int32 settled rows, -1 padding
    out_x: jax.Array,    # (S,) settled outcomes as 0/1 floats
    consecutive_n,       # () int32 trigger-2 N
    *,
    use_lower_bound: bool = False,
    check_drift: bool = False,
    block_n: int = 1024,
    interpret: bool = True,
):
    """Fused gate + settle + drift tick as one Pallas launch.

    Returns ``(post', flags', P_used, P_mean, EV, thr, C_spec, L_value,
    flag, enabled_req, triggered)`` with the request vectors shaped
    (Bp,), ``flag``/``enabled_req`` as int32 0/1 and ``triggered`` an
    (N,) int32 mask — the raw parts ``online.py``'s fused-tick wrapper
    reassembles into the ``_tick_impl`` output contract.  ``block_n`` is
    the row-tile tunable (sweep hook: ``benchmarks/kernels_bench.py``).
    """
    N = post.shape[0]
    Bp = row.shape[0]
    dt = post.dtype
    check_native_dtype(dt, interpret, "online_tick")
    block_n = min(block_n, max(N, 1))
    nb = -(-N // block_n)
    pad_n = nb * block_n - N

    a, b = post[:, 0], post[:, 1]
    gam, disc, floor = rowcfg[:, 0], rowcfg[:, 1], rowcfg[:, 2]
    en, run = flags[:, 0], flags[:, 1]
    if pad_n:
        # inert table rows: valid Beta params (the drift inversion stays
        # finite), never enabled, floor 0 -> never breached; requests and
        # settles cannot index them (row ids < N)
        a = jnp.pad(a, (0, pad_n), constant_values=1.0)
        b = jnp.pad(b, (0, pad_n), constant_values=1.0)
        gam = jnp.pad(gam, (0, pad_n), constant_values=0.5)
        disc = jnp.pad(disc, (0, pad_n), constant_values=1.0)
        floor = jnp.pad(floor, (0, pad_n))
        en = jnp.pad(en, (0, pad_n))
        run = jnp.pad(run, (0, pad_n))

    # Bp = 0 ticks (settle-only / drift-only): pad one sentinel request
    # slot so the revisited output blocks stay non-empty; sliced off.
    # S = 0 likewise gets one -1 sentinel settle (a no-op step).
    Bk = max(Bp, 1)
    if Bp == 0:
        row = jnp.full((1,), -1, jnp.int32)
        reqs = jnp.zeros((1, 7), dt)
    if out_row.shape[0] == 0:
        out_row = jnp.full((1,), -1, jnp.int32)
        out_x = jnp.zeros((1,), dt)

    zero1 = jnp.reshape(zero, (1,)).astype(dt)
    cn1 = jnp.reshape(jnp.asarray(consecutive_n, jnp.int32), (1,))

    kernel = functools.partial(
        _online_tick_kernel,
        use_lower_bound=bool(use_lower_bound),
        check_drift=bool(check_drift),
    )
    # Table columns ride as (1, Np) rows tiled along lanes; request
    # columns as (Bk, 1) so the one-hot gather is a (Bk, block_n)
    # broadcast Mosaic lays out without reshapes.
    # index maps return int32 zeros: a Python 0 would lower as i64 under
    # jax_enable_x64
    z = np.int32(0)

    def smem(n):
        return pl.BlockSpec((n,), lambda i: (z,), memory_space=pltpu.SMEM)

    col = pl.BlockSpec((Bk, 1), lambda i: (z, z))
    tile = pl.BlockSpec((1, block_n), lambda i: (z, i))
    Np = nb * block_n
    outs = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=([smem(1), smem(1), smem(out_row.shape[0]),
                   smem(out_row.shape[0])] + [col] * 8 + [tile] * 7),
        out_specs=[tile] * 5 + [col] * 8,
        out_shape=(
            [jax.ShapeDtypeStruct((1, Np), dt)] * 2
            + [jax.ShapeDtypeStruct((1, Np), jnp.int32)] * 3
            + [jax.ShapeDtypeStruct((Bk, 1), dt)] * 6
            + [jax.ShapeDtypeStruct((Bk, 1), jnp.int32)] * 2
        ),
        scratch_shapes=[pltpu.VMEM((Bk, 1), dt)] * 4,
        interpret=interpret,
    )(
        zero1, cn1, out_row.astype(jnp.int32), out_x.astype(dt),
        *(v.reshape(Bk, 1)
          for v in (row.astype(jnp.int32), *reqs.astype(dt).T)),
        *(v.reshape(1, Np) for v in (a, b, gam, disc, floor, en, run)),
    )
    (a2, b2, en2, run2, trig,
     pused, pmean, ev, thr, cspec, lval, flagv, enreq) = (
        o.reshape(-1) for o in outs)
    post2 = jnp.stack([a2[:N], b2[:N]], axis=1)
    flags2 = jnp.stack([en2[:N], run2[:N]], axis=1)
    return (post2, flags2, pused[:Bp], pmean[:Bp], ev[:Bp], thr[:Bp],
            cspec[:Bp], lval[:Bp], flagv[:Bp], enreq[:Bp], trig[:N])
