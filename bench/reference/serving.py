"""Plain reference of the decision service: the settle recurrence, the D4
gate and the trigger-2 kill switch, replayed over the ticks the service
actually ran.

Batch composition depends on timing, so the reference does not form its
own batches: it is handed the record of every tick (the logical rows and
request columns asked, the outcomes settled) and replays them in order
from the registration priors.  What it compares is what the service
answered in each tick and the table it left behind.

Rules (paper §6.1, §7.2, §12.5, §14.3), as the configuration states them:

* prior of a row: ``(p * n0, (1 - p) * n0)`` with ``p`` its dependency
  type's structural mean;
* settle, in arrival order: ``a <- a * d + x``, ``b <- b * d + (1 - x)``;
* gate: ``C = in_tok * in_price + out_tok * out_price``,
  ``L = latency * lambda``, ``P = a / (a + b)``,
  ``EV = P * L - (1 - P) * C``, ``thr = (1 - alpha) * C``,
  speculate iff ``EV >= thr`` and the row is enabled;
* kill switch, per row a tick touched with a request, after the tick's
  settles: ``P_low = BetaInv(gamma; a, b)``; a breach (``P_low < floor``)
  extends the row's breach run, anything else resets it; a run reaching
  ``n`` disables the row and restarts the run.

``dtype`` is float64 for the reference and a narrower type (bfloat16)
for the control, which stands in for the service.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from scipy import special

# a D4 flag is compared only outside this share of the EV's terms: a
# float32 service and the float64 reference may fall either side of a
# near tie
BAND_REL = 1e-5
# a kill-switch step whose float64 quantile lies within this of the floor
# is a tie: the chip's float32 quantile sits up to 4.02e-4 from SciPy's
TIE_BAND = 0.002

@dataclasses.dataclass
class TickRecord:
    """One tick as the service ran it (logical rows, real lanes only),
    and what it answered."""

    rows: np.ndarray                  # (B,) int64
    reqs: np.ndarray                  # (B, 7) as the service received them
    out_rows: np.ndarray              # (S,) int64 settled rows, in order
    out_x: np.ndarray                 # (S,) 1.0 success / 0.0 failure
    ok: bool = True                   # the tick returned
    EV: np.ndarray = None             # (B,)
    thr: np.ndarray = None            # (B,)
    flag: np.ndarray = None           # (B,) raw D4 flag
    enabled: np.ndarray = None        # (B,) row enabled before the tick
    triggered: np.ndarray = None      # logical rows tripped this tick


class FleetTables:
    """Per-row configuration, from the configuration file alone."""

    def __init__(self, cfg: dict) -> None:
        n = int(cfg["rows"])
        i = np.arange(n)
        mix = cfg["dep_mix"]
        p = np.asarray([d["p"] for d in mix], float)[i % len(mix)]
        n0 = float(cfg["n0"])
        self.a0, self.b0 = p * n0, (1.0 - p) * n0
        self.discount = np.where(i % cfg["discount"]["every"] == 0,
                                 float(cfg["discount"]["value"]), 1.0)
        fl = cfg["floor"]
        # the credible floor of a row pinned to its decision context
        floor = (1.0 - fl["alpha"]) * fl["C_spec_usd"] / (
            fl["L_value_usd"] + fl["C_spec_usd"])
        self.has_floor = i % fl["every"] == 0
        self.floor = np.where(self.has_floor, floor, -np.inf)
        self.gamma = float(cfg["gamma"])
        self.consecutive_n = int(cfg["credible_consecutive_n"])
        self.n = n


def _settle(a, b, d, rows, x, dt) -> None:
    """Apply settles in arrival order; rows repeat, so apply them in
    rounds of distinct rows (the k-th settle of each row in round k)."""
    if rows.size == 0:
        return
    order = np.argsort(rows, kind="stable")
    srt = rows[order]
    start = np.r_[0, np.flatnonzero(srt[1:] != srt[:-1]) + 1]
    occ = np.arange(srt.size) - np.repeat(start, np.diff(np.r_[start, srt.size]))
    rank = np.empty_like(occ)
    rank[order] = occ
    one = dt(1.0)
    for k in range(int(rank.max()) + 1):
        sel = rank == k
        r, xv = rows[sel], x[sel].astype(dt)
        a[r] = a[r] * d[r] + xv
        b[r] = b[r] * d[r] + (one - xv)


def gate(P, reqs, dt):
    """The D4 gate in type ``dt``: (EV, thr, flag, terms)."""
    q = reqs.astype(dt)
    alpha, lam, lat, itok, otok, ipr, opr = (q[:, j] for j in range(7))
    one = dt(1.0)
    C = itok * ipr + otok * opr
    L = lat * lam
    EV = P * L - (one - P) * C
    thr = (one - alpha) * C
    terms = np.abs(P * L) + np.abs((one - P) * C) + np.abs(thr)
    return EV, thr, EV >= thr, terms


@dataclasses.dataclass
class Replay:
    """What a replay produced: per tick the answers, the pre-tick enabled
    bits and the rows tripped; and the final table."""

    EV: list
    thr: list
    flag: list
    terms: list
    enabled: list
    triggered: list
    post: np.ndarray                  # (n, 2) final posteriors
    run: np.ndarray                   # final breach runs
    enabled_final: np.ndarray
    ambiguous: np.ndarray             # rows whose kill switch met a tie
    ambiguous_from: np.ndarray        # the tick of each row's first tie
    ties: int


def replay(tables: FleetTables, ticks: list[TickRecord], *,
           dtype=np.float64, tie_band: float = TIE_BAND) -> Replay:
    """Replay ``ticks`` from the priors in type ``dtype``.

    A kill-switch step whose ``P_low`` lies within ``tie_band`` of the
    floor is a tie: the service's own quantile may fall on either side,
    so the row is marked ambiguous and its kill switch is compared no
    further."""
    dt = np.dtype(dtype).type
    a = tables.a0.astype(dt)
    b = tables.b0.astype(dt)
    d = tables.discount.astype(dt)
    out = Replay([], [], [], [], [], [], None, None, None, None, None, 0)
    # pass 1: posteriors and answers; the floor rows each tick touched,
    # with their settled posteriors
    probe_rows, probe_a, probe_b, probe_tick = [], [], [], []
    for t, rec in enumerate(ticks):
        if not rec.ok:
            for lst in (out.EV, out.thr, out.flag, out.terms):
                lst.append(None)
            continue
        _settle(a, b, d, rec.out_rows, rec.out_x, dt)
        ga, gb = a[rec.rows], b[rec.rows]
        EV, thr, flag, terms = gate(ga / (ga + gb), rec.reqs, dt)
        out.EV.append(EV)
        out.thr.append(thr)
        out.flag.append(flag)
        out.terms.append(terms)
        touched = np.unique(rec.rows)
        touched = touched[tables.has_floor[touched]]
        probe_rows.append(touched)
        probe_a.append(a[touched].astype(np.float64))
        probe_b.append(b[touched].astype(np.float64))
        probe_tick.append(t)
    # pass 2: every quantile at once
    if probe_rows:
        pa, pb = np.concatenate(probe_a), np.concatenate(probe_b)
        p_low = special.betaincinv(pa, pb, tables.gamma)
        if dt is not np.float64:
            p_low = p_low.astype(dt).astype(np.float64)
        floors = tables.floor.astype(dt).astype(np.float64)
    # pass 3: breach runs and enabled bits, tick by tick
    run = np.zeros(tables.n, np.int64)
    enabled = np.ones(tables.n, bool)
    ambiguous = np.zeros(tables.n, bool)
    amb_from = np.full(tables.n, np.iinfo(np.int64).max)
    ties = 0
    pos = 0
    probe_iter = iter(zip(probe_tick, probe_rows))
    nxt = next(probe_iter, None)
    for t, rec in enumerate(ticks):
        if not rec.ok:
            out.enabled.append(None)
            out.triggered.append(None)
            continue
        out.enabled.append(enabled[rec.rows].copy())
        trig = []
        if nxt is not None and nxt[0] == t:
            rows = nxt[1]
            k = rows.size
            pl = p_low[pos:pos + k]
            fl = floors[rows]
            pos += k
            nxt = next(probe_iter, None)
            for r, p, f in zip(rows.tolist(), pl.tolist(), fl.tolist()):
                if ambiguous[r]:
                    continue
                if abs(p - f) <= tie_band:
                    ambiguous[r] = True
                    amb_from[r] = t
                    ties += 1
                    continue
                run[r] = run[r] + 1 if p < f else 0
                if run[r] >= tables.consecutive_n:
                    run[r] = 0
                    enabled[r] = False
                    trig.append(r)
        out.triggered.append(np.asarray(trig, np.int64))
    out.post = np.stack([a, b], 1).astype(np.float64)
    out.run, out.enabled_final = run, enabled
    out.ambiguous, out.ambiguous_from, out.ties = ambiguous, amb_from, ties
    return out


def control_records(tables: FleetTables, ticks: list[TickRecord],
                    dtype) -> tuple[list[TickRecord], Replay]:
    """The control: the reference in ``dtype`` put in the service's place.
    Returns the ticks with the control's answers and its replay."""
    ctl = replay(tables, ticks, dtype=dtype)
    recs = []
    for t, rec in enumerate(ticks):
        if not rec.ok:
            recs.append(rec)
            continue
        recs.append(dataclasses.replace(
            rec, EV=ctl.EV[t].astype(np.float64),
            thr=ctl.thr[t].astype(np.float64), flag=ctl.flag[t],
            enabled=ctl.enabled[t], triggered=ctl.triggered[t]))
    return recs, ctl


def compare(tables: FleetTables, ticks: list[TickRecord], ref: Replay, *,
            final_post: np.ndarray, final_flags: np.ndarray,
            touched: np.ndarray, band_rel: float = BAND_REL) -> dict:
    """The numbers compared between what the service did and the
    reference.  ``final_post`` / ``final_flags`` are the service's table
    for the ``touched`` rows after the run."""
    ev_gap = 0.0
    flag_mismatch = 0
    ks_mismatch = 0
    near_ties = 0
    answers = 0
    for t, rec in enumerate(ticks):
        if not rec.ok:
            continue
        EV_r, thr_r, flag_r, terms = (ref.EV[t], ref.thr[t], ref.flag[t],
                                      ref.terms[t])
        terms = np.maximum(terms, 1e-300)
        gap = np.maximum(np.abs(rec.EV - EV_r), np.abs(rec.thr - thr_r)) / terms
        ev_gap = max(ev_gap, float(gap.max(initial=0.0)))
        tie = np.abs(EV_r - thr_r) <= band_rel * terms
        near_ties += int(tie.sum())
        flag_mismatch += int(((rec.flag != flag_r) & ~tie).sum())
        answers += rec.rows.size
        # a row's kill switch is compared up to its first tie
        clear = ref.ambiguous_from[rec.rows] >= t
        ks_mismatch += int(((rec.enabled != ref.enabled[t]) & clear).sum())
        want = set(ref.triggered[t].tolist())
        got = {int(r) for r in rec.triggered if ref.ambiguous_from[r] > t}
        ks_mismatch += len(want ^ got)
    # the table the run left behind: posteriors (spills and fault-ins
    # included) and the kill-switch flags of every touched row
    post_r = ref.post[touched]
    post_gap = float(np.max(np.abs(final_post - post_r) / post_r,
                            initial=0.0))
    clear = ~ref.ambiguous[touched]
    ks_mismatch += int(((final_flags[:, 0] > 0) != ref.enabled_final[touched])
                       [clear].sum())
    ks_mismatch += int((final_flags[:, 1] != ref.run[touched])[clear].sum())
    return dict(ev_gap=ev_gap, posterior_gap=post_gap,
                flag_mismatch=flag_mismatch, kill_switch_mismatch=ks_mismatch,
                answers=answers, near_ties=near_ties,
                kill_switch_ties=ref.ties,
                trips=int(sum(t.size for t in ref.triggered if t is not None)))
