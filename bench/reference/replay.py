"""Plain reference of the §12.1 replay of the AutoReply workflow: one
upstream op feeding one downstream op over a k-way router edge, the
downstream streaming, one predictor, no stream refiner.

Per episode and grid point (alpha, lambda), with the edge's Beta(a, b)
carried from episode to episode (paper §6.1, §9.3, §14.3):

* ``P = a / (a + b)``; ``C = in_tok * in_price + out_tok * out_price`` of
  the downstream; ``L = min(lat_up, lat_down) * lambda``;
  ``EV = P * L - (1 - P) * C``; ``thr = (1 - alpha) * C``; launch iff
  ``EV >= thr``;
* the upstream runs over [0, d_up]; a launched downstream that the log
  says was predicted right commits at ``max(d_down, d_up)``; one that was
  wrong, or one not launched, runs again after the upstream, over
  [d_up, d_up + d_down];
* a wrong launch wastes ``in_tok * in_price + (f * out_tok) * out_price``
  with ``f = min(1, d_up / d_down)`` of the output generated;
* a launched edge observes one trial: ``a <- a * d + s``,
  ``b <- b * d + (1 - s)``.

The numbers compared: ``ev_gap`` (the gate's EV and threshold against
the reference's, relative to their terms), ``post_gap`` (each episode's
carried posterior, relative), and ``field_gap``, the widest relative gap
of any other per-episode field, where a launch, commit or speculate flag
that differs outside a near tie counts as a gap of 1.

``check`` is teacher-forced: it takes the program's launches, builds the
posterior every episode had from them and the log, and re-derives every
field from that; so one wrong step shows where it happens and is not
spread along the trajectory.  ``simulate`` runs the same rules on their
own trajectory, in any type: in a narrow type it is the control.
"""
from __future__ import annotations

import numpy as np

# a flag is compared only outside this share of the EV's terms: the
# float32 engine and the float64 reference may fall either side of a tie
BAND_REL = 1e-5

FIELDS_EG = ("makespan_s", "total_cost_usd", "waste_usd", "launched",
             "committed", "cancelled")
FIELDS_EGV = ("EV_usd", "threshold_usd", "speculate", "edge_launched",
              "edge_committed", "edge_waste_usd", "start_s", "finish_s",
              "post_alpha", "post_beta")


class Workflow:
    """The workflow's numbers, from the configuration file alone."""

    def __init__(self, cfg: dict) -> None:
        up, down = cfg["ops"]
        self.d_up, self.d_down = float(up["latency_s"]), float(down["latency_s"])
        pin, pout = cfg["price_in_usd"], cfg["price_out_usd"]
        self.cost_up = up["input_tokens"] * pin + up["output_tokens"] * pout
        self.cost_down = down["input_tokens"] * pin + down["output_tokens"] * pout
        self.c_in = down["input_tokens"] * pin
        self.out_tok, self.out_price = float(down["output_tokens"]), pout
        self.C = self.c_in + self.out_tok * pout
        self.lat_save = min(self.d_up, self.d_down)
        k, n0 = cfg["router_k"], cfg["n0"]
        self.a0, self.b0 = n0 / k, n0 * (1.0 - 1.0 / k)
        self.discount = float(cfg["discount"])
        self.frac = min(1.0, self.d_up / self.d_down)


def episode(xp, wf: Workflow, a, b, alphas, lambdas, success, dt):
    """One episode at every grid point: (fields, launched, committed) in
    type ``dt``; ``success`` broadcasts against the grid."""
    one = dt(1.0)
    P = a / (a + b)
    C = dt(wf.C)
    L = dt(wf.lat_save) * lambdas
    EV = P * L - (one - P) * C
    thr = (one - alphas) * C
    launched = EV >= thr
    committed = launched & success
    failed = launched & ~committed
    d_up, d_down = dt(wf.d_up), dt(wf.d_down)
    start = xp.where(committed, dt(0.0), d_up)
    finish = xp.where(committed, xp.maximum(d_down, d_up), d_up + d_down)
    waste = xp.where(failed, dt(wf.c_in) + (dt(wf.frac) * dt(wf.out_tok))
                     * dt(wf.out_price), dt(0.0))
    fields = dict(EV=EV, thr=thr, start=start, finish=finish, waste=waste,
                  makespan=xp.maximum(finish, d_up),
                  total_cost=dt(wf.cost_up + wf.cost_down) + waste)
    return fields, launched, committed


def _posterior_before(wf, launched, committed):
    """(E, G) posterior each episode started from, given the launches."""
    s = np.cumsum(committed, 0, dtype=np.float64)
    f = np.cumsum(launched & ~committed, 0, dtype=np.float64)
    a_after, b_after = wf.a0 + s, wf.b0 + f
    a_before = np.vstack([np.full((1, s.shape[1]), wf.a0), a_after[:-1]])
    b_before = np.vstack([np.full((1, f.shape[1]), wf.b0), b_after[:-1]])
    return a_before, b_before, a_after, b_after


def check(wf: Workflow, report: dict, success: np.ndarray, alphas,
          lambdas, *, band_rel: float = BAND_REL) -> dict:
    """Compare a replay report (field -> array, as ``FleetReport``) with
    the reference, teacher-forced on the report's launches.  The
    downstream op is index 1, the upstream 0."""
    if wf.discount != 1.0:
        raise ValueError("the closed-form handoff check needs discount 1")
    alphas = np.asarray(alphas, np.float64)[None]
    lambdas = np.asarray(lambdas, np.float64)[None]
    succ = np.asarray(success, bool)[:, None]
    launched_p = np.asarray(report["edge_launched"])[:, :, 1].astype(bool)
    committed_in = launched_p & succ
    a, b, a_after, b_after = _posterior_before(wf, launched_p, committed_in)
    f, launched, committed = episode(np, wf, a, b, alphas, lambdas, succ,
                                     np.float64)
    P = a / (a + b)
    terms = np.abs(P * wf.lat_save * lambdas) + np.abs((1 - P) * wf.C) \
        + np.abs(f["thr"])
    tie = np.abs(f["EV"] - f["thr"]) <= band_rel * terms

    def col(name, v=1):
        return np.asarray(report[name])[:, :, v]

    flag_mismatch = 0
    for got, want in ((col("speculate"), launched),
                      (col("edge_launched"), launched),
                      (col("edge_committed"), committed),
                      (np.asarray(report["launched"]), launched),
                      (np.asarray(report["committed"]), committed)):
        flag_mismatch += int(((got.astype(bool) != want) & ~tie).sum())
    flag_mismatch += int((np.asarray(report["cancelled"]) != 0).sum())
    # the upstream never launches, and its gate sees C = L = 0
    flag_mismatch += int((~col("speculate", 0).astype(bool)).sum())
    flag_mismatch += int(col("edge_launched", 0).astype(bool).sum())

    ev_gap = float(np.max(np.maximum(np.abs(col("EV_usd") - f["EV"]),
                                     np.abs(col("threshold_usd") - f["thr"]))
                          / terms, initial=0.0))
    up_ev = np.abs(col("EV_usd", 0)).max() + np.abs(col("threshold_usd", 0)).max()

    post_gap = 0.0
    for got, want in ((col("post_alpha"), a_after), (col("post_beta"), b_after),
                      (col("post_alpha", 0), 1.0), (col("post_beta", 0), 1.0)):
        post_gap = max(post_gap, float(np.max(np.abs(got - want)
                                              / np.abs(want), initial=0.0)))

    agree = (launched == launched_p)
    # a flag that differs outside a near tie is a gap of the whole value
    field_gap = max(float(up_ev), float(flag_mismatch > 0))
    for got, want in ((np.asarray(report["makespan_s"]), f["makespan"]),
                      (np.asarray(report["total_cost_usd"]), f["total_cost"]),
                      (np.asarray(report["waste_usd"]), f["waste"]),
                      (col("edge_waste_usd"), f["waste"]),
                      (col("start_s"), f["start"]),
                      (col("finish_s"), f["finish"]),
                      (col("start_s", 0), 0.0),
                      (col("finish_s", 0), wf.d_up),
                      (col("edge_waste_usd", 0), 0.0)):
        want = np.broadcast_to(want, got.shape)
        rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-12)
        field_gap = max(field_gap, float(np.max(np.where(agree, rel, 0.0),
                                                initial=0.0)))
    return dict(flag_mismatch=flag_mismatch, ev_gap=ev_gap,
                post_gap=post_gap, field_gap=field_gap,
                near_ties=int(tie.sum()),
                launches=int(launched_p.sum()))


def simulate(wf: Workflow, success: np.ndarray, alphas, lambdas, dtype):
    """The rules on their own trajectory in type ``dtype`` (a jitted scan
    over episodes); returns a report dict shaped like ``FleetReport``."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype).type
    al = jnp.asarray(alphas, dtype)
    lam = jnp.asarray(lambdas, dtype)

    def step(carry, s):
        a, b = carry
        f, launched, committed = episode(jnp, wf, a, b, al, lam, s, dt)
        d = dt(wf.discount)
        a2 = jnp.where(launched, a * d + committed.astype(dtype), a)
        b2 = jnp.where(launched, b * d + (~committed).astype(dtype), b)
        return (a2, b2), (f, launched, committed, a2, b2)

    G = al.shape[0]
    init = (jnp.full(G, wf.a0, dtype), jnp.full(G, wf.b0, dtype))
    _, (f, launched, committed, a, b) = jax.jit(
        lambda s: jax.lax.scan(step, init, s))(jnp.asarray(success, bool))
    f = {k: np.asarray(v, np.float64) for k, v in f.items()}
    launched, committed = np.asarray(launched), np.asarray(committed)
    E = launched.shape[0]

    def pair(up, down):
        return np.stack([np.broadcast_to(up, down.shape), down], -1)

    z = np.zeros((E, G))
    return dict(
        makespan_s=f["makespan"], total_cost_usd=f["total_cost"],
        waste_usd=f["waste"], launched=launched.astype(np.int32),
        committed=committed.astype(np.int32), cancelled=z.astype(np.int32),
        EV_usd=pair(0.0, f["EV"]), threshold_usd=pair(0.0, f["thr"]),
        speculate=pair(True, launched), edge_launched=pair(False, launched),
        edge_committed=pair(False, committed),
        edge_waste_usd=pair(0.0, f["waste"]), start_s=pair(0.0, f["start"]),
        finish_s=pair(wf.d_up, f["finish"]),
        post_alpha=pair(1.0, np.asarray(a, np.float64)),
        post_beta=pair(1.0, np.asarray(b, np.float64)))
