"""Input generators, driven by a configuration file and ``--seed``.

Copied from the program's sound generators so that later changes to the
program cannot move the yardstick:

* :class:`ZipfRows` — ``chip_smoke.Traffic``: Zipf(theta) ranks over the
  registry mapped through a seeded permutation, so hot rows scatter
  across tenants (YCSB's zipfian request distribution);
* :func:`request_params` — the store benchmark's request-parameter ranges;
* :func:`poisson_gaps` — ``benchmarks.frontend_load.poisson_arrivals``'
  exponential gaps, made once from a fixed stream and put in another
  order by the seed, so every seed offers the same work;
* :func:`register_fleet` — ``chip_smoke.Fleet.register`` (the 1M-row store
  benchmark's registration with its discount spread and a trigger-2
  floor on every 7th row);
* :func:`autoreply_workflow`, :func:`autoreply_success` —
  ``benchmarks.workflow_sim._autoreply_fleet``.

Only this module and the drivers import the program; the references
under ``bench/reference`` import nothing of it.
"""
from __future__ import annotations

import numpy as np

# the stream every seed's arrival gaps and rank multiset are drawn from;
# the run's seed only permutes them
BASE_SEED = 20260531
_SEED_MOD = 2 ** 63


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one purpose of one run (seeds of any size)."""
    return np.random.default_rng([int(seed) % _SEED_MOD, *stream])


# ------------------------------------------------------------------ fleet
class FleetLayout:
    """Row ``i`` belongs to archetype ``i % A`` and to tenant
    ``i // A % tenants_per_archetype`` of it (``chip_smoke.Fleet``)."""

    def __init__(self, cfg: dict) -> None:
        self.cfg = cfg
        self.n_rows = int(cfg["rows"])
        self.archetypes = sorted(cfg["archetypes"])
        self.n_arch = len(self.archetypes)
        self.tpa = int(cfg["tenants_per_archetype"])
        self.p_true = np.array(
            [min(cfg["p_mode_cap"], cfg["archetypes"][a]["p_mode"])
             for a in self.archetypes])
        i = np.arange(self.n_rows)
        self.has_floor = i % cfg["floor"]["every"] == 0
        mix = cfg["dep_mix"]
        self.dep_index = i % len(mix)
        self.discount = np.where(i % cfg["discount"]["every"] == 0,
                                 cfg["discount"]["value"], 1.0)

    def tenant(self, i: int) -> str:
        return f"{self.archetypes[i % self.n_arch]}/t{i // self.n_arch % self.tpa}"

    @staticmethod
    def edge(i: int) -> tuple[str, str]:
        return ("op", f"e{i}")


def register_fleet(svc, layout: FleetLayout) -> None:
    """Register every row one by one through the public API, as the
    deployment does."""
    from repro.core.taxonomy import DependencyType

    cfg = layout.cfg
    mix = [(DependencyType(d["dep_type"]), d.get("k")) for d in cfg["dep_mix"]]
    fl = cfg["floor"]
    floor = dict(floor_alpha=fl["alpha"], floor_C_spec_usd=fl["C_spec_usd"],
                 floor_L_value_usd=fl["L_value_usd"])
    gamma = cfg["gamma"]
    for i in range(layout.n_rows):
        dep, k = mix[layout.dep_index[i]]
        svc.register_edge(layout.edge(i), tenant=layout.tenant(i),
                          dep_type=dep, k=k, gamma=gamma,
                          discount=float(layout.discount[i]),
                          **(floor if layout.has_floor[i] else {}))


class ZipfRows:
    """Zipf(theta) row draws over the registry.  The rank -> row map is
    the run's own permutation; ``ranks`` draws from the run's stream."""

    def __init__(self, n_rows: int, theta: float, seed: int) -> None:
        w = 1.0 / np.arange(1, n_rows + 1, dtype=np.float64) ** theta
        self.cdf = np.cumsum(w) / w.sum()
        self.n_rows = n_rows
        self.by_rank = rng(seed, 1).permutation(n_rows)

    def ranks(self, u: np.ndarray) -> np.ndarray:
        r = np.searchsorted(self.cdf, u, side="right")
        return np.minimum(r, self.n_rows - 1)

    def rows(self, u: np.ndarray) -> np.ndarray:
        return self.by_rank[self.ranks(u)]


def request_params(r: np.random.Generator, n: int, ranges: dict) -> np.ndarray:
    """(n, 7) request columns [alpha, lambda, latency_s, in_tok, out_tok,
    in_price, out_price] (``chip_smoke.Traffic.params``)."""
    cols = []
    for name in ("alpha", "lambda_usd_per_s", "latency_s", "input_tokens",
                 "output_tokens", "input_price", "output_price"):
        lo, hi = ranges[name]
        if name == "input_tokens":
            cols.append(r.integers(lo, hi, n).astype(float))
        else:
            cols.append(r.uniform(lo, hi, n))
    return np.stack(cols, 1)


class RequestStream:
    """The requests of one run: rows, parameters and the upstream's
    outcome for each, all from the seed.  ``uniforms`` fixes the Zipf
    draws (a permuted base multiset for an open loop)."""

    def __init__(self, layout: FleetLayout, zipf: ZipfRows, ranges: dict,
                 seed: int, stream: int, uniforms: np.ndarray) -> None:
        n = uniforms.shape[0]
        self.rows = zipf.rows(uniforms)
        self.params = request_params(rng(seed, stream, 2), n, ranges)
        arch = self.rows % layout.n_arch
        self.outcome = (rng(seed, stream, 3).random(n)
                        < layout.p_true[arch])
        self.n = n


class GrowingStream:
    """The requests of a closed loop, made from the seed in chunks as the
    loop reaches them: request ``i`` is the same in every run of a seed,
    however far the loop gets."""

    CHUNK = 16384

    def __init__(self, layout: FleetLayout, zipf: ZipfRows, ranges: dict,
                 seed: int) -> None:
        self._args = (layout, zipf, ranges, seed)
        self.rows = np.zeros(0, np.int64)
        self.params = np.zeros((0, 7))
        self.outcome = np.zeros(0, bool)
        self.n = 0
        self.chunks = 0
        self.grow()

    def grow(self) -> None:
        layout, zipf, ranges, seed = self._args
        c = RequestStream(layout, zipf, ranges, seed, 100 + self.chunks,
                          rng(seed, 31, self.chunks).random(self.CHUNK))
        self.rows = np.concatenate([self.rows, c.rows])
        self.params = np.concatenate([self.params, c.params])
        self.outcome = np.concatenate([self.outcome, c.outcome])
        self.n += c.n
        self.chunks += 1


def base_uniforms(n: int, seed: int) -> np.ndarray:
    """``n`` Zipf uniforms: one fixed multiset, in the seed's order."""
    u = rng(BASE_SEED, 10).random(n)
    return u[rng(seed, 11).permutation(n)]


def poisson_gaps(rate_hz: float, n: int, seed: int) -> np.ndarray:
    """``n`` exponential gaps at ``rate_hz`` — one fixed multiset, in the
    seed's order — so each seed offers the same requests over the same
    span (``frontend_load.poisson_arrivals``' process)."""
    g = rng(BASE_SEED, 20).exponential(1.0 / rate_hz, n)
    return g[rng(seed, 21).permutation(n)]


# ---------------------------------------------------------------- replay
def autoreply_workflow(cfg: dict):
    """The AutoReply workflow lowered for the fleet engine
    (``workflow_sim.build_workflow`` / ``_autoreply_fleet``)."""
    from repro.core import (Edge, Operation, PlannerParams, Workflow,
                            lower_workflow)
    from repro.core.posterior import BetaPosterior
    from repro.core.predictor import HistoricalModalPredictor
    from repro.core.taxonomy import DependencyType

    up, down = cfg["ops"]
    wf = Workflow(cfg["name"])
    for op in (up, down):
        wf.add_op(Operation(op["name"], run=lambda x: x,
                            latency_est_s=op["latency_s"],
                            input_tokens_est=op["input_tokens"],
                            output_tokens_est=op["output_tokens"],
                            metadata=dict(op.get("metadata", {}))))
    k = cfg["router_k"]
    wf.add_edge(Edge(up["name"], down["name"],
                     dep_type=DependencyType.ROUTER_K_WAY, k=k))
    wf = wf.freeze()
    key = (up["name"], down["name"])
    params = PlannerParams(
        alpha=0.5, lambda_usd_per_s=cfg["lambdas"][0],
        posteriors={key: BetaPosterior.from_dependency_type(
            DependencyType.ROUTER_K_WAY, k=k)})
    pred = HistoricalModalPredictor()
    pred.observe(up["metadata"]["input"], cfg["modal_intent"])
    return lower_workflow(wf, params, predictors={key: pred})


def autoreply_success(cfg: dict, episodes: int, seed: int, job: int):
    """(E,) bool: the downstream's modal prediction held in each episode
    (the 5-way intent router drew its modal branch)."""
    p = np.asarray(cfg["intent_probs"], float)
    draws = rng(seed, 100, job).choice(p.shape[0], size=episodes, p=p / p.sum())
    return draws == 0


def grid(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """The §12.1 cross product as paired points: every alpha at every
    lambda (G = A x L)."""
    a = np.asarray(cfg["alphas"], float)
    lam = np.asarray(cfg["lambdas"], float)
    return np.tile(a, lam.shape[0]), np.repeat(lam, a.shape[0])
