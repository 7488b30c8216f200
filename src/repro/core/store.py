"""Paged hierarchical posterior store — the single owner of
(tenant, edge) -> Beta-row state at fleet scale.

Before this module, posterior state had three ad-hoc owners: the online
service's dense ``(N, 2)`` device tables behind an O(N)-rebuild host
registry, the fleet engine's per-call carries, and the serving
front-end's host posterior mirror.  ``PosteriorStore`` unifies them and
removes the small-N assumption:

Systems half (§14.3 (b)):

* **logical rows** are stable integer ids handed out by a host registry
  with a free-list, backed by structure-of-arrays config storage that
  doubles amortized-O(1) — registering a row never touches the device
  and never loops over existing rows;
* **physical rows** live in a device-resident table of power-of-two
  capacity.  In the default *auto-grow* mode (``resident_rows=None``)
  slot == logical id and capacity doubles with the registry (the dense
  behavior the online service always had, minus the O(N) Python rebuild:
  new rows apply in one batched scatter per tick).  In *paged* mode
  (``resident_rows=R``) the physical shape is **fixed forever** — the
  jit'd ``tick`` / scatter / gather executables can never recompile from
  growth — and cold rows spill, least-recently-touched first, to a
  host-side f64 shelf with transparent fault-in on next touch;
* spill/fault-in round-trips are **bitwise-f64 exact** (the shelf stores
  f64; under ``jax_enable_x64`` the device table is f64), so a paged
  store at any occupancy answers decisions bitwise-equal to the dense
  table on the same logical rows — property-pinned in tests/test_store.py.

Statistical half (§14.3 (a)):

* one jit'd empirical-Bayes **moment-matching fit** over the
  device-resident rows, grouped by taxonomy bucket
  (``jax.ops.segment_sum`` with a static power-of-two segment count),
  produces per-bucket Beta hyperpriors;
* a brand-new (tenant, edge) row is then born from its **bucket's
  learned prior** instead of the paper's fixed taxonomy prior, with
  shrinkage fading naturally as conjugate evidence accumulates.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .posterior import BetaPosterior
from .taxonomy import DEFAULT_N0, DependencyType, prior_params

__all__ = ["PosteriorStore", "BucketPrior", "_RowConfig", "ROLL_COLS"]


def _bucket(n: int, lo: int = 1) -> int:
    """Power-of-two shape bucket (compile-cache stability)."""
    if n <= 0:
        return 0
    return max(lo, 1 << (n - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class _RowConfig:
    """Host-side registration record for one (tenant, edge) row.
    ``alpha0``/``beta0`` are the row's *birth* prior — the learned bucket
    hyperprior when pooling applied, else the fixed taxonomy prior."""

    tenant: Optional[str]
    edge: tuple[str, str]
    alpha0: float
    beta0: float
    gamma: float
    discount: float
    floor: float


@dataclasses.dataclass(frozen=True)
class BucketPrior:
    """A fitted per-taxonomy-bucket empirical-Bayes hyperprior."""

    bucket: str
    alpha: float
    beta: float
    n_rows: int          # rows with enough evidence that entered the fit
    mean: float          # pooled success-rate estimate mu_g
    strength: float      # pseudo-count strength s_g (alpha + beta)


# --------------------------------------------------------------------------
# jit'd kernels.  All index arrays are padded to power-of-two lengths with
# the sentinel index == table capacity: out of bounds, so scatters drop the
# padding lanes and gathers clamp them (the gathered garbage is discarded
# host-side).  Executables key on (capacity, pad bucket, dtype) only — in
# paged mode every one of those is fixed after warm-up, which is what the
# zero-recompile churn property pins.
# --------------------------------------------------------------------------
@jax.jit
def _scatter_rows(post, rowcfg, flags, roll, slots, pvals, cvals, fvals,
                  rvals):
    return (post.at[slots].set(pvals, mode="drop"),
            rowcfg.at[slots].set(cvals, mode="drop"),
            flags.at[slots].set(fvals, mode="drop"),
            roll.at[slots].set(rvals, mode="drop"))


@jax.jit
def _scatter_post(post, slots, pvals):
    return post.at[slots].set(pvals, mode="drop")


@jax.jit
def _gather_rows(post, flags, roll, slots):
    s = jnp.minimum(slots, post.shape[0] - 1)
    return post[s], flags[s], roll[s]


@jax.jit
def _gather_post(post, slots):
    s = jnp.minimum(slots, post.shape[0] - 1)
    return post[s]


@functools.partial(jax.jit, static_argnames=("G",))
def _eb_moments(post, bucket, prior_n, alive, min_evidence, G):
    """Per-bucket weighted moment sums over the resident posterior table:
    one segment-sum pass yields (count, sum m, sum m^2) of the posterior
    means of rows whose accumulated evidence (pseudo-count mass beyond
    the birth prior) clears ``min_evidence``."""
    n = post[:, 0] + post[:, 1]
    m = post[:, 0] / n
    w = (alive & (n - prior_n >= min_evidence)).astype(post.dtype)
    cnt = jax.ops.segment_sum(w, bucket, num_segments=G)
    s1 = jax.ops.segment_sum(w * m, bucket, num_segments=G)
    s2 = jax.ops.segment_sum(w * m * m, bucket, num_segments=G)
    return cnt, s1, s2


_FRESH_FLAGS = np.array([1, 0], np.int32)    # enabled, zero breach run
# staged-rollout lifecycle columns (repro.core.rollout):
# [phase, cooldown, probes, ticks_in_phase, n_obs, s_obs].  Fresh rows are
# born in SHADOW (phase 1) with empty counters; the columns spill/fault-in
# alongside the posterior so phase state survives paging bitwise.
ROLL_COLS = 6
_FRESH_ROLL = np.array([1, 0, 0, 0, 0, 0], np.int32)


class PosteriorStore:
    """Single owner of (tenant, edge) -> Beta-row state.

    ``resident_rows=None`` (default) is the dense auto-grow mode: every
    live row is device-resident, slot == logical id, and capacity grows
    by power-of-two doubling.  ``resident_rows=R`` is the paged mode: at
    most ``bucket(R)`` rows are device-resident, the physical table shape
    never changes, and cold rows live on the host shelf.

    ``on_evict(edge, tenant)`` fires when a row is *evicted* (removed
    from the registry — e.g. a departed tenant); ``on_fault_in(edge,
    tenant)`` fires when a previously *spilled* row returns to the device
    — the drift monitor uses these to drop / re-seed its per-row host
    state (satellite: unbounded DriftMonitor growth).
    """

    def __init__(
        self,
        *,
        resident_rows: Optional[int] = None,
        min_rows: int = 16,
        mesh=None,
        axis_name: str = "fleet",
        on_evict: Optional[Callable] = None,
        on_fault_in: Optional[Callable] = None,
    ) -> None:
        if resident_rows is not None and int(resident_rows) < 1:
            raise ValueError("resident_rows must be >= 1")
        self.resident_rows = None if resident_rows is None else int(resident_rows)
        self.min_rows = int(min_rows)
        self.mesh = mesh
        self.axis_name = axis_name
        self.on_evict = on_evict
        self.on_fault_in = on_fault_in

        # ---- logical registry (host, amortized-O(1) insert)
        self._keys: dict = {}            # (tenant, edge) -> logical id
        self._row_keys: list = []        # id -> (tenant, edge) | None
        self._free_ids: list[int] = []
        self._host_cap = 0
        # per-logical-id SoA (grown by doubling, never per-row Python)
        self._prior = np.zeros((0, 2))           # birth prior [a0, b0]
        self._cfg = np.zeros((0, 3))             # [gamma, discount, floor]
        self._bucket_of = np.zeros(0, np.int32)  # taxonomy-bucket id
        self._shelf_post = np.zeros((0, 2))      # spilled [alpha, beta] (f64)
        self._shelf_flags = np.zeros((0, 2), np.int32)
        self._shelf_roll = np.zeros((0, ROLL_COLS), np.int32)
        self._shelved = np.zeros(0, bool)
        self._slot_of = np.zeros(0, np.int64)    # -1 = not device-resident
        self._alive = np.zeros(0, bool)

        # ---- physical device table
        self._post = self._rowcfg = self._flags = self._roll = None
        self._dtype: Optional[str] = None
        self._np_dtype = np.dtype(np.float64)
        self._capacity = 0
        self._logical_at: Optional[np.ndarray] = None  # slot -> id, -1 free
        self._free_slots: list[int] = []
        self._last_touch: Optional[np.ndarray] = None  # LRU clock per slot
        self._clock = 1
        self._identity = self.resident_rows is None
        self._pending: list[int] = []   # identity mode: rows awaiting the
                                        # once-per-tick batched scatter
        self.row_sharding = None

        # ---- empirical-Bayes bucket registry
        self._bucket_ids: dict[str, int] = {}
        self._bucket_labels: list[str] = []
        self.hyperpriors: dict[str, BucketPrior] = {}

        self.stats = {
            "registered": 0, "evictions": 0, "rebuilds": 0,
            "fault_ins": 0, "spills": 0, "scatter_batches": 0,
            "eb_fits": 0,
        }

    # ------------------------------------------------------------- registry
    @property
    def n_rows(self) -> int:
        """Logical id high-water mark (row ids index snapshots 0..n-1)."""
        return len(self._row_keys)

    @property
    def n_alive(self) -> int:
        return len(self._keys)

    @property
    def n_resident(self) -> int:
        if self._logical_at is None:
            return 0
        return int((self._logical_at >= 0).sum())

    @property
    def n_shelved(self) -> int:
        return int(self._shelved[: self.n_rows].sum())

    @property
    def identity(self) -> bool:
        """True while slot == logical id (dense auto-grow, no evictions)."""
        return self._identity

    @property
    def capacity(self) -> int:
        return self._capacity

    def _grow_host(self, need: int) -> None:
        if need <= self._host_cap:
            return
        cap = _bucket(max(need, self.min_rows, 16))

        def grow2(a, fill=0.0):
            out = np.full((cap,) + a.shape[1:], fill, a.dtype)
            out[: a.shape[0]] = a
            return out

        self._prior = grow2(self._prior)
        self._cfg = grow2(self._cfg)
        self._bucket_of = grow2(self._bucket_of)
        self._shelf_post = grow2(self._shelf_post)
        self._shelf_flags = grow2(self._shelf_flags)
        self._shelf_roll = grow2(self._shelf_roll)
        self._shelved = grow2(self._shelved, False)
        self._slot_of = grow2(self._slot_of, -1)
        self._alive = grow2(self._alive, False)
        self._host_cap = cap

    def _bucket_id(self, label: str) -> int:
        bid = self._bucket_ids.get(label)
        if bid is None:
            bid = len(self._bucket_labels)
            self._bucket_ids[label] = bid
            self._bucket_labels.append(label)
        return bid

    @staticmethod
    def bucket_label(dep_type: Optional[DependencyType],
                     k: Optional[int] = None) -> str:
        """Default taxonomy-bucket label: the dependency type, split by
        branching factor for routers (different k => different prior)."""
        if dep_type is None:
            return "_seeded"
        label = dep_type.value
        if dep_type is DependencyType.ROUTER_K_WAY and k is not None:
            label += f":k{int(k)}"
        return label

    def register(
        self,
        edge: tuple[str, str],
        *,
        tenant: Optional[str] = None,
        dep_type: Optional[DependencyType] = None,
        k: Optional[int] = None,
        rare_event_p: Optional[float] = None,
        n0: float = DEFAULT_N0,
        posterior: Optional[BetaPosterior] = None,
        gamma: float = 0.1,
        discount: float = 1.0,
        floor_alpha: float = 0.5,
        floor_C_spec_usd: Optional[float] = None,
        floor_L_value_usd: Optional[float] = None,
        bucket: Optional[str] = None,
        pooled: bool = True,
    ) -> int:
        """Add one (tenant, edge) row; returns its stable logical id.

        Pure host work — O(1) amortized, no device transfer, no loop over
        existing rows.  The birth prior is, in order of precedence: an
        explicit ``posterior`` (§12.1 data-seeded deployment), the
        bucket's learned empirical-Bayes hyperprior (when ``pooled`` and
        :meth:`fit_hyperpriors` has produced one), else the fixed
        taxonomy prior ``prior_params(dep_type, ...)``.
        """
        key = (tenant, tuple(edge))
        if key in self._keys:
            raise ValueError(f"edge already registered: {key}")
        if bucket is None:
            bucket = self.bucket_label(dep_type, k)
        if posterior is not None:
            a0, b0 = float(posterior.alpha), float(posterior.beta)
        elif dep_type is not None:
            hp = self.hyperpriors.get(bucket) if pooled else None
            if hp is not None:
                a0, b0 = hp.alpha, hp.beta
            else:
                a0, b0 = prior_params(dep_type, k=k, rare_event_p=rare_event_p,
                                      n0=n0)
        else:
            raise ValueError("register_edge needs dep_type or posterior")
        if a0 <= 0 or b0 <= 0:
            raise ValueError("Beta parameters must be positive")
        if not (0.0 < gamma < 1.0):
            raise ValueError("gamma must be in (0, 1)")
        if floor_C_spec_usd is not None and floor_L_value_usd is not None:
            # same expression as DriftMonitor.check_credible_bound
            floor = (1.0 - floor_alpha) * floor_C_spec_usd / (
                floor_L_value_usd + floor_C_spec_usd)
        else:
            floor = -np.inf

        if self._free_ids:
            i = self._free_ids.pop()
        else:
            i = len(self._row_keys)
            self._grow_host(i + 1)
            self._row_keys.append(None)
        self._row_keys[i] = key
        self._keys[key] = i
        self._prior[i] = a0, b0
        self._cfg[i] = float(gamma), float(discount), float(floor)
        self._bucket_of[i] = self._bucket_id(bucket)
        self._shelved[i] = False
        self._slot_of[i] = -1
        self._alive[i] = True
        if self._identity:
            self._pending.append(i)
        self.stats["registered"] += 1
        return i

    def row_index(self, edge: tuple[str, str],
                  tenant: Optional[str] = None) -> int:
        return self._keys[(tenant, tuple(edge))]

    def row_key(self, row: int):
        key = self._row_keys[row]
        if key is None:
            raise KeyError(f"row {row} was evicted")
        return key

    def row_config(self, row: int) -> _RowConfig:
        tenant, edge = self.row_key(row)
        a0, b0 = self._prior[row]
        g, d, fl = self._cfg[row]
        return _RowConfig(tenant=tenant, edge=edge, alpha0=float(a0),
                          beta0=float(b0), gamma=float(g), discount=float(d),
                          floor=float(fl))

    def check_rows(self, rows: np.ndarray, what: str = "request") -> None:
        """Bounds + liveness validation (the online service's tick/observe
        contract: bad ids raise, never silently scatter onto padding)."""
        rows = np.asarray(rows)
        if rows.size == 0:
            return
        n = self.n_rows
        if rows.min() < 0 or rows.max() >= n or not self._alive[rows].all():
            raise IndexError(f"{what} row out of range")

    # ------------------------------------------------------------- eviction
    def evict_row(self, row: int) -> None:
        """Remove a logical row entirely: registry entry dropped, logical
        id recycled through the free-list, any resident slot freed, shelf
        entry cleared.  Fires ``on_evict`` so host-side per-row state
        (DriftMonitor histories) is dropped with it."""
        key = self._row_keys[row]
        if key is None:
            raise KeyError(f"row {row} already evicted")
        self._leave_identity()
        slot = self._slot_of[row]
        if slot >= 0:
            # pure host bookkeeping: the stale device values are masked by
            # the slot maps and overwritten on reuse — no device op at all
            self._logical_at[slot] = -1
            self._last_touch[slot] = 0
            self._free_slots.append(int(slot))
        self._slot_of[row] = -1
        self._shelved[row] = False
        self._alive[row] = False
        self._row_keys[row] = None
        del self._keys[key]
        self._free_ids.append(row)
        self.stats["evictions"] += 1
        if self.on_evict is not None:
            tenant, edge = key
            self.on_evict(edge, tenant)

    def evict(self, edge: tuple[str, str],
              tenant: Optional[str] = None) -> None:
        self.evict_row(self.row_index(edge, tenant))

    def evict_tenant(self, tenant: Optional[str]) -> int:
        """Evict every row of one tenant; returns the count."""
        rows = [i for i, key in enumerate(self._row_keys)
                if key is not None and key[0] == tenant]
        for i in rows:
            self.evict_row(i)
        return len(rows)

    def _leave_identity(self) -> None:
        if not self._identity:
            return
        self._identity = False
        # pending rows now fault in lazily on first touch instead
        self._pending = []

    # --------------------------------------------------------- device table
    def _target_capacity(self) -> int:
        if self.resident_rows is not None:
            return _bucket(max(self.resident_rows, self.min_rows))
        return _bucket(max(self.n_rows, self.min_rows))

    def device_tables(self, dtype: str):
        """Ensure the device-resident tables exist for ``dtype`` and that
        every pending registration has materialized (identity mode: one
        batched scatter, not one rebuild per row).  Returns
        ``(post, rowcfg, flags, roll)``."""
        cap = self._target_capacity()
        if self._post is None or self._dtype != dtype or self._capacity != cap:
            self._rebuild(dtype, cap)
        elif self._identity and self._pending:
            self._apply_pending()
        return self._post, self._rowcfg, self._flags, self._roll

    def tables(self):
        return self._post, self._rowcfg, self._flags, self._roll

    def adopt(self, post, rowcfg, flags, roll) -> None:
        """Adopt the arrays a jit'd tick returned (the store stays the
        single owner across donated double-buffer updates)."""
        self._post, self._rowcfg, self._flags = post, rowcfg, flags
        self._roll = roll

    def logical_map(self) -> Optional[np.ndarray]:
        """Copy of the slot -> logical-id map, or None in identity mode
        (slot == id).  Snapshotted per tick for drift translation."""
        if self._identity:
            return None
        with TraceAnnotation("store.slot_map"):
            return self._logical_at.copy()

    def _device_put(self, post_np, cfg_np, flags_np, roll_np):
        self.row_sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            from ..sharding.rules import fleet_axis_spec

            spec = fleet_axis_spec(self.mesh, self._capacity,
                                   axis=self.axis_name)
            if spec is not None:
                self.row_sharding = NamedSharding(self.mesh, spec)
        if self.row_sharding is not None:
            self._post = jax.device_put(post_np, self.row_sharding)
            self._rowcfg = jax.device_put(cfg_np, self.row_sharding)
            self._flags = jax.device_put(flags_np, self.row_sharding)
            self._roll = jax.device_put(roll_np, self.row_sharding)
        else:
            self._post = jnp.asarray(post_np)
            self._rowcfg = jnp.asarray(cfg_np)
            self._flags = jnp.asarray(flags_np)
            self._roll = jnp.asarray(roll_np)

    def _rebuild(self, dtype: str, cap: int) -> None:
        """(Re)build the physical table — first build, dtype switch, or an
        identity-mode capacity doubling.  Never happens in paged mode
        after the first build, which is the zero-recompile guarantee.

        Live device values survive exactly: residents spill to the f64
        shelf first, then either fault back in eagerly (identity mode, one
        vectorized transfer) or on next touch (paged mode)."""
        if self._post is not None and self._logical_at is not None:
            res = np.flatnonzero(self._logical_at >= 0)
            if res.size:
                self._spill_slots(res)
        self.stats["rebuilds"] += 1
        self._capacity = cap
        self._dtype = dtype
        self._np_dtype = np.dtype(dtype)
        self._logical_at = np.full(cap, -1, np.int64)
        self._last_touch = np.zeros(cap, np.int64)
        self._clock = 1
        self._pending = []
        n = self.n_rows
        post = np.ones((cap, 2))
        cfg = np.stack([np.full(cap, 0.5), np.ones(cap),
                        np.full(cap, -np.inf)], 1)
        flags = np.zeros((cap, 2), np.int32)
        roll = np.tile(_FRESH_ROLL, (cap, 1))
        if self._identity and n:
            # eager vectorized materialization of every live row (identity
            # mode has no evictions, so rows 0..n-1 are all alive)
            sh = self._shelved[:n, None]
            post[:n] = np.where(sh, self._shelf_post[:n], self._prior[:n])
            cfg[:n] = self._cfg[:n]
            flags[:n] = np.where(sh, self._shelf_flags[:n], _FRESH_FLAGS)
            roll[:n] = np.where(sh, self._shelf_roll[:n], _FRESH_ROLL)
            self._shelved[:n] = False
            self._slot_of[:n] = np.arange(n)
            self._logical_at[:n] = np.arange(n)
            self._free_slots = list(range(cap - 1, n - 1, -1))
        else:
            # paged (or post-eviction) mode: rows stay on the shelf / as
            # unmaterialized priors and fault in on first touch
            self._free_slots = list(range(cap - 1, -1, -1))
        self._device_put(post.astype(self._np_dtype),
                         cfg.astype(self._np_dtype), flags, roll)

    def _apply_pending(self) -> None:
        """Identity mode: materialize all registrations since the last
        tick in one batched scatter (the satellite fix for the old
        O(N)-per-new-row host rebuild)."""
        ids = np.asarray(self._pending, np.int64)
        self._pending = []
        # identity invariant: fresh ids are consecutive and the free-slot
        # list's tail is exactly those slots in pop() order
        del self._free_slots[len(self._free_slots) - ids.size:]
        self._scatter(ids, self._prior[ids], self._cfg[ids],
                      np.broadcast_to(_FRESH_FLAGS, (ids.size, 2)),
                      np.broadcast_to(_FRESH_ROLL, (ids.size, ROLL_COLS)))
        self._slot_of[ids] = ids
        self._logical_at[ids] = ids
        self.stats["fault_ins"] += int(ids.size)

    def _scatter(self, slots, pvals, cvals, fvals, rvals) -> None:
        k = int(slots.size)
        kp = _bucket(k)
        spad = np.full(kp, self._capacity, np.int64)
        spad[:k] = slots
        pp = np.zeros((kp, 2), self._np_dtype)
        pp[:k] = pvals
        cc = np.zeros((kp, 3), self._np_dtype)
        cc[:k] = cvals
        ff = np.zeros((kp, 2), np.int32)
        ff[:k] = fvals
        rr = np.zeros((kp, ROLL_COLS), np.int32)
        rr[:k] = rvals
        self._post, self._rowcfg, self._flags, self._roll = _scatter_rows(
            self._post, self._rowcfg, self._flags, self._roll, spad, pp, cc,
            ff, rr)
        self.stats["scatter_batches"] += 1

    # ------------------------------------------------------ paging / LRU
    def ensure_resident(self, ids: np.ndarray) -> np.ndarray:
        """Fault the given logical rows onto the device (spilling LRU
        victims if the free-list runs dry) and touch their LRU clocks.
        Returns the slot of each id.  No-op identity fast path."""
        ids = np.unique(np.asarray(ids, np.int64))
        if self._identity:
            if self._pending:
                self._apply_pending()
            return ids
        if ids.size == 0:
            return ids
        self.check_rows(ids)
        slots = self._slot_of[ids]
        missing = ids[slots < 0]
        if missing.size:
            k = int(missing.size)
            if k > self._capacity:
                raise RuntimeError(
                    f"one tick touches {k} distinct rows > resident "
                    f"capacity {self._capacity}")
            # pin this tick's already-resident rows before victim choice
            res = slots[slots >= 0]
            self._last_touch[res] = self._clock
            shortfall = k - len(self._free_slots)
            if shortfall > 0:
                self._spill_lru(shortfall)
            new_slots = np.array(
                [self._free_slots.pop() for _ in range(k)], np.int64)
            sh = self._shelved[missing]
            pvals = np.where(sh[:, None], self._shelf_post[missing],
                             self._prior[missing])
            fvals = np.where(sh[:, None], self._shelf_flags[missing],
                             _FRESH_FLAGS)
            rvals = np.where(sh[:, None], self._shelf_roll[missing],
                             _FRESH_ROLL)
            self._scatter(new_slots, pvals, self._cfg[missing], fvals, rvals)
            self._slot_of[missing] = new_slots
            self._logical_at[new_slots] = missing
            self._shelved[missing] = False
            self.stats["fault_ins"] += k
            if self.on_fault_in is not None:
                for i in missing[sh]:       # only rows returning from spill
                    tenant, edge = self._row_keys[i]
                    self.on_fault_in(edge, tenant)
            slots = self._slot_of[ids]
        self._last_touch[slots] = self._clock
        self._clock += 1
        return slots

    def _spill_lru(self, need: int) -> None:
        cand = np.flatnonzero(self._logical_at >= 0)
        cand = cand[self._last_touch[cand] < self._clock]   # unpinned only
        if cand.size < need:
            raise RuntimeError(
                "one tick touches more distinct rows than resident capacity")
        order = np.lexsort((cand, self._last_touch[cand]))
        self._spill_slots(cand[order[:need]])

    def _spill_slots(self, victim_slots: np.ndarray) -> None:
        """Move resident rows to the host shelf (exact f64 values; the
        breach-run / enable bits and rollout phase columns ride along)."""
        k = int(victim_slots.size)
        kp = _bucket(k)
        pad = np.full(kp, self._capacity, np.int64)
        pad[:k] = victim_slots
        p, f, r = _gather_rows(self._post, self._flags, self._roll, pad)
        ids = self._logical_at[victim_slots]
        self._shelf_post[ids] = np.asarray(p, np.float64)[:k]
        self._shelf_flags[ids] = np.asarray(f)[:k]
        self._shelf_roll[ids] = np.asarray(r)[:k]
        self._shelved[ids] = True
        self._slot_of[ids] = -1
        self._logical_at[victim_slots] = -1
        self._last_touch[victim_slots] = 0
        self._free_slots.extend(int(s) for s in victim_slots)
        self.stats["spills"] += k

    def resident_ids(self) -> np.ndarray:
        """Sorted logical ids currently device-resident."""
        if self._logical_at is None:
            return np.zeros(0, np.int64)
        ids = self._logical_at[self._logical_at >= 0]
        return np.sort(ids)

    def translate(self, rows: np.ndarray) -> np.ndarray:
        """Map logical row ids (with -1 padding sentinels) to device
        slots.  Valid ids must already be resident (``ensure_resident``
        runs first in the tick path)."""
        if self._identity:
            return rows
        out = np.full(rows.shape, -1, np.int32)
        v = rows >= 0
        out[v] = self._slot_of[rows[v]]
        return out

    # ------------------------------------------------------------ snapshots
    def snapshot(self, dtype=np.float64) -> np.ndarray:
        """(n_rows, 2) composed alpha/beta view across every storage tier:
        device-resident rows (authoritative), shelf rows (exact spilled
        values), never-touched rows (their birth prior).  Evicted ids
        read as the (1, 1) padding prior."""
        n = self.n_rows
        dt = np.dtype(dtype)
        snap = np.where(self._shelved[:n, None], self._shelf_post[:n],
                        self._prior[:n]).astype(dt)
        dead = ~self._alive[:n]
        if dead.any():
            snap[dead] = 1.0
        if self._post is not None and self._logical_at is not None:
            res = np.flatnonzero(self._logical_at >= 0)
            if res.size:
                vals = np.asarray(self._post)[res].astype(dt, copy=False)
                snap[self._logical_at[res]] = vals
        return snap

    def flags_snapshot(self) -> np.ndarray:
        """(n_rows, 2) int32 composed [enabled, breach_run] view (same
        tier precedence as :meth:`snapshot`; evicted rows read disabled)."""
        n = self.n_rows
        out = np.where(self._shelved[:n, None], self._shelf_flags[:n],
                       _FRESH_FLAGS).astype(np.int32)
        dead = ~self._alive[:n]
        if dead.any():
            out[dead] = 0
        if self._flags is not None and self._logical_at is not None:
            res = np.flatnonzero(self._logical_at >= 0)
            if res.size:
                out[self._logical_at[res]] = np.asarray(self._flags)[res]
        return out

    def roll_snapshot(self) -> np.ndarray:
        """(n_rows, ROLL_COLS) int32 composed rollout-lifecycle view
        [phase, cooldown, probes, ticks_in_phase, n_obs, s_obs] — same
        tier precedence as :meth:`snapshot`; evicted rows read phase 0
        (DISABLED) with zeroed counters."""
        n = self.n_rows
        out = np.where(self._shelved[:n, None], self._shelf_roll[:n],
                       _FRESH_ROLL).astype(np.int32)
        dead = ~self._alive[:n]
        if dead.any():
            out[dead] = 0
        if self._roll is not None and self._logical_at is not None:
            res = np.flatnonzero(self._logical_at >= 0)
            if res.size:
                out[self._logical_at[res]] = np.asarray(self._roll)[res]
        return out

    def set_roll_rows(self, ids, values) -> None:
        """Overwrite the rollout-lifecycle columns for logical rows
        (faulting them in first in paged mode) — the host override path
        RolloutController uses for tier-2 demotion and operator revives."""
        ids = np.asarray(ids, np.int64)
        values = np.asarray(values, np.int32).reshape(ids.size, ROLL_COLS)
        self.check_rows(ids)
        if self._roll is None:
            raise RuntimeError("device tables not built; call device_tables")
        self.ensure_resident(ids)
        slots = ids if self._identity else self._slot_of[ids]
        k = int(ids.size)
        kp = _bucket(k)
        spad = np.full(kp, self._capacity, np.int64)
        spad[:k] = slots
        rr = np.zeros((kp, ROLL_COLS), np.int32)
        rr[:k] = values
        self._roll = self._roll.at[jnp.asarray(spad)].set(
            jnp.asarray(rr), mode="drop")

    def rows_snapshot(self, ids, dtype=np.float64) -> np.ndarray:
        """(k, 2) composed alpha/beta values for specific logical rows —
        the lazy per-row read path (front-end mirror misses) that never
        changes residency."""
        ids = np.asarray(ids, np.int64)
        self.check_rows(ids)
        dt = np.dtype(dtype)
        out = np.where(self._shelved[ids, None], self._shelf_post[ids],
                       self._prior[ids]).astype(dt)
        if self._post is not None:
            slots = self._slot_of[ids]
            res = slots >= 0
            if res.any():
                k = int(res.sum())
                kp = _bucket(k)
                pad = np.full(kp, self._capacity, np.int64)
                pad[:k] = slots[res]
                vals = np.asarray(_gather_post(self._post, pad), np.float64)
                out[res] = vals[:k].astype(dt, copy=False)
        return out

    def set_rows(self, ids, values) -> None:
        """Overwrite alpha/beta for logical rows (faulting them in first
        in paged mode) — the ``set_posterior`` / replay-seeding path."""
        ids = np.asarray(ids, np.int64)
        values = np.asarray(values, np.float64).reshape(ids.size, 2)
        if np.any(values <= 0):
            raise ValueError("Beta parameters must be positive")
        self.check_rows(ids)
        if self._post is None:
            raise RuntimeError("device tables not built; call device_tables")
        self.ensure_resident(ids)
        uids = np.unique(ids)
        vmap = {int(i): values[j] for j, i in enumerate(ids)}
        vals = np.stack([vmap[int(i)] for i in uids]) if uids.size else values
        k = int(uids.size)
        kp = _bucket(k)
        spad = np.full(kp, self._capacity, np.int64)
        spad[:k] = self._slot_of[uids] if not self._identity else uids
        pp = np.zeros((kp, 2), self._np_dtype)
        pp[:k] = vals
        self._post = _scatter_post(self._post, spad, pp)

    # ------------------------------------------------- empirical-Bayes fit
    def fit_hyperpriors(
        self,
        *,
        min_evidence: float = 5.0,
        min_bucket_rows: int = 2,
        strength_floor: Optional[float] = None,
        strength_cap: float = 1000.0,
        var_floor: float = 1e-6,
    ) -> dict[str, BucketPrior]:
        """One jit'd empirical-Bayes fit over the device-resident rows:
        moment-matching per taxonomy bucket.

        For each bucket g the posterior means of resident rows with at
        least ``min_evidence`` pseudo-counts beyond their birth prior
        give (mu_g, var_g); the method-of-moments Beta strength is
        ``s = mu (1 - mu) / var - 1``, clipped to
        ``[strength_floor (default n0), strength_cap]``, and the
        hyperprior is ``Beta(mu s, (1 - mu) s)``.  The result is stored
        on the instance — subsequent :meth:`register` calls with the same
        bucket are born from it.  Shelved rows are deliberately excluded:
        the fit is one segment-sum pass over the live table, no host loop.
        """
        G = len(self._bucket_labels)
        if G == 0 or self._post is None:
            self.hyperpriors = {}
            return self.hyperpriors
        cap = self._capacity
        ids = self._logical_at
        alive = ids >= 0
        safe = np.maximum(ids, 0)
        bucket = np.where(alive, self._bucket_of[safe], 0).astype(np.int32)
        prior_n = np.where(alive, self._prior[safe].sum(1), 0.0)
        Gp = _bucket(G)
        cnt, s1, s2 = _eb_moments(
            self._post, bucket, prior_n.astype(self._np_dtype), alive,
            self._np_dtype.type(min_evidence), Gp)
        cnt = np.asarray(cnt, np.float64)
        s1 = np.asarray(s1, np.float64)
        s2 = np.asarray(s2, np.float64)
        lo = DEFAULT_N0 if strength_floor is None else float(strength_floor)
        out: dict[str, BucketPrior] = {}
        for g, label in enumerate(self._bucket_labels):
            c = cnt[g]
            if c < min_bucket_rows:
                continue
            mu = s1[g] / c
            var = max(s2[g] / c - mu * mu, 0.0)
            mu = min(max(mu, 1e-6), 1.0 - 1e-6)
            s = mu * (1.0 - mu) / max(var, var_floor) - 1.0
            s = min(max(s, lo), float(strength_cap))
            out[label] = BucketPrior(
                bucket=label, alpha=mu * s, beta=(1.0 - mu) * s,
                n_rows=int(round(c)), mean=float(mu), strength=float(s))
        self.hyperpriors = out
        self.stats["eb_fits"] += 1
        return out

    # ------------------------------------------------------------- plumbing
    def adopt_posteriors(self, tenant_edges, post_alpha, post_beta,
                         **register_kw) -> list[int]:
        """Bulk-load a fleet calibration result (the
        ``MultiTenantReport.final_posterior_rows`` row layout) into the
        store: unknown keys register data-seeded, known keys get their
        values overwritten in one batched scatter."""
        post_alpha = np.asarray(post_alpha, np.float64)
        post_beta = np.asarray(post_beta, np.float64)
        rows: list[int] = []
        seen_ids: list[int] = []
        seen_vals: list = []
        for (tenant, edge), a, b in zip(tenant_edges, post_alpha, post_beta):
            key = (tenant, tuple(edge))
            i = self._keys.get(key)
            if i is None:
                i = self.register(
                    edge, tenant=tenant,
                    posterior=BetaPosterior(alpha=float(a), beta=float(b)),
                    **register_kw)
            else:
                seen_ids.append(i)
                seen_vals.append((float(a), float(b)))
            rows.append(i)
        if seen_ids:
            if self._post is None:
                dtype = ("float64" if jax.config.jax_enable_x64
                         else "float32")
                self.device_tables(dtype)
            self.set_rows(np.asarray(seen_ids), np.asarray(seen_vals))
        return rows

    def memory_stats(self) -> dict:
        """Host/device byte accounting for the EXPERIMENTS.md §Store
        memory-per-row table (SoA arrays only — Python-object registry
        overhead is reported separately as an estimate)."""
        host_arrays = (self._prior, self._cfg, self._bucket_of,
                       self._shelf_post, self._shelf_flags, self._shelf_roll,
                       self._shelved, self._slot_of, self._alive)
        host = int(sum(a.nbytes for a in host_arrays))
        per_row = int(sum(a.dtype.itemsize * int(np.prod(a.shape[1:]))
                          for a in host_arrays))
        dev = 0
        if self._post is not None:
            dev = int(self._post.dtype.itemsize * self._capacity * 5
                      + 4 * self._capacity * (2 + ROLL_COLS)
                      + 8 * 2 * self._capacity)   # logical_at + last_touch
        return {
            "logical_rows": self.n_rows,
            "alive_rows": self.n_alive,
            "resident_rows": self.n_resident,
            "shelved_rows": self.n_shelved,
            "host_soa_bytes": host,
            "host_soa_bytes_per_row": per_row,
            "device_table_bytes": dev,
            "capacity": self._capacity,
        }
