"""Each fault a cell's timed path can have, planted in the program
underneath a tiny run on the CPU, must turn ``correct`` false:

* a step that returns its state unchanged;
* half of the batch left out;
* the exchange between chips left out (the segment handoff);
* an answer altered where it is produced, or a request altered on its
  way to the tick.
"""
import dataclasses
import types

import jax
import numpy as np
import pytest

from bench.tests._tiny import judged

SERVE = ["fleet1m.zipf.steady", "fleet1m.zipf.saturate"]


@pytest.fixture(autouse=True)
def fresh_executables():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", SERVE)
def test_serving_tick_state_unchanged(cell, monkeypatch):
    from repro.core.store import PosteriorStore

    orig = PosteriorStore.adopt

    def keep_posteriors(self, post, rowcfg, flags, roll):
        orig(self, self._post, rowcfg, flags, roll)

    monkeypatch.setattr(PosteriorStore, "adopt", keep_posteriors)
    ok, numbers = judged(cell, 3)
    assert not ok, numbers


def _wrap_tick(monkeypatch, before=None, after=None):
    from repro.core.online import OnlineDecisionService

    orig = OnlineDecisionService.tick_packed

    def tick(self, row, reqs, **kw):
        if before is not None:
            row = before(row, kw)
        d = orig(self, row, reqs, **kw)
        if after is not None:
            after(d)
        return d

    monkeypatch.setattr(OnlineDecisionService, "tick_packed", tick)


@pytest.mark.parametrize("cell", SERVE)
def test_serving_half_batch_left_out(cell, monkeypatch):
    def drop_half(row, kw):
        B = kw.get("batch") or int((row >= 0).sum())
        row = row.copy()
        row[B // 2:B] = -1
        return row

    _wrap_tick(monkeypatch, before=drop_half)
    ok, numbers = judged(cell, 4)
    assert not ok, numbers


@pytest.mark.parametrize("cell", SERVE)
def test_serving_answer_altered(cell, monkeypatch):
    def alter(d):
        rows = np.array(d._rows)
        rows[0, 4] *= 1.5            # the first lane's EV
        d._rows = rows

    _wrap_tick(monkeypatch, after=alter)
    ok, numbers = judged(cell, 5)
    assert not ok, numbers


@pytest.mark.parametrize("cell", SERVE)
def test_serving_request_mixed_up(cell, monkeypatch):
    """The frontend packs one request's alpha and lambda into each
    other's column: the tick answers what it was handed, so only the
    check of each ticket's request against its tick's columns sees it."""
    from repro.serving.frontend import ServingFrontend

    orig = ServingFrontend._pack

    def pack(self, batch):
        row, reqs, B = orig(self, batch)
        reqs[0, [0, 1]] = reqs[0, [1, 0]]
        return row, reqs, B

    monkeypatch.setattr(ServingFrontend, "_pack", pack)
    ok, numbers = judged(cell, 7)
    assert not ok, numbers


def test_replay_state_unchanged(monkeypatch):
    from repro.core import fleet

    orig = fleet._episode

    def frozen(*args):
        _, stats = orig(*args)
        return args[5], stats          # the carried posterior, unmoved

    monkeypatch.setattr(fleet, "_episode", frozen)
    ok, numbers = judged("autoreply.grid35", 6, seconds=1.0)
    assert not ok, numbers


def _wrap_report(monkeypatch, name, change):
    import repro.core as core

    orig = getattr(core, name)

    def engine(lowered, success, *a, **k):
        return change(orig, lowered, success, *a, **k)

    monkeypatch.setattr(core, name, engine)


def test_replay_half_batch_left_out(monkeypatch):
    def half(orig, lowered, success, *a, **k):
        E = success.shape[0]
        rep = orig(lowered, success[: E // 2], *a, **k)
        fields = {f: np.concatenate([getattr(rep, f)] * 2)[:E]
                  for f in ("makespan_s", "total_cost_usd", "waste_usd",
                            "launched", "committed", "cancelled", "EV_usd",
                            "threshold_usd", "speculate", "edge_launched",
                            "edge_committed", "edge_waste_usd", "start_s",
                            "finish_s", "post_alpha", "post_beta")}
        return types.SimpleNamespace(**fields)

    _wrap_report(monkeypatch, "fleet_replay", half)
    ok, numbers = judged("autoreply.grid35", 8, seconds=1.0)
    assert not ok, numbers


def test_replay_answer_altered(monkeypatch):
    def flip(orig, lowered, success, *a, **k):
        rep = orig(lowered, success, *a, **k)
        launched = np.array(rep.edge_launched)
        launched[0, 0, 1] = ~launched[0, 0, 1]
        return dataclasses.replace(rep, edge_launched=launched)

    _wrap_report(monkeypatch, "fleet_replay", flip)
    ok, numbers = judged("autoreply.grid35", 9, seconds=1.0)
    assert not ok, numbers


def _segments(config, workload):
    """The replay cell through the episode-sharded engine (8 segments,
    two-pass) on the one device a test has."""
    workload["traffic"].update(engine="episode_sharded_replay", segments=8,
                               pipelined=False)


def test_segments_handoff_left_out(monkeypatch):
    from repro.core import fleet

    orig = fleet._boundary_scan

    def no_handoff(static, post0, *args, **kw):
        starts, final = orig(static, post0, *args, **kw)
        return jax.numpy.broadcast_to(post0, starts.shape), final

    monkeypatch.setattr(fleet, "_boundary_scan", no_handoff)
    ok, numbers = judged("autoreply.grid35", 10, seconds=1.0,
                         extra=_segments)
    assert not ok, numbers


def test_segments_sound_on_one_device():
    ok, numbers = judged("autoreply.grid35", 11, seconds=1.0,
                         extra=_segments)
    assert ok, numbers
