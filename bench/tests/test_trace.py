"""The trace reduction on a small trace recorded on a TPU v5e
(``bench/tools/record_trace.py``): twenty ``_tick`` runs, each in a
``bench.tick`` span and followed by 2 ms of host work in a
``bench.submit`` span, inside one ``bench.traced`` stretch."""
import pathlib
import types

import numpy as np
import pytest

from bench import readers, trace

DATA = pathlib.Path(__file__).parent / "data" / "tick.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_file(DATA)


def test_one_device_and_a_window(summary):
    assert summary.devices == 1
    # twenty ticks and 40 ms of host spans at the least
    assert summary.window_s > 0.04


def test_busy_is_the_union_of_op_intervals(summary):
    assert 0 < summary.busy_s[0] < summary.window_s
    assert summary.busy_s[0] <= sum(summary.op_seconds.values()) + 1e-12


def test_tick_module_found_each_run(summary):
    sec, n = summary.module_seconds(readers.TICK_MODULE)
    # the profiler can miss the first execution after it starts
    assert 19 <= n <= 20
    # the tick is all the device ran: its module time is the busy time
    assert sec == pytest.approx(summary.busy_s[0], rel=0.01)
    ms = readers.tick_device_ms({"trace": summary})
    assert ms == pytest.approx(sec / n * 1e3)


def test_idle_gaps_go_to_the_host_span_covering_them(summary):
    gaps = summary.idle_gaps
    assert sum(gaps.values()) == pytest.approx(
        summary.window_s - summary.busy_s[0])
    # 20 x 2 ms of host work with the device idle
    assert gaps.get("bench.submit", 0.0) > 0.03
    assert max(gaps, key=gaps.get) == "bench.submit"


def test_idle_share_and_breakdown(summary):
    idle = readers.device_idle({"trace": summary})
    assert 0 < idle < 1
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert b["idle_gaps"][0][0] == "bench.submit"


def test_tick_module_name_as_the_chip_gives_it():
    import re

    rx = re.compile(readers.TICK_MODULE)
    assert rx.search("jit__tick_impl(7213679522426105753)")
    assert not rx.search("jit__scatter_rows(8876974672652303946)")
    assert not rx.search("jit__append_tel(16369412063173728438)")


def test_union_merges_overlaps():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace._clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]


def _ev(name, start_ns, duration_ns):
    return types.SimpleNamespace(name=name, start_ns=start_ns,
                                 duration_ns=duration_ns)


def _planes(window, modules=(), ops=()):
    """A host plane holding the traced stretch and one device plane, in
    the shape ``reduce_planes`` reads."""
    line = types.SimpleNamespace
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        line(name="python3", events=[_ev(trace.WINDOW_EVENT, *window)])])
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        line(name="XLA Modules", events=[_ev(*m) for m in modules]),
        line(name="XLA Ops", events=[_ev(*o) for o in ops])])
    return [host, dev]


def test_module_cut_by_the_stretch_is_not_counted():
    tick = "jit__tick_impl(1)"
    mods = [(tick, 50, 100), (tick, 200, 100), (tick, 380, 100)]
    s = trace.reduce_planes(_planes((100, 300), mods,
                                    [("op", 210, 80)]))
    sec, n = s.module_seconds(readers.TICK_MODULE)
    assert n == 1
    assert sec == pytest.approx(100e-9)
    assert readers.tick_device_ms({"trace": s}) == pytest.approx(100e-6)


def _loop_ops(iterations, t0=0):
    """A loop body of five operations, one of them run twice by an inner
    loop, 5 us each, 3 us between iterations: 28 us an iteration."""
    body = ["a", "inner", "b", "inner", "c"]
    ops, t = [], t0
    for _ in range(iterations):
        for name in body:
            ops.append((name, t, 4000))
            t += 5000
        t += 3000
    return ops


def test_loop_step_reads_one_iteration():
    ops = [(n, s * 1e-9) for n, s, _ in _loop_ops(50)]
    # the stretch opens in the middle of an iteration
    assert trace.loop_step(ops[2:]) == pytest.approx(28e-6)


def test_loop_step_none_without_a_loop():
    rng = np.random.default_rng(0)
    ops = [(f"op{rng.integers(20)}", i * 1e-6) for i in range(500)]
    assert trace.loop_step(ops) is None


def test_replay_device_time_per_episode_from_the_loop():
    ops = _loop_ops(200, t0=1_000_000)
    s = trace.reduce_planes(_planes((1_000_000 + 7000, 4_000_000), ops=ops))
    us = readers.replay_device_us_per_episode(
        {"trace": s, "episodes_per_step": 1})
    assert us == pytest.approx(28.0)
    # an engine whose scan step is not one episode reads nothing
    assert readers.replay_device_us_per_episode(
        {"trace": s, "episodes_per_step": None}) is None


def test_loop_step_an_inner_loop_does_not_shorten_the_iteration():
    """An operation an inner loop runs twice, evenly spaced, recurs every
    half iteration; the iteration is still the whole body."""
    ops, t = [], 0
    for _ in range(60):
        for name in ("inner", "a", "inner", "b"):
            ops.append((name, t * 1e-9))
            t += 1000
    assert trace.loop_step(ops) == pytest.approx(4000e-9)
