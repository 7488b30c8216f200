"""The reduction of the program's spans (``bench/program_spans.py``): on
hand-built planes of known shape, and on a small trace recorded on a
TPU v5e (``bench/tools/record_flush_trace.py``): a ``ServingFrontend``
batcher thread over a small paged ``OnlineDecisionService``."""
import pathlib
import re
import shutil
import types

import pytest

from bench import program_spans as ps
from bench import readers, trace

DATA = pathlib.Path(__file__).parent / "data" / "flush.xplane.pb"
US = 1000                                # ns


def _ev(name, start_us, end_us):
    return types.SimpleNamespace(name=name, start_ns=start_us * US,
                                 duration_ns=(end_us - start_us) * US)


def _planes(batcher, ops, window=(0, 100)):
    """A host plane with the stretch on one line and the batcher's spans
    on another, and one device plane with ``ops`` (start, end) in us."""
    line = types.SimpleNamespace
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        line(name="bench-trace", events=[_ev(trace.WINDOW_EVENT, *window)]),
        line(name="frontend-batcher", events=[_ev(*e) for e in batcher]),
        line(name="client", events=[_ev("bench.submit", 5, 95)])])
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        line(name="XLA Ops", events=[_ev("op", s, e) for s, e in ops])])
    return [host, dev]


# one whole flush (10-70 us) with every phase, and one cut by the
# stretch's end (80-110 us); the device busy 20-45 and 85-95 us
BATCHER = [
    ("frontend.wait_batch", 0, 10),
    ("frontend.flush", 10, 70),
    ("frontend.pack", 10, 14),
    ("bench.tick", 14, 52),
    ("service.tick", 14, 40),
    ("store.page_in", 16, 22),
    ("store.slot_map", 36, 38),
    ("service.fetch", 40, 48),
    ("service.drift_mask", 48, 50),
    ("frontend.answer", 52, 62),
    ("frontend.refresh_mirror", 62, 68),
    ("frontend.wait_batch", 70, 80),
    ("frontend.flush", 80, 110),
    ("frontend.pack", 80, 84),
]
OPS = [(20, 45), (85, 95)]


@pytest.fixture(scope="module")
def hand():
    return ps.reduce_planes(_planes(BATCHER, OPS))


@pytest.mark.parametrize("phase,us", [
    ("frontend_host", 2 + 4 + 10),        # flush self + pack + answer
    ("service_host", 18 + 2),             # service.tick self + drift_mask
    ("page_in", 6 + 2),
    ("device_wait", 8),
    ("refresh_mirror", 6),
])
def test_phases_of_the_whole_flush(hand, phase, us):
    fl = hand.flushes()
    assert len(fl) == 1                   # the cut flush is left out
    assert fl[0][phase] == pytest.approx(us * 1e-6)


def test_phases_and_the_proxy_add_up_to_the_flush(hand):
    # bench.tick's own 2 us is the rest
    assert sum(hand.flushes()[0].values()) == pytest.approx(58e-6)


def test_metrics_read_medians_in_ms(hand):
    assert ps.QUANTITIES["device_wait_ms"](hand) == pytest.approx(8e-3)
    assert ps.QUANTITIES["frontend_host_ms"](hand) == pytest.approx(16e-3)


def test_idle_with_the_batcher_in_host_work(hand):
    # idle 0-20, 45-85, 95-100 us; waiting for a batch (0-10, 70-80) and
    # blocked in service.fetch (45-48) are left out
    host = 4 + 2 + 4 + 2 + 2 + 10 + 6 + 2 + 4 + 1 + 5
    assert hand.idle_host_share() == pytest.approx(host / 100)
    s = trace.reduce_planes(_planes(BATCHER, OPS))
    assert hand.idle_host_share() <= readers.device_idle({"trace": s})


def test_idle_put_down_to_each_thread_span(hand):
    by = hand.idle_by_span()
    batcher = next(v for k, v in by.items() if k.endswith("batcher"))
    assert batcher["frontend.wait_batch"] == pytest.approx(20e-6)
    assert batcher["service.fetch"] == pytest.approx(3e-6)
    assert batcher["bench.tick"] == pytest.approx(2e-6)
    assert batcher["-"] == pytest.approx(0.0, abs=1e-12)
    client = next(v for k, v in by.items() if k.endswith("client"))
    # idle 65 us, of which 10 us lie outside the client's 5-95 us span
    assert client["bench.submit"] == pytest.approx(55e-6)
    assert client["-"] == pytest.approx(10e-6)


def test_a_gap_under_wait_batch_alone_is_not_host_work():
    planes = _planes([("frontend.wait_batch", 0, 50),
                      ("frontend.flush", 50, 60)], [(50, 60)])
    assert ps.reduce_planes(planes).idle_host_share() == pytest.approx(0.0)


def test_a_stretch_with_no_flush_reads_none():
    planes = _planes([("frontend.wait_batch", 0, 100)], OPS)
    assert ps.reduce_planes(planes) is None
    # a flush outside the stretch gives no whole flush to read
    t = ps.reduce_planes(_planes([("frontend.flush", 90, 120)], OPS))
    assert ps.QUANTITIES["page_in_ms"](t) is None


def test_nest_and_self_time():
    roots = ps.nest([("a", 0, 10), ("b", 2, 4), ("c", 4, 10), ("d", 10, 12)])
    assert [r.name for r in roots] == ["a", "d"]
    assert [c.name for c in roots[0].children] == ["b", "c"]
    assert roots[0].self_time == 2


# -- the chip trace

@pytest.fixture(scope="module")
def chip():
    return ps.reduce_file(DATA)


def test_chip_trace_has_whole_flushes(chip):
    fl = chip.flushes()
    assert len(fl) >= 5
    for f in fl:
        assert f["device_wait"] > 0 and f["service_host"] > 0
        assert f["page_in"] > 0 and f["frontend_host"] > 0
    s = trace.reduce_file(DATA)
    assert 0 < chip.idle_host_share() <= readers.device_idle({"trace": s})


def test_fetch_ends_after_its_tick_module_on_the_chip():
    """The program's spans and the device share a clock: the first fetch
    of a flush blocks until the tick's executable has run, so each
    ``service.fetch`` of a flush ends after that tick's module ends."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(DATA)).planes)
    rx = re.compile(readers.TICK_MODULE)
    mods = sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns)
        for p in planes if trace._DEVICE_PLANE.match(p.name)
        for line in p.lines if line.name == trace._MODULES_LINE
        for ev in line.events if rx.search(ev.name))
    t = ps.reduce_planes(planes)
    checked = 0
    for r in t.batcher():
        for f in r.walk():
            if f.name != ps.FLUSH:
                continue
            tick = next(sp for sp in f.walk() if sp.name == "service.tick")
            ran = [m for m in mods if tick.start <= m[0] <= f.end]
            if not ran:
                continue                  # its module fell outside the trace
            fetches = [sp for sp in f.walk() if sp.name == ps.FETCH]
            assert fetches
            for sp in fetches:
                assert sp.end >= ran[0][1]
            checked += 1
    assert checked >= 5


def test_cell_reader_takes_the_runs_own_trace(tmp_path, monkeypatch):
    cell = "fleet1m.zipf.steady"
    d = tmp_path / "trace" / cell / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(DATA, d / "host.xplane.pb")
    monkeypatch.setattr(ps, "OUT", tmp_path)
    read = ps.reader("bench/metrics/device_wait_ms.steady.py")
    s = trace.reduce_file(DATA)
    assert read({"trace": s}) > 0
    # a stretch that is not this trace's, or no run summary: nothing
    other = types.SimpleNamespace(window=(s.window[0] + 1, s.window[1]))
    assert read({"trace": other}) is None
    assert read({"trace": None}) is None
