"""CPU tests of the readers of the program's own spans and counters.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

Each reader gets a synthetic context: a tracer holding hand-timed spans, a
registry holding lock waits, an executor delta, and the trace recorded on a
TPU v5e with spans placed over its idle gaps.  Each must also read nothing
from a program that records none of these.
"""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

from harness import cell, spans  # noqa: E402
from harness import trace as trace_mod  # noqa: E402
from repro.obs import Observability, Tracer  # noqa: E402

FIXTURE = BENCH / "tests" / "data" / "trace_small.xplane.pb"
MS = 1e-3


def ctx_of(obs, exec_delta=None, trace=None):
    return SimpleNamespace(dep=SimpleNamespace(service=SimpleNamespace(
        obs=obs)), exec_delta=exec_delta or {}, trace=trace)


def served_round(tr, t, host_ms, wait_ms, dominance_ms):
    """One round at ``t`` seconds: a dispatch whose phases take the given
    milliseconds, and an absorb with one dominance pass per entry."""
    rnd = tr.record_span("service.step_round", t, t + 1.0)
    solve = tr.record_span("service.solve", t, t + 0.5, parent=rnd)
    d = tr.record_span("exec.dispatch", t, t + 0.5, parent=solve)
    at = t
    for name, ms in zip(("exec.materialize", "exec.pack", "exec.launch",
                         "exec.wait", "exec.unpack"),
                        (host_ms[0], host_ms[1], wait_ms[0], wait_ms[1],
                         host_ms[2])):
        tr.record_span(name, at, at + ms * MS, parent=d)
        at += ms * MS
    absorb = tr.record_span("service.absorb", t + 0.6, t + 0.9, parent=rnd)
    for ms in dominance_ms:
        pf = tr.record_span("pf.absorb", t + 0.6, t + 0.7, parent=absorb)
        tr.record_span("store.dominance", t + 0.6, t + 0.6 + ms * MS,
                       parent=pf)


@pytest.fixture
def traced():
    obs = Observability(trace=True)
    tr = obs.tracer
    served_round(tr, 10.0, (100, 50, 20), (2, 8), (3, 4))
    served_round(tr, 20.0, (200, 30, 10), (1, 5), (6,))
    # a round with no dispatch (every session exhausted) is not a round
    # of the solve phase, and neither is a dispatch outside any round
    tr.record_span("service.step_round", 30.0, 30.1)
    tr.record_span("exec.dispatch", 40.0, 40.2)
    tr.record_span("exec.materialize", 40.0, 40.1)
    return obs


def read(name, ctx):
    return cell.metric_reader(name)(ctx)


def test_per_round_span_readers(traced):
    ctx = ctx_of(traced)
    assert read("exec_host_ms", ctx) == pytest.approx((170 + 240) / 2)
    assert read("exec_wait_ms", ctx) == pytest.approx((10 + 6) / 2)
    assert read("absorb_dominance_ms", ctx) == pytest.approx((7 + 6) / 2)


@pytest.mark.parametrize("name", ["exec_host_ms", "exec_wait_ms",
                                  "absorb_dominance_ms",
                                  "recommend_lock_wait_p99_ms",
                                  "transfers_per_tenant",
                                  "idle_in_exec_host_pct"])
def test_readers_read_nothing_from_a_program_without_them(name):
    """The parent's program: a bundle whose tracer recorded nothing, no
    lock-wait histograms, and an executor delta without the new keys."""
    obs = Observability()
    obs.tracer.record_span("service.step_round", 0.0, 1.0)
    base = {"dispatches": 3, "useful_rows": 10, "padded_rows": 16}
    assert read(name, ctx_of(obs, base, trace={"busy_s": 1.0})) is None
    no_obs = SimpleNamespace(dep=SimpleNamespace(service=SimpleNamespace()),
                             exec_delta=base, trace=None)
    assert read(name, no_obs) is None


def test_transfers_per_tenant_reads_the_window_delta():
    ctx = ctx_of(Observability(), {"requests": 8, "transfers": 388})
    assert read("transfers_per_tenant", ctx) == pytest.approx(48.5)
    assert read("transfers_per_tenant",
                ctx_of(Observability(), {"requests": 0,
                                         "transfers": 0})) is None


def test_recommend_lock_wait_p99_reads_its_histogram():
    obs = Observability(trace=True)
    h = obs.metrics.histogram("service.lock_wait_s",
                              {"service": "svc0", "op": "recommend"})
    other = obs.metrics.histogram("service.lock_wait_s",
                                  {"service": "svc0", "op": "absorb"})
    for i in range(100):
        h.record((i + 1) * 1e-4)
        other.record(1.0)
    assert read("recommend_lock_wait_p99_ms", ctx_of(obs)) == \
        pytest.approx(h.quantile(0.99) * 1e3)
    assert 9.8 < read("recommend_lock_wait_p99_ms", ctx_of(obs)) < 10.0


def _window_and_gaps():
    red = trace_mod.reduce(trace_mod.load(str(FIXTURE)))
    host = trace_mod.load(str(FIXTURE))["/host:CPU"]
    lo, hi = next((s, e) for evs in host.values() for n, s, e in evs
                  if n == trace_mod.WINDOW)
    return red, lo, hi


@pytest.mark.skipif(not FIXTURE.exists(), reason="fixture not recorded")
def test_idle_share_of_spans_over_the_chips_trace():
    red, lo, hi = _window_and_gaps()
    idle_s = red["window_s"] - red["busy_s"]
    names = ("exec.pack",)
    tr = Tracer(enabled=True)

    def ns(t):  # the tracer's seconds are the profile's ns / 1e9 here
        return t * 1e9

    whole = [tr.record_span("exec.pack", lo * 1e-9, hi * 1e-9)]
    assert spans.idle_share_in(str(FIXTURE), whole, ns, names) == \
        pytest.approx(100.0)
    assert spans.idle_share_in(str(FIXTURE), [], ns, names) == 0.0
    # the longest gap alone: a span a little wider than the gap counts
    # only the gap, and a span of another name counts nothing
    gap_s = red["idle_gaps"][0][1]
    gaps = []
    edge = lo
    busy = trace_mod._union(trace_mod._clip(
        [(s, e) for _n, s, e in
         trace_mod.load(str(FIXTURE))["/device:TPU:0"]["XLA Ops"]], lo, hi))
    for s, e in busy + [[hi, hi]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    g0, g1 = max(gaps, key=lambda g: g[1] - g[0])
    assert (g1 - g0) * 1e-9 == pytest.approx(gap_s)
    wide = [tr.record_span("exec.pack", (g0 - 1e3) * 1e-9,
                           (g1 + 1e3) * 1e-9),
            tr.record_span("exec.launch", lo * 1e-9, hi * 1e-9)]
    assert spans.idle_share_in(str(FIXTURE), wide, ns, names) == \
        pytest.approx(100.0 * gap_s / idle_s, rel=1e-3)


@pytest.mark.skipif(not FIXTURE.exists(), reason="fixture not recorded")
def test_idle_reader_maps_spans_by_the_anchors(monkeypatch):
    """The reader pairs the tracer's anchor with the profile's and maps an
    ``exec.materialize`` span through them onto the window."""
    import repro.obs

    red, lo, hi = _window_and_gaps()
    obs = Observability(trace=True)
    tr = obs.tracer
    tr._anchors.append(100.0)  # tracer clock at the profile's ``lo``
    # a materialize span over the whole window, in tracer seconds
    tr.record_span("exec.materialize", 100.0, 100.0 + (hi - lo) * 1e-9)
    monkeypatch.setattr(spans, "newest_trace_file", lambda: str(FIXTURE))
    monkeypatch.setattr(repro.obs, "profile_anchors", lambda path: [lo])
    ctx = ctx_of(obs, trace={"busy_s": red["busy_s"]})
    assert read("idle_in_exec_host_pct", ctx) == pytest.approx(100.0)
    # shifted past the window's end by the anchor, it covers no idle time
    tr._anchors[0] = 100.0 - (hi - lo) * 1e-9
    assert read("idle_in_exec_host_pct", ctx) == pytest.approx(0.0)
