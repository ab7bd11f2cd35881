"""Observability plane tests (DESIGN.md §14).

Covers the typed metrics registry (identity, exports, merge, thread
safety under concurrent writers), the tracer (no-op fast path, explicit
parent chains, Chrome export shape, anchors onto a JAX profile's clock),
the dispatch plane's transfer counters, the service-lock wait
histograms, the per-ticket latency attribution invariant (phase
breakdown sums to end-to-end latency), and the counter-drift regression
for the dispatch exception path.
"""

from __future__ import annotations

import json
import math
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import MOGDConfig
from repro.core.synthetic import mlp_surrogate_task
from repro.exec import ProbeExecutor
from repro.frontdesk import FrontDesk
from repro.obs import (
    NOOP_SPAN,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Observability,
    Tracer,
    profile_anchors,
)
from repro.obs import trace as trace_mod
from repro.service import MOOService

FAST = MOGDConfig(steps=12, multistart=2)


# -- metrics: instruments + registry ---------------------------------------

class TestInstruments:
    def test_counter_monotone_and_rejects_negative(self):
        c = Counter("c")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_moves_both_ways(self):
        g = Gauge("g")
        g.inc(3)
        g.dec()
        assert g.value == 2
        g.set(-7.5)
        assert g.value == -7.5

    def test_histogram_matches_numpy_quantiles(self):
        h = Histogram("h")
        rng = np.random.default_rng(0)
        vals = rng.lognormal(mean=-5, sigma=1.5, size=1000)
        for v in vals:
            h.record(float(v))
        assert len(h) == 1000
        for q in (0.5, 0.95, 0.99):
            assert h.quantile(q) == pytest.approx(
                float(np.quantile(vals, q)), rel=1e-9)
        s = h.summary()
        assert s["count"] == 1000
        assert s["mean_s"] == pytest.approx(float(vals.mean()))
        assert s["p95_s"] == h.p95

    def test_histogram_empty_summary_is_nan(self):
        s = Histogram("h").summary()
        assert s["count"] == 0
        assert math.isnan(s["p50_s"]) and math.isnan(s["max_s"])

    def test_histogram_export_buckets(self):
        h = Histogram("h")
        for v in (1e-4, 1e-2, 1.0, 1e4):  # last lands in overflow
            h.record(v)
        out = h.histogram(n_buckets=24, lo_s=1e-5, hi_s=100.0)
        assert len(out["edges_s"]) == 24
        assert len(out["counts"]) == 25
        assert sum(out["counts"]) == 4
        assert out["counts"][-1] == 1  # the 1e4 overflow

    def test_histogram_truncation_keeps_exact_count_sum(self):
        h = Histogram("h", max_samples=100)
        for i in range(1000):
            h.record(1e-3 * (1 + i % 7))
        assert h.count == 1000
        q = h.quantile(0.5)  # bucket interpolation path
        assert 1e-3 <= q <= 8e-3
        assert h.summary()["count"] == 1000

    def test_merge_accumulates(self):
        a, b = Histogram("h"), Histogram("h")
        for v in (0.01, 0.02):
            a.record(v)
        b.record(0.04)
        a.merge(b)
        assert a.count == 3
        assert a.sum == pytest.approx(0.07)


class TestRegistry:
    def test_get_or_create_identity_by_name_and_labels(self):
        m = MetricsRegistry()
        c1 = m.counter("x", {"a": "1"})
        c2 = m.counter("x", {"a": "1"})
        c3 = m.counter("x", {"a": "2"})
        assert c1 is c2 and c1 is not c3

    def test_kind_mismatch_raises(self):
        m = MetricsRegistry()
        m.counter("x")
        with pytest.raises(TypeError):
            m.gauge("x")

    def test_snapshot_and_json(self):
        m = MetricsRegistry()
        m.counter("reqs", {"plane": "p0"}).inc(3)
        m.histogram("lat").record(0.5)
        snap = m.snapshot()
        assert snap["reqs{plane=p0}"]["value"] == 3
        assert snap["lat"]["count"] == 1
        assert json.loads(m.to_json())  # strictly valid

    def test_prometheus_format(self):
        m = MetricsRegistry()
        m.counter("reqs_total", {"plane": "p0"}, help="requests").inc(2)
        m.histogram("lat").record(0.01)
        text = m.to_prometheus()
        assert "# TYPE reqs_total counter" in text
        assert 'reqs_total{plane="p0"} 2' in text
        assert 'le="+Inf"' in text
        assert "lat_count 1" in text

    def test_concurrent_hammer_snapshots_consistent(self):
        """N writer threads inc + record while the main thread
        snapshots: every snapshot must be internally consistent (the
        histogram count equals the counter value at the same moment —
        both mutate under one registry lock per writer iteration is NOT
        guaranteed, so assert monotonicity + exact final totals)."""
        m = MetricsRegistry()
        n_threads, n_iters = 4, 2000
        c = m.counter("ops")
        h = m.histogram("lat")
        stop = threading.Event()

        def writer():
            for i in range(n_iters):
                h.record(1e-3)
                c.inc()

        threads = [threading.Thread(target=writer) for _ in range(n_threads)]
        for t in threads:
            t.start()
        seen_c, seen_h = [], []
        while any(t.is_alive() for t in threads):
            snap = m.snapshot()
            seen_c.append(snap["ops"]["value"])
            seen_h.append(snap["lat"]["count"])
        for t in threads:
            t.join()
        stop.set()
        # monotone reads, never exceeding the true total
        total = n_threads * n_iters
        assert all(b >= a for a, b in zip(seen_c, seen_c[1:]))
        assert all(b >= a for a, b in zip(seen_h, seen_h[1:]))
        assert all(v <= total for v in seen_c + seen_h)
        final = m.snapshot()
        assert final["ops"]["value"] == total
        assert final["lat"]["count"] == total
        assert final["lat"]["sum"] == pytest.approx(total * 1e-3)


# -- tracer ----------------------------------------------------------------

class TestTracer:
    def test_disabled_returns_shared_noop(self):
        tr = Tracer(enabled=False)
        sp = tr.span("x")
        assert sp is NOOP_SPAN and not sp.enabled
        with sp:
            sp.set("k", 1)  # all no-ops
        assert len(tr) == 0
        assert tr.record_span("x", 0.0, 1.0) is None
        assert tr.now() == 0.0

    def test_nesting_parent_chain(self):
        tr = Tracer(enabled=True)
        with tr.span("outer") as outer:
            with tr.span("inner", parent=outer) as inner:
                pass
        spans = tr.spans()
        assert [s.name for s in spans] == ["inner", "outer"]
        assert spans[0].parent_id == outer.span_id
        assert spans[1].parent_id is None

    def test_record_span_retroactive(self):
        tr = Tracer(enabled=True)
        t0 = tr.now()
        t1 = tr.now()
        sp = tr.record_span("x", t0, t1, args={"a": 1})
        assert sp.t0 == t0 and sp.t1 == t1
        assert tr.spans()[0].args["a"] == 1

    def test_ring_buffer_bounds_memory(self):
        tr = Tracer(enabled=True, max_spans=10)
        for i in range(25):
            tr.span(f"s{i}").end()
        assert len(tr) == 10
        assert tr.spans()[0].name == "s15"

    def test_error_annotation_on_exception(self):
        tr = Tracer(enabled=True)
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        assert tr.spans()[0].args["error"] == "RuntimeError"

    def test_chrome_trace_shape_and_containment(self):
        tr = Tracer(enabled=True)
        with tr.span("parent") as p:
            with tr.span("child", parent=p):
                pass
        doc = tr.chrome_trace()
        assert doc["displayTimeUnit"] == "ms"
        evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert len(evs) == 2 and metas
        by_name = {e["name"]: e for e in evs}
        par, chi = by_name["parent"], by_name["child"]
        assert chi["args"]["parent_id"] == par["args"]["span_id"]
        # child interval nests inside the parent interval
        assert chi["ts"] >= par["ts"]
        assert chi["ts"] + chi["dur"] <= par["ts"] + par["dur"] + 1e-6
        json.dumps(doc)  # serializable

    def test_export_chrome_writes_loadable_json(self, tmp_path):
        tr = Tracer(enabled=True)
        tr.span("x").end()
        path = tr.export_chrome(tmp_path / "trace.json")
        doc = json.loads(open(path).read())
        assert any(e["name"] == "x" for e in doc["traceEvents"])

    def test_child_spans_follow_their_parent(self):
        tr = Tracer(enabled=True)
        with tr.span("outer") as outer:
            with outer.child("inner", cat="c", args={"n": 2}) as inner:
                pass
        assert inner.parent_id == outer.span_id and inner.args == {"n": 2}
        assert NOOP_SPAN.child("x") is NOOP_SPAN

    def test_disabled_anchor_reads_no_clock(self):
        def clock():
            raise AssertionError("a disabled tracer read its clock")

        tr = Tracer(enabled=False, clock=clock)
        assert tr.anchor() is None and tr.anchors() == []
        assert tr.now() == 0.0

    def test_profile_clock_pairs_anchors_in_order(self):
        ticks = iter([10.0, 30.0])
        tr = Tracer(enabled=True, clock=lambda: next(ticks))
        tr.anchor()
        tr.anchor()
        # the profile ran 1e-6 fast against the tracer between anchors
        to_ns = tr.profile_clock([5e9, 25e9 + 20e3])
        assert to_ns(10.0) == 5e9
        assert to_ns(20.0) == pytest.approx(15e9 + 10e3)
        with pytest.raises(ValueError):
            Tracer(enabled=True).profile_clock([1.0])
        tr.clear()
        assert tr.anchors() == []

    def test_anchor_maps_spans_onto_a_cpu_profile(self, tmp_path):
        """The anchor's profiler event and the tracer's reading inside it
        put a span opened around a TraceAnnotation onto that annotation,
        within 1 ms, at both ends."""
        import jax

        tr = Tracer(enabled=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            tr.anchor()
            time.sleep(0.02)
            with tr.span("work") as sp:
                with jax.profiler.TraceAnnotation("work.annotation"):
                    time.sleep(0.005)
            time.sleep(0.02)
            tr.anchor()
        finally:
            jax.profiler.stop_trace()
        anchors = profile_anchors(str(tmp_path))
        assert len(anchors) == 2
        ann = [(e.start_ns, e.start_ns + e.duration_ns)
               for plane in jax.profiler.ProfileData.from_file(
                   str(next(tmp_path.rglob("*.xplane.pb")))).planes
               for line in plane.lines for e in line.events
               if e.name == "work.annotation"]
        assert len(ann) == 1
        a0, a1 = ann[0]
        to_ns = tr.profile_clock(anchors)
        assert abs(to_ns(sp.t0) - a0) < 1e6
        assert abs(to_ns(sp.t1) - a1) < 1e6
        doc = tr.chrome_trace(profile_anchors_ns=anchors)
        ev = next(e for e in doc["traceEvents"] if e["name"] == "work")
        assert abs(ev["ts"] - a0 * 1e-3) < 1e3
        assert abs(ev["ts"] + ev["dur"] - a1 * 1e-3) < 1e3


# -- stats() surfaces stay views over the registry -------------------------

class TestStatsViews:
    def test_service_and_executor_stats_keys(self):
        svc = MOOService(mogd=FAST, batch_rects=1, grid_l=2)
        for key in ("sessions", "in_flight_probes", "in_flight_dispatches",
                    "solver_cache_hits", "coalesced_batches",
                    "vault_restores"):
            assert key in svc.stats()
        ex = svc.executor.stats()
        for key in ("dispatches", "probes", "compiles", "useful_rows",
                    "fill_ratio", "dispatch_origins"):
            assert key in ex
        # the whole stack shares ONE registry
        assert svc.executor.obs.metrics is svc.obs.metrics

    def test_service_adopts_a_given_executors_bundle(self):
        ex = ProbeExecutor(mesh=None)
        svc = MOOService(mogd=FAST, batch_rects=1, grid_l=2, executor=ex,
                         mesh=None)
        assert svc.obs is ex.obs
        assert FrontDesk(svc, capacity=4).obs is ex.obs
        own = Observability()
        assert MOOService(mogd=FAST, executor=ex, mesh=None,
                          obs=own).obs is own

    def test_registry_backs_legacy_int_surface(self):
        svc = MOOService(mogd=FAST, batch_rects=1, grid_l=2)
        sid = svc.create_session(mlp_surrogate_task(seed=0))
        svc.step_sessions([sid], origin="test")
        ex = svc.executor
        assert ex.dispatches >= 1
        assert ex.dispatch_origins.get("test", 0) >= 1
        snap = svc.obs.metrics.snapshot()
        key = next(k for k in snap if k.startswith("exec.dispatches{"))
        assert snap[key]["value"] == ex.dispatches


# -- dispatch plane: transfer counters -------------------------------------

class TestTransferCounters:
    @staticmethod
    def _requests(ex, sizes, starts):
        from repro.core.mogd import MOGDSolver

        reqs = []
        for i, B in enumerate(sizes):
            # compiled afresh: programs from as_problem's cache may hold
            # host copies made by earlier tests
            solver = MOGDSolver(mlp_surrogate_task(seed=i).compile(),
                                FAST, executor=ex)
            D, k = solver.problem.dim, solver.problem.k
            x0s = starts(i, (B, FAST.multistart, D))
            reqs.append(solver._request(x0s, np.zeros((B, k)),
                                        np.ones((B, k)), 0))
        return reqs

    def test_two_request_dispatch_counts_exactly(self):
        """Two coalesced requests, host-side starts and boxes, parameters
        on the device: host data is not counted until the launch, each
        program's parameters are read back once into its host copy, and
        the results cross back once per dispatch.  A second identical
        dispatch reads no parameters."""
        from repro.exec.executor import N_ROW_FIELDS

        ex = ProbeExecutor(mesh=None)
        sizes = (3, 5)
        reqs = self._requests(
            ex, sizes, lambda i, shape: np.random.default_rng(i).random(
                shape))
        leaves = jax.tree.leaves(reqs[0].program.params)
        assert all(not isinstance(a, np.ndarray) for a in leaves)
        L = len(leaves)
        P = sum(int(a.size) * 4 for a in leaves)
        S, D, k = FAST.multistart, reqs[0].x0s.shape[-1], 2
        x, f, feas = ex.solve_requests(reqs)
        assert len(x) == sum(sizes)
        st = ex.stats()
        assert st["requests"] == 2 and st["dispatches"] == 1
        assert st["host_param_misses"] == 2 and st["host_param_hits"] == 0
        Gp, Rp = ex.last_bucket
        assert (Gp, Rp) == (2, 8)
        # per dispatch: the packed parameters and 8 row fields to the
        # device, then x, f and feasible back
        assert N_ROW_FIELDS == 8
        per_dispatch = (L + 8) + 3
        launch_bytes = Gp * P + Gp * Rp * (S * D + 6 * k + 1) * 4
        unpack_bytes = Gp * Rp * ((D + k) * 4 + 1)
        # per program, once: its L parameter leaves read back
        assert st["transfers"] == 2 * L + per_dispatch
        assert st["transfer_bytes"] == 2 * P + launch_bytes + unpack_bytes

        x2, f2, feas2 = ex.solve_requests(reqs)
        st2 = ex.stats()
        assert st2["host_param_misses"] == 2 and st2["host_param_hits"] == 2
        assert st2["transfers"] - st["transfers"] == per_dispatch
        assert (st2["transfer_bytes"] - st["transfer_bytes"]
                == launch_bytes + unpack_bytes)
        np.testing.assert_array_equal(x2, x)
        np.testing.assert_array_equal(f2, f)
        np.testing.assert_array_equal(feas2, feas)

    def test_device_starts_are_read_back_once_per_request(self):
        """Starts drawn on the device (as ``solve_grouped`` draws them)
        cost one read-back per request, at their float32 size."""
        ex = ProbeExecutor(mesh=None)
        sizes = (3, 5)
        reqs = self._requests(
            ex, sizes, lambda i, shape: jax.random.uniform(
                jax.random.PRNGKey(i), shape))
        leaves = jax.tree.leaves(reqs[0].program.params)
        L = len(leaves)
        P = sum(int(a.size) * 4 for a in leaves)
        S, D, k = FAST.multistart, reqs[0].x0s.shape[-1], 2
        ex.solve_requests(reqs)
        st = ex.stats()
        Gp, Rp = ex.last_bucket
        assert st["transfers"] == 2 + 2 * L + (L + 8) + 3
        assert st["transfer_bytes"] == (
            sum(B * S * D * 4 for B in sizes) + 2 * P
            + Gp * P + Gp * Rp * (S * D + 6 * k + 1) * 4
            + Gp * Rp * ((D + k) * 4 + 1))


# -- end-to-end: request-path trace + latency attribution ------------------

@pytest.mark.slow
class TestServingTrace:
    def _stack(self):
        obs = Observability(trace=True)
        svc = MOOService(mogd=FAST, batch_rects=2, grid_l=2, obs=obs)
        return obs, svc

    def test_span_taxonomy_and_breakdown_sums(self):
        obs, svc = self._stack()
        desk = FrontDesk(svc, capacity=16)
        assert desk.obs is obs  # plane adopts the service bundle
        tickets = [desk.submit(spec=mlp_surrogate_task(seed=i),
                               n_probes=8, slo="standard")
                   for i in range(3)]
        for _ in range(50):
            desk.poll()
            if all(t.done for t in tickets):
                break
        assert all(t.ok for t in tickets)

        # -- attribution: phases sum to end-to-end, on the plane clock
        for t in tickets:
            b = t.breakdown()
            assert b["e2e_s"] is not None
            assert b["accounted_s"] == pytest.approx(b["e2e_s"],
                                                     abs=1e-6)
            assert all(b[k] >= 0.0 for k in
                       ("queue_wait_s", "batch_wait_s", "dispatch_s",
                        "absorb_s", "persist_s"))
            assert b["dispatch_s"] > 0.0  # real solves ran

        # -- taxonomy: the request path appears, correctly nested
        spans = {s.span_id: s for s in obs.tracer.spans()}
        names = {s.name for s in spans.values()}
        exec_phases = {"exec.materialize", "exec.pack", "exec.launch",
                       "exec.wait", "exec.unpack"}
        assert {"frontdesk.admit", "frontdesk.schedule",
                "frontdesk.dispatch", "service.step_round",
                "service.prepare", "service.solve", "service.absorb",
                "exec.dispatch", "pf.absorb", "store.dominance"
                } | exec_phases <= names

        def parents_of(name):
            out = set()
            for s in spans.values():
                if s.name == name and s.parent_id in spans:
                    out.add(spans[s.parent_id].name)
            return out

        assert parents_of("service.step_round") == {"frontdesk.dispatch"}
        assert parents_of("service.solve") == {"service.step_round"}
        assert parents_of("exec.dispatch") <= {"service.solve"}
        assert "service.solve" in parents_of("exec.dispatch")
        for name in exec_phases | {"exec.compile"}:
            assert parents_of(name) <= {"exec.dispatch"}, name
        assert parents_of("service.absorb") == {"service.step_round"}
        assert parents_of("pf.absorb") == {"service.absorb"}
        assert parents_of("store.dominance") <= {"pf.absorb"}
        assert "pf.absorb" in parents_of("store.dominance")
        # each pass says where it ran (host numpy: these stores run no
        # kernel), and stats() sums the sessions' store counters
        dom = [s for s in spans.values() if s.name == "store.dominance"]
        assert {s.args["path"] for s in dom} == {"host"}
        assert all(s.args["offers"] >= 1 for s in dom)
        st = svc.stats()
        stores = [s.state.store for s in svc._sessions.values()]
        assert st["host_passes"] == sum(s.host_passes for s in stores)
        assert st["host_passes"] >= len(dom) > 0
        # each served dispatch's phases sit inside it, one of each
        for d in (s for s in spans.values() if s.name == "exec.dispatch"
                  and s.parent_id in spans):
            kids = [s.name for s in spans.values() if s.parent_id == d.span_id]
            assert sorted(set(kids) - {"exec.compile"}) == sorted(
                exec_phases)
            assert len(kids) == len(set(kids))
        # the lock waits of the traced round were recorded
        waits = {i.labels["op"]: i.count for i in
                 obs.metrics.instruments("service.lock_wait_s")}
        assert waits["prepare"] >= 1 and waits["absorb"] >= 1
        # every child interval nests inside its parent's
        for s in spans.values():
            if s.parent_id in spans:
                p = spans[s.parent_id]
                assert s.t0 >= p.t0 - 1e-9
                assert s.t1 <= p.t1 + 1e-9

        # the stats() latency view carries the recorded phases
        lat = desk.stats()["latency"]
        assert lat["e2e_s"]["count"] == 3
        assert lat["dispatch_s"]["count"] == 3

    def test_breakdown_without_tracing(self):
        """Attribution is metrics-path, not tracing-path: it must hold
        with the tracer disabled (the default)."""
        svc = MOOService(mogd=FAST, batch_rects=2, grid_l=2)
        desk = FrontDesk(svc, capacity=16)
        t = desk.submit(spec=mlp_surrogate_task(seed=0), n_probes=8)
        for _ in range(50):
            desk.poll()
            if t.done:
                break
        assert t.ok and len(svc.obs.tracer) == 0
        b = t.breakdown()
        assert b["accounted_s"] == pytest.approx(b["e2e_s"], abs=1e-6)

    def test_disabled_tracer_reads_no_clock_and_allocates_no_span(
            self, monkeypatch):
        """The dispatch phases, the absorb spans and the lock-wait
        brackets cost a disabled tracer no clock read and no span."""
        def clock():
            raise AssertionError("a disabled tracer read its clock")

        def no_span(*a, **kw):
            raise AssertionError("a disabled tracer allocated a span")

        monkeypatch.setattr(trace_mod.Span, "__init__", no_span)
        obs = Observability(tracer=Tracer(enabled=False, clock=clock))
        svc = MOOService(mogd=FAST, batch_rects=2, grid_l=2, obs=obs)
        sid = svc.create_session(mlp_surrogate_task(seed=0))
        for _ in range(2):
            svc.step_sessions([sid], origin="test")
        assert svc.recommend(sid).frontier_size >= 1
        assert len(obs.tracer) == 0 and obs.tracer.anchor() is None
        assert all(i.count == 0 for i in
                   obs.metrics.instruments("service.lock_wait_s"))
        assert svc.executor.stats()["transfers"] > 0  # counters stay on

    def test_recommend_lock_wait_recorded_while_tracing(self):
        obs = Observability(trace=True)
        svc = MOOService(mogd=FAST, batch_rects=2, grid_l=2, obs=obs)
        sid = svc.create_session(mlp_surrogate_task(seed=0))
        svc.step_sessions([sid], origin="test")
        for _ in range(3):
            svc.recommend(sid)
        rec = [i for i in obs.metrics.instruments("service.lock_wait_s")
               if i.labels["op"] == "recommend"]
        assert len(rec) == 1 and rec[0].count == 3
        assert 0.0 <= rec[0].quantile(0.99) < 1.0


# -- counter drift on the dispatch exception path --------------------------

@pytest.mark.slow
class TestCounterDrift:
    def test_failed_dispatch_restores_baseline(self):
        svc = MOOService(mogd=FAST, batch_rects=2, grid_l=2)
        desk = FrontDesk(svc, capacity=16)
        t0 = desk.submit(spec=mlp_surrogate_task(seed=0), n_probes=8)
        for _ in range(50):
            desk.poll()
            if t0.done:
                break
        assert t0.ok
        ex = svc.executor
        base_ex = {"dispatches": ex.dispatches,
                   "compiles": ex.total_compiles}
        base_svc = svc.stats()

        orig = ex.solve_requests

        def boom(requests, origin=None, **kw):
            raise RuntimeError("mid-flight device failure")

        ex.solve_requests = boom
        t1 = desk.submit(session_id=t0.session_id, n_probes=8,
                         slo="batch")
        desk.poll()
        assert t1.state == "error"
        ex.solve_requests = orig

        st = svc.stats()
        # in-flight gauges wound back by the exception path
        assert st["in_flight_dispatches"] == 0
        assert st["in_flight_probes"] == 0
        # restore() pushes back the prepared grid cells (they partition
        # the popped rectangles, preserving uncertain volume), so the
        # queue is non-empty — nothing was lost to the failed dispatch
        assert st["queue_depth"] >= base_svc["queue_depth"]
        # the failed round never reached the executor's counters
        assert ex.dispatches == base_ex["dispatches"]
        assert ex.total_compiles == base_ex["compiles"]
        assert desk.stats()["dispatch_errors"] == 1
        # and the plane still serves: the next round succeeds
        t2 = desk.submit(session_id=t0.session_id, n_probes=8,
                         slo="batch")
        for _ in range(50):
            desk.poll()
            if t2.done:
                break
        assert t2.ok
        assert svc.stats()["in_flight_dispatches"] == 0
