"""Set-up, warm-up and the open-loop measured window.

The window drives the system's own entry points and nothing else:
``FrontDesk.submit`` for tickets and ``MOOService.recommend`` for reads.
Around them the benchmark keeps three taps, installed on the instances it
built:

* ``Recorder`` wraps ``ProbeExecutor.solve_requests`` and keeps, for every
  dispatch, each tenant's starts, boxes, targets and results, so that the
  plain reference can recompute them once the window has closed;
* ``RoundLog`` wraps ``MOOService.step_sessions`` and keeps each round's
  measured ``timing``;
* in a traced run both, ``submit``, ``recommend`` and the PF engine's
  phases also record host spans (``HostSpans``), which the trace reduction
  puts on the profiler's clock beside the device's operations.

Every latency is timed from the request's due time on ``time.monotonic``,
the front desk's own clock, so a late generator shows as latency and its
lateness is reported apart.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

import numpy as np


class HostSpans:
    """Named host intervals on ``time.perf_counter_ns``, from any thread.

    The profiler records ``TraceAnnotation`` spans of the main thread only,
    so the spans of the front desk's dispatcher and of the load threads are
    kept here and aligned to the trace by the window's own span."""

    def __init__(self):
        self.on = False
        self.rows: list[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.on:
            yield
            return
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.rows.append((name, t0, time.perf_counter_ns()))


class Recorder:
    """Keeps every dispatch's per-tenant inputs and results."""

    def __init__(self, dep, spans: HostSpans):
        self.phase = "setup"
        self.calls: list[dict] = []
        ex = dep.executor
        orig = ex.solve_requests
        by_program = dep.by_program

        def solve_requests(requests, origin=None, parent_span=None):
            requests = list(requests)
            with spans("exec.solve_requests"):
                if parent_span is not None:
                    out = orig(requests, origin=origin,
                               parent_span=parent_span)
                else:
                    out = orig(requests, origin=origin)
            x, f, feas = out
            off, phase, t = 0, self.phase, time.monotonic()
            for r in requests:
                B = int(np.shape(r.x0s)[0])
                self.calls.append(dict(
                    phase=phase, t=t,
                    tenant=by_program.get(id(r.program)),
                    x0s=r.x0s, los=np.asarray(r.los), his=np.asarray(r.his),
                    targets=np.asarray(r.targets),
                    alphas=None if r.alphas is None else np.asarray(r.alphas),
                    x=x[off:off + B], f=f[off:off + B],
                    feas=feas[off:off + B]))
                off += B
            return out

        ex.solve_requests = solve_requests


class RoundLog:
    """Keeps each ``step_sessions`` round of the window: start, end and its
    ``timing``; and the start and end of every round of the process, so
    that a round the window's close cuts still counts up to the close."""

    def __init__(self, dep, spans: HostSpans):
        self.rounds: list[dict] = []
        self.intervals: list[tuple[float, float]] = []
        self.on = False
        svc = dep.service
        orig = svc.step_sessions

        def step_sessions(session_ids, *a, **kw):
            t0 = time.monotonic()
            with spans("service.step_round"):
                out = orig(session_ids, *a, **kw)
            self.intervals.append((t0, time.monotonic()))
            if self.on:
                self.rounds.append(dict(t0=t0, t1=time.monotonic(),
                                        sessions=out["sessions"],
                                        probes=out["probes"],
                                        timing=dict(out["timing"])))
            return out

        svc.step_sessions = step_sessions


def annotate_engine(spans: HostSpans) -> None:
    """Host spans around the PF engine's phases (traced runs only: the
    wrappers stay on the class for the rest of the process)."""
    from repro.core.progressive_frontier import ProgressiveFrontier as PF

    for name in ("initialize", "prepare_parallel", "absorb"):
        orig = getattr(PF, name)

        def wrapped(self, *a, __orig=orig, __name=name, **kw):
            with spans(f"pf.{__name}"):
                return __orig(self, *a, **kw)

        setattr(PF, name, wrapped)


class CompileCounter:
    """Counts executables built or loaded, and functions traced, by JAX."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.backend = 0
        self.traced = 0
        self.misses = 0
        self.names: list[str] = []
        be, tr = dispatch.BACKEND_COMPILE_EVENT, dispatch.JAXPR_TRACE_EVENT

        def on_duration(event, duration, **kw):
            if event == be:
                self.backend += 1
                self.names.append(str(kw.get("fun_name", "?")))
            elif event == tr:
                self.traced += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.backend, self.traced, self.misses, len(self.names)


class GcWatch:
    """Counts the garbage collector's passes and their pauses while on."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []
        self._t0 = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))
            self._t0 = None

    def start(self) -> None:
        import gc

        gc.callbacks.append(self._cb)

    def stop(self) -> dict:
        import gc

        gc.callbacks.remove(self._cb)
        full = [s for g, s in self.pauses if g == 2]
        return {"passes": len(self.pauses), "full": len(full),
                "full_max_s": max(full, default=0.0),
                "total_s": sum(s for _, s in self.pauses)}


def open_sessions(dep, tenants: list, n_probes: int,
                  timeout_s: float = 900.0) -> float:
    """Open each tenant's session with one never-shed ``batch`` ticket, as
    a recurring job's first request would; returns the seconds taken."""
    t0 = time.monotonic()
    tickets = [dep.desk.submit(spec=dep.tenants[w].spec, slo="batch",
                               n_probes=n_probes) for w in tenants]
    for t in tickets:
        t.wait(timeout=timeout_s)
    bad = [t.state for t in tickets if not t.ok]
    if bad:
        raise RuntimeError(f"set-up tickets not done: {sorted(set(bad))}")
    return time.monotonic() - t0


def warm_buckets(dep, tenant: int, max_groups: int, rows: int = 16) -> None:
    """Build every (G, R) program the window can dispatch, and the slicing
    of its results: per power-of-two group count up to ``max_groups``, one
    coalesced solve whose last group has each cell count a round can pop
    (smaller row buckets reuse these programs).  Then each cell count
    alone, for a lone group's own row buckets and its start draws."""
    from repro.core.mogd import solve_grouped

    svc = dep.service
    sid = dep.desk._spec_sessions[dep.tenants[tenant].spec.signature()]
    with svc._lock:
        solver = svc._sessions[sid].engine.solver
        st = svc._sessions[sid].state
        box = np.stack([st.utopia, st.nadir])

    def boxes(n):
        return np.broadcast_to(box, (n, 2, box.shape[1]))

    counts = (rows, 12, 8, 4)
    g = 1
    while g <= max_groups:
        for b in counts:
            solve_grouped([(solver, boxes(rows), 0)] * (g - 1)
                          + [(solver, boxes(b), 0)], origin="warmup")
        g *= 2
    for b in (1, *counts):
        solve_grouped([(solver, boxes(b), 0)], origin="warmup")


def warm_frontier_sizes(max_points: int, k: int) -> None:
    """Compile the hypervolume's Pareto mask for every frontier size up to
    ``max_points``: the session's gain telemetry runs it, unpadded, after
    every absorb."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    from repro.core.pareto import pareto_mask

    def one(n):
        jax.block_until_ready(pareto_mask(jnp.zeros((n, k), jnp.float32)))

    # one program per size: loading each from the persistent cache takes a
    # TPU host about 0.2 s, so they load side by side
    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(one, range(1, max_points + 1)))


def warm_store_passes(k: int, max_capacity: int) -> None:
    """Compile the frontier store's dominance pass for every store capacity
    and offer size a session can reach in the window."""
    import jax.numpy as jnp

    from repro.core.frontier_store import _incremental_pass

    cap = 64
    while cap <= max_capacity:
        for bb in (4, 8, 16):
            # numpy in, as the store passes them: strong float32 on device
            _incremental_pass(jnp.asarray(np.full((cap, k), np.inf)),
                              jnp.asarray(np.zeros(cap, bool)),
                              jnp.asarray(np.ones((bb, k))),
                              jnp.asarray(np.ones(bb, bool)))
        cap *= 2


@dataclasses.dataclass
class WindowResult:
    seconds: float
    tickets: list  # dicts: tenant, due, ticket, late
    recs: list  # dicts: tenant, due, start, end, rec, follow
    t0: float  # monotonic time the window opened
    t_close: float
    drained: bool
    gc: dict | None = None  # the collector's passes in the window


def run_window(dep, events: list, seconds: float, mix: dict,
               spans: HostSpans, on_open=None, on_close=None,
               drain_s: float = 60.0) -> WindowResult:
    """Issue ``events`` open loop for ``seconds`` and wait for the tickets
    due in the window to settle.  ``on_open`` runs at the window's opening
    (the tracer starts there) and ``on_close`` at its close, when the last
    due time has passed, before the drain."""
    desk, svc = dep.desk, dep.service
    tenants = dep.tenants
    slo, n_probes = mix["slo"], int(mix["n_probes"])
    follow = bool(mix.get("recommend_after_ticket", False))
    tickets: list[dict] = []
    recs: list[dict] = []
    pending: list[dict] = []  # tickets awaiting their follow-up recommend
    lock = threading.Lock()
    stop = threading.Event()
    t0 = time.monotonic() + 0.05
    t_close = t0 + seconds

    def sleep_until(t):
        d = t - time.monotonic()
        if d > 0:
            time.sleep(d)

    def submitter():
        for e in (e for e in events if e.kind == "ticket"):
            due = t0 + e.due_s
            sleep_until(due)
            late = time.monotonic() - due
            with spans("frontdesk.submit"):
                t = desk.submit(spec=tenants[e.tenant].spec, slo=slo,
                                n_probes=n_probes)
            row = dict(tenant=e.tenant, due=due, ticket=t, late=late)
            with lock:
                tickets.append(row)
                if follow:
                    pending.append(row)

    def recommend(tenant, due, follow_up):
        start = time.monotonic()
        sid = desk._spec_sessions.get(tenants[tenant].spec.signature())
        err, rec = None, None
        try:
            with spans("service.recommend"):
                rec = svc.recommend(sid)
        except (KeyError, RuntimeError) as exc:
            err = repr(exc)
        recs.append(dict(tenant=tenant, due=due, start=start,
                         end=time.monotonic(), rec=rec, err=err,
                         follow=follow_up))

    def recommender():
        bg = [e for e in events if e.kind == "recommend"]
        i = 0
        while True:
            now = time.monotonic()
            with lock:
                done = [r for r in pending if r["ticket"].done]
                for r in done:
                    pending.remove(r)
            for r in done:
                t = r["ticket"]
                if t.ok and t.finished_at < t_close:
                    recommend(r["tenant"], t.finished_at, True)
            while i < len(bg) and t0 + bg[i].due_s <= now:
                recommend(bg[i].tenant, t0 + bg[i].due_s, False)
                i += 1
            if stop.is_set() and i >= len(bg):
                with lock:
                    if not pending:
                        return
            nxt = t0 + bg[i].due_s if i < len(bg) else now + 0.002
            time.sleep(max(0.0, min(nxt - time.monotonic(), 0.002)))

    threads = [threading.Thread(target=submitter, name="bench-submit",
                                daemon=True),
               threading.Thread(target=recommender, name="bench-recommend",
                                daemon=True)]
    sleep_until(t0 - 0.02)
    if on_open is not None:
        on_open()
    for th in threads:
        th.start()
    sleep_until(t_close)
    if on_close is not None:
        on_close()
    threads[0].join()
    drained = True
    for row in tickets:
        if not row["ticket"].wait(timeout=max(0.0, t_close + drain_s
                                              - time.monotonic())):
            drained = False
    stop.set()
    threads[1].join(timeout=drain_s)
    return WindowResult(seconds, tickets, recs, t0, t_close, drained)


def ticket_latencies(win: WindowResult, deadline_s: float) -> np.ndarray:
    """Per ticket due in the window: completion minus due time; a ticket
    that did not complete counts at no less than its deadline."""
    out = []
    for row in win.tickets:
        t = row["ticket"]
        end = t.finished_at if t.finished_at is not None else win.t_close
        lat = end - row["due"]
        out.append(lat if t.ok else max(lat, deadline_s))
    return np.asarray(out)
