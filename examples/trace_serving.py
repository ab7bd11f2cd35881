"""Observability plane end to end: trace a serving burst (DESIGN.md §14).

One ``Observability`` bundle rides the whole stack — frontdesk →
service → executor → vault — so a burst of tickets produces (a) a
Chrome-trace JSON you can load in chrome://tracing or
https://ui.perfetto.dev showing admit → schedule → dispatch →
step_round → solve (materialize, pack, launch, wait, unpack) → absorb
(each session's absorb and its dominance pass) nested across the real
threads, (b) a snapshot-consistent Prometheus export of every counter on
the path, with the service-lock waits, (c) a per-ticket latency
breakdown whose phases sum to the end-to-end latency — an SLO miss names
its culprit — and (d) the same spans overlaid on a JAX device profile of
the burst: the tracer's anchors put them on the profile's clock, so each
device idle gap sits under the host span that was open across it.

    PYTHONPATH=src python examples/trace_serving.py
"""

import glob
import gzip
import json
import os
import tempfile

import jax

from repro.core import MOGDConfig
from repro.core.synthetic import mlp_surrogate_task
from repro.frontdesk import FrontDesk
from repro.obs import Observability, profile_anchors
from repro.service import MOOService


def main():
    obs = Observability(trace=True)  # default is trace=False: ~free
    svc = MOOService(mogd=MOGDConfig(steps=24, multistart=4),
                     batch_rects=2, grid_l=2, obs=obs)

    print("== serving burst (tracing on, under a device profile) ==")
    profile_dir = tempfile.mkdtemp(prefix="serving_profile_")
    jax.profiler.start_trace(profile_dir, create_perfetto_trace=True)
    obs.tracer.anchor()  # this instant, on both clocks
    try:
        with FrontDesk(svc, capacity=32) as desk:  # adopts svc.obs
            # "batch" SLO: the first cold JIT compile can take seconds,
            # and this demo wants every ticket to finish, not
            # demonstrate load shedding
            tickets = [desk.submit(spec=mlp_surrogate_task(seed=i % 4),
                                   n_probes=8, slo="batch")
                       for i in range(12)]
            desk.drain(timeout=60.0)
    finally:
        obs.tracer.anchor()  # a second pair corrects clock drift
        jax.profiler.stop_trace()
    done = [t for t in tickets if t.ok]
    print(f"  {len(done)}/{len(tickets)} tickets completed")

    # -- per-ticket latency attribution --------------------------------
    print("== where the latency went (first completed ticket) ==")
    b = done[0].breakdown()
    for k in ("queue_wait_s", "batch_wait_s", "dispatch_s",
              "absorb_s", "persist_s"):
        print(f"  {k:14s} {b[k] * 1e3:8.3f} ms")
    print(f"  {'accounted_s':14s} {b['accounted_s'] * 1e3:8.3f} ms "
          f"(e2e {b['e2e_s'] * 1e3:.3f} ms)")
    assert abs(b["accounted_s"] - b["e2e_s"]) < 1e-6

    # -- one registry for the whole stack ------------------------------
    print("== metrics (Prometheus text, excerpt) ==")
    prom = obs.metrics.to_prometheus()
    for line in prom.splitlines():
        if line.startswith(("frontdesk_completed", "frontdesk_dispatches",
                            "exec_dispatches{", "exec_transfers{",
                            "service_coalesced")):
            print(f"  {line}")
    print("== service-lock waits (recorded while tracing) ==")
    for h in obs.metrics.instruments("service.lock_wait_s"):
        if h.count:
            print(f"  {h.labels['op']:9s} n={h.count:4d} "
                  f"p99 {h.quantile(0.99) * 1e3:7.3f} ms")

    # -- Chrome trace --------------------------------------------------
    path = tempfile.mktemp(prefix="serving_trace_", suffix=".json")
    obs.tracer.export_chrome(path)
    spans = obs.tracer.spans()
    names = sorted({s.name for s in spans})
    print("== trace ==")
    print(f"  {len(spans)} spans across "
          f"{len({s.thread_id for s in spans})} threads: {names}")
    print(f"  load {path} in chrome://tracing or ui.perfetto.dev")
    assert {"frontdesk.admit", "frontdesk.dispatch",
            "service.step_round", "exec.dispatch"} <= set(names)

    # -- overlay on the device profile ---------------------------------
    # the profile's own Perfetto JSON and the program's spans on the
    # profile's clock merge into one file: load it in ui.perfetto.dev
    anchors = profile_anchors(profile_dir)
    ours = obs.tracer.chrome_trace(profile_anchors_ns=anchors)
    prof = glob.glob(os.path.join(profile_dir, "**",
                                  "perfetto_trace.json.gz"), recursive=True)
    with gzip.open(max(prof, key=os.path.getmtime)) as f:
        doc = json.load(f)
    doc["traceEvents"] = doc["traceEvents"] + ours["traceEvents"]
    overlay = os.path.join(profile_dir, "overlay.json")
    with open(overlay, "w") as f:
        json.dump(doc, f, default=str)
    print("== device profile overlay ==")
    print(f"  {len(anchors)} anchors; load {overlay} in ui.perfetto.dev")


if __name__ == "__main__":
    main()
