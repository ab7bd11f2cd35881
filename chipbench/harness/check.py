"""What decides ``correct``: the window's answers against the reference.

Every probe span the window dispatched (one tenant's cells in one
coalesced dispatch) is solved again by the plain reference, in blocks of a
fixed shape, and each served cell's gap is its widest coordinate gap to the
reference's configuration (a cell whose feasibility differs counts as a gap
of 1).  The descent numbers summarise those gaps over the window:

``descent_gap_p50``   the median gap;
``descent_gap_p99``   the 99th percentile gap: a fault confined to a few
                      groups of each dispatch (one tenant's slot, the last
                      group of a padded bucket) lifts the tail, not the
                      median;
``descent_off_share`` the share of cells whose gap exceeds ``OFF_GAP``;
``descent_span_off_max`` the largest share of such cells inside any one
                      span: a single group answered wrong anywhere in the
                      window.

and the exact numbers check the service's bookkeeping:

``frontier_mismatch`` sampled sessions whose live frontier is not the
                      brute-force Pareto set of every point their dispatches
                      offered them;
``rec_unoffered``     sampled in-window recommendations that are not a point
                      some dispatch offered that session;
``rec_pick_mismatch`` sampled sessions whose recommendation after the window
                      is not the reference's utopia-nearest pick on the
                      reference frontier.

The configuration's ``check.limits`` names the numbers that decide
``correct`` and their limits (``PERF.md`` gives the readings each was set
from); the others are reported beside them.
"""

from __future__ import annotations

import numpy as np

from . import reference

OFF_GAP = 1e-3  # a configuration this far from the reference's is another point
MOGD_FIELDS = ("steps", "lr", "penalty", "feas_tol", "tie_break_eps",
               "lr_floor", "adam_b1", "adam_b2", "adam_eps")


def mogd_dict(service) -> dict:
    cfg = service.default_mogd
    return {f: getattr(cfg, f) for f in MOGD_FIELDS}


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), 99]))


def _stack(trees):
    import jax

    return jax.tree.map(lambda *a: np.stack(a), *trees)


def window_spans(calls: list) -> list:
    """Every tenant span the window dispatched, in dispatch order."""
    return [c for c in calls if c["phase"] == "window"
            and c["tenant"] is not None]


def solve_reference(dep, spans: list, precision: str = "highest",
                    block: int = 64):
    """Reference ``(x, f, feas)`` for each span, rows padded to ROWS; the
    spans go through in blocks of ``block`` (one program shape for every
    run), the last block padded with copies of its first span."""
    R = reference.ROWS
    mogd = mogd_dict(dep.service)
    outs = []
    for b0 in range(0, len(spans), block):
        part = spans[b0:b0 + block]
        x0s, los, his, tg, params = [], [], [], [], []
        for c in part + part[:1] * (block - len(part)):
            pad = R - len(c["x"])
            x0 = np.asarray(c["x0s"], np.float32)
            x0s.append(np.concatenate([x0, np.repeat(x0[:1], pad, 0)]))
            los.append(np.concatenate([c["los"],
                                       np.repeat(c["los"][:1], pad, 0)]))
            his.append(np.concatenate([c["his"],
                                       np.repeat(c["his"][:1], pad, 0)]))
            tg.append(np.concatenate([c["targets"],
                                      np.repeat(c["targets"][:1], pad, 0)]))
            params.append(dep.tenants[c["tenant"]].weights)
        out = reference.solve_spans(
            dep.kind, dep.alpha, mogd, _stack(params), np.stack(x0s),
            np.stack(los), np.stack(his), np.stack(tg), precision=precision)
        outs.append(tuple(a[:len(part)] for a in out))
    return tuple(np.concatenate(a) for a in zip(*outs))


def descent_gaps(spans: list, ref) -> list:
    """Per span, per served cell: widest |x - x_ref|, or 1 where
    feasibility differs."""
    rx, _rf, rfeas = ref
    gaps = []
    for i, c in enumerate(spans):
        B = len(c["x"])
        g = np.abs(np.asarray(c["x"], np.float64) - rx[i, :B]).max(-1)
        gaps.append(np.where(np.asarray(c["feas"]) != rfeas[i, :B], 1.0, g))
    return gaps


def descent_numbers(gaps: list) -> dict:
    """The window's descent numbers from its per-span gaps."""
    if not gaps:
        return {}
    allg = np.concatenate(gaps)
    return {"descent_gap_p50": float(np.median(allg)),
            "descent_gap_p99": float(np.quantile(allg, 0.99)),
            "descent_off_share": float(np.mean(allg > OFF_GAP)),
            "descent_span_off_max": float(max(np.mean(g > OFF_GAP)
                                              for g in gaps))}


def offered_points(calls: list) -> dict:
    """Per tenant, every objective vector a dispatch offered its frontier
    store, in order: the reference solves of its opening (B == 1) as
    returned, and each feasible probe cell clipped into its cell."""
    out: dict[int, list] = {}
    refs: dict[int, list] = {}
    for c in calls:
        w = c["tenant"]
        if w is None or c["phase"] == "warm":
            continue
        f = np.asarray(c["f"], np.float64)
        if len(f) == 1 and len(refs.setdefault(w, [])) < f.shape[1]:
            refs[w].append(f[0])
            out.setdefault(w, []).append(f[0])
            continue
        for j in np.nonzero(np.asarray(c["feas"]))[0]:
            out.setdefault(w, []).append(np.clip(f[j], c["los"][j],
                                                 c["his"][j]))
    return ({w: np.asarray(v) for w, v in out.items()},
            {w: np.asarray(v) for w, v in refs.items()})


def run_checks(dep, calls: list, win, limits: dict, seed: int,
               block: int, n_sessions: int, n_recs: int,
               ref=None) -> tuple[dict, dict]:
    """Compare the window's answers with the reference; returns
    ``({name: {"value", "limit"}}, info)``: the numbers ``limits`` names,
    and the others with the sample sizes.  ``ref`` is the reference's
    answer for :func:`window_spans`, where the caller has it already."""
    spans = window_spans(calls)
    if spans and ref is None:
        ref = solve_reference(dep, spans, block=block)
    gaps = descent_gaps(spans, ref) if spans else []
    offered, refs = offered_points(calls)
    desk, svc = dep.desk, dep.service

    def sid_of(w):
        return desk._spec_sessions.get(dep.tenants[w].spec.signature())

    active = sorted({row["tenant"] for row in win.tickets
                     if sid_of(row["tenant"]) is not None})
    rng = _rng(seed)
    chosen = (list(rng.choice(active, min(n_sessions, len(active)),
                              replace=False)) if active else [])
    frontier_bad = pick_bad = 0
    for w in chosen:
        F, _ = svc.frontier(sid_of(w))
        live = {np.round(r, 9).tobytes() for r in F}
        want, ref_F = reference.pareto_rows(
            offered.get(w, np.zeros((0, 2))))
        frontier_bad += int(live != set(want))
        if want and w in refs and len(refs[w]) == 2:
            pick = reference.utopia_nearest(ref_F, refs[w])
            got = svc.recommend(sid_of(w)).objectives
            pick_bad += int(np.round(np.asarray(got, np.float64), 9).tobytes()
                            != np.round(pick, 9).tobytes())
    served = [r for r in win.recs if r["rec"] is not None]
    if len(served) > n_recs:
        served = [served[i] for i in sorted(
            rng.choice(len(served), n_recs, replace=False))]
    unoffered = 0
    for r in served:
        keys = {np.round(p, 9).tobytes() for p in offered.get(r["tenant"],
                                                                ())}
        key = np.round(np.asarray(r["rec"].objectives, np.float64),
                       9).tobytes()
        unoffered += int(key not in keys)
    values = {
        **descent_numbers(gaps),
        "frontier_mismatch": frontier_bad,
        "rec_unoffered": unoffered,
        "rec_pick_mismatch": pick_bad,
    }
    # a window that dispatched nothing has no descent to compare: not correct
    out = {n: {"value": values.get(n, float("inf")), "limit": limits[n]}
           for n in limits}
    info = {n: v for n, v in values.items() if n not in limits}
    info.update(cells=int(sum(len(g) for g in gaps)), spans=len(spans),
                sessions=len(chosen), recs=len(served))
    return out, info
