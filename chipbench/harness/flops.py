"""Operations and bytes of one descent dispatch, counted from its shapes.

The work is the algorithm's, whichever implementation runs it: every useful
cell descends from ``S`` starts for ``steps`` projected-Adam steps, and each
step costs one forward of the ``k`` effective objectives and one backward
to the input (no weight gradients), which is about twice the forward.
Padding rows and the snap-and-score epilogue are not counted: they are
overhead, and a share of the roofline should show them as such.
"""

from __future__ import annotations


def mlp_forward_flops(dims) -> float:
    """Multiply-adds of a dense ReLU MLP ``dims = (D, h1, ..., 1)``, x2."""
    return 2.0 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def gp_forward_flops(n: int, d: int, with_std: bool) -> float:
    """Exact-GP mean over ``n`` training points (squared distances, kernel,
    dot with alpha), plus the predictive std's triangular solve."""
    mean = 2.0 * n * d + 3.0 * n
    return mean + (n * n + 2.0 * n if with_std else 0.0)


def step_flops(kind: str, k: int, dims=None, n_train: int = 0, d: int = 0,
               with_std: bool = False) -> float:
    """Operations of one row-step: forward plus input gradient, k models."""
    per = (mlp_forward_flops(dims) if kind == "mlp"
           else gp_forward_flops(n_train, d, with_std))
    return 2.0 * k * per


def param_bytes(kind: str, k: int, dims=None, n_train: int = 0, d: int = 0,
                with_std: bool = False) -> float:
    """Bytes of one tenant's surrogate parameters that a descent must read."""
    if kind == "mlp":
        n = sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
    else:
        n = n_train * d + n_train + (n_train * (n_train + 1) / 2
                                     if with_std else 0)
    return 4.0 * k * n


def dispatch_cost(cells: int, groups: int, starts: int, steps: int, k: int,
                  d: int, row_flops: float, group_bytes: float):
    """``(flops, bytes)`` of one dispatch: ``cells`` useful cells over
    ``groups`` tenants.  Bytes: each tenant's parameters once, each start
    in, each cell's box and target in and its point, values and flag out."""
    flops = cells * starts * steps * row_flops
    io = cells * (starts * d + 2 * k + 1 + d + k + 1) * 4.0
    return flops, groups * group_bytes + io


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bw: float):
    """``(share, bound)``: the least time the chip could take over the time
    it took, and which of the two limits sets that least time."""
    t_f, t_b = flops / peak_flops, nbytes / peak_bw
    bound = "compute" if t_f >= t_b else "memory"
    return max(t_f, t_b) / seconds, bound
