"""Mean, in ms, of a round's frontier-store dominance passes: the program's
``store.dominance`` spans (under ``pf.absorb``, under ``service.absorb``)
summed per round."""

import numpy as np


def read(ctx):
    from harness.spans import per_round_s

    v = per_round_s(ctx, ("store.dominance",))
    return float(np.mean(v)) * 1e3 if v else None
