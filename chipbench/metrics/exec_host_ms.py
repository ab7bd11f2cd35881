"""Mean, in ms, of a round's host work in the dispatch plane: the program's
``exec.materialize``, ``exec.pack`` and ``exec.unpack`` spans summed per
round, over the rounds that started in the window."""

import numpy as np


def read(ctx):
    from harness.spans import EXEC_HOST, per_round_s

    v = per_round_s(ctx, EXEC_HOST)
    return float(np.mean(v)) * 1e3 if v else None
