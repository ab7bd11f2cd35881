"""Mean, in ms, of a round's launch and wait for the device: the program's
``exec.launch`` (the call, with its host-to-device copies) and
``exec.wait`` (until the outputs are ready) spans summed per round."""

import numpy as np


def read(ctx):
    from harness.spans import EXEC_WAIT, per_round_s

    v = per_round_s(ctx, EXEC_WAIT)
    return float(np.mean(v)) * 1e3 if v else None
