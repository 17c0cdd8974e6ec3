"""95th percentile of every gap between consecutive output tokens of a
request due in the window, as the host saw them until the run stopped (a
step that admits a request reports its first two tokens together: a gap
of 0)."""

import numpy as np

from chipbench import harness


def read(rec):
    g = [np.diff(r.tok_t) for r in rec.window if len(r.tok_t) > 1]
    if not g:
        return None
    return harness.percentile(np.concatenate(g), 95) * 1e3
