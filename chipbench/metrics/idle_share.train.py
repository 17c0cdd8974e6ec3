"""Share of the traced window in which no operation ran on the chip
(1 - busy union / window), in percent; on several chips, the largest."""

from chipbench import trace


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    return 100.0 * max(1.0 - trace.busy_s(tr, p) / tr.window_s
                       for p in tr.ops)
