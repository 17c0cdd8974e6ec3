"""Share of the traced window in which no operation ran on the chip
(1 - busy union / window), in percent."""

from chipbench import trace


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    busy = [trace.busy_s(tr, p) for p in tr.ops]
    return 100.0 * (1.0 - sum(busy) / len(busy) / tr.window_s)
