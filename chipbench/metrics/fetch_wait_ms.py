"""Time from the device finishing a decode step to its metrics reaching
the host: over the ``engine.fetch_metrics`` spans wholly inside the traced
window, the mean of the span's end less the end of the decode program run
it waited on, in ms (host span and device program on the profiler's one
clock). Nothing to read where the program opens no spans."""

from chipbench import spans


def read(rec):
    sp = spans.load(rec)
    if sp is None:
        return None
    return spans.fetch_wait_ms(rec.trace, sp)
