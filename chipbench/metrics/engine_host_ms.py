"""Host time inside ``Engine.step`` that waits on no transfer, per step:
over the engine steps wholly inside the traced window, the mean of the
benchmark's ``engine.step`` annotation less the program's
``engine.fetch_metrics`` and ``engine.evict_fetch`` spans inside it, in
ms. Nothing to read where the program opens no spans."""

from chipbench import spans


def read(rec):
    sp = spans.load(rec)
    if sp is None:
        return None
    return spans.engine_host_ms(rec.trace, sp)
