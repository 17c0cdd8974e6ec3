"""95th percentile of the wait of the window's requests from their due
time to the start of the engine step that admitted them (admission is the
first thing a step does after eviction)."""

from chipbench import harness


def read(rec):
    t = [(r.admit_t if r.admit_t is not None else rec.t_end)
         - (rec.t0 + r.spec.due) for r in rec.window]
    v = harness.percentile(t, 95)
    return None if v is None else v * 1e3
