"""95th percentile of time to first token over every request due in the
window: from the request's due time to the host's receipt of the step
metrics that first report a token of it. A request that never got one
counts with the time until the run stopped waiting."""

from chipbench import harness


def read(rec):
    t = [(r.tok_t[0] if r.tok_t else rec.t_end) - (rec.t0 + r.spec.due)
         for r in rec.window]
    v = harness.percentile(t, 95)
    return None if v is None else v * 1e3
