"""The step's share of the chip's bf16 peak in the traced sub-window:
the operations a recycled step needs (forward and backward on the kept
rows, nothing recomputed counted) times the steps that ran whole inside
the traced window, over the traced window's length (profiler clock) times
chips times peak, in percent."""


def read(rec):
    tr, span = rec.trace, rec.trace_span
    if tr is None:
        return None
    job = rec.job
    n = sum(1 for a, b in rec.steps if a >= span[0] and b <= span[1])
    if n == 0:
        return None
    kept = max(1, round(job["ratio"] * job["global_batch"]))
    flops = n * rec.costs.recycled_step(rec.sizes, kept, job["seq_len"])
    return 100.0 * flops / (tr.window_s * len(rec.devices)
                            * rec.peak["bf16_flops_per_s"])
