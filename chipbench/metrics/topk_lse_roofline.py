"""The top-k + log-sum-exp kernel against its roofline in the traced
window: every decode step summarises all slots' logits, every admission
one row; the larger of operations over the bf16 peak and bytes (each
logit read once, the summaries written) over HBM bandwidth, summed, over
the kernel's device time, in percent."""

from chipbench.drivers import serve


def read(rec):
    if rec.trace is None:
        return None
    p, c, s = rec.peak, rec.costs, rec.sizes
    least = 0.0
    for t in serve.traced_ticks(rec):
        for rows in [rec.slots] + [1] * len(t[6]):
            flops, nbytes = c.topk_lse(s, rows, rec.topk,
                                       rec.logits_itemsize)
            least += max(flops / p["bf16_flops_per_s"],
                         nbytes / p["hbm_bytes_per_s"])
    if not least:
        return None
    return 100.0 * least / serve.kernel_seconds(rec, "topk_lse")
