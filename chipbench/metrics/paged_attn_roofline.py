"""The paged decode attention kernel against its roofline in the traced
window: per decode step, the larger of its operations over the bf16 peak
and its bytes (keys and values of every occupied slot's attended
positions, queries and outputs) over HBM bandwidth, summed, over the
kernel's device time, in percent."""

from chipbench.drivers import serve


def read(rec):
    if rec.trace is None:
        return None
    p = rec.peak
    least = 0.0
    for t in serve.traced_ticks(rec):
        flops, nbytes = rec.costs.paged_attn(rec.sizes, t[4], t[5])
        least += max(flops / p["bf16_flops_per_s"],
                     nbytes / p["hbm_bytes_per_s"])
    if not least:
        return None
    return 100.0 * least / serve.kernel_seconds(rec, "paged_attn")
