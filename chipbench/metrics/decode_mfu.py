"""The fused decode step's share of the bf16 peak in the traced window:
model operations of the rows that produced a token, over the decode
program's device time times the peak, in percent."""

from chipbench.drivers import serve


def read(rec):
    if rec.trace is None:
        return None
    ticks = serve.traced_ticks(rec)
    flops = sum(rec.costs.decode_flops(rec.sizes, t[2], t[3])
                for t in ticks if t[2])
    if not flops:
        return None
    secs = serve.program_seconds(rec, "decode")
    return 100.0 * flops / (secs * rec.peak["bf16_flops_per_s"])
