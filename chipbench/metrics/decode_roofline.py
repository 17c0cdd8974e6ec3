"""The fused decode step against its roofline in the traced window: per
step the larger of operations over the bf16 peak and required bytes (the
non-embedding weights once, the embedding rows used, the keys and values
attended) over HBM bandwidth, summed, over the decode program's device
time, in percent."""

from chipbench.drivers import serve


def read(rec):
    if rec.trace is None:
        return None
    c, s, p = rec.costs, rec.sizes, rec.peak
    least = sum(max(c.decode_flops(s, t[2], t[3]) / p["bf16_flops_per_s"],
                    c.decode_bytes(s, t[2], t[3]) / p["hbm_bytes_per_s"])
                for t in serve.traced_ticks(rec) if t[2])
    if not least:
        return None
    return 100.0 * least / serve.program_seconds(rec, "decode")
