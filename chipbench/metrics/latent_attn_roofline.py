"""The paged latent attention kernel against its roofline in the traced
window: per decode step, the larger of its operations over the bf16 peak
and its bytes (the latents of every occupied slot's attended positions,
each read once for all heads, with the queries and outputs) over HBM
bandwidth, summed, over the kernel's device time (``paged_latent_attn*``),
in percent."""

from chipbench import trace
from chipbench.drivers import serve


def kernel(name: str) -> bool:
    return name.startswith("paged_latent_attn")


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    p = rec.peak
    least = 0.0
    for t in serve.traced_ticks(rec):
        flops, nbytes = rec.costs.latent_attn(rec.sizes, t[4], t[5])
        least += max(flops / p["bf16_flops_per_s"],
                     nbytes / p["hbm_bytes_per_s"])
    if not least:
        return None
    secs = sum(sum(b - a for a, b in trace.clip(
        trace.find(tr, plane, kernel, "paged latent attention"),
        tr.window_ns)) for plane in tr.ops) * 1e-9
    return 100.0 * least / secs
