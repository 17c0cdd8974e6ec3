"""The grouped expert matmuls against their roofline in the traced window:
for each decode step's ``engine.moe`` span and each ``engine.prefill``
span that start in the window, the larger of operations over the bf16
peak and bytes over HBM bandwidth (``costs.moe``: 6 d Fe a token-expert
pair; the weights of the experts hit and the pairs' activations), summed,
over the device time of the grouped-matmul operations (``ragged-dot*``)
in the window, in percent. A decode step's pairs and experts hit are the
span's ``routed`` and ``experts_hit``, every slot's row counted as the
step computes it; a prefill's pairs are its padded length x k x the MoE
layers, every expert of every MoE layer hit (a prompt of 1,024 tokens or
more routes thousands of pairs a layer over 64 experts). Nothing to read
where the program opens no spans."""

from chipbench import spans, trace


def gmm(name: str) -> bool:
    return name.startswith("ragged-dot")


def read(rec):
    sp = spans.load(rec)
    if sp is None:
        return None
    s, c, p, tr = rec.sizes, rec.costs, rec.peak, rec.trace
    lo, hi = tr.window_ns
    layers = s["layers"] - s["dense"]
    calls = [c.moe(s, x.args["routed"], x.args["experts_hit"])
             for x in spans.named(sp, "engine.moe") if lo <= x.start < hi]
    calls += [c.moe(s, x.args["padded_len"] * s["k"] * layers,
                    s["e"] * layers)
              for x in sp if x.name == "engine.prefill" and lo <= x.start < hi]
    least = sum(max(f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"])
                for f, b in calls)
    if not least:
        return None
    secs = sum(sum(t - s0 for s0, t in trace.clip(
        trace.find(tr, plane, gmm, "grouped matmul"), tr.window_ns))
        for plane in tr.ops) * 1e-9
    return 100.0 * least / secs
