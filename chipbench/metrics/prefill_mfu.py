"""The prefill program's share of the bf16 peak in the traced window:
model operations of the prompts (unpadded, from each ``engine.prefill``
span's ``prompt``) over the device time of the prefill runs those spans
dispatched, times the peak, in percent. About four admissions fall in a
3 s window at 1.2 req/s, so it is noisy; nothing to read where no prefill
ran whole in the window or the program opens no spans."""

from chipbench import spans


def read(rec):
    sp = spans.load(rec)
    if sp is None:
        return None
    runs = spans.prefills(rec.trace, sp)
    if not runs:
        return None
    flops = rec.costs.prefill_flops(rec.sizes,
                                    [s.args["prompt"] for s, _ in runs])
    secs = sum(d for _, d in runs)
    return 100.0 * flops / (secs * rec.peak["bf16_flops_per_s"])
