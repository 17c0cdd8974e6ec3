"""Stream tokens consumed (global batch x sequence length, every step)
from the window's opening to the end of its last step."""


def read(rec):
    if not rec.steps:
        return None
    job = rec.job
    tokens = len(rec.steps) * job["global_batch"] * job["seq_len"]
    return tokens / (rec.steps[-1][1] - rec.t_open)
