"""Output tokens that reached the host inside the window, from every
request, over the window's seconds."""


def read(rec):
    n = sum(1 for r in rec.requests for t in r.tok_t
            if rec.t_open <= t < rec.t_close)
    return n / rec.seconds
