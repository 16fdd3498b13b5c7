"""Output tokens per second over the measured window: every token served in
it (each request's first, from the prefill, and every decoded one) over the
window's whole length, prefills and host work included."""


def read(run):
    tokens = run.batch * (len(run.window_prefills())
                          + sum(c.k for _, c in run.window_chunks()))
    t0, t1 = run.window
    return tokens / (t1 - t0) if tokens else None
