"""95th percentile over every output token after a request's first of the
time per token, in milliseconds: each token is charged its decode chunk's
host time (dispatch to tokens ready) over the chunk's k.  Not the gap
between deliveries, which is k times that."""
from chipbench.record import quantile


def read(run):
    chunks = run.window_chunks()
    if not chunks:
        return None
    per_token = [(c.t1 - c.t0) / c.k * 1e3 for _, c in chunks]
    weights = [run.batch * c.k for _, c in chunks]
    return quantile(per_token, weights, 0.95)
