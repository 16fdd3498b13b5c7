"""90th percentile over every request of the time from its job's start on
the host (prompt in hand) to its first token on the host, in milliseconds."""
from chipbench.record import quantile


def read(run):
    jobs = run.window_prefills()
    if not jobs:
        return None
    return quantile([(j.t_first - j.t_start) * 1e3 for j in jobs], [run.batch] * len(jobs), 0.90)
