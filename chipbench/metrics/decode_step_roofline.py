"""Decode steps' share of their roofline: the least time the traced
window's decode steps could take at peak HBM bandwidth, for the bytes they
need (each weight once, valid cache entries or the recurrent state once:
``chipbench.counts``), over the device time of the decode programs' runs,
in percent.  Bandwidth bounds these steps: their operations take under a
tenth of the time at peak FLOP/s."""
from chipbench import counts


def read(run):
    if run.trace is None:
        return None
    dev_s = sum(v for n, v in run.trace["module_s"].items() if n in run.decode_modules)
    need = sum(counts.decode_step_bytes(run.model_config, run.batch, c.pos + i)
               for _, c in run.window_chunks(run.traced) for i in range(c.k))
    if not dev_s or not need:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / dev_s
