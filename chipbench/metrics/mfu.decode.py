"""Decode steps' share of the chip's peak: the operations the decode steps
of the traced window need (``chipbench.counts``) over the device time of
the decode programs' runs times peak FLOP/s, in percent."""
from chipbench import counts


def read(run):
    if run.trace is None:
        return None
    dev_s = sum(v for n, v in run.trace["module_s"].items() if n in run.decode_modules)
    flops = sum(counts.decode_step_flops(run.model_config, run.batch, c.pos + i)
                for _, c in run.window_chunks(run.traced) for i in range(c.k))
    if not dev_s or not flops:
        return None
    return 100.0 * flops / (dev_s * run.peaks["flops_bf16"])
