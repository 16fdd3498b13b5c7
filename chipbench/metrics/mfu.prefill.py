"""Prefills' share of the chip's peak: the operations the traced window's
prefills need (linear layers, causal attention over half the score matrix,
the first token's LM head: ``chipbench.counts``) over the device time of
the prefill programs' runs times peak FLOP/s, in percent."""
from chipbench import counts


def read(run):
    if run.trace is None:
        return None
    dev_s = sum(v for n, v in run.trace["module_s"].items() if n in run.prefill_modules)
    flops = sum(counts.prefill_flops(run.model_config, run.batch, j.prompts.shape[1])
                for j in run.window_prefills(run.traced))
    if not dev_s or not flops:
        return None
    return 100.0 * flops / (dev_s * run.peaks["flops_bf16"])
