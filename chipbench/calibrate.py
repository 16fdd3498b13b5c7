#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, in one process.

    python chipbench/calibrate.py --workload qwen2_7b.decode --seconds 20 \
        --seeds 11,12,13 --control-seeds 11,12,13 [--out chiprun_out/cal.jsonl]

For each seed this runs the cell as ``run.py`` does (set-up, a window of
``--seconds`` at the cell's own load, the comparison with the reference)
and reports the program's widest logit gap; for each control seed it also
reads the control's: the reference itself computed through float8 at the
same positions of the same requests.  The control is judged in the
program's place by the run's own comparison against the cell's limits
(``control_correct``, which must come out false).  The benchmark's own runs
never run the control.  One JSON line per seed goes to standard output (and
to ``--out``).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", default="", help="comma-separated")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)

    import jax

    from chipbench import catalog
    from chipbench.peaks import peaks_for

    cell = catalog.cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        run.log(f"JAX found no TPU (platform {dev.platform!r})")
        return 2
    peaks = peaks_for(dev.device_kind)
    run.use_compile_cache()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        gc.collect()  # the last seed's reference buffers, before this seed's weights
        t0 = time.perf_counter()
        out = run.measure(cell, seed, args.seconds, bool(args.trace), t_start=t0,
                          peaks=peaks, control=seed in controls)
        line = {"workload": args.workload, "seed": seed, "seconds": args.seconds,
                **{k: v for k, v in out["gaps"].items()},
                "correct": out["result"]["correct"], "metrics": out["result"]["metrics"],
                "device": out["result"]["device"], "attempted": out["result"]["attempted"],
                "router": {k: out["router_stats"][k] for k in
                           ("explores", "exploits", "drift_resets", "inband_builds",
                            "candidate_failures")},
                "wall_s": time.perf_counter() - t0}
        if "control_correct" in out:
            line["control_correct"] = out["control_correct"]
            line["control_checks"] = out["control_checks"]
        if "breakdown" in out["result"]:
            line["breakdown"] = out["result"]["breakdown"]
        text = json.dumps(line)
        print(text, flush=True)
        if args.out is not None:
            args.out.parent.mkdir(parents=True, exist_ok=True)
            with args.out.open("a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
