"""Training launcher.

Local (CPU / single host) mode runs the fault-tolerant driver end-to-end:

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_7b --tiny --steps 50

Cluster mode is the same program under a device mesh: pass --mesh to place
the (data, model) axes; on a real TPU pod slice, start one process per host
with jax.distributed.initialize() (env-driven) and the identical arguments —
the in/out shardings come from repro.parallel.sharding either way.
"""
import argparse
import json

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="qwen2_7b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--tune", action="store_true", help="PATSMA single-iteration mode")
    ap.add_argument("--runtime", type=str, default=None, choices=["adaptive"],
                    help="adaptive: keep tuning while training (epsilon-rationed "
                         "exploration + drift-triggered warm re-search)")
    ap.add_argument("--epsilon", type=float, default=1.0,
                    help="explored fraction of steps while a search is live "
                         "(adaptive runtime mode)")
    ap.add_argument("--db", type=str, default=None,
                    help="tuning DB path; warm-starts step knobs across runs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--distributed", action="store_true",
                    help="initialize jax.distributed from env (multi-host)")
    ap.add_argument("--obs-dir", type=str, default=None,
                    help="write observability artifacts (events.jsonl, "
                         "trace.json, metrics.json) into this directory "
                         "(default: the REPRO_OBS env var, else off)")
    args = ap.parse_args()

    if args.distributed:
        jax.distributed.initialize()

    from repro import obs
    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    if args.obs_dir:
        obs.configure(args.obs_dir)
    else:
        obs.configure_from_env()

    from repro.runtime import TrainJob

    job = TrainJob(
        arch=args.arch,
        tiny=args.tiny,
        steps=args.steps,
        global_batch=args.global_batch,
        seq_len=args.seq_len,
        lr=args.lr,
        seed=args.seed,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        tune=args.tune or args.runtime is not None,
        tune_db=args.db,
        runtime=args.runtime,
        tune_epsilon=args.epsilon,
    )
    try:
        with obs.span("train", steps=args.steps):
            hist = job.run()
    finally:
        obs.shutdown()
    print(json.dumps({
        "final_loss": hist["loss"][-1],
        "steps": len(hist["loss"]),
        "mean_step_s": sum(hist["step_time"]) / len(hist["step_time"]),
        "final_knobs": hist["final_knobs"],
        "watchdog_events": len(hist["watchdog_events"]),
        "resets": len(hist["resets"]),
    }, indent=2))


if __name__ == "__main__":
    main()
