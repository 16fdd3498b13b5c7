"""Observability overhead benchmark: tuning throughput with obs off vs on.

The observability layer's contract is that it is a **sidecar**: disabled, an
instrumentation site costs one attribute check (the tracer's ``enabled``
flag, the event module's ``_SINK is None`` early-out) and a profiler
annotation that reads no clock while no profiler session records; enabled, the fsynced
event stream and span bookkeeping ride along without distorting the search.
This benchmark measures both prices on ``tuning_throughput``'s contexts,
but with a **deterministic** ``cost_fn`` instead of wall-clock costs: a
measured cost is noisy, so CSA trajectories diverge between passes and an
occasional never-seen candidate triggers a cold XLA compile (~100ms) that
swamps the few-ms signal.  The cost function still *executes* each
candidate (the loop does the real, GIL-releasing work a measured search
does) but returns a constant, so every pass asks the exact same candidates
and the warm-up pass compiles all of them once.

Measurement is paired to survive CI-runner load drift: each round times
off → on → off phases and contributes one paired ratio
``on / mean(off, off)``; the reported ``on_ratio`` is the **median** over
rounds (an unpaired min-vs-min estimate flaps by ±15% on a busy machine,
far above the effect being measured).  Within a round each phase is the
**min of ``reps`` back-to-back sweeps**, shedding one-off scheduler or
writer-drain interference before the ratio is formed.  ``off_ratio`` —
the same pairing applied to two disabled phases — is the self-noise
floor, reported but not gated.

**Gate: on_ratio ≤ 1.05** (the CI smoke lane asserts this), above the
< 2% design target so CI noise does not flake the lane.

Prints ``obs_overhead_{off,on},us,ratio=...`` CSV lines for the CI artifact.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

#: CI gate: obs-on tuning throughput may cost at most 5% over obs-off
GATE_RATIO = 1.05


def _contexts(n_ctx: int = 2):
    """(kernel, args) pairs at production-ish sizes.  The obs cost per
    candidate is fixed (a handful of span/event calls); what the ratio
    means depends on how much real work a candidate does.  Tuning a
    64x64 toy would overstate the relative overhead of any workload a
    search is actually pointed at, so these shapes are sized to the
    pretune grid's upper end."""
    import jax

    def rnd(seed, shape):
        return jax.random.normal(jax.random.PRNGKey(seed), shape)

    ctxs = [
        ("matmul", (rnd(0, (128, 128)), rnd(1, (128, 128)))),
        ("matmul", (rnd(2, (192, 192)), rnd(3, (192, 192)))),
        ("matmul", (rnd(4, (256, 256)), rnd(5, (256, 256)))),
    ]
    return ctxs[:n_ctx]


def _det_cost(executable, *args) -> float:
    """Run the candidate like a measured search would (``RuntimeCost``'s
    warmup + 2 repeats), but return a constant: identical trajectories
    every pass."""
    import jax

    for _ in range(3):
        jax.block_until_ready(executable(*args))
    return 1.0


def _sweep(ctxs, *, num_opt, max_iter) -> float:
    """One timed pass: tune every context against a throwaway DB (no
    exact-hit replay) with the deterministic cost function — after the
    warm-up pass every candidate build is an executable-cache hit."""
    from repro.kernels.autotuned import tune_call
    from repro.tuning import TuningDB

    t0 = time.perf_counter()
    for name, args in ctxs:
        tune_call(name, *args, db=TuningDB(None), interpret=True,
                  num_opt=num_opt, max_iter=max_iter, measure="fixed",
                  cost_fn=_det_cost)
    return time.perf_counter() - t0


def _median(xs):
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def run(n_ctx=2, num_opt=4, max_iter=2, rounds=7, reps=3, verbose=True) -> dict:
    from repro import obs
    from repro.kernels.autotuned import exec_cache

    ctxs = _contexts(n_ctx)
    obs.shutdown()  # make sure a stray REPRO_OBS doesn't skew the baseline

    def phase():
        # min over back-to-back sweeps: one slow sweep (scheduler hiccup,
        # writer drain landing mid-loop) must not poison the round's ratio
        return min(_sweep(ctxs, num_opt=num_opt, max_iter=max_iter)
                   for _ in range(reps))

    # warm: backend init + every candidate executable into the process
    # cache, once per mode so neither pass pays first-time costs
    obs_tmp = tempfile.mkdtemp(prefix="obs-overhead-")
    _sweep(ctxs, num_opt=num_opt, max_iter=max_iter)
    obs.configure(obs_tmp)
    _sweep(ctxs, num_opt=num_opt, max_iter=max_iter)
    obs.shutdown()

    on_ratios: list = []
    off_ratios: list = []
    offs: list = []
    ons: list = []
    try:
        for _ in range(rounds):
            off_a = phase()
            off_b = phase()
            obs.configure(obs_tmp)
            on = phase()
            obs.shutdown()
            off_c = phase()
            on_ratios.append(on / ((off_b + off_c) / 2.0))
            off_ratios.append(off_b / ((off_a + off_c) / 2.0))
            offs += [off_a, off_b, off_c]
            ons.append(on)
    finally:
        obs.shutdown()
        shutil.rmtree(obs_tmp, ignore_errors=True)

    on_ratio = _median(on_ratios)
    res = {
        "contexts": len(ctxs),
        "rounds": rounds,
        "reps": reps,
        "off_s": _median(offs),
        "on_s": _median(ons),
        "off_ratio": _median(off_ratios),  # self-noise floor
        "on_ratio": on_ratio,
        "gate_ratio": GATE_RATIO,
        "gate_ok": on_ratio <= GATE_RATIO,
        "cache_hits": exec_cache().stats()["hits"],
    }
    if verbose:
        print(
            f"obs overhead over {len(ctxs)} contexts x {rounds} rounds: "
            f"off={res['off_s'] * 1e3:.1f}ms on={res['on_s'] * 1e3:.1f}ms "
            f"ratio={on_ratio:.3f} (gate {GATE_RATIO}, "
            f"self-noise {res['off_ratio']:.3f})"
        )
    return res


def _print_csv(out: dict) -> None:
    print(f"obs_overhead_off,{out['off_s'] * 1e6:.0f},ratio={out['off_ratio']:.3f}")
    print(f"obs_overhead_on,{out['on_s'] * 1e6:.0f},ratio={out['on_ratio']:.3f}")


def smoke():
    out = run(n_ctx=2, num_opt=4, max_iter=2, rounds=7, verbose=True)
    _print_csv(out)
    assert out["gate_ok"], (
        f"obs-on tuning throughput ratio {out['on_ratio']:.3f} "
        f"exceeds the {GATE_RATIO} gate"
    )
    return out


def main(argv=None):
    out = run(n_ctx=3, num_opt=4, max_iter=3, rounds=7, verbose=True)
    _print_csv(out)
    if not out["gate_ok"]:
        raise SystemExit(
            f"obs-on tuning throughput ratio {out['on_ratio']:.3f} "
            f"exceeds the {GATE_RATIO} gate"
        )
    return out


if __name__ == "__main__":
    main()
