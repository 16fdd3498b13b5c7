"""The paper's illustrative example (§3): Red-Black Gauss-Seidel with the
parallel chunk auto-tuned — Algorithms 5 (entire) and 6 (single) side by side.

The paper tunes OpenMP's ``schedule(dynamic, chunk)``.  The JAX analogue
with the same runtime trade-off is the row-block size of the red/black
update sweeps: small blocks -> dispatch/loop overhead; large blocks -> cache
pressure; the optimum depends on the machine — exactly the knob class PATSMA
targets.  It is tuned three ways (entire-execution runtime mode with CSA and
with NM, and single-iteration runtime mode) and compared with an exhaustive
sweep, mirroring the paper's comparison of its two modes.

    PYTHONPATH=src python examples/rb_gauss_seidel.py
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CSA, Autotuning, LogIntDim, NelderMead, SearchSpace


def make_rb_step(n: int, block_rows: int):
    """One red-black Gauss-Seidel sweep on an (n, n) grid, row-blocked."""
    nblocks = n // block_rows
    red = (jnp.indices((n, n)).sum(0) % 2 == 0).astype(jnp.float32)

    @jax.jit
    def step(u, f):
        def color_sweep(u, mask):
            # vectorized neighbor average, applied block-of-rows at a time
            def block_body(i, u):
                r0 = i * block_rows
                rows = jax.lax.dynamic_slice(u, (r0, 0), (block_rows, n))
                up = jax.lax.dynamic_slice(u, (jnp.maximum(r0 - 1, 0), 0), (block_rows, n))
                dn = jax.lax.dynamic_slice(u, (jnp.minimum(r0 + 1, n - block_rows), 0), (block_rows, n))
                lf = jnp.roll(rows, 1, axis=1)
                rt = jnp.roll(rows, -1, axis=1)
                fb = jax.lax.dynamic_slice(f, (r0, 0), (block_rows, n))
                mb = jax.lax.dynamic_slice(mask, (r0, 0), (block_rows, n))
                new = 0.25 * (up + dn + lf + rt + fb)
                rows = jnp.where(mb > 0, new, rows)
                return jax.lax.dynamic_update_slice(u, rows, (r0, 0))

            return jax.lax.fori_loop(0, nblocks, block_body, u)

        u = color_sweep(u, red)
        u = color_sweep(u, 1.0 - red)
        return u

    return step


def run(n: int = 512, iters: int = 60, seed: int = 0, verbose: bool = True) -> dict:
    rng = np.random.default_rng(seed)
    f = jnp.asarray(rng.normal(size=(n, n)), jnp.float32) / n
    u0 = jnp.zeros((n, n), jnp.float32)
    space = SearchSpace([LogIntDim("block_rows", 4, n // 2)])
    steps = {}

    def get_step(block_rows):
        if block_rows not in steps:
            steps[block_rows] = make_rb_step(n, block_rows)
        return steps[block_rows]

    def timed_sweep(block_rows, u, reps=1):
        st = get_step(block_rows)
        t0 = time.perf_counter()
        for _ in range(reps):
            u = st(u, f)
        jax.block_until_ready(u)
        return time.perf_counter() - t0, u

    # --- exhaustive truth: every distinct block size on an 8-point sweep ----
    truth = {}
    for z in np.linspace(-1, 1, 8):
        br = space.dims[0].decode(z)
        if br in truth:
            continue
        timed_sweep(br, u0)  # compile+warm
        dt, _ = timed_sweep(br, u0, reps=3)
        truth[br] = dt / 3
    best_truth = min(truth, key=truth.get)

    results = {"truth": truth, "best_truth": best_truth}

    # --- Entire Execution mode (paper Alg. 5): tune on a replica up front --
    for name, opt in [
        ("csa_entire", CSA(1, num_opt=4, max_iter=6, seed=seed)),
        ("nm_entire", NelderMead(1, error=0.0, max_iter=18, seed=seed)),
    ]:
        at = Autotuning(space=space, ignore=1, search=opt, cache=True)
        t0 = time.perf_counter()
        u = u0

        def replica(block_rows):
            nonlocal u
            _, u = timed_sweep(block_rows, u)

        at.entire_exec_runtime(replica)
        tune_time = time.perf_counter() - t0
        results[name] = {
            "point": at.best_point["block_rows"],
            "tune_time_s": tune_time,
            "measurements": at.num_measurements,
            "slowdown_vs_best": truth.get(at.best_point["block_rows"], np.inf)
            / truth[best_truth],
        }

    # --- Single Iteration mode (paper Alg. 6): tuning rides the solve ------
    at = Autotuning(
        space=space, ignore=1,
        search=CSA(1, num_opt=4, max_iter=6, seed=seed), cache=True,
    )
    u = u0
    t0 = time.perf_counter()
    for it in range(iters):
        p = at.start()
        _, u = timed_sweep(p["block_rows"], u)
        at.end()
    total_single = time.perf_counter() - t0
    # reference solve at the true best block size
    u = u0
    t0 = time.perf_counter()
    for it in range(iters):
        _, u = timed_sweep(best_truth, u)
    total_best = time.perf_counter() - t0
    results["csa_single"] = {
        "point": at.best_point["block_rows"],
        "total_s": total_single,
        "oracle_total_s": total_best,
        "overhead_pct": 100.0 * (total_single - total_best) / total_best,
    }

    if verbose:
        print("rb_gauss_seidel truth (block_rows -> s/sweep):")
        for k in sorted(truth):
            mark = " <- best" if k == best_truth else ""
            print(f"  {k:6d}: {truth[k]*1e3:8.2f} ms{mark}")
        for k in ("csa_entire", "nm_entire", "csa_single"):
            print(f"  {k}: {results[k]}")
    return results


if __name__ == "__main__":
    out = run(n=256, iters=40)
    print("\nsummary:")
    print(" exhaustive best block:", out["best_truth"])
    print(" CSA entire-execution :", out["csa_entire"]["point"],
          f"({out['csa_entire']['measurements']} replica sweeps)")
    print(" NM  entire-execution :", out["nm_entire"]["point"])
    print(" CSA single-iteration : overhead",
          f"{out['csa_single']['overhead_pct']:.1f}% vs oracle")
