#!/usr/bin/env python3
"""Bring-up check: PATSMA's main path, end to end, on one TPU chip.

    python chip_smoke.py                # one chip: device, kernels, tune, serve
    python chip_smoke.py --chips 4      # four chips: the fleet race only

Everything runs in this one process, which holds the chip(s); it starts no
child that touches JAX.  The phases run in order, each printing its lines and
its seconds with compile seconds kept apart, and any failure exits non-zero:

* device  — JAX must report a TPU; on any other platform the script stops.
* kernels — every registered Pallas kernel, compiled (never interpreted), at
  real widths: matmul and flash/decode attention at Qwen2-7B widths (28
  heads, 4 KV heads, head dim 128, decode batch 8), ``rwkv_scan`` at
  RWKV6-7B widths (64 heads of 64), ``lru_scan`` at RecurrentGemma-2B width
  (2560), each checked against its ``kernels/ref.py`` oracle.
* tune    — one PATSMA search on the chip through ``tune_call`` at the
  Qwen2-7B FFN shape; the committed record must be keyed to this chip with no
  crashed candidate, the tuned output must match the oracle, and
  ``autotuned`` must replay the record as an exact hit with no new search.
* serve   — ``repro.launch.serve``'s adaptive decode-k path on Qwen2-7B at its
  published widths, cut to 8 of 28 layers (fewer if the compiled programs'
  peak memory does not fit the chip), batch 8, prompt 128, 32 new tokens.
  The first decode step's logits must match a forward pass over the prompt
  plus that token, and no decode candidate may fail to build or build on the
  serving thread.

``--chips 4`` runs only the race across chips: a ``ShardedPortfolio`` over
``local_device_pool`` + ``device_bound_measure`` with one member per chip,
each member's executables and outputs on its own chip, beside the serial
``Portfolio`` race it is compared with.

Weights and data are random, made from fixed seeds.  DBs and logs go under
``--out``; the compile cache follows ``repro.compile_cache``.  The last line
of standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: tolerances on max|got - want| / max(1, max|want|), by what is compared
TOL_BF16 = 2e-2
TOL_F32 = 2e-3
#: decode step vs forward pass: the same bf16 model on two paths through its layers
TOL_DECODE = 5e-2
#: share of the chip's memory one serve program may peak at; the rest is for
#: buffers its compile does not see (the previous call's decode state, the
#: allocator's slack)
FIT_SHARE = 0.95


class SmokeFailure(RuntimeError):
    pass


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require(phase: str, ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(f"{phase}: {msg}")


class Phase:
    """Times one phase; ``compile`` AOT-compiles and books its seconds apart."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.compile_s = 0.0

    def __enter__(self) -> "Phase":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if exc[0] is None:
            log(self.name, f"passed in {time.perf_counter() - self.t0:.1f} s "
                           f"(compile {self.compile_s:.1f} s)")

    def compile(self, fn, *args):
        import jax

        t0 = time.perf_counter()
        ex = jax.jit(fn).lower(*args).compile()
        self.compile_s += time.perf_counter() - t0
        return ex

    def run(self, ex, *args):
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(ex(*args))
        return out, time.perf_counter() - t0

    def close(self, what: str, got, want, tol: float) -> float:
        import numpy as np

        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        require(self.name, got.shape == want.shape, f"{what}: shape {got.shape} != {want.shape}")
        require(self.name, bool(np.isfinite(got).all()), f"{what}: non-finite output")
        err = float(np.max(np.abs(got - want))) / max(1.0, float(np.max(np.abs(want))))
        log(self.name, f"{what}: max err {err:.3g} (tol {tol:g}, scaled by max|ref|)")
        require(self.name, err <= tol, f"{what}: max err {err:.3g} > {tol:g}")
        return err


# -------------------------------------------------------------------- device
def phase_device(chips: int) -> dict:
    import jax

    with Phase("device"):
        devs = jax.devices()
        d0 = devs[0]
        log("device", f"platform={d0.platform} kind={d0.device_kind} count={len(devs)}")
        require("device", d0.platform == "tpu",
                f"JAX found no TPU (platform {d0.platform!r}); this check never runs elsewhere")
        require("device", len(devs) >= chips, f"{chips} chips asked for, {len(devs)} present")
        return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


# ------------------------------------------------------------------- kernels
def kernel_cases(*, small: bool = False):
    """(kernel, label, args, oracle, tol) at real widths (``small``: a CPU
    rehearsal size with the same head/group structure)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref

    bf, f32 = jnp.bfloat16, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def rnd(shape, dt=bf):
        return jax.random.normal(next(keys), shape, f32).astype(dt)

    # Qwen2-7B attention: 28 heads, 4 KV heads, head dim 128; FFN 3584 -> 18944
    M, D, F = (64, 256, 384) if small else (2048, 3584, 18944)
    S = 256 if small else 4096
    H, Kh, hd = 28, 4, (16 if small else 128)
    Bd = 8  # decode batch
    length = S * 3 // 4 - 5  # a partly filled cache, not block-aligned
    # RWKV6-7B: 64 heads of 64; RecurrentGemma-2B: recurrence width 2560
    T = 64 if small else 1024
    rH, rhd = (4, 16) if small else (64, 64)
    Dl = 256 if small else 2560

    valid = jnp.broadcast_to((jnp.arange(S) < length).astype(jnp.int32), (Bd, S))
    lw = -jnp.exp(0.5 * rnd((1, T, rH, rhd), f32) - 1.0)
    return [
        ("matmul", f"{M}x{D}@{D}x{F} bf16", (rnd((M, D)), rnd((D, F))), ref.matmul_ref, TOL_BF16),
        ("flash_attention", f"q(1,{S},{H},{hd}) kv(1,{Kh},{S},{hd}) bf16 causal",
         (rnd((1, S, H, hd)), rnd((1, Kh, S, hd)), rnd((1, Kh, S, hd))),
         ref.flash_attention_ref, TOL_BF16),
        ("decode_attention", f"q({Bd},{H},{hd}) kv({Bd},{Kh},{S},{hd}) bf16 len={length}",
         (rnd((Bd, H, hd)), rnd((Bd, Kh, S, hd)), rnd((Bd, Kh, S, hd)), valid),
         lambda q, k, v, _valid: ref.decode_attention_ref(q, k, v, length), TOL_BF16),
        ("rwkv_scan", f"(1,{T},{rH},{rhd}) f32",
         (rnd((1, T, rH, rhd), f32), rnd((1, T, rH, rhd), f32), rnd((1, T, rH, rhd), f32), lw,
          rnd((rH, rhd), f32), rnd((1, rH, rhd, rhd), f32)),
         ref.rwkv_scan_ref, TOL_F32),
        ("lru_scan", f"(2,{T},{Dl}) f32",
         (jax.nn.sigmoid(rnd((2, T, Dl), f32)), rnd((2, T, Dl), f32), rnd((2, Dl), f32)),
         ref.lru_scan_ref, TOL_F32),
    ]


def phase_kernels(*, small: bool = False, interpret: bool = False) -> None:
    import functools

    import jax

    from repro.kernels.autotuned import get_spec, registered

    with Phase("kernels") as ph:
        cases = kernel_cases(small=small)
        require("kernels", sorted(c[0] for c in cases) == registered(),
                f"cases {sorted(c[0] for c in cases)} != registered kernels {registered()}")
        for name, label, args, oracle, tol in cases:
            spec = get_spec(name)
            knobs = spec.defaults(*args)
            ex = ph.compile(functools.partial(spec.fn, **knobs, interpret=interpret), *args)
            if not interpret:
                require("kernels", "tpu_custom_call" in ex.as_text(),
                        f"{name}: no Pallas kernel in the compiled program")
            got, dt = ph.run(ex, *args)
            with jax.default_matmul_precision("highest"):
                want = jax.block_until_ready(oracle(*args))
            log("kernels", f"{name} {label} {knobs}: ran in {dt * 1e3:.2f} ms")
            for i, (g, w) in enumerate(zip(jax.tree.leaves(got), jax.tree.leaves(want))):
                ph.close(f"{name} output {i} vs ref.py", g, w, tol)


# ---------------------------------------------------------------------- tune
def phase_tune(out: Path, device: tuple, *, small: bool = False, interpret: bool = False) -> None:
    """``device``: the (backend, device_kind) the committed record must be keyed to."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import autotuned, ops, ref, tune_call
    from repro.kernels.autotuned import get_spec
    from repro.tuning import TuningDB, make_key

    with Phase("tune") as ph:
        M, D, F = (64, 256, 384) if small else (2048, 3584, 18944)
        ka, kb = jax.random.split(jax.random.PRNGKey(1))
        a = jax.random.normal(ka, (M, D), jnp.float32).astype(jnp.bfloat16)
        b = jax.random.normal(kb, (D, F), jnp.float32).astype(jnp.bfloat16)
        path = out / "chip.json"
        if path.exists():
            path.unlink()  # a fresh search every run, never a replay of the last
        db = TuningDB(str(path))
        stats: dict = {}
        t0 = time.perf_counter()
        rec = tune_call("matmul", a, b, interpret=interpret, db=db, measure_stats=stats)
        dt = time.perf_counter() - t0
        require("tune", rec is not None, "the search committed no record")
        log("tune", f"matmul {M}x{D}@{D}x{F} bf16: best={rec.point} cost={rec.cost * 1e3:.3f} ms "
                    f"evals={rec.evals} crashed={rec.crashed} reps={stats.get('reps')} "
                    f"culled={stats.get('culled')} pruned={stats.get('pruned_roofline')} "
                    f"in {dt:.1f} s (compiles included)")
        log("tune", f"record key: backend={rec.key.backend} device_kind={rec.key.device_kind}")
        require("tune", (rec.key.backend, rec.key.device_kind) == tuple(device),
                f"record keyed {rec.key.backend}/{rec.key.device_kind}, not {device}")
        require("tune", rec.crashed == 0, f"{rec.crashed} candidate(s) crashed")
        tuned = jax.block_until_ready(ops.matmul(a, b, **rec.point, interpret=interpret))
        ph.close("tuned matmul vs ref.py", tuned, ref.matmul_ref(a, b), TOL_BF16)

        # replay from the file: an exact hit, and autotuned(tune=True) runs no search
        db2 = TuningDB(str(path))
        spec = get_spec("matmul")
        key = make_key("matmul", args=(a, b), space=spec.space(a, b),
                       extra={"interpret": bool(interpret)})
        hit, exact = db2.lookup(key)
        require("tune", exact and hit.point == rec.point, "the committed record is not an exact hit")
        replay = jax.block_until_ready(autotuned("matmul", a, b, db=db2, tune=True, interpret=interpret))
        after = db2.get(key)
        require("tune", (after.created, after.evals) == (hit.created, hit.evals),
                "autotuned searched again instead of replaying the record")
        require("tune", bool(jnp.array_equal(replay, tuned)), "replayed output differs from the tuned run")
        log("tune", f"replay: exact hit {hit.point}, 0 measurements, output identical")


# --------------------------------------------------------------------- serve
def _fits(ph: Phase, cfg, batch: int, prompt: int, gen: int, limit: int) -> bool:
    """Whether every program of the serve phase fits ``limit`` device bytes
    at this depth: their AOT compiles' ``memory_analysis()`` peaks
    (parameters included), or the compiler's own refusal."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import DECODE_KS, decode_chunk
    from repro.models import ExecConfig, Model

    model = Model(cfg, ExecConfig(rec_chunk=4))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((batch, prompt), jnp.int32)
    seq = jax.ShapeDtypeStruct((batch, prompt + 1), jnp.int32)
    prefill = lambda p, t: model.prefill(p, {"tokens": t, "max_len": prompt + gen})  # noqa: E731
    hidden, states = jax.eval_shape(prefill, params, tokens)
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    progs = {
        "prefill": (prefill, (params, tokens)),
        f"decode k={max(DECODE_KS)}": (decode_chunk(model, max(DECODE_KS)), (params, tok, states, pos)),
        "forward": (lambda p, t: model.logits(p, model.forward(p, {"tokens": t})[0][:, -1:]), (params, seq)),
    }
    for name, (fn, args) in progs.items():
        try:
            peak = ph.compile(fn, *args).memory_analysis().peak_memory_in_bytes
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            log("serve", f"{cfg.n_layers} layers: {name} refused: {str(e).splitlines()[0]}")
            return False
        log("serve", f"{cfg.n_layers} layers: {name} peak {peak / 2**30:.2f} GiB")
        if peak > limit:
            return False
    return True


def phase_serve(out: Path, *, layers: int = 8, batch: int = 8, prompt: int = 128, gen: int = 32,
                tiny: bool = False) -> None:
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.launch import serve

    arch = "qwen2_7b"
    with Phase("serve") as ph:
        if tiny:
            argv = ["--tiny"]
        else:
            limit = int(FIT_SHARE * jax.devices()[0].memory_stats()["bytes_limit"])
            full = configs.get(arch).n_layers
            for n in range(layers, 0, -1):
                if _fits(ph, configs.get(arch, n_layers=n), batch, prompt, gen, limit):
                    break
                log("serve", f"{n} layers do not fit {limit / 2**30:.2f} GiB; cutting depth")
            else:
                raise SmokeFailure("serve: not even one layer fits the chip")
            log("serve", f"{arch}: {n} of {full} layers fit {limit / 2**30:.2f} GiB")
            argv = ["--layers", str(n)]
        db = out / "serve.json"
        if db.exists():
            db.unlink()  # tune online every run
        args = serve.parser().parse_args(argv + [
            "--arch", arch, "--batch", str(batch), "--prompt-len", str(prompt),
            "--gen", str(gen), "--db", str(db)])
        t0 = time.perf_counter()
        res = serve._serve(args)
        log("serve", f"served {batch}x{gen} tokens after a {batch}x{prompt} prompt in "
                     f"{time.perf_counter() - t0:.1f} s (prefill and decode compiles included)")
        rs = res["router"].stats()
        require("serve", rs["candidate_failures"] == 0,
                f"{rs['candidate_failures']} decode candidate(s) failed to build")
        require("serve", rs["inband_builds"] == 0, f"{rs['inband_builds']} in-band build(s)")

        # the first decode step against a forward pass over prompt + that token
        model, params, tokens = res["model"], res["params"], res["batch"]["tokens"]
        P = tokens.shape[1]
        prefill = ph.compile(lambda p, t: model.prefill(p, {"tokens": t, "max_len": P + 1}),
                             params, tokens)
        (hidden, states), _ = ph.run(prefill, params, tokens)
        tok = jnp.argmax(model.logits(params, hidden[:, None])[:, 0], -1).astype(jnp.int32)[:, None]
        step = ph.compile(model.decode_step, params, tok, states, jnp.int32(P))
        (got, _), _ = ph.run(step, params, tok, states, jnp.int32(P))
        seq = jnp.concatenate([tokens, tok], axis=1)
        fwd = ph.compile(lambda p, t: model.logits(p, model.forward(p, {"tokens": t})[0][:, -1:])[:, 0],
                         params, seq)
        want, _ = ph.run(fwd, params, seq)
        ph.close("first decode step vs forward logits", got, want, TOL_DECODE)


# --------------------------------------------------------------------- fleet
def phase_fleet(chips: int, *, small: bool = False, interpret: bool = False) -> None:
    """ShardedPortfolio with one member per chip vs the serial Portfolio."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import CSA, ExecutableCache, Portfolio, time_rep
    from repro.kernels import ops, ref
    from repro.kernels.autotuned import get_spec
    from repro.tuning import ShardedPortfolio, local_device_pool
    from repro.tuning.fleet import device_bound_measure

    with Phase("fleet") as ph:
        M, D, F = (64, 256, 384) if small else (2048, 3584, 18944)
        ka, kb = jax.random.split(jax.random.PRNGKey(2))
        a = jax.random.normal(ka, (M, D), jnp.float32).astype(jnp.bfloat16)
        b = jax.random.normal(kb, (D, F), jnp.float32).astype(jnp.bfloat16)
        space = get_spec("matmul").space(a, b)
        slots = local_device_pool(chips, cache=ExecutableCache())
        # each member's inputs live on its own chip: computations follow
        # committed inputs, whatever the default device says
        inputs = [jax.device_put((a, b), s.device) for s in slots]
        placed: dict = {}

        def measure(slot, xs, i, points):
            costs = []
            for p in points:
                knobs = space.decode(p)

                def build(knobs=knobs):
                    t0 = time.perf_counter()
                    ex = jax.jit(functools.partial(ops.matmul, **knobs, interpret=interpret)).lower(*xs).compile()
                    ph.compile_s += time.perf_counter() - t0
                    return ex

                ex = slot.cache.get_or_build(tuple(sorted(knobs.items())), build)
                if isinstance(ex, BaseException):
                    raise ex
                o = jax.block_until_ready(ex(*xs))
                placed.setdefault(i, set()).update(d.id for d in o.devices())
                costs.append(float(np.median([time_rep(ex, *xs) for _ in range(3)])))
            return costs

        def members():
            return [CSA(len(space), num_opt=2, max_iter=3, seed=s) for s in range(chips)]

        fleet = ShardedPortfolio(members(), rung=2)
        res = fleet.run(device_bound_measure(
            lambda i, pts: measure(slots[i], inputs[i], i, pts), slots))
        want = {i: {slots[i].device.id} for i in range(chips)}
        log("fleet", f"sharded race: {res.spent} tells, survivors {res.survivors}, "
                     f"best {space.decode(res.best_x)} {res.best_cost * 1e3:.3f} ms, "
                     f"host wall {res.wall_s:.2f} s; member -> chip ids {dict(sorted(placed.items()))}")
        require("fleet", placed == want, f"member placement {placed} != one chip each {want}")

        # the serial race pays its own compiles: a fresh cache, on chip 0
        serial = Portfolio(members(), rung=2)
        slot0 = local_device_pool(1, cache=ExecutableCache())[0]
        placed.clear()
        t0 = time.perf_counter()
        while not serial.is_end():
            batch = serial.ask()
            if not batch:
                break
            serial.tell(measure(slot0, inputs[0], 0, batch))
        wall = time.perf_counter() - t0
        log("fleet", f"serial race on chip {slot0.device.id}: {serial.spent} tells, survivors "
                     f"{serial.active}, best {space.decode(serial.best_solution)} "
                     f"{serial.best_cost * 1e3:.3f} ms, host wall {wall:.2f} s")
        require("fleet", placed == {0: {slot0.device.id}}, f"serial race ran on {placed}")
        for label, x in (("sharded", res.best_x), ("serial", serial.best_solution)):
            o = ops.matmul(*inputs[0], **space.decode(x), interpret=interpret)
            ph.close(f"{label} race best tiles vs ref.py", o, ref.matmul_ref(*inputs[0]), TOL_BF16)


# ---------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the race across four chips")
    ap.add_argument("--out", type=Path, default=ROOT / "chip_smoke_out",
                    help="directory for the tuning DBs and logs")
    args = ap.parse_args(argv)
    args.out.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(args.out / "tpu_logs"))
    sys.path.insert(0, str(ROOT / "src"))

    from repro.compile_cache import use_compile_cache

    cache = use_compile_cache()
    t0 = time.perf_counter()
    try:
        device = phase_device(args.chips)
        log("main", f"compile cache: {cache}")
        if args.chips == 4:
            phase_fleet(args.chips)
        else:
            phase_kernels()
            phase_tune(args.out, ("tpu", device["kind"]))
            phase_serve(args.out)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log("main", f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
