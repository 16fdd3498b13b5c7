"""Closed loop of lockstep batch jobs through the program's serving layers.

A job is ``batch`` prompts of one length: a jitted ``Model.prefill`` that
also picks the first token, then ``gen`` greedy tokens decoded through
``repro.launch.serve.decode_chunk`` executables routed by a
``repro.runtime.ContextRouter`` whose decode-k route is registered as
``serve._serve`` registers it.  The loop is closed because the program has
no request queue and no continuous batching: when a job has emitted its
tokens, the next job starts at once.

The tuning DB is a fresh file in a temporary directory, so every run tunes
decode k from cold inside the window (PATSMA's online mode) and pays its
exploration there.  Set-up compiles and runs every program the window can
call: each prompt length's prefill, each candidate k and every shorter
stream-tail chunk, and then runs them all again ``SOAK_ROUNDS`` times so
that the window starts with the chip and the host in their steady state.
The window ends with the job in flight when its time is up: it always
holds whole jobs, whose work is the same in every run.
"""
from __future__ import annotations

import dataclasses
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import ChoiceDim, SearchSpace
from repro.launch import serve
from repro.models import ExecConfig, Model
from repro.runtime import ContextRouter
from repro.tuning import TuningDB

#: the execution settings ``launch/serve.py``'s ``_serve`` builds its model
#: with: ``ExecConfig(rec_chunk=4)``, so ``attn_impl`` is the default "xla"
EXEC_CFG = ExecConfig(rec_chunk=4)
ROUTE = "serve/decode_k"
#: extra rounds of every set-up program (each prefill, each chunk size)
#: before the window: the first seconds after compiling ran slow otherwise
SOAK_ROUNDS = 2


@dataclasses.dataclass
class Chunk:
    k: int
    t0: float
    t1: float
    pos: int  # position of the chunk's first decoded token
    explore: bool


@dataclasses.dataclass
class Job:
    index: int
    prompts: np.ndarray  # (B, P) int32
    t_start: float
    t_first: float = float("nan")  # first token on the host
    tokens: list = dataclasses.field(default_factory=list)  # (B, n) blocks
    chunks: list = dataclasses.field(default_factory=list)
    emitted: int = 0  # decoded tokens after the first
    finished: bool = False

    @property
    def served(self) -> np.ndarray:
        """(B, 1 + emitted) served tokens, the prefill's first among them."""
        return np.concatenate(self.tokens, axis=1)


class Engine:
    def __init__(self, cfg, traffic, params, *, extra: dict):
        self.cfg = cfg
        self.traffic = traffic
        self.params = params
        self.model = Model(cfg, EXEC_CFG)
        self.extra = dict(extra)
        self.jobs: list = []
        self._next_job = 0
        self._live = None  # (job, token, states, pos) of the job in flight
        self._db_dir = tempfile.TemporaryDirectory(prefix="chipbench-db-")
        self.prefill_modules: set = set()
        self.decode_modules: set = set()

    # ------------------------------------------------------------- set-up
    def _prefill_fn(self):
        model, max_len = self.model, self.traffic.max_len

        def prefill_first_token(params, tokens):
            hidden, states = model.prefill(params, {"tokens": tokens, "max_len": max_len})
            logits = model.logits(params, hidden[:, None])[:, 0]
            return jnp.argmax(logits, -1).astype(jnp.int32)[:, None], states

        return jax.jit(prefill_first_token)

    def setup(self) -> None:
        """Compile and run every program the window can use."""
        B, gen = self.traffic.batch, self.traffic.gen
        params = self.params
        fn = self._prefill_fn()
        self.prefill = {}
        for P in self.traffic.buckets:
            tokens = jnp.zeros((B, P), jnp.int32)
            ex = fn.lower(params, tokens).compile()
            self.prefill[P] = ex
            self.prefill_modules.add(_module_name(ex))
            token, states = ex(params, tokens)
        self.pos0 = {P: jnp.int32(P) for P in self.traffic.buckets}
        pos = self.pos0[self.traffic.buckets[-1]]
        jax.block_until_ready((token, states))
        # the route's builds lower against shapes, so no buffer of set-up's
        # own job stays alive in the build closure
        spec = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (token, states, pos))

        # the decode-k route, registered as serve._serve registers it
        self.ks = ks = tuple(k for k in serve.DECODE_KS if k <= gen) or (1,)
        space = SearchSpace([ChoiceDim("k", ks)])
        db = TuningDB(os.path.join(self._db_dir.name, "serve.json"))
        model = self.model
        router = ContextRouter(db=db, jobs=max(1, len(serve.DECODE_KS)))
        router.register(
            ROUTE,
            space=lambda: space,
            build=lambda point: serve.decode_chunk(model, point["k"]).lower(
                params, *spec).compile(),
            defaults=lambda: {"k": 1},
            epsilon=0.25,
            num_opt=3,
            max_iter=4,
            measure=None,
            drift={"window": 8, "min_samples": 4, "factor": 1.5},
            extra=self.extra,
        )
        self.router = router
        self.tuner = router.tuner(ROUTE)
        self.tuner.prewarm([{"k": k} for k in ks], wait=True)
        # the stream tail's shorter chunks, which _serve compiles on demand
        self.chunk_fns = {}
        for k in range(1, ks[-1] + 1):
            ex = self.tuner.executable_for({"k": k}) if k in ks else None
            if ex is None:
                ex = serve.decode_chunk(model, k).lower(params, *spec).compile()
            self.chunk_fns[k] = ex
            self.decode_modules.add(_module_name(ex))
            jax.block_until_ready(ex(params, token, states, pos))
        for _ in range(SOAK_ROUNDS):
            for P in self.traffic.buckets:
                del token, states  # as in the window: one job's state at a time
                token, states = self.prefill[P](params, jnp.zeros((B, P), jnp.int32))
                jax.block_until_ready(states)
            for fn in self.chunk_fns.values():
                jax.block_until_ready(fn(params, token, states, pos))
        del token, states

    # ------------------------------------------------------------- serving
    def _start_job(self) -> None:
        i = self._next_job
        self._next_job += 1
        with TraceAnnotation("new_job"):
            prompts = self.traffic.prompts(i)
            job = Job(i, prompts, time.perf_counter())
            tokens = jnp.asarray(prompts)
        with TraceAnnotation("prefill"):
            token, states = self.prefill[prompts.shape[1]](self.params, tokens)
            first = np.asarray(token)
        job.t_first = time.perf_counter()
        job.tokens.append(first)
        self.jobs.append(job)
        self._live = (job, token, states, self.pos0[prompts.shape[1]])

    def _step(self) -> None:
        """One unit of work: start a job, or decode one chunk of the live one
        (the decode loop of ``serve._serve``)."""
        if self._live is None:
            self._start_job()
            return
        job, token, states, pos = self._live
        rem = self.traffic.gen - job.emitted
        ks = self.ks
        decision = None
        if rem < ks[-1]:
            # stream tail: serve the clamped best, unmeasured
            k = max(1, min(int(self.tuner.exploit_point().get("k", 1)), rem))
            fn = (self.tuner.executable_for({"k": k}) if k in ks else None) or self.chunk_fns[k]
        else:
            with TraceAnnotation("router.begin"):
                decision = self.router.begin(ROUTE)
            k = int(decision.point["k"])
            fn = decision.executable if decision.executable is not None else self.chunk_fns[k]
        with TraceAnnotation("decode_chunk"):
            tc = time.perf_counter()
            token, states, pos, toks = fn(self.params, token, states, pos)
            jax.block_until_ready(toks)
            t1 = time.perf_counter()
        if decision is not None:
            with TraceAnnotation("router.observe"):
                self.router.observe(decision, (t1 - tc) / k)
        job.chunks.append(Chunk(k, tc, t1, job.prompts.shape[1] + job.emitted,
                                decision is not None and decision.kind == "explore"))
        job.tokens.append(np.asarray(toks)[:, :, 0].T)
        job.emitted += k
        if job.emitted >= self.traffic.gen:
            job.finished = True
            self._live = None
        else:
            self._live = (job, token, states, pos)

    def run(self, seconds: float, *, finish_job: bool = True) -> tuple:
        """Serve until ``seconds`` have passed and (with ``finish_job``) the
        job then in flight has finished; returns the (start, end) host times
        of the window, the end being when its last unit of work ended."""
        t0 = time.perf_counter()
        deadline = t0 + seconds
        end = t0
        while end < deadline or (finish_job and self._live is not None):
            self._step()
            end = time.perf_counter()
        return t0, end

    def close(self) -> None:
        """Drop every device buffer and executable the engine holds."""
        self.router.wait_pending()
        self._live = None
        self.params = self.prefill = self.chunk_fns = None
        self.router = self.tuner = None
        self._db_dir.cleanup()


def _module_name(compiled) -> str:
    """The HLO module name an executable runs under in a profiler trace."""
    head = compiled.as_text().split("\n", 1)[0]
    return head.split()[1].rstrip(",")
