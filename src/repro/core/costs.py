"""Cost backends for the tuner.

* :class:`RuntimeCost` — the paper's Runtime mode: wall time of a callable,
  with ``jax.block_until_ready`` so asynchronous dispatch is included.
* :class:`ExecutableCache` + :func:`aot_compile` + :func:`compile_fanout` —
  the batched measurement layer: AOT ``jit(...).lower().compile()`` fanned out
  over a thread pool (XLA compilation releases the GIL) with a process-level
  cache of compiled executables, so revisited candidates — across tuning
  rounds, optimizer resets, and pretune grid cells — never recompile.
  Wall-clock *measurement* stays strictly serial for timing fidelity; only
  compilation overlaps.
* :func:`roofline_terms` — beyond-paper: roofline terms derived from an XLA
  ``compiled`` artifact.  This is what lets the dry-run launch search
  (``launch/spaces.py``) score a candidate on a CPU-only host: its cost is
  its dominant roofline term on the target hardware, not a wall clock on
  the host.

Also home to :func:`collective_bytes` — the HLO-text parser used by the
roofline analysis (sums operand bytes of all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute ops).
"""
from __future__ import annotations

import dataclasses
import re
import threading
import time
from collections import OrderedDict
from concurrent.futures import FIRST_COMPLETED, Future, ThreadPoolExecutor
from concurrent.futures import wait as futures_wait
from typing import Any, Callable, Hashable, List, Optional, Sequence, Tuple

from repro.obs import metrics as _metrics
from repro.obs.trace import tracer as _tracer

__all__ = [
    "HardwareSpec",
    "TPU_V5E",
    "PEAKS",
    "peaks_for",
    "RuntimeCost",
    "ExecutableCache",
    "CachePartition",
    "aot_compile",
    "compile_fanout",
    "roofline_terms",
    "collective_bytes",
    "hlo_flops_bytes",
]


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip peak numbers for the roofline (target hardware, not host)."""

    name: str
    peak_flops: float  # FLOP/s (bf16)
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per link
    hbm_bytes: float = 16e9  # HBM capacity per chip (launch-space feasibility)

    def __str__(self) -> str:
        return self.name


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links of 50 GB/s).
TPU_V5E = HardwareSpec("tpu-v5e", 197e12, 819e9, 50e9, 16e9)

#: published peaks per chip, keyed by ``jax.Device.device_kind``
PEAKS = {"TPU v5 lite": TPU_V5E}


def peaks_for(device_kind: str) -> Optional[HardwareSpec]:
    """The published peaks of ``device_kind``, or ``None`` for a kind not in
    :data:`PEAKS` — such a device gets no roofline bound, never another
    chip's."""
    return PEAKS.get(device_kind)


class RuntimeCost:
    """Wall time of ``fn(*args)`` over ``repeats`` runs (after ``warmup``
    discarded runs — the `ignore` idea at measurement level); the returned
    statistic is the ``objective`` over those reps (median by default,
    ``"p95"``/``"p99"`` for tail-latency tuning — see
    :data:`repro.core.measure.OBJECTIVES`).

    The per-repeat raw times of the most recent call are kept on
    :attr:`last_times` (:attr:`last_std` is their sample standard deviation),
    so callers can surface measurement confidence — ``cost_std`` /
    ``repeats_spent`` on committed :class:`~repro.tuning.TuningRecord`\\ s —
    without re-measuring.  Control-flow exceptions (``KeyboardInterrupt``,
    ``SystemExit``) raised by the measured callable always propagate; they
    must never be classified into a candidate failure cost by the layers
    above."""

    def __init__(
        self, warmup: int = 1, repeats: int = 3, objective: str = "median"
    ) -> None:
        from .measure import objective_quantile

        self.warmup = warmup
        self.repeats = repeats
        objective_quantile(objective)  # raises on unknown names
        self.objective = str(objective).strip().lower()
        self.last_times: list = []  # raw measured reps of the latest call

    def __call__(self, fn: Callable, *args, **kwargs) -> float:
        from jax import block_until_ready as block

        self.last_times = []
        try:
            for _ in range(self.warmup):
                block(fn(*args, **kwargs))
            times = []
            for _ in range(self.repeats):
                t0 = time.perf_counter()
                block(fn(*args, **kwargs))
                times.append(time.perf_counter() - t0)
        except (KeyboardInterrupt, SystemExit):
            # an interrupt mid-measurement is a user action, not a candidate
            # cost — re-raise before any classifying handler can eat it
            raise
        self.last_times = list(times)
        if self.objective not in ("median", "p50"):
            from .measure import objective_value

            return objective_value(times, self.objective)
        times.sort()
        return times[len(times) // 2]

    @property
    def last_std(self) -> float:
        """Sample standard deviation of the latest call's measured reps."""
        ts = self.last_times
        if len(ts) < 2:
            return 0.0
        mean = sum(ts) / len(ts)
        return (sum((t - mean) ** 2 for t in ts) / (len(ts) - 1)) ** 0.5


# ----------------------------------------------------------- AOT compilation
def aot_compile(fn: Callable, *args, **kwargs):
    """Ahead-of-time compile ``fn`` for the given example arguments.

    Returns the compiled executable (callable with arguments of the same
    shapes/dtypes).  Unlike first-call ``jax.jit`` dispatch, the trace +
    XLA compile happen *now*, so a driver can overlap many of these on a
    thread pool before any measurement starts.
    """
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return jitted.lower(*args, **kwargs).compile()


class ExecutableCache:
    """Thread-safe process-level cache of compiled executables.

    Keys are caller-chosen hashables (the tuning layer uses the context
    fingerprint + decoded knobs).  Values are whatever ``build`` returns —
    or the exception it raised: an illegal tile stays illegal, so a revisited
    crashing candidate should not pay a recompile either.  ``cache_failures``
    (a predicate on the exception) can veto that for failures that may be
    transient — e.g. RESOURCE_EXHAUSTED under concurrent compile load — so a
    revisit rebuilds instead of replaying a stale error; ``None`` caches every
    failure.  Concurrent requests for the same key share one build (per-key
    future).

    Stats: ``hits`` / ``misses`` count lookups, ``recompiles`` counts builds
    of a key that had already been built once (only possible after an LRU
    eviction — the acceptance gate for the batched tuner is that this stays
    at zero on the smoke grid; an uncached transient failure counts as a
    plain miss on retry, not a recompile).

    Multi-tenant budgets (default off): ``max_entries`` caps live entries
    below ``maxsize`` and ``max_bytes`` caps the summed ``size_of(result)``
    of *completed* builds — both evict least-recently-used completed entries
    (in-flight builds are never dropped mid-compile: racing waiters hold the
    future).  Every eviction increments :attr:`evictions` and the process
    registry counter ``cache.evictions``.
    """

    def __init__(
        self,
        maxsize: int = 1024,
        *,
        max_entries: Optional[int] = None,
        max_bytes: Optional[int] = None,
        size_of: Optional[Callable[[Any], int]] = None,
        cache_failures: Optional[Callable[[BaseException], bool]] = None,
        guard: Optional[Callable[[Callable[[], Any]], Callable[[], Any]]] = None,
    ) -> None:
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        if max_entries is not None and max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        if max_bytes is not None and max_bytes < 1:
            raise ValueError(f"max_bytes must be >= 1, got {max_bytes}")
        self.maxsize = int(maxsize)
        self.max_entries = int(max_entries) if max_entries is not None else None
        self.max_bytes = int(max_bytes) if max_bytes is not None else None
        self._size_of = size_of
        self._cache_failures = cache_failures
        # optional resilience hook: wraps every owner build (e.g.
        # ``FaultPolicy.wrap`` adds a watchdog timeout + transient retries)
        # without callers having to wrap each build thunk themselves
        self._guard = guard
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Hashable, Future]" = OrderedDict()
        self._built: set = set()  # keys ever built (recompile accounting)
        self._sizes: dict = {}  # key -> size_of(result), completed builds only
        self._bytes = 0
        # lookup accounting on the obs metric primitive (repro.obs.metrics):
        # stats() below is a snapshot of these counters, not a parallel copy
        self.hits = _metrics.Counter()
        self.misses = _metrics.Counter()
        self.recompiles = _metrics.Counter()
        self.evictions = _metrics.Counter()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def _evict_one(self, key: Hashable) -> None:
        # caller holds self._lock
        del self._entries[key]
        self._bytes -= self._sizes.pop(key, 0)
        self.evictions.inc()
        _metrics.counter("cache.evictions").inc()

    def _enforce_caps(self) -> None:
        # caller holds self._lock; in-flight builds (no recorded size — their
        # future is unresolved) are skipped so waiters never lose their build
        entry_cap = self.maxsize
        if self.max_entries is not None:
            entry_cap = min(entry_cap, self.max_entries)
        while len(self._entries) > entry_cap:
            victim = next(
                (k for k in self._entries if k in self._sizes or
                 self._entries[k].done()),
                None,
            )
            if victim is None:
                break
            self._evict_one(victim)
        if self.max_bytes is not None:
            while self._bytes > self.max_bytes:
                victim = next((k for k in self._entries if k in self._sizes), None)
                if victim is None:
                    break
                self._evict_one(victim)

    def get_or_build(self, key: Hashable, build: Callable[[], Any]) -> Any:
        """Return the cached executable for ``key``, building it (once) on a
        miss.  Build failures are returned (and cached) as the exception
        object rather than raised — the measurement layer classifies them."""
        with self._lock:
            fut = self._entries.get(key)
            if fut is not None:
                self.hits.inc()
                self._entries.move_to_end(key)
                owner = False
            else:
                fut = Future()
                self._entries[key] = fut
                self.misses.inc()
                if key in self._built:
                    self.recompiles.inc()
                self._built.add(key)
                owner = True
                self._enforce_caps()
        if owner:
            t_build = time.perf_counter()
            try:
                run = self._guard(build) if self._guard is not None else build
                result: Any = run()
            except Exception as e:  # cached: deterministic for a fixed context
                result = e
                _metrics.counter("compile.failed").inc()
                if self._cache_failures is not None and not self._cache_failures(e):
                    # possibly transient: answer current waiters with the
                    # error but drop the entry so a revisit rebuilds
                    with self._lock:
                        if self._entries.get(key) is fut:
                            del self._entries[key]
                        self._built.discard(key)
            except BaseException as e:
                # never cache (e.g. KeyboardInterrupt mid-compile would
                # poison the key): drop the entry, unblock waiters, propagate
                with self._lock:
                    if self._entries.get(key) is fut:
                        del self._entries[key]
                    self._built.discard(key)
                fut.set_result(e)
                raise
            if not isinstance(result, BaseException):
                _metrics.histogram("compile.seconds").observe(
                    time.perf_counter() - t_build
                )
                if self.max_bytes is not None:
                    size = self._measure_size(result)
                    with self._lock:
                        if self._entries.get(key) is fut:
                            self._bytes += size - self._sizes.get(key, 0)
                            self._sizes[key] = size
                            self._enforce_caps()
            fut.set_result(result)
        return fut.result()

    def _measure_size(self, result: Any) -> int:
        """Byte size of one completed build for the ``max_bytes`` budget:
        the caller's ``size_of`` when given, else the executable's own code
        size where the artifact exposes one, else a ``sys.getsizeof``
        floor."""
        if self._size_of is not None:
            try:
                return max(0, int(self._size_of(result)))
            except Exception:
                return 0
        try:
            ma = result.memory_analysis()
            for attr in ("generated_code_size_in_bytes", "serialized_size"):
                v = getattr(ma, attr, None)
                if v:
                    return int(v)
        except Exception:
            pass
        import sys

        try:
            return int(sys.getsizeof(result))
        except Exception:
            return 0

    def peek(self, key: Hashable, default: Any = None) -> Any:
        """Non-building, non-blocking lookup: the cached value (executable or
        cached exception object) if the build for ``key`` has *completed*,
        else ``default``.  Does not count toward hit/miss stats and does not
        touch LRU order — this is the serving hot path's "is it ready yet?"
        probe, which must never trigger or wait on a compile."""
        with self._lock:
            fut = self._entries.get(key)
        if fut is not None and fut.done():
            return fut.result()
        return default

    def stats(self) -> dict:
        with self._lock:
            return {
                "size": len(self._entries),
                "bytes": self._bytes,
                "hits": self.hits.value,
                "misses": self.misses.value,
                "recompiles": self.recompiles.value,
                "evictions": self.evictions.value,
            }

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._built.clear()
            self._sizes.clear()
            self._bytes = 0
            for c in (self.hits, self.misses, self.recompiles, self.evictions):
                c.inc(-c.value)

    def partition(self, tag: Hashable) -> "CachePartition":
        """A namespaced view of this cache: every key is transparently
        prefixed with ``tag``.  Fleet workers pinned to different devices
        compile the *same* candidate into device-specific executables —
        partitioned views keep those from colliding under one key while
        still sharing the process-wide LRU budget, stats, and per-key
        build deduplication."""
        return CachePartition(self, tag)


class CachePartition:
    """A key-prefixed view over a shared :class:`ExecutableCache` (see
    :meth:`ExecutableCache.partition`).  Same surface as the base cache;
    ``stats()``/``clear()`` act on the *shared* underlying cache."""

    def __init__(self, base: ExecutableCache, tag: Hashable) -> None:
        self.base = base
        self.tag = tag

    def _key(self, key: Hashable) -> Hashable:
        return ("__partition__", self.tag, key)

    def get_or_build(self, key: Hashable, build: Callable[[], Any]) -> Any:
        return self.base.get_or_build(self._key(key), build)

    def peek(self, key: Hashable, default: Any = None) -> Any:
        return self.base.peek(self._key(key), default)

    def partition(self, tag: Hashable) -> "CachePartition":
        return CachePartition(self.base, (self.tag, tag))

    def stats(self) -> dict:
        return self.base.stats()

    def clear(self) -> None:
        self.base.clear()

    def __len__(self) -> int:
        return len(self.base)


def compile_fanout(
    items: Sequence[Tuple[Hashable, Callable[[], Any]]],
    *,
    cache: Optional[ExecutableCache] = None,
    jobs: int = 1,
    deadline: Optional[float] = None,
    fatal: Optional[Callable[[BaseException], bool]] = None,
) -> List[Any]:
    """Compile ``items`` = [(key, build), ...] concurrently, deduped through
    ``cache``.  Returns one executable-or-exception per item, in order.

    XLA compilation releases the GIL, so a thread pool genuinely overlaps the
    expensive part; Python tracing inside each ``build`` stays GIL-bound.

    ``deadline`` bounds the *whole round* in seconds: builds not finished at
    the deadline are cancelled where possible (never-started futures) or
    abandoned (in-flight builds keep running in the background and still
    populate the cache for a later round), and their items come back as
    :class:`~repro.core.guard.GuardTimeout` failure objects.

    ``fatal`` is a predicate on completed failure results: the first failure
    it marks fatal cancels every outstanding future and is **raised** instead
    of returned — a poisoned round (e.g. a TypeError that would hit every
    candidate identically) fails fast instead of silently draining the
    executor.  Non-fatal failures keep the classic returned-not-raised
    contract.
    """
    from .guard import GuardTimeout

    if cache is None:
        cache = ExecutableCache(maxsize=max(len(items), 1))
    if jobs <= 1 or len(items) <= 1:
        t0 = time.monotonic()
        results: List[Any] = []
        for k, b in items:
            if deadline is not None and (time.monotonic() - t0) >= deadline:
                results.append(GuardTimeout(
                    f"compile round exceeded deadline of {deadline:.3g}s"
                ))
                continue
            # span the build thunk, not the lookup: a cache hit never runs
            # ``b``, so hits cost no span and the trace shows real compiles
            r = cache.get_or_build(k, _tracer().wrap(b, "compile"))
            if fatal is not None and isinstance(r, BaseException) and fatal(r):
                raise r
            results.append(r)
        return results
    pool = ThreadPoolExecutor(max_workers=min(jobs, len(items)))
    # wrap() captures *this* thread's current span, so worker-side compile
    # spans attach to the round that submitted them (pool threads have no
    # ambient span of their own); wrapping the build thunk rather than the
    # lookup means cache hits cost no span and the trace shows real compiles
    tr = _tracer()
    futs = [pool.submit(cache.get_or_build, k, tr.wrap(b, "compile"))
            for k, b in items]
    results = [None] * len(items)
    pending = {f: i for i, f in enumerate(futs)}
    try:
        t0 = time.monotonic()
        while pending:
            budget = None
            if deadline is not None:
                budget = deadline - (time.monotonic() - t0)
                if budget <= 0:
                    break
            done, _ = futures_wait(
                list(pending), timeout=budget, return_when=FIRST_COMPLETED
            )
            if not done:
                break  # deadline expired with builds still in flight
            for f in done:
                i = pending.pop(f)
                r = f.result()
                results[i] = r
                if fatal is not None and isinstance(r, BaseException) and fatal(r):
                    for pf in pending:
                        pf.cancel()
                    raise r
        for f, i in pending.items():
            f.cancel()
            results[i] = GuardTimeout(
                f"compile round exceeded deadline of {deadline:.3g}s"
            )
    finally:
        # never wait: a hung build must not block the round past its deadline
        pool.shutdown(wait=False)
    return results


# --------------------------------------------------------------------- HLO
# Matches e.g.:  %all-reduce.5 = bf16[4096,1024]{1,0} all-reduce(...)
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|tuple\([^)]*\)|[a-z0-9]+\[[^\]]*\][^ ]*)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    nbytes = _DTYPE_BYTES.get(dtype)
    if nbytes is None:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            if d:
                n *= int(d)
    return n * nbytes


def collective_bytes(hlo_text: str, per_op: bool = False):
    """Sum output-shape bytes of every collective op in an HLO module text.

    cost_analysis() does not expose collective traffic, so the roofline's
    collective term is derived here.  We count the op's *result* bytes
    (operand bytes ≈ result bytes for AG/AR/A2A/CP; reduce-scatter result is
    the post-scatter shard — the wire cost of RS equals its *operand* size,
    but HLO text reliably exposes the result shape, and for the ring
    algorithms AG/RS wire bytes = (n-1)/n * full size; we report result bytes
    as the canonical, mesh-independent proxy and fold algorithm factors into
    the roofline model).
    """
    totals: dict = {
        "all-gather": 0,
        "all-reduce": 0,
        "reduce-scatter": 0,
        "all-to-all": 0,
        "collective-permute": 0,
    }
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        # bytes of the result: first shape(s) on the line left of the op name
        head = line[: m.end(1)]
        byte_count = 0
        for dt, dims in _SHAPE_RE.findall(head):
            byte_count += _shape_bytes(dt, dims)
        totals[op] += byte_count
    if per_op:
        return totals
    return sum(totals.values())


def hlo_flops_bytes(compiled) -> tuple:
    """(flops, bytes_accessed) from compiled.cost_analysis(); robust to the
    per-device dict/list shapes different jax versions return."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", ca.get("bytes_accessed", 0.0)))
    return flops, nbytes


@dataclasses.dataclass
class RooflineTerms:
    """The three §Roofline terms, in seconds, plus bookkeeping."""

    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    bytes_accessed: float
    coll_bytes: float
    chips: int
    hw: HardwareSpec

    @property
    def dominant(self) -> str:
        vals = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(vals, key=vals.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "coll_bytes": self.coll_bytes,
            "chips": self.chips,
        }


def roofline_terms(
    compiled,
    chips: int,
    hw: HardwareSpec,
    hlo_text: Optional[str] = None,
    links_per_chip: int = 4,
) -> RooflineTerms:
    """Compute the three roofline terms from a compiled artifact.

    Notes on normalization: XLA's SPMD cost_analysis reports the *per
    partition* program (flops/bytes of one device's share), so terms divide by
    per-chip peaks directly.  The collective bytes from the HLO are likewise
    the per-device program's collective results; each chip drives
    ``links_per_chip`` ICI links (v5e: 4 usable links in a 2D torus).
    """
    flops, nbytes = hlo_flops_bytes(compiled)
    text = hlo_text if hlo_text is not None else compiled.as_text()
    cbytes = float(collective_bytes(text))
    return RooflineTerms(
        compute_s=flops / hw.peak_flops,
        memory_s=nbytes / hw.hbm_bw,
        collective_s=cbytes / (hw.ici_bw * links_per_chip),
        flops=flops,
        bytes_accessed=nbytes,
        coll_bytes=cbytes,
        chips=chips,
        hw=hw,
    )
