"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

* busy time: the union of the intervals in which an operation of the
  ``XLA Ops`` line ran on a ``/device:TPU:n`` plane, averaged over the chips
  that ran any; the idle share is 1 minus busy over the traced window;
* module time: the summed device duration of each run of a compiled
  program (``XLA Modules`` line), by HLO module name;
* top device operations by self time (an op's duration less the ops it
  contains, as a ``while`` contains its body), named ``module:op``;
* idle gaps: the time between busy intervals inside the window, summed by
  the innermost host span (``jax.profiler.TraceAnnotation``) that was open
  at the middle of the gap, or ``host`` where none was.

Host and device clocks differ by an offset; it is estimated from the host's
``CompleteCallbacks`` event of each program run, which cannot start before
that run ended on the device: the smallest such lag is taken as the offset.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os


#: the host span the benchmark opens around the traced window
WINDOW_SPAN = "bench.trace_window"


@dataclasses.dataclass
class Trace:
    ops: dict  # device plane -> [(start_ns, end_ns, "module:op")]
    modules: list  # [(start_ns, end_ns, module name, plane)]
    spans: list  # host annotations [(start_ns, end_ns, name)]
    offset_ns: float  # host time = device time + offset_ns


def profile_options():
    """Profiler settings of a traced window: host spans and device ops, no
    Python function tracer (it slows the host loop being measured) and no
    HLO protos (size)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {files}")
    return files[0]


def load(path: str, span_names) -> Trace:
    """Read the device ops, program runs and the host spans named in
    ``span_names`` from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    span_names = set(span_names)
    pd = ProfileData.from_file(path)
    ops, modules, spans, callbacks, run_end = {}, [], [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, raw = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        s, d = e.start_ns, e.duration_ns
                        name = e.name.split("(", 1)[0]
                        mods.append((s, s + d, name, plane.name))
                        run_end[dict(e.stats).get("run_id")] = s + d
                elif line.name == "XLA Ops":
                    raw = [(e.start_ns, e.start_ns + e.duration_ns,
                            e.name.split(" = ", 1)[0].lstrip("%")) for e in line.events]
            mods.sort()
            starts = [m[0] for m in mods]
            named = []
            for s, t, op in raw:
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= t else "?"
                named.append((s, t, f"{mod}:{op}"))
            if named:
                # an op before the ops it contains when they start together
                ops[plane.name] = sorted(named, key=lambda e: (e[0], -e[1]))
            modules.extend(mods)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
                    elif e.name == "CompleteCallbacks":
                        callbacks.append((e.start_ns, dict(e.stats).get("run_id")))
    lags = [s - run_end[r] for s, r in callbacks if r in run_end]
    return Trace(ops, sorted(modules), sorted(spans), min(lags) if lags else 0.0)


def _union(intervals):
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            if t > out[-1][1]:
                out[-1][1] = t
        else:
            out.append([s, t])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(t, hi)) for s, t in intervals if t > lo and s < hi]


def window(trace: Trace, span: str) -> tuple:
    """(start, end) in device time of the one host span named ``span``."""
    hits = [(s, t) for s, t, n in trace.spans if n == span]
    if len(hits) != 1:
        raise ValueError(f"expected one {span!r} span in the trace, found {len(hits)}")
    s, t = hits[0]
    return s - trace.offset_ns, t - trace.offset_ns


def reduce(trace: Trace, win: tuple, *, top: int = 10) -> dict:
    """Busy and window seconds, device ops by self time and idle gaps by
    host span inside ``win`` (device ns), and each module's summed run time
    over the whole trace (which holds only the traced window's work)."""
    lo, hi = win
    busy, gaps = [], collections.Counter()
    self_time = collections.Counter()
    for plane, ops in trace.ops.items():
        merged = _union(_clip([(s, t) for s, t, _ in ops], lo, hi))
        busy.append(sum(t - s for s, t in merged))
        stack = []  # (end, name) of enclosing ops
        for s, t, name in ops:
            if t <= lo or s >= hi:
                continue
            while stack and stack[-1][0] <= s:
                stack.pop()
            dur = min(t, hi) - max(s, lo)
            self_time[name] += dur
            if stack:
                self_time[stack[-1][1]] -= dur
            stack.append((t, name))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps[_span_at(trace, (g0 + g1) / 2 + trace.offset_ns)] += g1 - g0
    chips = max(1, len(busy))
    module_ns = collections.Counter()
    for s, t, name, _ in trace.modules:
        module_ns[name] += t - s
    return {
        "busy_s": sum(busy) / chips * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[n, v / chips * 1e-9] for n, v in self_time.most_common(top)],
        "idle_gaps": [[n, v / chips * 1e-9] for n, v in gaps.most_common(top)],
        "module_s": {n: v / chips * 1e-9 for n, v in module_ns.items()},
    }


def _span_at(trace: Trace, t_host: float) -> str:
    """The innermost (latest-starting) benchmark span open at ``t_host``;
    ``host`` where only the window itself is.  The engine's spans do not
    nest deeply, so only the last few that started before are looked at."""
    i = bisect.bisect_right(trace.spans, (t_host, float("inf"), "~"))
    for s, t, name in reversed(trace.spans[max(0, i - 8):i]):
        if t >= t_host and name != WINDOW_SPAN:
            return name
    return "host"
