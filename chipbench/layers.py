"""Device time and idle time by the program's own names.

``chipbench/trace.py`` names device work by HLO fusion and idle time by the
benchmark's own host spans.  This module reads what the program names
itself:

* scope map: each op of a compiled program mapped to the innermost
  ``jax.named_scope`` of the model it was traced under (``SCOPES``), read
  from the ``op_name`` metadata of ``compiled.as_text()``.  A fusion that
  holds a matmul (a ``dot`` or ``convolution``) takes the matmul's scope,
  so a norm or a residual add fused into a projection counts with the
  projection; any other fusion takes its own or its root's scope, as XLA
  names it.  The layer scan's slices of per-layer data (weights, cache)
  that only move bytes take the block scope of the ops that consume them,
  as the FFN's weight slices do where the compiler fuses them into the
  matmul; under ``layers`` stay the scan's stacking of the state it
  returns and its loop control;
* join: each program run on the trace carries the id the trace gives it
  (``jit_decode_k1(1234…)``).  Programs that share a module name (the
  prefill of each prompt length) are told apart by the shapes of their
  ops, which the trace's op names carry;
* scope time: the self time of every op summed by program and scope, plus
  the time of the program's runs in which no op ran (``BETWEEN``), so that
  a program's scopes sum to its run time;
* idle by span: each idle interval of the traced window split by overlap
  with the self time of the host spans (at each instant the innermost open
  span), the program's spans (``PROGRAM_SPANS``) among them.
  ``chipbench.trace.reduce`` gives a whole gap to the span open at its
  midpoint instead.
"""
from __future__ import annotations

import bisect
import collections
import re

from chipbench import trace

#: the model's named scopes (``repro.models``, ``serve.decode_chunk``)
SCOPES = ("embed", "layers", "attn", "kv_write", "ffn", "norm", "lm_head", "sample")
#: host spans the program opens on the serving path (``repro.obs.span``)
PROGRAM_SPANS = ("serve.dispatch", "serve.wait", "tuner.begin", "tuner.observe",
                 "tuner.tell", "tuner.commit", "tuner.drift_reset", "drift.observe")
#: scope of the ops outside every named scope
UNSCOPED = "unscoped"
#: the part of a program's runs in which none of its ops ran
BETWEEN = "between_ops"
#: a program run the shapes of its ops could not tell from another's
AMBIGUOUS = "ambiguous"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^\s,}]+)")
_MATMUL = re.compile(r"\s(?:dot|convolution)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s.*\{\s*$")
_DIMS = re.compile(r"\[([\d,]*)\]")
_REF = re.compile(r"%([^\s,()]+)")
#: ops that only move bytes
_MOVES = {"parameter", "constant", "copy", "copy-start", "copy-done", "dynamic-slice", "slice",
          "bitcast", "reshape", "transpose", "broadcast", "get-tuple-element", "tuple"}
#: the block a nested scope belongs to
_BLOCK = {"kv_write": "attn"}


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an ``op_name`` path, else ``UNSCOPED``."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def _opcode(rhs: str) -> str:
    """The opcode of an instruction's right-hand side (after its shape)."""
    if rhs.startswith("("):  # a tuple shape
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.split(" ", 1)[-1]
    return rhs.strip().split("(", 1)[0]


def _parse(hlo_text: str):
    """``({computation: [(op, rhs)]}, {computation: root rhs})``."""
    comps, roots, comp = collections.defaultdict(list), {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if m and comp is not None:
            comps[comp].append(m.groups())
            if line.lstrip().startswith("ROOT "):
                roots[comp] = m.group(2)
    return comps, roots


def scope_map(hlo_text: str) -> dict:
    """``{op: scope}`` for every instruction of an optimized HLO module (the
    rules are in the module's docstring)."""
    comps, roots = _parse(hlo_text)

    def own(rhs):
        m = _OP_NAME.search(rhs)
        return scope_of(m.group(1)) if m else UNSCOPED

    def inner(name, seen):
        """Scopes of the matmuls and of the other ops a computation holds,
        and whether it only moves bytes."""
        mm, other, moves = [], [], True
        for _, rhs in comps.get(name, ()):
            (mm if _MATMUL.search(rhs) else other).append(own(rhs))
            moves &= _opcode(rhs) in _MOVES
            for callee in _CALLS.findall(rhs):
                if callee not in seen:
                    seen.add(callee)
                    a, b, mv = inner(callee, seen)
                    mm, other, moves = mm + a, other + b, moves and mv
        return [x for x in mm if x != UNSCOPED], [x for x in other if x != UNSCOPED], moves

    out, moves = {}, set()
    for instrs in comps.values():
        for op, rhs in instrs:
            scope, code = own(rhs), _opcode(rhs)
            calls = _CALLS.findall(rhs)
            if code == "fusion" and calls:
                mm, other, only_moves = inner(calls[0], {calls[0]})
                if mm:
                    scope = mm[0]
                elif scope == UNSCOPED:
                    scope = own(roots.get(calls[0], ""))
                if scope == UNSCOPED and other:
                    scope = collections.Counter(other).most_common(1)[0][0]
                if only_moves:
                    moves.add(op)
            elif code in _MOVES:
                moves.add(op)
            out[op] = scope

    for instrs in comps.values():
        users = collections.defaultdict(list)
        for op, rhs in instrs:
            for ref in _REF.findall(rhs.split("calls=", 1)[0].split("metadata=", 1)[0]):
                users[ref].append(op)
        for op, _ in instrs:
            if out[op] != "layers" or op not in moves:
                continue
            found, todo, seen = set(), list(users[op]), {op}
            while todo:
                u = todo.pop()
                if u in seen:
                    continue
                seen.add(u)
                if u in moves and out[u] in ("layers", UNSCOPED):
                    todo += users[u]
                else:
                    found.add(_BLOCK.get(out[u], out[u]))
            if len(found) == 1 and found <= set(SCOPES) - {"layers"}:
                out[op] = found.pop()
    return out


def op_dims(hlo_text: str) -> dict:
    """``{op: the dims of its (first) result shape}``, as the trace's op
    names carry them."""
    comps, _ = _parse(hlo_text)
    out = {}
    for instrs in comps.values():
        for op, rhs in instrs:
            m = _DIMS.search(rhs)
            out[op] = m.group(1) if m else ""
    return out


def load(path: str, span_names) -> tuple:
    """``(trace, dims)``: the trace as ``chipbench.trace.load`` reads it, with
    each program named by its module and the id the trace gives its runs
    (``jit_decode_k1(1234…)``), and ``{program: {op: dims}}`` of the ops
    its runs hold."""
    from jax.profiler import ProfileData

    base = trace.load(path, span_names)
    ops, modules, dims = {}, [], collections.defaultdict(dict)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        mods, raw = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name, plane.name)
                              for e in line.events)
            elif line.name == "XLA Ops":
                raw = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
        starts = [m[0] for m in mods]
        named = []
        for s, t, text in raw:
            op, _, rhs = text.partition(" = ")
            op = op.lstrip("%")
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= t else "?"
            m = _DIMS.search(rhs)
            dims[prog][op] = m.group(1) if m else ""
            named.append((s, t, f"{prog}:{op}"))
        if named:
            ops[plane.name] = sorted(named, key=lambda e: (e[0], -e[1]))
        modules.extend(mods)
    return trace.Trace(ops, sorted(modules), base.spans, base.offset_ns), dict(dims)


def describe(hlo_text: str) -> tuple:
    """``(module name, op_dims, scope_map)`` of one compiled program."""
    module = hlo_text.split("\n", 1)[0].split()[1].rstrip(",")
    return module, op_dims(hlo_text), scope_map(hlo_text)


def join(dims: dict, programs) -> dict:
    """``{program: scope map}``: each program on the trace (``dims`` as
    :func:`load` gives them) joined to the compiled program of its module
    name whose ops' shapes agree with it most; where two agree equally,
    every op counts as ``AMBIGUOUS``.  ``programs`` are :func:`describe`
    of every compiled program."""
    by_module = collections.defaultdict(list)
    for module, op_dims_, smap in programs:
        by_module[module].append((op_dims_, smap))
    out = {}
    for prog, seen in dims.items():
        cands = by_module.get(prog.split("(", 1)[0], [])
        score = [sum(d.get(op) == v for op, v in seen.items()) for d, _ in cands]
        best = [c for c, s in zip(cands, score) if s == max(score, default=None)]
        if len(best) == 1:
            out[prog] = best[0][1]
        elif best:
            out[prog] = {op: AMBIGUOUS for op in seen}
    return out


def _self_times(ops):
    """``{"program:op": self ns}``: each op's time less the ops it holds."""
    self_time = collections.Counter()
    stack = []  # (end, name) of enclosing ops
    for s, t, name in ops:
        while stack and stack[-1][0] <= s:
            stack.pop()
        self_time[name] += t - s
        if stack:
            self_time[stack[-1][1]] -= t - s
        stack.append((t, name))
    return self_time


def scope_time(tr: trace.Trace, scopes: dict) -> dict:
    """``{program: {scope: seconds}}`` over the whole trace, averaged over
    the chips that ran any op; ``scopes`` maps a program (as ``tr`` names
    it) to its scope map.  An op missing from its program's map counts
    under ``"?"``; ``BETWEEN`` holds the rest of each run."""
    out = collections.defaultdict(collections.Counter)
    for plane, ops in tr.ops.items():
        for name, ns in _self_times(ops).items():
            prog, op = name.rsplit(":", 1)
            out[prog][scopes.get(prog, {}).get(op, "?")] += ns
        starts = [o[0] for o in ops]
        for s, t, prog, run_plane in tr.modules:
            if run_plane == plane:
                inside = ops[bisect.bisect_left(starts, s):bisect.bisect_right(starts, t)]
                covered = sum(b - a for a, b in trace._union([(a, b) for a, b, _ in inside]))
                out[prog][BETWEEN] += (t - s) - covered
    chips = max(1, len(tr.ops))
    return {p: {k: v / chips * 1e-9 for k, v in c.items()} for p, c in out.items()}


def _segments(spans):
    """Non-overlapping ``(start, end, name)`` pieces of the host timeline,
    each owned by the innermost span open there (the spans of one thread
    nest)."""
    out, stack, cur = [], [], None

    def close_until(s):
        nonlocal cur
        while stack and stack[-1][0] <= s:
            end, name = stack.pop()
            if end > cur:
                out.append((cur, end, name))
            cur = max(cur, end)

    for s, t, name in sorted(spans, key=lambda e: (e[0], -e[1])):
        close_until(s)
        if stack:
            if s > cur:
                out.append((cur, s, stack[-1][1]))
            t = min(t, stack[-1][0])
        stack.append((t, name))
        cur = s
    close_until(float("inf"))
    return out


def idle_by_span(tr: trace.Trace, win: tuple) -> dict:
    """``{span: seconds}`` of device idle time inside ``win`` (device ns),
    each idle interval split by overlap with the innermost host span open
    at each instant; ``host`` where none but the window's was.  Averaged
    over the chips that ran any op: with the busy time it tiles the
    window."""
    lo, hi = win
    segs = _segments([s for s in tr.spans if s[2] != trace.WINDOW_SPAN])
    seg_starts = [s[0] for s in segs]
    out = collections.Counter()
    for ops in tr.ops.values():
        merged = trace._union(trace._clip([(s, t) for s, t, _ in ops], lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            a, b = g0 + tr.offset_ns, g1 + tr.offset_ns
            owned = 0.0
            i = max(0, bisect.bisect_right(seg_starts, a) - 1)
            while i < len(segs) and segs[i][0] < b:
                s, t, name = segs[i]
                ov = min(t, b) - max(s, a)
                if ov > 0:
                    out[name] += ov
                    owned += ov
                i += 1
            out["host"] += (b - a) - owned
    chips = max(1, len(tr.ops))
    return {k: v / chips * 1e-9 for k, v in out.items() if v > 0}


def span_count(tr: trace.Trace, name: str, win: tuple) -> int:
    """Host spans named ``name`` that started inside ``win`` (device ns)."""
    lo, hi = win[0] + tr.offset_ns, win[1] + tr.offset_ns
    return sum(1 for s, _, n in tr.spans if n == name and lo <= s < hi)
