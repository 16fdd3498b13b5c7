"""Per-layer metrics from the program's named scopes and spans.

Each reads the reductions of ``chipbench/layers.py`` of one traced window:
``scope_s`` (``layers.scope_time``: ``{program: {scope: seconds}}``) and
``idle`` (``layers.idle_by_span``: ``{span: seconds}``), with what the
window served: ``positions``, the absolute position of every decode step
(one per token a chunk decoded), and ``seqs``, the prompt length of every
prefill.  Device time is summed over the programs of one kind (decode or
prefill), named by their HLO modules.  A metric whose scope or span the
trace does not hold reads ``None``.
"""
from __future__ import annotations

from chipbench import layer_counts

#: spans of the online tuner and its drift detector
ROUTER_SPANS = ("tuner.begin", "tuner.observe", "tuner.tell", "tuner.commit",
                "tuner.drift_reset", "drift.observe")
DISPATCH_SPANS = ("serve.dispatch", "serve.wait")


def seconds(scope_s: dict, modules, scopes=None) -> float:
    """Device seconds of ``scopes`` (default: all) in the programs of the
    HLO ``modules``."""
    return sum(v for prog, by in scope_s.items() if prog.split("(", 1)[0] in modules
               for s, v in by.items() if scopes is None or s in scopes)


def _share(need_s: float, dev_s: float):
    return 100.0 * need_s / dev_s if dev_s and need_s else None


def ffn_roofline(scope_s, modules, cfg, positions, peaks):
    """FFN weight bytes of the steps at peak HBM bandwidth over the ``ffn``
    scope's device time, in percent."""
    need = len(positions) * layer_counts.ffn_weight_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return _share(need, seconds(scope_s, modules, ("ffn",)))


def attn_roofline(scope_s, modules, cfg, batch, positions, peaks):
    """Attention weight bytes plus the valid cache entries read and the new
    ones written, at peak HBM bandwidth, over the device time of the
    ``attn`` scope with the ``kv_write`` inside it, in percent."""
    need = sum(layer_counts.attn_decode_bytes(cfg, batch, p) for p in positions)
    return _share(need / peaks["hbm_bytes_per_s"],
                  seconds(scope_s, modules, ("attn", "kv_write")))


def lm_head_roofline(scope_s, modules, cfg, positions, peaks):
    """LM head weight bytes at peak HBM bandwidth over the device time of
    the ``lm_head`` and ``sample`` (argmax) scopes, in percent."""
    need = len(positions) * layer_counts.head_weight_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return _share(need, seconds(scope_s, modules, ("lm_head", "sample")))


def layer_state_share(scope_s, modules):
    """Share of the programs' device time under ``layers`` and outside every
    block scope: the layer scan's slicing and stacking of per-layer state,
    in percent."""
    return _share(seconds(scope_s, modules, ("layers",)), seconds(scope_s, modules))


def idle_ms_per(idle: dict, spans, count: int):
    """Device idle milliseconds overlapping ``spans``, per ``count``."""
    if not count or not any(s in idle for s in spans):
        return None
    return 1e3 * sum(idle.get(s, 0.0) for s in spans) / count


def attn_mfu(scope_s, modules, cfg, batch, seqs, peaks):
    """Attention operations of the prefills (``layer_counts``) over the
    device time of the ``attn`` scope (with ``kv_write``) at peak FLOP/s,
    in percent."""
    flops = sum(layer_counts.attn_prefill_flops(cfg, batch, s) for s in seqs)
    return _share(flops / peaks["flops_bf16"], seconds(scope_s, modules, ("attn", "kv_write")))


def decode_metrics(scope_s, idle, *, modules, cfg, batch, positions, peaks,
                   chunks: int, routed: int) -> dict:
    """Every decode metric of one traced window by name, with the parts of
    ``dispatch_idle_ms.decode``."""
    return {
        "ffn_roofline.decode": ffn_roofline(scope_s, modules, cfg, positions, peaks),
        "attn_roofline.decode": attn_roofline(scope_s, modules, cfg, batch, positions, peaks),
        "lm_head_roofline.decode": lm_head_roofline(scope_s, modules, cfg, positions, peaks),
        "layer_state_share.decode": layer_state_share(scope_s, modules),
        "dispatch_idle_ms.decode": idle_ms_per(idle, DISPATCH_SPANS, chunks),
        "dispatch_idle_ms.decode.dispatch": idle_ms_per(idle, DISPATCH_SPANS[:1], chunks),
        "dispatch_idle_ms.decode.wait": idle_ms_per(idle, DISPATCH_SPANS[1:], chunks),
        "router_idle_ms.decode": idle_ms_per(idle, ROUTER_SPANS, routed),
    }
