"""A whole run with the timed path broken underneath must come out not
correct.  The device check is skipped; the rest of ``run.measure`` runs as
on the chip, at tiny size, with ``serve.decode_chunk`` (the program's
decode path that the window drives) replaced by a faulty one.  The
faults a serving cell can have: a step that returns its state unchanged,
and a token altered where it is produced.  (Half a batch left out of a
mean, and an exchange between chips, belong to training and to several
chips; no cell here has either.)"""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from bench_helpers import PEAKS, tiny_cell

from chipbench import run
from repro.launch import serve

WORKLOADS = Path(run.ROOT) / "chipbench" / "workloads"


def _state_unchanged(orig):
    def decode_chunk(model, k):
        f = orig(model, k)

        @jax.jit
        def run(params, token, states, pos):
            token2, _, pos2, toks = f(params, token, states, pos)
            return token2, states, pos2, toks

        return run
    return decode_chunk


def _token_altered(orig):
    def decode_chunk(model, k):
        f = orig(model, k)
        V = model.cfg.vocab_size

        @jax.jit
        def run(params, token, states, pos):
            token2, states2, pos2, toks = f(params, token, states, pos)
            return (token2 + 1) % V, states2, pos2, (toks + 1) % V

        return run
    return decode_chunk


@pytest.mark.parametrize("arch,cell_name", [("qwen2_7b", "qwen2_7b.decode"), ("qwen2_7b", "qwen2_7b.prefill")])
@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
def test_broken_decode_is_not_correct(arch, cell_name, fault, monkeypatch):
    # held to the cell's own limits
    limits = json.loads((WORKLOADS / f"{cell_name}.json").read_text())["check"]["limits"]
    cell = tiny_cell(arch, 1.0)
    cell["check"]["limits"] = limits
    monkeypatch.setattr(serve, "decode_chunk", fault(serve.decode_chunk))
    out = run.measure(cell, 2**33 + 99, 0.5, False, t_start=time.perf_counter(), peaks=PEAKS)
    checks = out["result"]["checks"]
    assert out["result"]["correct"] is False
    assert any(checks[name]["value"] > limit for name, limit in limits.items())
    assert checks["window_compiles"]["value"] == 0


def test_decode_fault_wrappers_change_the_output():
    # the wrappers really break the step they wrap
    class M:
        class cfg:
            vocab_size = 10

    def orig(model, k):
        return jax.jit(lambda p, t, s, pos: (t + 2, s * 2, pos + k, jnp.stack([t] * k)))

    t, s, pos = jnp.ones((2, 1), jnp.int32), jnp.ones(3), jnp.int32(4)
    _, s2, _, _ = _state_unchanged(orig)(M, 2)(None, t, s, pos)
    assert bool((s2 == s).all())
    t2, _, _, toks = _token_altered(orig)(M, 2)(None, t, s, pos)
    assert int(t2[0, 0]) == 4 and int(toks[0, 0, 0]) == 2
