"""The one traffic generator: reads a mix file from ``traffic/`` and makes
the jobs of a run from its seed.

A mix file is JSON with these keys:

* ``loop``: ``"closed"`` — one client sends its next job when the last has
  finished (the program has no request queue to fill from an open loop);
* ``batch``: requests per job, served in lockstep (one prompt length);
* ``prompt_mix``: ``[[length, count], ...]`` — one block of jobs holds each
  length ``count`` times; every block is the same multiset in an order the
  seed sets, so every seed serves the same sizes and any whole number of
  blocks has the stated mix exactly;
* ``gen``: tokens decoded after the first, which the prefill produces;
* ``max_len``: cache slots per row, at least the longest prompt plus ``gen``.

Prompt tokens are uniform over the vocabulary, drawn per job from the seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _words(seed: int) -> list:
    s = int(seed) % (1 << 64)
    return [s & 0xFFFFFFFF, s >> 32]


@dataclasses.dataclass(frozen=True)
class Traffic:
    spec: dict
    seed: int
    vocab: int

    def __post_init__(self):
        spec = self.spec
        if spec.get("loop") != "closed":
            raise ValueError(f"only closed-loop mixes are generated, not {spec.get('loop')!r}")
        if self.max_len < max(self.buckets) + self.gen:
            raise ValueError(f"max_len {self.max_len} < longest prompt + gen")

    @property
    def batch(self) -> int:
        return int(self.spec["batch"])

    @property
    def gen(self) -> int:
        return int(self.spec["gen"])

    @property
    def max_len(self) -> int:
        return int(self.spec["max_len"])

    @property
    def block(self) -> list:
        return [int(n) for n, c in self.spec["prompt_mix"] for _ in range(int(c))]

    @property
    def buckets(self) -> list:
        return sorted(set(self.block))

    def prompt_len(self, job: int) -> int:
        block = self.block
        b, i = divmod(int(job), len(block))
        order = np.random.default_rng(_words(self.seed) + [1, b]).permutation(len(block))
        return block[order[i]]

    def prompts(self, job: int) -> np.ndarray:
        """The ``(batch, prompt_len)`` int32 token ids of job ``job``."""
        rng = np.random.default_rng(_words(self.seed) + [2, int(job)])
        return rng.integers(0, self.vocab, (self.batch, self.prompt_len(job)), dtype=np.int32)
