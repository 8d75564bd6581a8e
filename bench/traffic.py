"""The one traffic generator: every mix is a data file in `traffic/`.

A mix file gives the loop (`closed`: each of `clients` sends its next
request when its last one completes; `clients` may be a number or
"max_slots"), the prompt and output lengths as log-uniform ranges, the
block size of the balanced draw, and the sampling (`greedy`).

Lengths are drawn in balanced blocks: each block of `block` requests holds
the same `block` quantiles of the range, in an order drawn from the seed.
So every seed serves the same lengths, only in another order, and two
seeds do the same work. Token ids are uniform over the vocabulary.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def quantiles(dist: dict, n: int) -> np.ndarray:
    if dist["dist"] != "loguniform":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    lo, hi = np.log(dist["min"]), np.log(dist["max"])
    u = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(lo + u * (hi - lo))).astype(np.int64)


class Requests:
    """The seeded stream of requests of one run, in the order they are sent."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng([seed, 7])
        self.block = int(mix["block"])
        self._prompts = quantiles(mix["prompt_tokens"], self.block)
        self._outputs = quantiles(mix["output_tokens"], self.block)
        self._pending: list = []

    def __next__(self):
        """(prompt int32 array, max_new_tokens)."""
        if not self._pending:
            p = self.rng.permutation(self._prompts)
            o = self.rng.permutation(self._outputs)
            self._pending = list(zip(p.tolist(), o.tolist()))[::-1]
        plen, olen = self._pending.pop()
        prompt = self.rng.integers(0, self.vocab, plen, dtype=np.int32)
        return prompt, int(olen)


def clients(mix: dict, max_slots: int) -> int:
    c = mix["clients"]
    return max_slots if c == "max_slots" else int(c)


def max_tokens(mix: dict) -> int:
    """Prompt plus output of the longest request the mix can send."""
    return int(mix["prompt_tokens"]["max"]) + int(mix["output_tokens"]["max"])
