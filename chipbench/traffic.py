"""The one traffic generator: reads a mix's parameters, makes its requests.

A mix (``traffic/<mix>.json``) gives:

* ``entry`` — the adapter under ``entries/`` that serves it;
* ``loop: "closed"`` with ``clients`` — B clients, each sending its next
  request as soon as its reply is in hand; the static engine serves the B
  requests of a round as one batch;
* ``prompt_tokens: [lo, hi]`` — each request's prompt length is drawn
  uniformly over ``[lo, hi]`` from the seed, anew every round, as the
  ``random`` dataset of vLLM's serving benchmark draws its input lengths
  (``source`` names it); prompts are right-padded to ``hi``;
* ``max_new`` — tokens generated per request: the static engine has one
  limit for the whole batch, and random weights all but never emit EOS, so
  every request runs to it, as that benchmark's requests do with EOS ignored;
* ``temperature``; every ``greedy_every``-th round (the first one of the
  window included) is served greedily, so that its tokens can be checked
  against the reference;
* ``check_requests`` — how many greedy requests the check compares;
* ``quantiles`` — the length quantiles each reply carries.

Prompts are right-padded to ``hi``, so the device runs the same programs at
the same shapes whatever lengths are drawn; the seed changes only which
tokens are real.
"""

from __future__ import annotations

import numpy as np

WARMUP_ROUND = -1


class Mix:
    def __init__(self, spec: dict, vocab: int, seed: int):
        self.spec = spec
        self.B = int(spec["clients"])
        self.lo, self.hi = (int(x) for x in spec["prompt_tokens"])
        self.max_new = int(spec["max_new"])
        self.vocab = int(vocab)
        self.seed = int(seed)
        if spec.get("loop") != "closed":
            raise ValueError(f"unknown loop {spec.get('loop')!r}")
        if not 1 <= self.lo <= self.hi:
            raise ValueError(f"bad prompt range {spec['prompt_tokens']}")

    def greedy(self, i: int) -> bool:
        return i >= 0 and i % int(self.spec["greedy_every"]) == 0

    def round(self, i: int):
        """Round ``i``'s prompts (B, hi) int32, right-padded with 0, and
        their lengths (B,)."""
        rng = np.random.default_rng([self.seed & (2**63 - 1), i + 1])
        plens = rng.integers(self.lo, self.hi + 1, self.B)
        prompts = rng.integers(0, self.vocab, (self.B, self.hi), dtype=np.int32)
        prompts[np.arange(self.hi)[None, :] >= plens[:, None]] = 0
        return prompts, plens
