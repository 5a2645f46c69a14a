"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the greedy requests the window finished — drawn from the seed, the longest
of them always in it — is run through the configuration's plain reference,
one request at a time: the prompt followed by the tokens served for it.
Three numbers are compared, each with a limit of its own
(``limits/<cell>.json``):

* ``token_logit_gap`` — the widest gap, over every served token of the
  sample, by which the served token's reference logit lies below the
  reference's best logit at that position. The first token comes from the
  prefill, the rest from decode steps through the cache, so a fault in
  either shows. Greedy tokens only: a sampled token may lie anywhere.
* ``phi_gap`` — the largest relative distance ``|φ - φ_ref| / |φ_ref|``
  between the hidden state φ the program handed the head (the last prompt
  token's, after the final norm) and the reference's.
* ``head_cdf_gap`` — the largest difference, over the sample and the
  levels q, between q and the reference head's CDF, on the program's φ, at
  the length quantile served for q. The reference head reads the same φ as
  the Pallas head did, so this number is the head's alone; ``phi_gap``
  holds its input. It is measured in probability, not in tokens: a quantile
  in a bin of little mass moves by many tokens for a tiny change in the CDF.

The control (``mode="fp8"``) is the reference put in the program's place at
the next lower precision: its first choice at each position of the same
prompts and tokens, its φ, and its quantiles from its φ, read against the
reference the same way.
"""

from __future__ import annotations

import numpy as np

NAMES = ("token_logit_gap", "phi_gap", "head_cdf_gap")


def cdf_at(probs, edges, x):
    """The piecewise-linear CDF of bin probabilities ``probs`` (K,) over
    ``edges`` (K + 1,), at lengths ``x``."""
    cdf = np.concatenate([[0.0], np.cumsum(probs, dtype=np.float64)])
    return np.interp(x, np.asarray(edges, np.float64), cdf)


def sample(served, n: int, seed: int):
    """Indices into ``served`` (a list of dicts with ``length``), of up to
    ``n`` requests: the longest, then others drawn from ``seed``."""
    if not served:
        return []
    lengths = np.array([r["length"] for r in served])
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 7])
    order = rng.permutation(len(served))
    first = order[np.argmax(lengths[order])]
    rest = [i for i in order if i != first]
    return [int(first)] + [int(i) for i in rest[: n - 1]]


def compare(ref, cfg, mix, W, head_w, edges, served, seed, controls=()):
    """Readings of the sampled requests: {"program": {...}, <control>: {...}}.
    ``ref`` is the configuration's reference module.

    ``served``: greedy requests, each a dict with ``prompt`` (P,) ids,
    ``tokens`` (max_new,) as served, ``length``, ``phi`` (d,) as the head
    got it and ``quantiles`` (Q,)."""
    picks = sample(served, int(mix["check_requests"]), seed)
    if not picks:
        raise ValueError("the window finished no greedy request to check")
    max_new = int(mix["max_new"])
    S = int(mix["prompt_tokens"][1]) + max_new
    qs = tuple(float(q) for q in mix["quantiles"])
    out = {name: dict.fromkeys(NAMES, 0.0) for name in ("program",) + tuple(controls)}
    n_tokens = 0
    for i in picks:
        r = served[i]
        P, n = len(r["prompt"]), int(r["length"])
        seq = np.zeros(S, np.int32)
        seq[:P] = r["prompt"]
        seq[P:P + n] = r["tokens"][:n]
        read = P - 1 + np.arange(max_new)
        logits, phi = ref.forward(W, cfg, seq, read, P - 1)
        logits = np.asarray(logits)[:n]
        best = logits.max(axis=-1)
        served_logit = logits[np.arange(n), r["tokens"][:n]]
        gaps = {"program": best - served_logit}
        phi = np.asarray(phi, np.float64)
        rel = lambda x: float(np.linalg.norm(np.asarray(x, np.float64) - phi)
                              / np.linalg.norm(phi))
        phis = {"program": rel(r["phi"])}
        level = lambda p, x: np.abs(cdf_at(p, edges, np.asarray(x, np.float64)) - qs)
        p_prog = np.asarray(ref.head_quantiles(head_w, r["phi"][None], edges, qs)[0])[0]
        dq = {"program": level(p_prog, r["quantiles"])}
        for mode in controls:
            c_logits, c_phi = ref.forward(W, cfg, seq, read, P - 1, mode=mode)
            pick = np.asarray(c_logits)[:n].argmax(axis=-1)
            gaps[mode] = best - logits[np.arange(n), pick]
            phis[mode] = rel(c_phi)
            c_q = ref.head_quantiles(head_w, c_phi[None], edges, qs, mode=mode)[1]
            p_c = np.asarray(ref.head_quantiles(head_w, c_phi[None], edges, qs)[0])[0]
            dq[mode] = level(p_c, np.asarray(c_q)[0])
        for name in out:
            o = out[name]
            o["token_logit_gap"] = max(o["token_logit_gap"], float(np.max(gaps[name])))
            o["phi_gap"] = max(o["phi_gap"], phis[name])
            o["head_cdf_gap"] = max(o["head_cdf_gap"], float(np.max(dq[name])))
        n_tokens += n
    for name in out:
        out[name]["n_requests"], out[name]["n_tokens"] = len(picks), n_tokens
    return out
