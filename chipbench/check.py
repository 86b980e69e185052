"""The comparison that decides ``correct`` for a served model.

After the window has closed and the program's state is freed, a sample of
the greedy requests, drawn from the seed and holding the longest of them,
is scored by the plain reference: one forward pass over each prompt
followed by the tokens the client received for it.  A request the server
finished is scored whole; one still streaming at the close is scored on
the prefix it had served.  At each served position the gap is how far the
served token's reference logit lies below the reference's best logit there
(0 where it is the reference's own choice).  ``verdict`` holds the widest
gap over the sample, the mean gap over its positions, the number of
positions scored and the stream errors to their limits.  The widest gap
catches one token gone wrong; the mean gap a drift that moves every
position a little, each too little for the widest gap to see.

The control reads the same positions with the reference computed one
precision lower (int4 activations, ``act_bits=4``) in the program's place:
the gap of the token that the control puts first.  Its gaps go through the
same ``verdict``.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

import jax.numpy as jnp
import numpy as np

__all__ = ["scorable", "pick", "gaps", "verdict"]


def scorable(r) -> bool:
    """A greedy request whose stream is sound: finished whole, or still
    streaming when the window closed, with at least one token."""
    if not r.req.greedy or r.error is not None or r.status != 200 or not r.tokens:
        return False
    return r.ok if r.done else len(r.tokens) <= r.req.max_tokens


def pick(records: Iterable, seed: int, want_tokens: int, max_requests: int) -> List:
    """The longest scorable request, then others in an order drawn from
    the seed, until the sample serves ``want_tokens`` tokens or holds
    ``max_requests`` requests."""
    done = [r for r in records if scorable(r)]
    if not done:
        return []
    done.sort(key=lambda r: r.req.index)
    longest = max(done, key=lambda r: (len(r.req.prompt) + len(r.tokens), -r.req.index))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out, served = [longest], len(longest.tokens)
    for i in order:
        if served >= want_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        served += len(rest[i].tokens)
    return out


def gaps(reference, params, config: dict, prompt, served,
         control: bool = False) -> Dict[str, np.ndarray]:
    """Per served position, the gap below the reference's best logit of
    the served token (``"program"``) and, where ``control`` is set, of the
    token the control puts first (``"control"``)."""
    toks = list(prompt) + list(served)
    n, start = len(served), len(prompt) - 1
    ref = reference.logits_rows(params, config, toks, start, n)[:n]
    idx = jnp.arange(n)
    best = ref.max(axis=-1)
    out = {"program": np.asarray(best - ref[idx, jnp.asarray(served)])}
    if control:
        low = reference.logits_rows(params, config, toks, start, n, act_bits=4)[:n]
        out["control"] = np.asarray(best - ref[idx, low.argmax(axis=-1)])
    return out


def verdict(per_request: List[np.ndarray], failed: int, ck: dict) -> Tuple[bool, dict]:
    """(correct, checks) from the gaps of each request in the sample and
    the number of failed streams; ``checks`` maps each number compared to
    its value and limit."""
    allg = np.concatenate(per_request) if per_request else np.zeros(0)
    widest = float(allg.max()) if allg.size else None  # nothing scored
    mean = float(allg.mean()) if allg.size else None
    checks = {
        "served_gap": {"value": widest, "limit": ck["served_gap_limit"]},
        "mean_gap": {"value": mean, "limit": ck["mean_gap_limit"]},
        "stream_errors": {"value": failed, "limit": 0},
        "sampled_tokens": {"value": int(allg.size), "limit": ck["min_tokens"]},
    }
    correct = (allg.size >= max(ck["min_tokens"], 1) and failed == 0
               and widest <= ck["served_gap_limit"] and mean <= ck["mean_gap_limit"])
    return correct, checks
