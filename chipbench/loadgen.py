"""Open-loop request schedule drawn from a traffic file and a seed.

One general generator for every traffic mix; a mix is only parameters:

    {"rate_per_s": 1.2,            # Poisson arrivals, fixed per cell
     "ramp_s": 20,                 # arrivals start this long before the window
     "prompt_len": {"grid": [...], "median": 512, "sigma": 0.8},
     "max_tokens": {"median": 160, "sigma": 0.7, "min": 32, "max": 512},
     "sampling": {"temperature": 1.0, "top_p": 1.0, "top_k": 0,
                  "greedy_share": 0.25}}

Prompt lengths are a lognormal snapped to the grid (nearest point in log
space), so set-up can warm every prefill shape.  To keep runs of different
seeds alike, the ramp and the window are drawn apart, each holding
``rate * duration`` requests, and every seed gets the SAME prompt lengths,
output lengths and inter-arrival gaps in each, in the SAME order —
stratified quantiles of the distributions, the gaps scaled to span the
segment, shuffled once by a fixed generator — so the seed draws only the
token ids and the per-request sampling seeds.  (A per-seed order let the
seed choose which requests finish inside the window, and with them how
many admissions stall every stream there.)  Greedy requests sit at fixed
places in the arrival order, spread evenly (every second one for a share
of 0.5), so every seed has the same mix of greedy and sampled rows at
every point of the run.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np

__all__ = ["Request", "schedule", "grid_lengths"]


@dataclasses.dataclass
class Request:
    index: int
    due_s: float  # offset from the start of arrivals
    prompt: List[int]
    max_tokens: int
    temperature: float
    top_p: float
    top_k: int
    seed: int

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0

    def payload(self) -> dict:
        return {"prompt": self.prompt, "max_tokens": self.max_tokens,
                "stream": True, "temperature": self.temperature,
                "top_p": self.top_p, "top_k": self.top_k, "seed": self.seed}


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(u: np.ndarray, median: float, sigma: float) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    return median * np.exp(sigma * z)


def grid_lengths(spec: dict, n: int) -> np.ndarray:
    """n prompt lengths: lognormal quantiles snapped to the grid."""
    grid = np.asarray(sorted(spec["grid"]), float)
    x = _lognormal(_quantiles(n), spec["median"], spec["sigma"])
    idx = np.abs(np.log(x)[:, None] - np.log(grid)[None, :]).argmin(axis=1)
    return grid[idx].astype(int)


def output_lengths(spec: dict, n: int) -> np.ndarray:
    x = _lognormal(_quantiles(n), spec["median"], spec["sigma"])
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def count(traffic: dict, seconds: float) -> int:
    """Requests due from the start of the ramp to the close of the window."""
    return sum(n for n, _ in _segments(traffic, seconds))


def _segments(traffic: dict, seconds: float):
    rate = traffic["rate_per_s"]
    return [(max(1, round(rate * d)), d) for d in (traffic["ramp_s"], seconds) if d > 0]


def greedy_flags(share: float, n: int) -> np.ndarray:
    """Request i of n is greedy where a running count of ``share`` per
    request steps up: about ``share * n`` of them, evenly spread, the first
    one greedy wherever the share is above 0."""
    i = np.arange(n + 1)
    steps = np.floor(i * share + (1 - share) * (share > 0))
    return np.diff(steps) > 0


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """The run's requests, in due order: the ramp's, then the window's."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(0)  # one order of the sizes, for every seed
    smp = traffic["sampling"]
    out: List[Request] = []
    start = 0.0
    for n, span in _segments(traffic, seconds):
        u = _quantiles(n)
        gaps = order.permutation(-np.log1p(-u))
        due = start + span * (np.cumsum(gaps) - gaps[0]) / gaps.sum()
        plens = order.permutation(grid_lengths(traffic["prompt_len"], n))
        olens = order.permutation(output_lengths(traffic["max_tokens"], n))
        greedy = greedy_flags(smp["greedy_share"], n)
        for i in range(n):
            out.append(Request(
                index=len(out), due_s=float(due[i]),
                prompt=[int(t) for t in rng.integers(0, vocab, int(plens[i]))],
                max_tokens=int(olens[i]),
                temperature=0.0 if greedy[i] else float(smp["temperature"]),
                top_p=float(smp["top_p"]), top_k=int(smp["top_k"]),
                seed=int(rng.integers(0, 2**31 - 1)),
            ))
        start += span
    return out


def warmup(traffic: dict, seed: int, vocab: int, max_tokens: int = 4) -> List[Request]:
    """One short request per prompt length of the grid and per decoding
    kind the mix uses (greedy, sampled): every shape the window will see."""
    rng = np.random.default_rng([seed, 1])
    smp = traffic["sampling"]
    kinds = []
    if smp["greedy_share"] > 0:
        kinds.append(0.0)
    if smp["greedy_share"] < 1:
        kinds.append(float(smp["temperature"]))
    out = []
    for plen in sorted(traffic["prompt_len"]["grid"]):
        for temp in kinds:
            out.append(Request(
                index=len(out), due_s=0.0,
                prompt=[int(t) for t in rng.integers(0, vocab, int(plen))],
                max_tokens=max_tokens, temperature=temp,
                top_p=float(smp["top_p"]), top_k=int(smp["top_k"]),
                seed=int(rng.integers(0, 2**31 - 1)),
            ))
    return out
