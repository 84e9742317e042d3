"""The one traffic generator: reads a mix's parameters and makes its work.

A mix is a JSON file under ``bench/traffic/`` (its ``kind`` says which of
the arrival processes below it uses).  Sizes and gaps are quantiles of the
stated distributions, put in an order drawn from the mix's own
``order_seed``: every run replays the same schedule of lengths and
arrivals, and ``--seed`` draws the token ids.  The seed changes the content,
never the work, so that a tail over a few dozen requests repeats from run
to run.

Kinds:

* ``poisson`` — open loop: independent requests at ``rate_per_s``;
* ``sessions`` — multi-turn chat: sessions start at ``session_rate_per_s``,
  each turn's prompt is a shared system prompt plus the conversation so
  far, and the next turn is due a think time after the answer completes;
* ``tune`` — a kernel-tuning job at one shape (no requests).
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _u(n: int) -> np.ndarray:
    """n evenly spaced probabilities, (i + 0.5) / n."""
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """n quantiles of a lognormal length distribution (median, sigma),
    clipped to [min, max]."""
    z = np.array([_NORMAL.inv_cdf(p) for p in _u(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def uniform_lengths(lo: int, hi: int, n: int) -> np.ndarray:
    return np.floor(lo + _u(n) * (hi - lo + 1)).astype(np.int64)


def exponential_gaps(mean_s: float, n: int) -> np.ndarray:
    return -np.log1p(-_u(n)) * mean_s


@dataclasses.dataclass
class Request:
    due: float                 # seconds after the window opens
    prompt: np.ndarray
    max_new: int


def order(mix: dict, part: str) -> np.random.Generator:
    """The generator that orders one part of a mix's schedule."""
    return np.random.default_rng([mix["order_seed"], sum(map(ord, part))])


def poisson(mix: dict, seconds: float, vocab: int, rng,
            part: str = "window") -> list[Request]:
    """Open-loop requests due within ``seconds``; ``rng`` draws tokens."""
    o = order(mix, part)
    n = max(1, round(mix["rate_per_s"] * seconds))
    gaps = o.permutation(exponential_gaps(1.0 / mix["rate_per_s"], n))
    plens = o.permutation(lognormal_lengths(mix["prompt_tokens"], n))
    olens = o.permutation(lognormal_lengths(mix["output_tokens"], n))
    due = np.cumsum(gaps) - gaps[0]
    return [Request(float(t), rng.integers(0, vocab, p, dtype=np.int32),
                    int(m)) for t, p, m in zip(due, plens, olens)
            if t < seconds]


@dataclasses.dataclass
class Session:
    start: float
    system: int                # which shared system prompt
    users: list[np.ndarray]    # one message per turn
    outputs: list[int]         # output budget per turn
    thinks: list[float]        # seconds from an answer to the next turn


def sessions(mix: dict, seconds: float, vocab: int, rng,
             part: str = "window"):
    """(system prompts, sessions) for a ``sessions`` mix; ``rng`` draws
    tokens."""
    o = order(mix, part)
    n = max(1, round(mix["session_rate_per_s"] * seconds))
    systems = [rng.integers(0, vocab, mix["system_tokens"], dtype=np.int32)
               for _ in range(mix["system_prompts"])]
    lo, hi = mix["turns"]
    turns = o.permutation(uniform_lengths(lo, hi, n))
    total = int(turns.sum())
    ulens = o.permutation(uniform_lengths(*mix["user_tokens"], total))
    olens = o.permutation(lognormal_lengths(mix["output_tokens"], total))
    thinks = o.permutation(exponential_gaps(mix["think_s_mean"], total))
    gaps = o.permutation(exponential_gaps(1.0 / mix["session_rate_per_s"],
                                          n))
    starts = np.cumsum(gaps) - gaps[0]
    systems_of = o.permutation(np.arange(n) % mix["system_prompts"])
    out, k = [], 0
    for i in range(n):
        t = int(turns[i])
        out.append(Session(
            start=float(starts[i]), system=int(systems_of[i]),
            users=[rng.integers(0, vocab, int(u), dtype=np.int32)
                   for u in ulens[k:k + t]],
            outputs=[int(x) for x in olens[k:k + t]],
            thinks=[float(x) for x in thinks[k:k + t]]))
        k += t
    return systems, [s for s in out if s.start < seconds]
