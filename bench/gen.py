"""Open-loop request traffic from a mix file and a seed.

Adapted from the program's ``repro.serve.trace`` generator (exponential
or Pareto inter-arrival gaps, a shared-system-prompt population,
exponential length draws clipped to a range), with a uniform length
draw added.  It lives here so that the yardstick cannot move with the
program.

A mix file (``bench/traffic/<mix>.json``) fixes the schedule: the
``n = round(rate * seconds)`` inter-arrival gaps, the request sizes and
which requests open with which shared system prompt are drawn from the
mix's own ``population_seed``, in that order.  The run's ``--seed``
draws every token id (the system prompts' and the bodies').  So every
seed offers the same work at the same times, and two runs of one seed
offer the same requests: with ~40 requests in a window, a seed that only
reordered the sizes moved the tokens a window delivers by a third.  The
gaps are scaled so that they sum to ``seconds``: every request is due
inside the window, and the offered rate is exactly ``n / seconds``.

Mix keys:
  rate_per_s           offered requests per second
  arrival              {"kind": "poisson"} | {"kind": "pareto", "shape": a}
  prompt               length draw of the prompt body (see ``_draw``)
  output               length draw of the output budget
  shared_prefix        {"share": f, "prompts": k, "tokens": m} or absent:
                       a share f of the requests opens with one of k
                       system prompts of m tokens, before the body
  population_seed      seed of the fixed population of gaps and sizes
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    """One request: when it is due (seconds from the window's start),
    its prompt and its output budget."""
    rid: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int
    shared: int            # index of its system prompt, -1 for none


def _draw(rng: np.random.Generator, d: Dict, n: int) -> np.ndarray:
    """n lengths from {"dist": "exponential", "mean", "min", "max"} (the
    draw rounds down and clips to [min, max]) or {"dist": "uniform",
    "min", "max"} (integers, both ends included)."""
    lo, hi = int(d.get("min", 1)), int(d["max"])
    if d["dist"] == "exponential":
        x = rng.exponential(float(d["mean"]), n)
        return np.clip(x.astype(np.int64), lo, hi)
    if d["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    raise ValueError(f"unknown length distribution {d['dist']!r}")


def _gaps(rng: np.random.Generator, arrival: Dict, rate: float,
          n: int) -> np.ndarray:
    """n inter-arrival gaps of mean 1/rate: exponential (Poisson
    arrivals) or Pareto of the given shape (bursts)."""
    mean_gap = 1.0 / rate
    if arrival["kind"] == "poisson":
        return rng.exponential(mean_gap, n)
    if arrival["kind"] == "pareto":
        a = float(arrival["shape"])
        if a <= 1.0:
            raise ValueError("a Pareto shape must exceed 1 for a finite "
                             "mean gap")
        xm = mean_gap * (a - 1.0) / a
        return xm * (1.0 + rng.pareto(a, n))
    raise ValueError(f"unknown arrival kind {arrival['kind']!r}")


def population_size(mix: Dict, seconds: float) -> int:
    return max(1, int(round(float(mix["rate_per_s"]) * seconds)))


def generate(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    """The requests of one run, in order of their due times."""
    n = population_size(mix, seconds)
    pop = np.random.default_rng(int(mix["population_seed"]))
    gaps = _gaps(pop, mix["arrival"], float(mix["rate_per_s"]), n)
    prompt_len = _draw(pop, mix["prompt"], n)
    out_len = _draw(pop, mix["output"], n)
    sp = mix.get("shared_prefix")
    shared = np.full(n, -1, np.int64)
    if sp:
        k = int(round(float(sp["share"]) * n))
        shared[pop.permutation(n)[:k]] = np.arange(k) % int(sp["prompts"])
    gaps = gaps * (seconds / gaps.sum())
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])

    rng = np.random.default_rng(int(seed))
    systems = [rng.integers(0, vocab, int(sp["tokens"]), dtype=np.int64)
               for _ in range(int(sp["prompts"]))] if sp else []
    reqs = []
    for i in range(n):
        body = rng.integers(0, vocab, int(prompt_len[i]), dtype=np.int64)
        if shared[i] >= 0:
            body = np.concatenate([systems[shared[i]], body])
        reqs.append(Req(rid=i, due_s=float(due[i]),
                        prompt=body.astype(np.int32),
                        max_new_tokens=int(out_len[i]),
                        shared=int(shared[i])))
    return reqs


def longest_request(mix: Dict) -> int:
    """Prompt plus output budget of the longest request the mix can
    make, in tokens (sizes the cache)."""
    sp = mix.get("shared_prefix")
    return (int(mix["prompt"]["max"]) + int(mix["output"]["max"]) +
            (int(sp["tokens"]) if sp else 0))
