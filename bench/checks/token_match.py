"""Correctness of served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the run finished is drawn from the seed, the one with the
most served tokens always among them, until it holds ``tokens`` served
tokens or ``max_requests`` requests (``bench/cells/<workload>.json``).
The reference runs once over the sampled prompts with their served
tokens and gives the logits at every position that produced a served
token.  A served token's gap is how far its reference logit lies below
the reference's best there (0 where the program served the reference's
best).  One number is compared:

    mean_gap           the mean gap over every sampled token

The widest gap is not compared: the sign activations turn any single
rounding difference between two compiled programs into a cascade (one
flipped bit flips about 2% of the next layer's bits), so a sound run's
widest gap reads about what the control's does (``PERF.md``).  Nor is
the mean gap of the worst sampled request: the lower-precision control
reads it only about twice as high as sound runs do.  A request due in
the window that did not get its whole budget of tokens also makes the
run not correct.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np


def reference_module(cfg: Dict):
    return importlib.import_module(
        f"bench.reference.{cfg['reference']['module']}")


def select(reqs, served, seed: int, tokens: int, max_requests: int) -> List:
    """The sample: the longest finished request, then others in an order
    drawn from the seed."""
    done = [r for r in reqs if len(served[r.rid]) == r.max_new_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(served[r.rid]), -r.rid))
    pick, total = [longest], len(served[longest.rid])
    rng = np.random.default_rng([int(seed), 2])
    for i in rng.permutation(len(done)):
        r = done[i]
        if total >= tokens or len(pick) >= max_requests:
            break
        if r is longest:
            continue
        pick.append(r)
        total += len(served[r.rid])
    return pick


def rows_and_tokens(req, served_tokens):
    """The sequence the reference reads (prompt, then served tokens) and
    the positions whose next-token logits chose each served token."""
    p, n = len(req.prompt), len(served_tokens)
    toks = np.concatenate([req.prompt, served_tokens]).astype(np.int32)
    return toks, np.arange(p - 1, p - 1 + n)


def served_gaps(ref, spec, seed, reqs, served, act_dtype="bfloat16"):
    """Per sampled request, per served token: the reference's best logit
    minus the served token's.  One reference pass for all of them."""
    seqs, rows = zip(*[rows_and_tokens(r, served[r.rid]) for r in reqs])
    lgs = ref.logits(spec, seed, seqs, rows, act_dtype)
    return [lg.max(-1) - lg[np.arange(len(lg)), served[r.rid]]
            for r, lg in zip(reqs, lgs)]


def control_gaps(ref, spec, seed, reqs, served, lower: str):
    """Per sampled request and position: how far below the reference's
    best lies the token that the reference computed at the ``lower``
    precision puts first (the control, in the program's place)."""
    seqs, rows = zip(*[rows_and_tokens(r, served[r.rid]) for r in reqs])
    lgs = ref.logits(spec, seed, seqs, rows, "bfloat16")
    los = ref.logits(spec, seed, seqs, rows, lower)
    return [lg.max(-1) - lg[np.arange(len(lg)), lo.argmax(-1)]
            for lg, lo in zip(lgs, los)]


def mean_gap(gaps) -> float:
    """The mean gap over every token of every sampled request."""
    if not gaps:
        return float("inf")
    return float(sum(g.sum() for g in gaps)) / sum(len(g) for g in gaps)


def verdict(gaps, unfinished: int, limits: Dict) -> Dict:
    """Judge one set of gaps (the program's, or the control's in its
    place) against the cell's limits.  ``failed`` counts the unfinished
    requests, and every sampled request where the mean gap is over its
    limit."""
    numbers = {
        "mean_gap": {"value": mean_gap(gaps), "limit": limits["mean_gap"]},
        "unfinished_requests": {"value": unfinished, "limit": 0},
    }
    correct = bool(gaps) and all(n["value"] <= n["limit"]
                                 for n in numbers.values())
    off = numbers["mean_gap"]["value"] > limits["mean_gap"]
    return {"correct": correct,
            "failed": unfinished + (len(gaps) if off else 0),
            "numbers": numbers}


def sample_gaps(run):
    """(reference module, spec, sampled requests, their gaps)."""
    cfg, cell = run.ctx.cfg, run.ctx.cell["check"]
    ref = reference_module(cfg)
    spec = ref.spec_from_config(cfg)
    seed = run.ctx.args.seed
    reqs, served = run.check["requests"], run.check["served"]
    pick = select(reqs, served, seed, cell["tokens"], cell["max_requests"])
    gaps = served_gaps(ref, spec, seed, pick, served) if pick else []
    return ref, spec, pick, gaps


def check(run) -> Dict:
    _, _, _, gaps = sample_gaps(run)
    return verdict(gaps, run.unfinished, run.ctx.cell["check"])
