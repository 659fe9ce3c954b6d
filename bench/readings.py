#!/usr/bin/env python3
"""Readings that a correctness limit is set from, on the chip.

  python3 bench/readings.py --workload <name> --seed <first> \
      --seconds <s> --seeds 12 [--lower float8_e4m3fn]

For each of ``--seeds`` seeds (``--seed``, ``--seed + 1``, ...): new
weights from the seed, one window of the cell's own mix at its own rate,
the cell's own sample of finished requests, and the cell's comparison
(``bench/checks/token_match.verdict``, at the cell's limits) twice:

  program  the served tokens' gaps below the reference's best: the lower
           reading of each compared number
  control  the gaps of the tokens that the reference computed in the
           ``--lower`` precision puts first, in the program's place: the
           upper reading; the control has to come out not correct

Each line also gives the engine's iteration counts for the window (how
many iterations were speculative verify steps, and how many tokens a
verify step emitted per slot).

One process serves every seed: the engine is built once and its
weights are swapped, so nothing compiles again.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = harness.parser(__doc__)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--lower", default="float8_e4m3fn")
    args = ap.parse_args(argv)
    ctx = harness.context(args)

    from bench import gen
    from bench.checks import token_match
    from bench.drivers import serve

    eng = serve.setup(ctx)
    ref = token_match.reference_module(ctx.cfg)
    spec = ref.spec_from_config(ctx.cfg)
    chk = ctx.cell["check"]
    for seed in range(args.seed, args.seed + args.seeds):
        serve.swap_weights(eng, seed, ctx.cfg["reference"]["embed_scale"])
        reqs = gen.generate(ctx.mix, seed, args.seconds,
                            ctx.cfg["reference"]["vocab_size"])
        served, _, _, _, counters, _ = serve.serve_window(
            eng, reqs, args.seconds)
        unfinished = sum(1 for r in reqs
                         if len(served[r.rid]) < r.max_new_tokens)
        pick = token_match.select(reqs, served, seed, chk["tokens"],
                                  chk["max_requests"])
        gs = token_match.served_gaps(ref, spec, seed, pick, served)
        cs = token_match.control_gaps(ref, spec, seed, pick, served,
                                      args.lower)
        per = [{"rid": r.rid, "prompt": len(r.prompt),
                "served": len(served[r.rid]), "mean_gap": float(g.mean()),
                "share_off": float((g > 0).mean()),
                "control_mean_gap": float(c.mean()),
                "control_share_off": float((c > 0).mean())}
               for r, g, c in zip(pick, gs, cs)]
        print(json.dumps({
            "seed": seed,
            "program": token_match.verdict(gs, unfinished, chk),
            "control": token_match.verdict(cs, unfinished, chk),
            "tokens": sum(p["served"] for p in per),
            "engine": serve.engine_numbers(counters),
            "requests": per}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
