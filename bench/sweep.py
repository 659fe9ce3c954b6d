#!/usr/bin/env python3
"""Find a cell's knee once, by a sweep of offered rates on the chip.

  python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \
      --rates 1,2,4

One set-up, then for each rate one window of the cell's mix at that
rate (the mix file's own rate is ignored).  Per rate it prints the
offered and completed request rates, output tokens per second, the
TTFT and token-gap tails, how long the drain after the window took and
the TTFT tail of the last third of the requests against the first
third: a backlog that grows through the window shows as a rising tail
and a long drain.  The knee is the highest rate that keeps up.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = harness.parser(__doc__)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    ctx = harness.context(args)
    from bench import common, gen
    from bench.drivers import serve

    eng = serve.setup(ctx)
    vocab = ctx.cfg["reference"]["vocab_size"]
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(ctx.mix, rate_per_s=rate)
        reqs = gen.generate(mix, args.seed, args.seconds, vocab)
        served, stamps, t_call, t_done, counters, win = serve.serve_window(
            eng, reqs, args.seconds)
        h = serve.host_numbers(reqs, served, stamps, t_call, t_done,
                               counters, args.seconds)
        t_end = t_call + args.seconds
        done_in = sum(1 for s in stamps if s and s[-1] < t_end)
        third = len(reqs) // 3
        first = [(s[0] - t_call - r.due_s) * 1e3
                 for r, s in zip(reqs[:third], stamps[:third])]
        last = [(s[0] - t_call - r.due_s) * 1e3
                for r, s in zip(reqs[-third:], stamps[-third:])]
        print(json.dumps({
            "rate_offered": len(reqs) / args.seconds,
            "rate_completed_in_window": done_in / args.seconds,
            "output_tok_s": h["output_tokens_in_window"] / args.seconds,
            "ttft_p50_ms": common.percentile(h["ttft_ms"], 50),
            "ttft_p95_ms": common.percentile(h["ttft_ms"], 95),
            "itl_p95_ms": common.percentile(h["itl_ms"], 95),
            "ttft_p95_first_third_ms": common.percentile(first, 95),
            "ttft_p95_last_third_ms": common.percentile(last, 95),
            "drain_s": h["drain_s"], "unfinished": h["unfinished"],
            "window_compiles": win["compiles"],
            "slot_utilization": counters["slot_utilization"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
