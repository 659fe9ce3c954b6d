"""mfu (%): the model's useful operations in the window over the window's
seconds and the chip's bf16 peak.

Useful work is what an ideal server must compute for the tokens the
window delivered: every output token stamped in the window (one position
through every layer, attention over the keys it sees, and the output
head), and, for each request whose first token landed in the window, its
prompt positions, except the system-prompt positions of a prompt that an
earlier request of the run already brought (an ideal prefix cache serves
those).  Padded positions, recomputed prefixes and idle time are not
useful work, so they only lower this share.  Operations per position
come from ``bench/counts.py``.
"""
from bench import counts
from bench.peaks import peaks_for


def read(run):
    cfg = run.ctx.cfg
    reqs = run.check["requests"]
    stamps = run.check["stamps"]
    t_end = run.check["t_call"] + run.host["seconds"]
    seen = set()
    ops = 0
    for r in sorted(reqs, key=lambda q: q.due_s):
        s = stamps[r.rid]
        p = len(r.prompt)
        if s and s[0] < t_end:
            skip = 0
            if r.shared >= 0:
                if r.shared in seen:
                    skip = run.ctx.mix["shared_prefix"]["tokens"]
                seen.add(r.shared)
            ops += sum(counts.useful_ops(cfg, i, sampled=(i == p - 1))
                       for i in range(skip, p))
        elif r.shared >= 0:
            seen.add(r.shared)
        for k in range(1, len(s)):
            if s[k] < t_end:
                ops += counts.useful_ops(cfg, p + k - 1, sampled=True)
    peak = peaks_for(run.ctx.devices[0].device_kind)["bf16_flops"]
    return 100.0 * ops / run.host["seconds"] / peak
