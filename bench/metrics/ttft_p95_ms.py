"""ttft_p95_ms: 95th percentile, over every request due in the window,
of its first token's host-clock stamp minus the time it was due (open
loop: queueing before admission counts)."""
from bench.common import percentile


def read(run):
    return percentile(run.host["ttft_ms"], 95)
