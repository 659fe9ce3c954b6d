"""itl_p95_ms: 95th percentile over every gap between consecutive output
tokens of every request due in the window, drain included."""
from bench.common import percentile


def read(run):
    return percentile(run.host["itl_ms"], 95)
