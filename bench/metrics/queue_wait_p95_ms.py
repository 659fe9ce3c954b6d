"""queue_wait_p95_ms (ms): the 95th percentile of the engine's own wait
from a request's due time to its first admission into a slot
(``EngineReport.queue_wait_s``, on ``serve()``'s clock): the part of
the time to first token spent before any prompt position was computed.

In a traced run it reads only the requests admitted inside the profiled
part of the window (``trace["window_s"]`` from the window's start):
stopping the profiler stalls the host for tens of seconds at full size,
and a request due then waits through the stall.  Prints the number of
requests it read to standard error.  Nothing to read where the program
does not count the waits.
"""
import sys

from bench import common


def read(run):
    waits = run.counters.get("queue_wait_s")
    if not waits:
        return None
    if run.trace:
        due = {r.rid: r.due_s for r in run.check["requests"]}
        waits = {rid: w for rid, w in waits.items()
                 if due[rid] + w <= run.trace["window_s"]}
    print(f"queue_wait_p95_ms: {len(waits)} requests", file=sys.stderr,
          flush=True)
    return common.percentile([1e3 * w for w in waits.values()], 95)
