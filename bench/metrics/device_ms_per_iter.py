"""device_ms_per_iter (ms): device busy time of the traced window (the
union of the intervals in which an operation ran on the chip) over the
engine iterations that started in it (executions of the engine's step
programs, all named ``jit_step``)."""

STEP = "jit_step("


def read(run):
    t = run.trace
    if not t:
        return None
    n = sum(m["count"] for name, m in t["modules"].items()
            if name.startswith(STEP))
    if not n:
        return None
    return 1e3 * t["busy_s"] / n
