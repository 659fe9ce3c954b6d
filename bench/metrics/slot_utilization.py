"""slot_utilization (%): the engine's own count of busy slot-iterations
over slot-iterations, for the whole serve call (window and drain)."""


def read(run):
    v = run.counters.get("slot_utilization")
    return None if v is None else 100.0 * v
