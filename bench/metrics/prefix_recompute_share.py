"""prefix_recompute_share (%): the share of the prompt positions the
pooled chunk steps computed whose page the arena had adopted from an
earlier sharer, ``prefill_positions_adopted`` / ``prefill_positions``:
K/V^T that was already cached and was computed again.  The engine's own
counts for the whole serve call; nothing to read where the program does
not count them."""


def read(run):
    c = run.counters
    if not c.get("prefill_positions"):
        return None
    return 100.0 * c["prefill_positions_adopted"] / c["prefill_positions"]
