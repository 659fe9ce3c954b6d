"""padded_share (%): the share of the positions the pooled chunk steps
computed that carried no token, 1 - ``chunk_valid_positions`` /
``chunk_positions``: the engine's own counts for the whole serve call
(window and drain).  Nothing to read where the program does not count
them."""


def read(run):
    c = run.counters
    if not c.get("chunk_positions"):
        return None
    return 100.0 * (1.0 - c["chunk_valid_positions"] / c["chunk_positions"])
