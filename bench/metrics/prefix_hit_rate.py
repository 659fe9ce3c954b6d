"""prefix_hit_rate (%): the page arena's own count of admission prefix
pages found already mapped, over pages looked up, for the whole serve
call.  Nothing to read where no page was looked up."""


def read(run):
    if not run.counters.get("prefix_lookups"):
        return None
    return 100.0 * run.counters["prefix_hit_rate"]
