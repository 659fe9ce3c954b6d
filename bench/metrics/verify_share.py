"""verify_share (%): the engine's own count of speculative verify steps
over all its iterations, for the whole serve call (window and drain).
The rest are pooled chunk steps, which run whenever a prompt is in
flight.  Nothing to read where the mix does not speculate."""


def read(run):
    return None if not run.engine.get("verify_steps") else \
        100.0 * run.engine["verify_share"]
