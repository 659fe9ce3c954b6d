"""spec_tokens_per_step (tokens): tokens a speculative verify step
emitted per busy slot, by the engine's own counts for the whole serve
call: the accepted drafts plus the verify's own token.  Nothing to read
where the mix does not speculate."""


def read(run):
    return None if not run.engine.get("verify_steps") else \
        run.engine["spec_tokens_per_step"]
