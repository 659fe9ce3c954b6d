"""output_tok_s: output tokens stamped inside the window divided by the
window's seconds (tokens of the drain do not count)."""


def read(run):
    return run.host["output_tokens_in_window"] / run.host["seconds"]
