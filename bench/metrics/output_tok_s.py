"""output_tok_s (tokens/s, host clock): every token served in the window
over the window's seconds, from the first wave's submission to the drain of
the last wave."""


def read(run):
    w = run.window
    return sum(len(r.output) for r in w.requests) / w.seconds
