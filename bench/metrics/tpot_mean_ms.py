"""Mean time per output token a streaming client sees (client clock): over
every request, the time from its first chunk to each later chunk that came
in the window, summed, over the tokens those later chunks brought, summed.
Tokens come in chunks of a superstep each, so a gap between two tokens of
one chunk reads 0; this spreads each chunk's wait over its tokens and
weighs every token once."""


def read(run):
    wait, toks = 0.0, 0
    for r in run["records"]:
        ch = r["chunks"]
        for (t0, _), (t1, n) in zip(ch, ch[1:]):
            if t1 < run["seconds"]:
                wait += t1 - t0
                toks += n
    run.setdefault("samples", {})["tpot_mean_ms"] = toks
    return wait / toks * 1e3 if toks else None
