"""Tokens decoded per decode step in the traced span: served tokens
after each request's first, over the engine's ``decode_steps`` counter."""


def read(ctx):
    n = ctx.stats1["decode_steps"] - ctx.stats0["decode_steps"]
    if n <= 0:
        return None
    toks = sum(1 for s in ctx.steps for _, j in s["tokens"] if j >= 1)
    return toks / n
