"""tokens_per_s: every output token stamped inside the window, partial
requests included, over the window's seconds (host clock)."""


def read(ctx):
    n = sum(1 for r in ctx.recs for t in r.times if 0 <= t < ctx.seconds)
    ctx.log(f"tokens_per_s: {n} tokens in {ctx.seconds} s")
    return n / ctx.seconds
