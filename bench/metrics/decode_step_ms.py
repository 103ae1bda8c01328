"""decode_step_ms: mean device time of one execution of the engine's decode
program (``jit_decode``) in the traced window."""


def read(ctx):
    tr = ctx.trace
    m = tr and tr["modules"].get("jit_decode")
    if not m or not m["n"]:
        return None
    return 1e3 * m["s"] / m["n"]
