"""latent_attn_share: the device time of the decode program's ops under the
program's ``mla.attend`` scope (scores, softmax and output over the latent
cache) over the device time of ``jit_decode`` in the traced window (%).
Nothing where the program opens no such scope."""

from bench import scope_reduce, span_reduce

SCOPE = "mla.attend"


def read(ctx):
    tr = ctx.trace
    m = tr and tr["modules"].get("jit_decode")
    path = span_reduce.trace_file() if m and m["s"] else None
    if path is None:
        return None
    s, n = scope_reduce.scope_seconds(str(path), "jit_decode", SCOPE)
    ctx.log(f"{ctx.name}: {n} ops under {SCOPE}, {s!r} s of jit_decode's {m['s']!r} s")
    return 100.0 * s / m["s"] if n else None
