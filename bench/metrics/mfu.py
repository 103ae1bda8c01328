"""mfu: the model FLOPs the traced window's steps needed (every prefill at
its true prompt length, every decode row attending its live context;
bench/work.py) over the traced window times the chip's bf16 peak (%)."""

from bench import work


def read(ctx):
    tr = ctx.trace
    if tr is None or ctx.peak is None or not ctx.traced_steps:
        return None
    flops = 0.0
    for _, dec, pre in ctx.traced_steps:
        if dec:
            flops += work.decode_step(ctx.cfg, dec)["flops"]
        for n in pre:
            flops += work.prefill(ctx.cfg, n)["flops"]
    return 100.0 * flops / (tr["window_s"] * ctx.peak["bf16_flops"])
