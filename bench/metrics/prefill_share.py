"""prefill_share: device time of the engine's prefill program
(``jit_prefill_into``) over the traced window (%)."""


def read(ctx):
    tr = ctx.trace
    if tr is None or "jit_prefill_into" not in tr["modules"]:
        return None
    return 100.0 * tr["modules"]["jit_prefill_into"]["s"] / tr["window_s"]
