"""first_token_hold_ms: how long a first token already on the host waits for
``step()`` to return, mean over the engine's ``serve.prefill`` spans in the
traced window of (end of the enclosing ``serve.step`` - end of the
prefill) (ms)."""

from bench import span_reduce


def read(ctx):
    red = span_reduce.for_run(ctx)
    if not red:
        return None
    steps = red["spans"].get("serve.step", [])
    holds = [b - p_b for p_a, p_b in red["spans"].get("serve.prefill", [])
             for a, b in steps if a <= p_a and p_b <= b]
    if not holds:
        return None
    return 1e3 * sum(holds) / len(holds)
