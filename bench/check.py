"""The comparison that decides ``correct``.

The timed path yields greedy token ids (sampling is fused into the engine's
jitted programs), so a served token is judged by the plain reference's
logits at its position: its gap is how far its reference logit lies below
the reference's best there.  A sound bf16 engine serves the reference's
argmax or a near tie; a wrong cache row, a missed write or an altered
token serves a token far down the reference's list.

The number compared is the widest gap over a sample of the requests that
the window finished, drawn from the seed, with the one that served the
most tokens in it, until the sample holds ``check_tokens`` served tokens.

``control_gap`` is the control: the reference computed in float8, read at
the same prompts and tokens, with the token that the float8 forward puts
first in place of the served one.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench.reference import model as ref

ROWS_STEP = 128  # logits rows pad to a multiple of this (fewer compiles)


def sample(finished: list, seed: int, check_tokens: int) -> list:
    """``finished``: [(prompt, served)]; the one with most served tokens,
    then others in a seeded order until ``check_tokens`` are covered."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (-len(finished[i][1]), -len(finished[i][0]), i))
    first, rest = order[0], np.random.default_rng(int(seed) + 1).permutation(order[1:])
    out, n = [finished[first]], len(finished[first][1])
    for i in rest:
        if n >= check_tokens:
            break
        out.append(finished[int(i)])
        n += len(finished[int(i)][1])
    return out


def _rows(prompt, served):
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(seq))
    pad = -(-len(rows) // ROWS_STEP) * ROWS_STEP
    return seq, np.concatenate([rows, np.full(pad - len(rows), rows[-1])]), len(rows)


def gaps(w: dict, cfg: dict, prompt: np.ndarray, served: np.ndarray,
         control: bool = False) -> np.ndarray:
    """Per served token: reference best logit minus the reference logit of
    the served token (``control``: of the float8 forward's first token)."""
    seq, rows, n = _rows(prompt, served)
    logits = ref.logits_at(w, cfg, seq, rows)
    best = jnp.max(logits, axis=-1)
    if control:
        pick = jnp.argmax(ref.logits_at(w, cfg, seq, rows, control=True), axis=-1)
    else:
        pick = jnp.asarray(np.concatenate([served, np.zeros(len(rows) - n, served.dtype)]))
    got = jnp.take_along_axis(logits, pick[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return np.asarray(best - got)[:n]


def widest_gap(w: dict, cfg: dict, picked: list, control: bool = False) -> dict:
    out = {"value": 0.0, "tokens": 0, "off_argmax": 0}
    for prompt, served in picked:
        g = gaps(w, cfg, prompt, np.asarray(served, np.int32), control=control)
        out["value"] = max(out["value"], float(g.max()))
        out["tokens"] += len(g)
        out["off_argmax"] += int((g > 0).sum())
    return out
