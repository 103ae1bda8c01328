"""The system under test, as the benchmark drives it: the program's model
and its continuous-batching engine (``repro.runtime.serving``), given the
benchmark's weights.  Nothing else of the benchmark imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import build_model
from repro.runtime.serving import ContinuousBatchingEngine


@jax.jit
def _norms_to_program(norms):
    # the program stores RMSNorm weights as an offset from 1
    return jax.tree.map(lambda w: w - jnp.ones((), w.dtype), norms)


def program_params(w: dict, model) -> dict:
    """The benchmark's weights (reference layout) in the program's tree; the
    matrices are the same device arrays, not copies."""
    lw = w["layers"]
    norms = _norms_to_program(
        {"final": w["final_norm"], "ln1": lw["attn_norm"], "ln2": lw["mlp_norm"]}
    )
    block = {
        "ln1": norms["ln1"],
        "mixer": {"w_q": lw["wq"], "w_k": lw["wk"], "w_v": lw["wv"], "w_o": lw["wo"]},
        "ln2": norms["ln2"],
        "ffn": {"w_gate": lw["w_gate"], "w_up": lw["w_up"], "w_down": lw["w_down"]},
    }
    params = {"embed": w["embed"], "final_norm": norms["final"], "lm_head": w["lm_head"],
              "decoder": (block,)}
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or jax.tree.leaves(
        jax.tree.map(lambda a, b: a.shape != b.shape or a.dtype != b.dtype, want, got)
    ).count(True):
        raise ValueError(f"weights do not match the program's parameters:\n{want}\n{got}")
    return params


def build_engine(cfg, w: dict, n_slots: int, max_len: int):
    model = build_model(cfg)
    return ContinuousBatchingEngine(model, program_params(w, model), n_slots=n_slots,
                                    max_len=max_len)
