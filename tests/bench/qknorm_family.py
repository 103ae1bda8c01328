"""A second family, for the tests only: ``gqa`` with qk-norm, a per-head
RMSNorm on the queries and the keys before RoPE (the program's
``cfg.qk_norm``; OLMoE and Qwen3 carry it).  It lives here and never under
``bench/``: ``install`` puts this module in ``sys.modules`` as
``bench.families.gqa_qknorm``, and ``spec.family`` finds it by that name.
It is never a benchmark configuration.

Two norm leaves a layer join the ``gqa`` tree (``q_norm``, ``k_norm``,
``[layers, head_dim]``: the weight maker's norm rule draws them), and its
own plain reference adds the two norms to ``gqa``'s layer.
"""

from __future__ import annotations

import dataclasses
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import program, spec
from bench.families import gqa
from bench.reference import model as ref

NAME = "gqa_qknorm"
MODULE = f"bench.families.{NAME}"

reference_config = gqa.reference_config
decode_step = gqa.decode_step
prefill = gqa.prefill


def install(monkeypatch, cell: str | None = None) -> None:
    """Inject the family by module name; with ``cell``, that cell's
    configuration names it (the file on disk unchanged)."""
    monkeypatch.setitem(sys.modules, MODULE, sys.modules[__name__])
    if cell is not None:
        load = spec.load_cell

        def load_cell(workload, bench=None):
            c = load(workload, bench)
            if workload != cell:
                return c
            return dataclasses.replace(c, config={**c.config, "family": NAME})

        monkeypatch.setattr(spec, "load_cell", load_cell)


def model_config(config: dict, reduced: bool = False):
    return dataclasses.replace(gqa.model_config(config, reduced), qk_norm=True)


def shapes(cfg: dict) -> dict:
    out = gqa.shapes(cfg)
    n, hd = cfg["num_hidden_layers"], cfg["head_dim"]
    out["layers"].update(q_norm=(n, hd), k_norm=(n, hd))
    return out


def program_params(w: dict, model) -> dict:
    params = gqa.program_params(w, model)
    lw = w["layers"]
    params["decoder"][0]["mixer"].update(
        program.norms_to_program({"q_norm": lw["q_norm"], "k_norm": lw["k_norm"]}))
    return params


@partial(jax.jit, static_argnames=("cfg_items", "control"))
def _forward(w, tokens, rows, *, cfg_items, control):
    cfg = dict(cfg_items)
    eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens].astype(jnp.float32)
        t = x.shape[0]
        pos = jnp.arange(t)
        for i in range(cfg["num_hidden_layers"]):
            lw = jax.tree.map(lambda a: a[i], w["layers"])
            h = ref.rms_norm(x, lw["attn_norm"], eps)
            q = ref.rms_norm(ref._mm(h, lw["wq"], control).reshape(t, nh, hd), lw["q_norm"], eps)
            k = ref.rms_norm(ref._mm(h, lw["wk"], control).reshape(t, nkv, hd), lw["k_norm"],
                             eps)
            v = ref._mm(h, lw["wv"], control).reshape(t, nkv, hd)
            q, k = ref.rope(q, pos, cfg["rope_theta"]), ref.rope(k, pos, cfg["rope_theta"])
            a = ref.attention(q, k, v, cfg["sliding_window"]).reshape(t, nh * hd)
            x = x + ref._mm(a, lw["wo"], control)
            h = ref.rms_norm(x, lw["mlp_norm"], eps)
            u = jax.nn.silu(ref._mm(h, lw["w_gate"], control)) * ref._mm(h, lw["w_up"], control)
            x = x + ref._mm(u, lw["w_down"], control)
        return ref._mm(ref.rms_norm(x[rows], w["final_norm"], eps), w["lm_head"], control)


def logits_at(w: dict, cfg: dict, tokens: np.ndarray, rows: np.ndarray,
              control: bool = False) -> jax.Array:
    """The plain float32 reference (``control``: float8 operands), the whole
    sequence at once, padded as ``gqa``'s reference pads it."""
    t = len(tokens)
    padded = np.zeros(-(-t // ref.LENGTH_STEP) * ref.LENGTH_STEP, np.int32)
    padded[:t] = tokens
    return _forward(w, jnp.asarray(padded), jnp.asarray(rows, jnp.int32),
                    cfg_items=tuple(sorted(cfg.items())), control=control)
