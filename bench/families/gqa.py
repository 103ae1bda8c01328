"""The ``gqa`` family: a decoder-only transformer whose every layer is
grouped-query attention (optionally over a sliding window) and a SwiGLU
MLP, with RMSNorm, RoPE and an untied LM head.  Both benchmark
configurations are of this family (``"family": "gqa"`` in their files).

A family is what the harness needs to know of one kind of block
(``bench/spec.py``): the program's ``ModelConfig`` and the reference's sizes
from a configuration file, the weight tree in the reference's layout, the
same arrays in the program's tree, the plain float32 reference (with its
float8 control), and the work the algorithm needs.  This family's reference
is ``bench/reference/model.py`` and its work counts ``bench/work.py``.
"""

from __future__ import annotations

import dataclasses

from bench import program, work
from bench.reference import model as reference

# published config.json keys -> fields of the program's ModelConfig
CONFIG_FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}

logits_at = reference.logits_at
decode_step = work.decode_step
prefill = work.prefill


def model_config(config: dict, reduced: bool = False):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry with every published key of the file put in, bf16 weights as
    served.  ``reduced`` takes the registry's CPU-sized preset instead (tests
    only), with the file's dtypes."""
    from repro.configs.base import get_config

    base = get_config(config["registry_id"], reduced=reduced)
    dtype = config["torch_dtype"]
    if reduced:
        return dataclasses.replace(base, param_dtype=dtype, compute_dtype=dtype)
    fields = {f: config[k] for k, f in CONFIG_FIELDS.items()}
    fields["rope_theta"] = float(fields["rope_theta"])
    if config.get("sliding_window"):
        fields.update(attn_type="swa", sliding_window=int(config["sliding_window"]))
    return dataclasses.replace(base, param_dtype=dtype, compute_dtype=dtype, **fields)


def reference_config(cfg) -> dict:
    """The plain reference's sizes, in the published keys, read back from
    the ``ModelConfig`` that runs (so reduced test sizes agree too)."""
    out = {k: getattr(cfg, f) for k, f in CONFIG_FIELDS.items()}
    out["head_dim"] = cfg.head_dim
    out["sliding_window"] = cfg.sliding_window if cfg.attn_type == "swa" else 0
    return out


def shapes(cfg: dict) -> dict:
    """The weight tree in the reference's layout: per-layer matrices stacked
    along a leading layer axis."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n, hd = cfg["num_hidden_layers"], cfg["head_dim"]
    qd, kvd = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {
        "embed": (v, d),
        "final_norm": (d,),
        "lm_head": (d, v),
        "layers": {
            "attn_norm": (n, d),
            "wq": (n, d, qd),
            "wk": (n, d, kvd),
            "wv": (n, d, kvd),
            "wo": (n, qd, d),
            "mlp_norm": (n, d),
            "w_gate": (n, d, f),
            "w_up": (n, d, f),
            "w_down": (n, f, d),
        },
    }


def program_params(w: dict, model) -> dict:
    """The weights (reference layout) in the program's tree: one scanned
    block; the matrices are the same device arrays, not copies."""
    lw = w["layers"]
    norms = program.norms_to_program(
        {"final": w["final_norm"], "ln1": lw["attn_norm"], "ln2": lw["mlp_norm"]}
    )
    block = {
        "ln1": norms["ln1"],
        "mixer": {"w_q": lw["wq"], "w_k": lw["wk"], "w_v": lw["wv"], "w_o": lw["wo"]},
        "ln2": norms["ln2"],
        "ffn": {"w_gate": lw["w_gate"], "w_up": lw["w_up"], "w_down": lw["w_down"]},
    }
    return {"embed": w["embed"], "final_norm": norms["final"], "lm_head": w["lm_head"],
            "decoder": (block,)}
