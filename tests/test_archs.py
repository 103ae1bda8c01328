"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step on CPU, asserting output shapes + no NaNs, plus a
decode-vs-teacher-forced consistency check (exact when MoE capacity does
not drop)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.models import build_model

B, S = 2, 32


def _batch(cfg, rng, seq=S):
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, seq)), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    if cfg.frontend and cfg.frontend.n_tokens:
        n = min(cfg.frontend.n_tokens, seq // 2)
        batch["frontend_embeds"] = jnp.asarray(
            rng.normal(size=(B, n, cfg.frontend.d_frontend)), jnp.float32
        )
    if cfg.enc_dec:
        batch["encoder_frames"] = jnp.asarray(
            rng.normal(size=(B, seq, cfg.frontend.d_frontend)), jnp.float32
        )
    return batch


def _reduced(arch, **over):
    cfg = get_config(arch, reduced=True)
    if cfg.moe is not None and "moe" not in over:
        over["moe"] = dataclasses.replace(cfg.moe, capacity_factor=8.0)
    return dataclasses.replace(cfg, compute_dtype="float32", remat=False, **over)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_shapes_and_finite(arch):
    cfg = _reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, np.random.default_rng(0))
    loss, metrics = jax.jit(model.train_loss)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss))
    grads = jax.grad(lambda p: model.train_loss(p, batch)[0])(params)
    gsum = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gsum) and gsum > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_teacher_forcing(arch):
    cfg = _reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    batch = _batch(cfg, rng)
    tokens = batch["tokens"]
    pre = dict(batch, tokens=tokens[:, : S - 1])
    logits_pre, caches = jax.jit(model.prefill)(params, pre)
    assert logits_pre.shape == (B, 1, cfg.vocab)
    caches = model.prepare_decode_caches(caches, capacity=S + 8)
    logits_step, new_caches = jax.jit(model.decode_step)(
        params, caches, tokens[:, S - 1 :], jnp.full((B,), S - 1, jnp.int32)
    )
    logits_full, _ = jax.jit(model.prefill)(params, batch)
    rel = float(jnp.max(jnp.abs(logits_step - logits_full))) / (
        float(jnp.max(jnp.abs(logits_full))) + 1e-9
    )
    assert rel < 1e-4, f"decode diverges from teacher forcing: {rel}"
    # caches keep their structure
    assert jax.tree.structure(new_caches) == jax.tree.structure(caches)


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "mamba2-1.3b", "jamba-v0.1-52b"])
def test_long_context_archs_have_bounded_state(arch):
    """The three long_500k archs must not require O(seq) full-attention KV."""
    cfg = get_config(arch, reduced=True)
    assert cfg.supports_long_context()
    model = build_model(dataclasses.replace(cfg, compute_dtype="float32"))
    caches = model.init_cache(batch=1, seq_len=4096)
    for bc in caches:
        mixer = bc.get("mixer", {})
        if "kv" in mixer and cfg.attn_type == "swa":
            # ring buffer bounded by the window
            assert mixer["kv"].shape[-2] <= cfg.sliding_window  # [r, B, Kv, L, 2*D]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_parameter_scale(arch):
    """Full configs instantiate abstractly (no allocation) at a plausible
    parameter count for their nameplate size."""
    cfg = get_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    nominal = {
        "jamba-v0.1-52b": 52e9,
        "granite-moe-1b-a400m": 1.3e9,
        "olmoe-1b-7b": 6.9e9,
        "minicpm3-4b": 4e9,
        "internlm2-1.8b": 1.8e9,
        "h2o-danube-1.8b": 1.8e9,
        "qwen3-32b": 32e9,
        "seamless-m4t-large-v2": 2.3e9,
        "mamba2-1.3b": 1.3e9,
        "phi-3-vision-4.2b": 3.8e9,
        "deepseek-v2-lite": 15.7e9,
    }[arch]
    assert 0.5 * nominal < n < 1.7 * nominal, f"{arch}: {n/1e9:.2f}B vs nominal {nominal/1e9:.1f}B"
