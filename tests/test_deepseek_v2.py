"""DeepSeek-V2-Lite's mechanisms in the program, on the CPU at the
registry's reduced sizes: YaRN against the published formula, the
configuration from its published keys, the dropless held-share MoE layer
(rows independent of their batch, every assignment computed once), the
engine's count of held assignments, and the prelude's dense layer ahead of
the stacked MoE layers."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import deepseek_v2_lite
from repro.configs.base import RopeScaling, get_config
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.models.layers import apply_rope, rope_attention_scale, rope_frequencies
from repro.models.published import rope_deinterleave
from repro.runtime.serving import ContinuousBatchingEngine


def _published_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """``DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache``, transcribed in
    float64 numpy from the published modelling code."""

    def correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def _published_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


@pytest.mark.parametrize("dim", [64, 8])
def test_yarn_frequencies_and_scale_follow_the_published_formula(dim):
    rs = deepseek_v2_lite.CONFIG.rope_scaling
    want = _published_inv_freq(dim, 10000.0, rs.factor, rs.original_max_position,
                               rs.beta_fast, rs.beta_slow)
    got = np.asarray(rope_frequencies(dim, 10000.0, rs))
    # float32 against float64: the powers of 10000 round in the last bit
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert not np.allclose(got, np.asarray(rope_frequencies(dim, 10000.0)))  # it does scale
    m = _published_mscale(rs.factor, rs.mscale_all_dim)
    assert rope_attention_scale(rs) == pytest.approx(m * m, rel=1e-12)
    assert rope_attention_scale(rs) == pytest.approx(1.2608 ** 2, rel=1e-4)
    assert rope_attention_scale(None) == 1.0


def test_yarn_rope_of_the_placed_columns_is_the_published_rotation():
    """Rotating half against half, with cos and sin carrying
    mscale(factor, mscale) / mscale(factor, mscale_all_dim), of a rotary
    part whose columns the weight placement has put in de-interleaved order
    (``published.rope_deinterleave``) is the published
    de-interleave-then-rotate-half of the part as the checkpoint lays it."""
    rs = RopeScaling(factor=40.0, original_max_position=4096, mscale=1.0, mscale_all_dim=0.707)
    dim = 16
    x = np.random.default_rng(0).normal(size=(5, 3, dim)).astype(np.float32)
    pos = np.array([0, 1, 7, 4100, 9000])
    placed = jnp.asarray(x[..., rope_deinterleave(dim)])
    got = np.asarray(apply_rope(placed, jnp.asarray(pos), 10000.0, rs))
    inv = _published_inv_freq(dim, 10000.0, 40.0, 4096, 32.0, 1.0)
    m = _published_mscale(40.0, 1.0) / _published_mscale(40.0, 0.707)
    # the published code takes the angles in float32, as the program does
    freqs = pos.astype(np.float32)[:, None] * inv.astype(np.float32)
    emb = np.concatenate([freqs, freqs], axis=-1).astype(np.float64)
    cos, sin = (np.cos(emb) * m)[:, None], (np.sin(emb) * m)[:, None]
    de = x.reshape(5, 3, dim // 2, 2).swapaxes(-1, -2).reshape(5, 3, dim)
    want = de * cos + np.concatenate([-de[..., dim // 2:], de[..., : dim // 2]], -1) * sin
    # float32 sin and cos of the same float32 angles (1e-5 here), where a
    # wrong pairing or frequency is off by O(1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_config_comes_from_the_published_keys():
    cfg = deepseek_v2_lite.CONFIG
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == (27, 2048, 16, 10944,
                                                                              102400)
    assert cfg.n_dense_layers == 1 and cfg.pattern_period() == 1
    assert [cfg.layer_is_moe(i) for i in range(3)] == [False, True, True]
    assert cfg.ffn_width(0) == 10944 and cfg.ffn_width(1) == 1408
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.n_shared_experts, cfg.moe.n_held) == (
        64, 6, 2, 64)
    assert not cfg.moe.norm_topk_prob and cfg.mla.q_lora_rank == 0
    share = deepseek_v2_lite.from_published(deepseek_v2_lite.PUBLISHED, held=(8, 8))
    assert (share.moe.held_first, share.moe.n_held, share.moe.n_experts) == (8, 8, 64)
    with pytest.raises(ValueError, match="unsupported"):
        deepseek_v2_lite.from_published({**deepseek_v2_lite.PUBLISHED, "topk_method": "group"})


def _reduced(held=None, **over):
    cfg = get_config("deepseek-v2-lite", reduced=True)
    moe = cfg.moe if held is None else dataclasses.replace(cfg.moe, held_first=held[0],
                                                           n_held=held[1])
    return dataclasses.replace(cfg, compute_dtype="float32", remat=False, moe=moe, **over)


def _moe_params(cfg, key):
    return moe_mod.moe_init(moe_mod.Initializer(key), cfg, jnp.float32)[0]


def test_dropless_rows_do_not_depend_on_their_batch():
    """Each token's output from the held share is its own: the same rows
    give the same bits whatever other rows share the call, and a token whose
    assignments all land outside the share gets exactly the shared experts.
    A capacity (rows past it dropped) would change a row with its batch."""
    cfg = _reduced(held=(4, 4))
    params = _moe_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.d_model), jnp.float32)
    fn = jax.jit(lambda p, x: moe_mod.moe_held(p, x, cfg))
    full, _, held, touched = fn(params, x)
    same = jnp.concatenate([x[:8], x[8:16][::-1], x[16:]])
    other, _, _, _ = fn(params, same)
    np.testing.assert_array_equal(np.asarray(full[:8]), np.asarray(other[:8]))
    np.testing.assert_array_equal(np.asarray(full[16:]), np.asarray(other[16:]))
    # all 24 tokens in one expert's rows: still nothing dropped
    crowd = jnp.broadcast_to(x[:1], x.shape)
    crowded, _, n, one = fn(params, crowd)
    np.testing.assert_array_equal(np.asarray(crowded), np.broadcast_to(full[:1], full.shape))
    _, experts, _ = moe_mod.route_dropless(params["router"], x, cfg.moe)
    mine = (np.asarray(experts) >= 4) & (np.asarray(experts) < 8)
    assert int(held) == mine.sum() and int(n) == 24 * mine[0].sum()
    # the experts given rows: those the kernel reads
    assert int(touched) == len(np.unique(np.asarray(experts)[mine])) > int(one) == mine[0].sum()
    outside = ~mine.any(axis=1)
    assert outside.any()
    shared = moe_mod.mlp_apply(params["shared"], x, jnp.float32)
    np.testing.assert_allclose(np.asarray(full)[outside], np.asarray(shared)[outside], atol=1e-6)


def test_shares_sum_to_the_whole_layer():
    """The 8 two-expert shares of the 16 reduced experts, each with the
    shared experts, sum to the layer that holds all 16 plus seven more
    copies of the shared part: every (token, expert) assignment is computed
    once, by the share that holds its expert."""
    whole = _reduced(held=(0, 16))
    params = _moe_params(whole, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (20, whole.d_model), jnp.float32)
    want, _, n_all, _ = moe_mod.moe_held(params, x, whole)
    total, counted = 0.0, 0
    for first in range(0, 16, 2):
        share = _reduced(held=(first, 2))
        p = {**params, **{k: params[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")}}
        out, _, n, _ = moe_mod.moe_held(p, x, share)
        total = total + out
        counted += int(n)
    shared = moe_mod.mlp_apply(params["shared"], x, jnp.float32)
    assert counted == int(n_all) == 20 * whole.moe.top_k
    # float32 sums in another order
    np.testing.assert_allclose(np.asarray(total - 7 * shared), np.asarray(want), atol=2e-5)


def test_a_layer_of_the_stack_is_the_layer_alone():
    """In the decode loop the grouped kernel is handed every layer's held
    experts with the loop's layer: the result is the layer's own, to the
    bit, for each layer of the stack."""
    cfg = _reduced(held=(4, 4))
    x = jax.random.normal(jax.random.PRNGKey(6), (10, cfg.d_model), jnp.float32)
    layers = [_moe_params(cfg, jax.random.PRNGKey(7 + i)) for i in range(3)]
    stack = {**layers[0], **{k: jnp.stack([p[k] for p in layers]) for k in moe_mod.HELD_WEIGHTS}}
    stacked = jax.jit(lambda p, x, i: moe_mod.moe_held(p, x, cfg, i))
    alone = jax.jit(lambda p, x: moe_mod.moe_held(p, x, cfg))
    for i, p in enumerate(layers):
        got = stacked({**p, **{k: stack[k] for k in moe_mod.HELD_WEIGHTS}}, x, jnp.int32(i))
        want = alone(p, x)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_engine_counts_every_held_assignment_of_every_decode_row():
    """With every expert held, each decode step computes top_k assignments
    for every row of the pool in every MoE layer, and the count rides the
    step's tokens to the host."""
    cfg = _reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(model, params, n_slots=3, max_len=48, seed=0)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab, n).astype(np.int32) for n in (7, 12, 5, 9)]
    out = eng.generate(prompts, [6, 4, 5, 3])
    assert [len(o) for o in out] == [6, 4, 5, 3]
    moe_layers = cfg.n_layers - cfg.n_dense_layers
    m = eng.metrics
    assert m.moe_held_assignments == m.decode_steps * 3 * cfg.moe.top_k * moe_layers > 0
    # one row's top_k experts are distinct; three rows give at most 3 * top_k
    assert (m.decode_steps * moe_layers * cfg.moe.top_k <= m.moe_held_experts
            <= m.decode_steps * moe_layers * 3 * cfg.moe.top_k)


def test_prelude_layer_runs_first_and_its_cache_is_a_one_deep_stack():
    cfg = _reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert len(params["prelude"]) == 1 and "router" not in params["prelude"][0]["ffn"]
    assert params["decoder"][0]["ffn"]["router"].shape[0] == cfg.n_layers - 1
    caches = model.init_cache(2, 16)
    assert [c["mixer"]["latent"].shape for c in caches] == [(1, 2, 16, 40), (3, 2, 16, 40)]
    # the first layer is the dense one: removing it changes the logits
    toks = jnp.asarray([[3, 9, 27, 81]], jnp.int32)
    logits, _ = model.prefill(params, {"tokens": toks})
    zeroed = jax.tree.map(jnp.zeros_like, params["prelude"])
    logits0, _ = model.prefill({**params, "prelude": zeroed}, {"tokens": toks})
    assert not np.allclose(np.asarray(logits), np.asarray(logits0))
