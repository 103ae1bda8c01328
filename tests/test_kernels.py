"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all in interpret mode against the pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import reference_attention
from repro.kernels.moe_gmm.ops import expert_ffn, gmm
from repro.kernels.moe_gmm.ref import reference_expert_ffn, reference_grouped_matmul
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import reference_ssd
from repro.models.ssm import ssd_chunked


def _tol(dtype):
    return {"float32": 2e-5, "bfloat16": 2e-2}[jnp.dtype(dtype).name]


# ---------------------------------------------------------------- flash attn
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,kv,d,causal,window,bq,bk",
    [
        (2, 256, 4, 2, 64, True, 0, 128, 128),
        (1, 512, 8, 8, 32, True, 0, 128, 256),
        (2, 256, 4, 1, 64, True, 64, 64, 64),
        (1, 128, 2, 2, 128, False, 0, 64, 64),
        (1, 384, 6, 3, 64, True, 128, 128, 128),
    ],
)
def test_flash_attention_sweep(b, s, h, kv, d, causal, window, bq, bk, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, block_q=bq, block_k=bk,
                          interpret=True)
    ref = reference_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=_tol(dtype), rtol=_tol(dtype)
    )


@given(
    s_blocks=st.integers(1, 4),
    h=st.sampled_from([2, 4]),
    g=st.sampled_from([1, 2]),
    d=st.sampled_from([32, 64]),
    causal=st.booleans(),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=12, deadline=None)
def test_flash_attention_property(s_blocks, h, g, d, causal, seed):
    """Property: kernel == oracle for random block-aligned shapes."""
    rng = np.random.default_rng(seed)
    s = 64 * s_blocks
    kv = h // g
    q = jnp.asarray(rng.normal(size=(1, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, s, kv, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_scale_invariance():
    """Softmax shift invariance: adding a constant to all logits via a
    common key direction must not change the output (online-softmax
    stability)."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32) * 30.0  # large logits
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32) * 30.0
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------- grouped gemm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "e,c,d,f",
    [(4, 256, 256, 128), (8, 128, 512, 256), (2, 128, 128, 128), (16, 128, 256, 128)],
)
def test_gmm_sweep(e, c, d, f, dtype):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(e, c, d)), dtype)
    w = jnp.asarray(rng.normal(size=(e, d, f)) / np.sqrt(d), dtype)
    out = gmm(x, w, interpret=True)
    ref = reference_grouped_matmul(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=5 * _tol(dtype),
        rtol=5 * _tol(dtype)
    )


def test_expert_ffn_matches_reference():
    rng = np.random.default_rng(2)
    e, c, d, f = 4, 128, 128, 256
    params = {
        "w_gate": jnp.asarray(rng.normal(size=(e, d, f)) / np.sqrt(d), jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(e, d, f)) / np.sqrt(d), jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(e, f, d)) / np.sqrt(f), jnp.float32),
    }
    buckets = jnp.asarray(rng.normal(size=(e, c, d)), jnp.float32)
    out = expert_ffn(params, buckets, interpret=True)
    ref = reference_expert_ffn(params, buckets)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------- ssd scan
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,p,n,chunk",
    [(2, 128, 4, 32, 16, 32), (1, 256, 2, 64, 32, 64), (1, 64, 8, 16, 128, 16)],
)
def test_ssd_kernel_sweep(b, s, h, p, n, chunk, dtype):
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), dtype)
    dt = jnp.asarray(rng.uniform(0.001, 0.2, size=(b, s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, size=(h,)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, s, n)), dtype)
    cc = jnp.asarray(rng.normal(size=(b, s, n)), dtype)
    y, hf = ssd(x, dt, a, bb, cc, chunk=chunk, interpret=True)
    y_ref, h_ref = reference_ssd(x, dt, a, bb, cc)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
        atol=20 * _tol(dtype), rtol=20 * _tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(hf), np.asarray(h_ref), atol=20 * _tol(dtype),
                               rtol=20 * _tol(dtype))


# ---------------------------------------------------------------- gradients
# Backward paths (custom_vjp): kernel forward + recompute-based VJP must
# match grad-through-the-reference on every input.
def _grads_allclose(fn_kernel, fn_ref, args, atol, argnums=None):
    argnums = tuple(range(len(args))) if argnums is None else argnums
    gk = jax.grad(fn_kernel, argnums=argnums)(*args)
    gr = jax.grad(fn_ref, argnums=argnums)(*args)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   atol=atol, rtol=atol)


@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
def test_flash_attention_grads(causal, window):
    rng = np.random.default_rng(5)
    b, s, h, kv, d = 1, 128, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)

    def loss_kernel(q, k, v):
        out = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
        return jnp.sum(out * cot)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal, window=window) * cot)

    _grads_allclose(loss_kernel, loss_ref, (q, k, v), atol=1e-4)


def test_gmm_grads_both_operands():
    """The grouped-GEMM backward is two grouped GEMMs through the same
    Pallas kernel: check dx and dw against grad-through-einsum."""
    rng = np.random.default_rng(6)
    e, c, d, f = 2, 128, 128, 128
    x = jnp.asarray(rng.normal(size=(e, c, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(e, d, f)) / np.sqrt(d), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(e, c, f)), jnp.float32)

    def loss_kernel(x, w):
        return jnp.sum(gmm(x, w, interpret=True) * cot)

    def loss_ref(x, w):
        return jnp.sum(reference_grouped_matmul(x, w) * cot)

    _grads_allclose(loss_kernel, loss_ref, (x, w), atol=2e-4)


def test_expert_ffn_grads():
    """SwiGLU FFN composed of differentiable grouped GEMMs backprops into
    activations and every weight."""
    rng = np.random.default_rng(7)
    e, c, d, f = 2, 128, 128, 128
    params = {
        "w_gate": jnp.asarray(rng.normal(size=(e, d, f)) / np.sqrt(d), jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(e, d, f)) / np.sqrt(d), jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(e, f, d)) / np.sqrt(f), jnp.float32),
    }
    buckets = jnp.asarray(rng.normal(size=(e, c, d)), jnp.float32)

    def loss_kernel(params, buckets):
        return jnp.sum(expert_ffn(params, buckets, interpret=True) ** 2)

    def loss_ref(params, buckets):
        return jnp.sum(reference_expert_ffn(params, buckets) ** 2)

    _grads_allclose(loss_kernel, loss_ref, (params, buckets), atol=2e-3)


def test_ssd_grads():
    rng = np.random.default_rng(8)
    b, s, h, p, n = 1, 64, 2, 16, 16
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.2, size=(b, s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 3.0, size=(h,)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)

    def loss_kernel(x, dt, a, bb, cc):
        y, hf = ssd(x, dt, a, bb, cc, chunk=32, interpret=True)
        return jnp.sum(y**2) + jnp.sum(hf**2)

    def loss_ref(x, dt, a, bb, cc):
        y, hf = reference_ssd(x, dt, a, bb, cc)
        return jnp.sum(y**2) + jnp.sum(hf**2)

    _grads_allclose(loss_kernel, loss_ref, (x, dt, a, bb, cc), atol=5e-4)


@given(
    chunks=st.integers(1, 4),
    h=st.sampled_from([1, 2, 4]),
    p=st.sampled_from([8, 16]),
    n=st.sampled_from([8, 16]),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=10, deadline=None)
def test_ssd_chunk_invariance(chunks, h, p, n, seed):
    """Property: the chunked model path equals the sequential recurrence for
    any chunking — the state-passing identity of the SSD paper."""
    rng = np.random.default_rng(seed)
    s = 32 * chunks
    x = jnp.asarray(rng.normal(size=(1, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.3, size=(1, s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.2, 3.0, size=(h,)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(1, s, n)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(1, s, n)), jnp.float32)
    y1, h1 = ssd_chunked(x, dt, a, bb, cc, chunk=32)
    y2, h2 = reference_ssd(x, dt, a, bb, cc)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------- call sites
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mamba2-1.3b", "olmoe-1b-7b"])
def test_model_call_site_interprets_on_cpu(arch):
    """The model's ``impl="pallas"`` call sites pass no ``interpret``: the
    kernels' one switch picks the interpreter off the TPU (compiling a
    Pallas TPU kernel for the CPU backend would raise), and the result
    matches the XLA path."""
    import dataclasses

    from repro.configs.base import get_config
    from repro.kernels import resolve_interpret
    from repro.models import build_model

    assert jax.default_backend() != "tpu"
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    cfg = dataclasses.replace(get_config(arch, reduced=True), compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.default_rng(9).integers(1, cfg.vocab, (2, 32)), jnp.int32)
    ref, _ = model.prefill(params, {"tokens": tokens}, impl="xla")
    out, _ = model.prefill(params, {"tokens": tokens}, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4)
