"""Top-level model: embeddings + stack(s) + LM head, with the three entry
points the launcher lowers: ``train_step`` (via train_loss), ``prefill`` and
``decode_step``.

Multimodal configs ([vlm]/[audio]) consume precomputed frontend embeddings
(the modality encoder is a stub per the assignment): the first
``frontend.n_tokens`` positions of the sequence are projected frontend
embeddings, the rest text tokens; the loss masks frontend positions.

Encoder-decoder configs (seamless-m4t) run a bidirectional encoder over
frontend frames and a causal decoder with cross-attention; decode steps
attend over the cached encoder memory.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import transformer as tf
from .layers import Initializer, cross_entropy_loss, dense_init, embed_init, rms_norm

__all__ = ["Model", "build_model"]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ---------------- init ----------------
    def init(self, key: jax.Array):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        init = Initializer(key)
        params = {
            "embed": embed_init(init, cfg.vocab, cfg.d_model, dtype),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(init, (cfg.d_model, cfg.vocab), dtype)
        if cfg.frontend is not None:
            params["frontend_proj"] = dense_init(
                init, (cfg.frontend.d_frontend, cfg.d_model), dtype
            )
        if cfg.enc_dec:
            params["encoder"] = tf.stack_init(
                init, cfg, dtype, n_layers=cfg.n_encoder_layers, encoder=True
            )
            params["decoder"] = tf.stack_init(init, cfg, dtype, cross=True)
        else:
            if cfg.n_dense_layers:
                params["prelude"] = tf.prelude_init(init, cfg, dtype)
            params["decoder"] = tf.stack_init(init, cfg, dtype)
        return params

    def param_axes(self):
        cfg = self.cfg
        axes = {
            "embed": ("vocab", "embed"),
            "final_norm": ("embed",),
        }
        if not cfg.tie_embeddings:
            axes["lm_head"] = ("embed", "vocab")
        if cfg.frontend is not None:
            axes["frontend_proj"] = (None, "embed")
        if cfg.enc_dec:
            axes["encoder"] = tf.stack_axes(cfg, n_layers=cfg.n_encoder_layers, encoder=True)
            axes["decoder"] = tf.stack_axes(cfg, cross=True)
        else:
            if cfg.n_dense_layers:
                axes["prelude"] = tf.prelude_axes(cfg)
            axes["decoder"] = tf.stack_axes(cfg)
        return axes

    # ---------------- shared pieces ----------------
    def _embed_inputs(self, params, batch, compute):
        """Token (+frontend) embeddings -> [B, S, D], loss mask [B, S]."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = params["embed"].astype(compute)[tokens]
        mask = jnp.ones(tokens.shape, jnp.float32)
        if cfg.frontend is not None and "frontend_embeds" in batch:
            fe = batch["frontend_embeds"].astype(compute) @ params["frontend_proj"].astype(compute)
            n = fe.shape[1]
            x = jnp.concatenate([fe, x[:, n:]], axis=1)
            mask = mask.at[:, :n].set(0.0)
        return x, mask

    def _encode(self, params, batch, compute, mesh=None):
        cfg = self.cfg
        fe = batch["encoder_frames"].astype(compute)
        x = fe @ params["frontend_proj"].astype(compute)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x, _, _ = tf.stack_apply(
            params["encoder"], x, cfg, positions=positions, encoder=True,
            n_layers=cfg.n_encoder_layers, mesh=mesh,
        )
        return rms_norm(x, params["final_norm"], cfg.norm_eps)

    def _decoder(self, params, x, positions, *, caches=None, update_cache=False, impl="xla",
                 key=None, mesh=None, ragged=False):
        """The prelude's layers, then the stack's: returns (x, caches, aux),
        the caches one tuple, the prelude's entries first."""
        n_pre = self.cfg.n_dense_layers
        pre, aux = (), None
        if n_pre:
            x, pre, aux = tf.prelude_apply(
                params["prelude"], x, self.cfg, positions=positions,
                caches=caches[:n_pre] if caches is not None else None,
                update_cache=update_cache, impl=impl, mesh=mesh, ragged=ragged,
            )
        x, new_caches, a = tf.stack_apply(
            params["decoder"], x, self.cfg, positions=positions,
            caches=caches[n_pre:] if caches is not None else None, update_cache=update_cache,
            impl=impl, key=key, mesh=mesh, ragged=ragged,
        )
        if new_caches is not None and n_pre:
            new_caches = tuple(pre) + tuple(new_caches)
        return x, new_caches, (tf.add_aux(aux, a) if n_pre else a)

    def _logits(self, params, x, compute):
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return x @ head.astype(compute)

    def _decoder_cross_caches(self, params, memory):
        """Precompute per-layer cross K/V from encoder memory."""
        cfg = self.cfg
        p = len(params["decoder"])
        r = cfg.n_layers // p
        caches = []
        for j in range(p):
            layer = params["decoder"][j]
            if r > 1:
                kv = jax.vmap(lambda lp: tf.cross_kv(lp["cross"], memory, cfg))(layer)
            else:
                kv = tf.cross_kv(layer["cross"], memory, cfg)
            caches.append(kv)
        return tuple(caches)

    # ---------------- training ----------------
    def train_loss(self, params, batch, key=None, impl: str = "xla", mesh=None):
        cfg = self.cfg
        compute = jnp.dtype(cfg.compute_dtype)
        if cfg.enc_dec:
            memory = self._encode(params, batch, compute, mesh=mesh)
            x = params["embed"].astype(compute)[batch["tokens"]]
            mask = jnp.ones(batch["tokens"].shape, jnp.float32)
            b, s, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            cross = self._decoder_cross_caches(params, memory)
            caches = tuple({"cross": c} for c in cross)
            x, _, aux = tf.stack_apply(
                params["decoder"], x, cfg, positions=positions, caches=caches, impl=impl,
                key=key, mesh=mesh,
            )
        else:
            x, mask = self._embed_inputs(params, batch, compute)
            b, s, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            x, _, aux = self._decoder(params, x, positions, impl=impl, key=key, mesh=mesh)
        if isinstance(aux, dict):  # a held expert share also counts assignments
            aux = aux["balance"]
        logits = self._logits(params, x, compute)
        mask = mask * batch.get("mask", jnp.ones_like(mask))
        loss = cross_entropy_loss(logits, batch["targets"], mask)
        metrics = {"loss": loss, "aux_loss": aux}
        if cfg.moe is not None:
            loss = loss + 0.01 * aux
        return loss, metrics

    # ---------------- serving ----------------
    def init_cache(self, batch: int, seq_len: int, mem_len: int = 0):
        """Decode caches: the prelude layers' entries, then the stack's."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        return tf.init_prelude_cache(cfg, batch, seq_len, dtype) + tf.init_stack_cache(
            cfg, batch, seq_len, cross=cfg.enc_dec, mem_len=mem_len, dtype=dtype,
        )

    def prefill(self, params, batch, impl: str = "xla", mesh=None, last_pos=None):
        """Full forward over the prompt; returns (last_logits, caches).

        ``last_pos`` ([B] int32, optional) selects the per-row position whose
        logits are returned — the last *real* prompt token when prompts are
        right-padded to a bucket length (continuous-batching prefill).  Causal
        attention guarantees right padding cannot leak into those logits; pair
        with :meth:`mask_prompt_cache` so the pad entries never enter decode.
        Default (``None``) keeps the seed behaviour: logits at position -1.
        """
        cfg = self.cfg
        compute = jnp.dtype(cfg.compute_dtype)
        if cfg.enc_dec:
            memory = self._encode(params, batch, compute, mesh=mesh)
            x = params["embed"].astype(compute)[batch["tokens"]]
            b, s, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            cross = self._decoder_cross_caches(params, memory)
            caches = tuple({"cross": c} for c in cross)
            x, new_caches, _ = tf.stack_apply(
                params["decoder"], x, cfg, positions=positions, caches=caches,
                update_cache=True, impl=impl, mesh=mesh,
            )
        else:
            x, _ = self._embed_inputs(params, batch, compute)
            b, s, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            x, new_caches, _ = self._decoder(params, x, positions, update_cache=True, impl=impl,
                                             mesh=mesh)
        if last_pos is None:
            x_last = x[:, -1:]
        else:
            idx = jnp.asarray(last_pos, jnp.int32).reshape(-1)  # [B]
            x_last = x[jnp.arange(x.shape[0]), idx][:, None]
        logits = self._logits(params, x_last, compute)
        return logits, new_caches

    def mask_prompt_cache(self, caches, true_len):
        """Invalidate cache entries written by right-pad positions >= ``true_len``
        (scalar or [B]) so ``prepare_decode_caches`` drops them and decode never
        attends to padding.  Only attention/MLA caches carry ``pos``; SSM state
        has no positional record — SSM/hybrid configs must prefill at the exact
        prompt length instead (the serving engine enforces this)."""
        true_len = jnp.asarray(true_len, jnp.int32)
        # pos leaves are [..., B, S]; a per-row [B] bound broadcasts as [B, 1]
        bound = true_len[:, None] if true_len.ndim == 1 else true_len

        def fix(entry):
            m = entry.get("mixer")
            if isinstance(m, dict) and "pos" in m:
                keep = m["pos"] < bound  # pos == arange(S) at prefill
                m = dict(m)
                m["pos"] = jnp.where(keep, m["pos"], -1)
                entry = dict(entry)
                entry["mixer"] = m
            return entry

        return tuple(fix(dict(e)) for e in caches)

    def prepare_decode_caches(self, caches, capacity: int):
        """Re-lay prefill caches into decode (ring) buffers with headroom,
        layer-stacked (the layout of :meth:`init_cache`).

        Full-attention layers get ``capacity`` slots (entry at slot
        pos % capacity); SWA layers keep ``min(capacity, window)`` most
        recent entries.  SSM and cross-attention caches pass through."""
        cfg = self.cfg

        def relay_mixer(c):
            if "pos" not in c:
                return c  # ssm: O(1) state
            cap = capacity
            if "k" in c and cfg.attn_type == "swa" and cfg.sliding_window:
                cap = min(capacity, cfg.sliding_window)
            if "k" in c:  # GQA: K and V side by side, as the decode cache holds them
                c = {"kv": jnp.concatenate([c["k"], c["v"]], axis=-1), "pos": c["pos"]}
            names = ("kv",) if "kv" in c else ("latent",)
            pos = c["pos"]  # [..., B, L]
            max_pos = jnp.max(pos, axis=-1, keepdims=True)
            keep = (pos >= 0) & (pos > max_pos - cap)
            slot = jnp.where(keep, pos % cap, cap)  # cap = discard slot

            def scatter_one(arr, fill):
                def core(sl, src):  # sl [L]; src [L, ...]
                    dst = jnp.full((cap + 1,) + src.shape[1:], fill, src.dtype)
                    return dst.at[sl].set(src)[:cap]

                fn = core
                for _ in range(pos.ndim - 1):
                    fn = jax.vmap(fn)
                return fn(slot, arr)

            out = {n: scatter_one(c[n], 0) for n in names}
            if "kv" in c:  # [.., B, L, Kv, 2D] -> head-major [.., B, Kv, L, 2D]
                out["kv"] = out["kv"].swapaxes(-3, -2)
            out["pos"] = scatter_one(jnp.where(keep, pos, -1), -1)
            return out

        def relay_block(bc):
            out = dict(bc)
            if "mixer" in out:
                out["mixer"] = relay_mixer(out["mixer"])
            return out

        n_pre = cfg.n_dense_layers
        prelude = tuple(jax.tree.map(lambda t: t[None], relay_block(bc)) for bc in caches[:n_pre])
        return prelude + tf.stack_layers(tuple(relay_block(bc) for bc in caches[n_pre:]),
                                         cfg.n_layers - n_pre)

    def decode_step(self, params, caches, tokens, pos, impl: str = "xla", mesh=None,
                    ragged: bool = False, with_aux: bool = False):
        """One token per sequence.  tokens [B, 1]; pos [B] absolute position.

        ``ragged=False`` (seed behaviour) assumes the batch advances in
        lockstep — all rows share one ring slot per step.  ``ragged=True`` is
        the continuous-batching contract: each row is an independent request
        at its own position, writing its own (slot-indexed) cache row.

        Returns (logits [B, 1, V], new_caches), and with ``with_aux`` the
        blocks' summed auxiliary outputs third (``transformer.zero_aux``).
        """
        cfg = self.cfg
        compute = jnp.dtype(cfg.compute_dtype)
        x = params["embed"].astype(compute)[tokens]
        positions = pos[:, None]
        x, new_caches, aux = self._decoder(params, x, positions, caches=caches, impl=impl,
                                           mesh=mesh, ragged=ragged)
        logits = self._logits(params, x, compute)
        return (logits, new_caches, aux) if with_aux else (logits, new_caches)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
