"""Unified LM over all assigned families.

``build_model(cfg)`` returns an :class:`LM` exposing:

* ``init(rng) -> params``
* ``loss_fn(params, batch) -> (loss, metrics)``  (training)
* ``prefill(params, batch, max_len) -> (logits, cache)``
* ``decode_step(params, cache, tokens, positions) -> (logits, cache)``

Batches are dicts of arrays (see ``repro.data``). All layer stacks are
scanned; remat policy is configurable.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    cross_entropy_loss, dense_init, embed, init_embedding, init_rmsnorm,
    lm_logits, rmsnorm)

PyTree = Any


_REMAT_POLICIES = {
    # full per-layer recompute: only the residual-stream carry survives the
    # forward pass — the policy that fits 40-60L models in 16 GB HBM
    "nothing": jax.checkpoint_policies.nothing_saveable,
    # save matmul outputs (fastest backward, ~4-6× the live activations)
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def _remat(fn, enabled: bool, policy: str = "nothing"):
    if not enabled:
        return fn
    return jax.checkpoint(fn, policy=_REMAT_POLICIES[policy])


class LM:
    """Decoder-only LM (dense / moe / ssm / hybrid / vlm) or enc-dec."""

    def __init__(self, cfg, *, param_dtype=jnp.float32,
                 compute_dtype=jnp.float32, chunk_size: int = 512,
                 remat: bool = True, remat_policy: str = "nothing",
                 ep_axes: tuple = (), scan_unroll: bool = False,
                 kv_cache_dtype: str = "native"):
        self.cfg = cfg
        # "int8": quantized KV cache for dense-GQA decode (§Perf hillclimb C)
        self.kv_cache_dtype = kv_cache_dtype
        self.ep_axes = tuple(ep_axes)
        # scan_unroll=True removes every while loop so cost_analysis counts
        # all work exactly — used by the roofline sample compiles (DESIGN §6)
        self.scan_unroll = scan_unroll
        self.param_dtype = param_dtype
        self.compute_dtype = compute_dtype
        self.chunk_size = chunk_size
        self.remat = remat
        self.remat_policy = remat_policy
        fam = cfg.family
        if fam == "hybrid":
            h = cfg.hybrid
            self.n_seg = cfg.num_layers // h.shared_block_period
            self.seg_len = h.shared_block_period
            self.tail_len = cfg.num_layers - self.n_seg * self.seg_len
        if fam == "moe":
            self.n_dense = cfg.moe.first_k_dense
            self.n_moe = cfg.num_layers - self.n_dense

    # ------------------------------------------------------------------ init
    def init(self, rng) -> PyTree:
        cfg, dt = self.cfg, self.param_dtype
        keys = jax.random.split(rng, 12)
        p: dict = {"embed": init_embedding(keys[0], cfg.padded_vocab,
                                           cfg.d_model, dt)}
        if not cfg.tie_embeddings:
            p["head"] = init_embedding(keys[1], cfg.padded_vocab,
                                       cfg.d_model, dt)
        p["final_ln"] = init_rmsnorm(cfg.d_model, dt)
        fam = cfg.family

        if fam in ("attn_dense", "vlm"):
            p["blocks"] = B.stack_init(
                lambda k: B.init_decoder_block(k, cfg, dt, ffn_kind="dense"),
                keys[2], cfg.num_layers)
        elif fam == "moe":
            if self.n_dense:
                p["dense_blocks"] = B.stack_init(
                    lambda k: B.init_decoder_block(k, cfg, dt,
                                                   ffn_kind="dense"),
                    keys[3], self.n_dense)
            p["moe_blocks"] = B.stack_init(
                lambda k: B.init_decoder_block(k, cfg, dt, ffn_kind="moe"),
                keys[2], self.n_moe)
        elif fam == "ssm":
            p["blocks"] = B.stack_init(
                lambda k: B.init_ssm_block(k, cfg, dt), keys[2],
                cfg.num_layers)
        elif fam == "hybrid":
            seg = B.stack_init(
                lambda k: B.stack_init(
                    lambda k2: B.init_ssm_block(k2, cfg, dt), k, self.seg_len),
                keys[2], self.n_seg)
            p["mamba_seg"] = seg
            if self.tail_len:
                p["mamba_tail"] = B.stack_init(
                    lambda k: B.init_ssm_block(k, cfg, dt), keys[3],
                    self.tail_len)
            p["shared_blocks"] = B.stack_init(
                lambda k: B.init_shared_block(k, cfg, dt), keys[4],
                cfg.hybrid.num_shared_blocks)
            p["loras"] = B.stack_init(
                lambda k: B.init_lora(k, cfg, dt), keys[5], self.n_seg)
        elif fam == "encdec":
            p["enc_blocks"] = B.stack_init(
                lambda k: B.init_encoder_block(k, cfg, dt), keys[2],
                cfg.num_encoder_layers)
            p["dec_blocks"] = B.stack_init(
                lambda k: B.init_encdec_decoder_block(k, cfg, dt), keys[3],
                cfg.num_layers)
            p["enc_ln"] = init_rmsnorm(cfg.d_model, dt)
        else:
            raise ValueError(fam)

        if cfg.frontend.kind == "vision":
            d_f = cfg.frontend.d_frontend
            ks = jax.random.split(keys[6], cfg.frontend.projector_layers)
            proj = [dense_init(ks[0], d_f, cfg.d_model, dt)]
            for i in range(1, cfg.frontend.projector_layers):
                proj.append(dense_init(ks[i], cfg.d_model, cfg.d_model, dt))
            p["projector"] = proj
        return p

    # ------------------------------------------------------------- embedding
    def _embed_tokens(self, params, tokens):
        h = embed(params["embed"], tokens, self.cfg.embedding_scale)
        return h.astype(self.compute_dtype)

    def _project_frontend(self, params, embeds):
        h = embeds.astype(self.compute_dtype)
        for i, w in enumerate(params["projector"]):
            if i:
                h = jax.nn.gelu(h, approximate=True)
            h = h @ w.astype(self.compute_dtype)
        return h

    def _logits(self, params, h):
        return lm_logits(params["embed"], params.get("head"), h,
                         self.cfg.tie_embeddings, self.cfg.logit_scale,
                         self.cfg.logit_soft_cap,
                         vocab_size=self.cfg.vocab_size)

    # ------------------------------------------------------------ backbones
    def _run_decoder_stack(self, params, h, positions, collect_kv=False):
        """Dense/MoE/VLM scanned decoder stack. Returns (h, kv_list, aux)."""
        cfg, cs = self.cfg, self.chunk_size
        aux_total = jnp.zeros((), jnp.float32)
        kvs = {}

        def make_body(ffn_kind):
            def body(carry, layer_params):
                hh = carry
                hh, kv, aux = B.apply_decoder_block(
                    layer_params, cfg, hh, positions, ffn_kind=ffn_kind,
                    chunk_size=cs, ep_axes=self.ep_axes,
                    unroll=self.scan_unroll)
                out = kv if collect_kv else (jnp.zeros((), jnp.float32),) * 2
                return hh, (out, aux)
            return body

        if cfg.family == "moe":
            if self.n_dense:
                h, (kv_d, aux_d) = jax.lax.scan(
                    _remat(make_body("dense"), self.remat, self.remat_policy), h,
                    params["dense_blocks"], unroll=self.scan_unroll)
                aux_total += jnp.sum(aux_d)
                kvs["dense"] = kv_d
            h, (kv_m, aux_m) = jax.lax.scan(
                _remat(make_body("moe"), self.remat, self.remat_policy), h,
                params["moe_blocks"], unroll=self.scan_unroll)
            aux_total += jnp.sum(aux_m)
            kvs["moe"] = kv_m
        else:
            h, (kv, aux) = jax.lax.scan(
                _remat(make_body("dense"), self.remat, self.remat_policy), h,
                params["blocks"], unroll=self.scan_unroll)
            aux_total += jnp.sum(aux)
            kvs["blocks"] = kv
        return h, kvs, aux_total

    def _run_ssm_stack(self, params, h, collect_state=False):
        cfg = self.cfg

        def body(carry, layer_params):
            hh = carry
            hh, state = B.apply_ssm_block(layer_params, cfg, hh,
                                          unroll=self.scan_unroll)
            out = state if collect_state else (
                jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
            return hh, out
        h, states = jax.lax.scan(_remat(body, self.remat, self.remat_policy),
                                 h, params["blocks"],
                                 unroll=self.scan_unroll)
        return h, states

    def _run_hybrid_stack(self, params, h, positions, collect=False):
        """Zamba2: n_seg × (seg_len mamba + shared attn w/ LoRA) + tail."""
        cfg, cs = self.cfg, self.chunk_size
        n_shared = cfg.hybrid.num_shared_blocks

        def seg_body(carry, xs):
            hh, seg_idx = carry
            seg_params, lora = xs

            def inner(c, lp):
                c2, state = B.apply_ssm_block(lp, cfg, c,
                                              unroll=self.scan_unroll)
                out = state if collect else (jnp.zeros(()), jnp.zeros(()))
                return c2, out
            hh, states = jax.lax.scan(inner, hh, seg_params,
                                      unroll=self.scan_unroll)
            shared = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, seg_idx % n_shared, 0, keepdims=False),
                params["shared_blocks"])
            hh, kv = B.apply_shared_block(shared, lora, cfg, hh, positions,
                                          chunk_size=cs,
                                          unroll=self.scan_unroll)
            out_kv = kv if collect else (jnp.zeros(()), jnp.zeros(()))
            return (hh, seg_idx + 1), (states, out_kv)

        (h, _), (seg_states, shared_kv) = jax.lax.scan(
            _remat(seg_body, self.remat, self.remat_policy), (h, 0),
            (params["mamba_seg"], params["loras"]),
            unroll=self.scan_unroll)

        tail_states = None
        if self.tail_len:
            def tail_body(c, lp):
                c2, state = B.apply_ssm_block(lp, cfg, c,
                                              unroll=self.scan_unroll)
                out = state if collect else (jnp.zeros(()), jnp.zeros(()))
                return c2, out
            h, tail_states = jax.lax.scan(
                _remat(tail_body, self.remat, self.remat_policy), h,
                params["mamba_tail"], unroll=self.scan_unroll)
        return h, (seg_states, shared_kv, tail_states)

    def _run_encoder(self, params, src, collect=False):
        cfg, cs = self.cfg, self.chunk_size
        Bz, T, _ = src.shape
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (Bz, T))

        def body(carry, lp):
            return B.apply_encoder_block(lp, cfg, carry, positions,
                                         chunk_size=cs,
                                         unroll=self.scan_unroll), None
        h, _ = jax.lax.scan(_remat(body, self.remat, self.remat_policy),
                            src.astype(self.compute_dtype),
                            params["enc_blocks"], unroll=self.scan_unroll)
        return rmsnorm(params["enc_ln"], h, cfg.norm_eps)

    # ---------------------------------------------------------------- train
    def loss_fn(self, params, batch):
        cfg = self.cfg
        params = jax.tree.map(
            lambda a: a.astype(self.compute_dtype)
            if a.dtype in (jnp.float32, jnp.bfloat16, jnp.float16) and
            a.ndim >= 1 else a, params)
        tokens = batch["tokens"]
        Bz, S = tokens.shape
        h = self._embed_tokens(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (Bz, S))
        aux = jnp.zeros((), jnp.float32)
        fam = cfg.family

        if fam == "vlm":
            img = self._project_frontend(params, batch["frontend_embeds"])
            n_img = img.shape[1]
            h = jnp.concatenate([img, h], axis=1)
            total = n_img + S
            positions = jnp.broadcast_to(
                jnp.arange(total, dtype=jnp.int32), (Bz, total))
            h, _, aux = self._run_decoder_stack(params, h, positions)
            h = h[:, n_img:]
        elif fam in ("attn_dense", "moe"):
            h, _, aux = self._run_decoder_stack(params, h, positions)
        elif fam == "ssm":
            h, _ = self._run_ssm_stack(params, h)
        elif fam == "hybrid":
            h, _ = self._run_hybrid_stack(params, h, positions)
        elif fam == "encdec":
            enc_out = self._run_encoder(params, batch["frontend_embeds"])
            ek_ev = None
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (Bz, S))

            def body(carry, lp):
                kk, vv = B.cross_kv(lp, cfg, enc_out)
                out, _ = B.apply_encdec_decoder_block(
                    lp, cfg, carry, positions, kk, vv,
                    chunk_size=self.chunk_size, unroll=self.scan_unroll)
                return out, None
            h, _ = jax.lax.scan(_remat(body, self.remat, self.remat_policy),
                                h, params["dec_blocks"],
                                unroll=self.scan_unroll)
        else:
            raise ValueError(fam)

        h = rmsnorm(params["final_ln"], h, cfg.norm_eps)
        logits = self._logits(params, h)
        loss = cross_entropy_loss(logits, batch["labels"],
                                  batch.get("loss_mask"))
        if cfg.moe is not None:
            loss = loss + cfg.moe.router_aux_weight * aux
        metrics = {"loss": loss, "aux_loss": aux}
        return loss, metrics

    # -------------------------------------------------------------- prefill
    def _pad_kv_to(self, kv, max_len):
        """kv: (L, B, S, ...) -> padded to (L, B, max_len, ...). A frontend
        prefix (VLM image tokens) may push S past max_len — never truncate."""
        max_len = max(max_len, kv.shape[2])
        pad = max_len - kv.shape[2]
        widths = [(0, 0)] * kv.ndim
        widths[2] = (0, pad)
        return jnp.pad(kv, widths)

    def prefill(self, params, batch, max_len: int, n_logits: int = 1):
        """Run the prompt, return (logits of the last ``n_logits``
        positions, decode cache)."""
        cfg = self.cfg
        params = jax.tree.map(
            lambda a: a.astype(self.compute_dtype)
            if a.dtype in (jnp.float32, jnp.bfloat16) and a.ndim >= 1 else a,
            params)
        tokens = batch["tokens"]
        Bz, S = tokens.shape
        h = self._embed_tokens(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (Bz, S))
        fam = cfg.family
        cache: dict = {"pos": jnp.full((Bz,), S, jnp.int32)}

        if fam == "vlm":
            img = self._project_frontend(params, batch["frontend_embeds"])
            n_img = img.shape[1]
            h = jnp.concatenate([img, h], axis=1)
            total = n_img + S
            positions = jnp.broadcast_to(jnp.arange(total, dtype=jnp.int32),
                                         (Bz, total))
            cache["pos"] = jnp.full((Bz,), total, jnp.int32)

        if fam in ("attn_dense", "moe", "vlm"):
            h, kvs, _ = self._run_decoder_stack(params, h, positions,
                                                collect_kv=True)
            if cfg.family == "moe":
                parts = [kvs[k] for k in ("dense", "moe") if k in kvs]
                kv = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *parts)
            else:
                kv = kvs["blocks"]
            if cfg.mla is not None:
                cache["c"] = self._pad_kv_to(kv[0], max_len)
                cache["kr"] = self._pad_kv_to(kv[1], max_len)
            elif self.kv_cache_dtype == "int8" and cfg.family != "moe":
                from repro.models.attention import quantize_kv
                kq, ks = quantize_kv(self._pad_kv_to(kv[0], max_len))
                vq, vs = quantize_kv(self._pad_kv_to(kv[1], max_len))
                cache["k"], cache["k_scale"] = kq, ks
                cache["v"], cache["v_scale"] = vq, vs
            else:
                cache["k"] = self._pad_kv_to(kv[0], max_len)
                cache["v"] = self._pad_kv_to(kv[1], max_len)
        elif fam == "ssm":
            h, states = self._run_ssm_stack(params, h, collect_state=True)
            cache["conv"] = states[0]
            cache["ssm"] = states[1]
        elif fam == "hybrid":
            h, (seg_states, shared_kv, tail_states) = self._run_hybrid_stack(
                params, h, positions, collect=True)
            cache["seg_conv"], cache["seg_ssm"] = seg_states
            cache["shared_k"] = self._pad_kv_to(shared_kv[0], max_len)
            cache["shared_v"] = self._pad_kv_to(shared_kv[1], max_len)
            if tail_states is not None:
                cache["tail_conv"], cache["tail_ssm"] = tail_states
        elif fam == "encdec":
            enc_out = self._run_encoder(params, batch["frontend_embeds"])

            def body(carry, lp):
                kk, vv = B.cross_kv(lp, cfg, enc_out)
                out, kv = B.apply_encdec_decoder_block(
                    lp, cfg, carry, positions, kk, vv,
                    chunk_size=self.chunk_size, unroll=self.scan_unroll)
                return out, (kv, (kk, vv))
            h, (self_kv, cross_kv_) = jax.lax.scan(
                body, h, params["dec_blocks"], unroll=self.scan_unroll)
            cache["k"] = self._pad_kv_to(self_kv[0], max_len)
            cache["v"] = self._pad_kv_to(self_kv[1], max_len)
            cache["ek"], cache["ev"] = cross_kv_
        else:
            raise ValueError(fam)

        h = rmsnorm(params["final_ln"], h, cfg.norm_eps)
        logits = self._logits(params, h[:, -n_logits:])
        return logits, cache

    # ---------------------------------------------------- paged decode step
    def cache_descriptor(self, page_tokens: int = 16):
        """This model's :class:`~repro.core.engines.desc.CacheDescriptor`
        — the frozen plane layout that drives the pooled serving path —
        or None when the family has no pooled layout (hybrid/encdec stay
        on the mirrored dense-cache fallback)."""
        from repro.core.engines.desc import descriptor_for
        return descriptor_for(self.cfg, self.kv_cache_dtype,
                              self.compute_dtype, page_tokens)

    def supports_paged_decode(self) -> bool:
        """True when this model can decode directly over a paged pool —
        i.e. when a cache descriptor exists for its config. Dense GQA pools
        ``(k, v)`` planes, int8 adds scale planes, MLA pools the latent,
        SSM rides its state rows alongside the page tables; hybrid and
        encdec have no descriptor yet and stay mirrored."""
        return self.cache_descriptor() is not None

    def _decoder_plane_names(self):
        return tuple(p.name for p in self.cache_descriptor().paged_planes)

    def _cast_params(self, params):
        return jax.tree.map(
            lambda a: a.astype(self.compute_dtype)
            if a.dtype in (jnp.float32, jnp.bfloat16) and a.ndim >= 1 else a,
            params)

    def _scan_paged_planes(self, params, h, pools, step_fn):
        """Scan the decoder stack with per-layer pool-plane slices as xs.
        ``step_fn(ffn_kind) -> (carry, (lp, *planes)) -> (carry, planes)``.
        MoE configs split into the dense-prefix and MoE scans (same split
        as :meth:`decode_step`); everything else is one scan."""
        cfg = self.cfg
        if cfg.family == "moe":
            n_d = self.n_dense
            parts = []
            if n_d:
                h, out_d = jax.lax.scan(
                    step_fn("dense"), h,
                    (params["dense_blocks"],) + tuple(p[:n_d] for p in pools),
                    unroll=self.scan_unroll)
                parts.append(out_d)
            h, out_m = jax.lax.scan(
                step_fn("moe"), h,
                (params["moe_blocks"],) + tuple(p[n_d:] for p in pools),
                unroll=self.scan_unroll)
            parts.append(out_m)
            new_pools = tuple(
                jnp.concatenate([part[i] for part in parts], 0)
                for i in range(len(pools)))
        else:
            h, new_pools = jax.lax.scan(
                step_fn("dense"), h, (params["blocks"],) + tuple(pools),
                unroll=self.scan_unroll)
        return h, new_pools

    def decode_step_paged(self, params, cache, tokens, positions):
        """One decode step over the device-resident paged pool.

        cache: ``pos (B,)``, one ``pool_<plane>`` array per descriptor
        plane (``(L, P, T, *shape)``), and ``block_table (B, MP)`` (dead
        entries clamped/skipped by the kernel). The layer scan carries the
        pool-plane slices as xs, each layer scattering its new token into
        its page slot and attending through the family's paged kernel — no
        dense per-sequence cache row is ever materialized, which is what
        keeps the serving mirror's device→host traffic at zero. SSM
        configs have no paged planes: their state rows ARE the cache, so
        this is exactly :meth:`decode_step`.
        """
        desc = self.cache_descriptor()
        if desc is None:
            raise ValueError(
                f"no cache descriptor for family={self.cfg.family!r} "
                f"kv_cache_dtype={self.kv_cache_dtype!r}; paged decode "
                f"needs a pooled layout")
        if not desc.has_pages:
            return self.decode_step(params, cache, tokens, positions)
        cfg = self.cfg
        params = self._cast_params(params)
        h = self._embed_tokens(params, tokens)
        table = cache["block_table"]
        names = self._decoder_plane_names()
        pools = tuple(cache["pool_" + n] for n in names)

        def step_fn(ffn_kind):
            def body(carry, xs):
                hh, planes = B.decode_paged_block(
                    xs[0], cfg, carry, xs[1:], table, positions,
                    ffn_kind=ffn_kind, ep_axes=self.ep_axes)
                return hh, planes
            return body

        h, new_pools = self._scan_paged_planes(params, h, pools, step_fn)
        new_cache = {"pos": positions + 1, "block_table": table}
        for n, p in zip(names, new_pools):
            new_cache["pool_" + n] = p
        h = rmsnorm(params["final_ln"], h, cfg.norm_eps)
        logits = self._logits(params, h)
        return logits, new_cache

    # ----------------------------------------------- fused ragged step (I5)
    def supports_ragged_step(self) -> bool:
        """True when this model can run a fused mixed-batch tick: a ragged
        multi-token step where decode rows (1 new token) and prefill-chunk
        rows (several) share one forward. Same gate as paged decode — a
        cache descriptor exists; families without one keep the per-chunk
        batch=1 fallback."""
        return self.supports_paged_decode()

    def step_paged_ragged(self, params, cache, tokens, ctx_lens, q_lens):
        """One fused mixed-batch step over the device-resident paged pool.

        tokens: (B, Qmax) int32 — row ``b``'s ``q_lens[b]`` new tokens
        (decode rows hold 1, prefill-chunk rows up to the chunk budget),
        padded to the bucketing ladder's Qmax; ctx_lens: (B,) tokens already
        in the pool per row; q_lens: (B,) with 0 marking batch-width padding
        rows (they scatter nothing and their outputs are garbage to
        discard). cache: one ``pool_<plane>`` per descriptor plane +
        ``block_table (B, MP)``; SSM configs instead carry their
        ``conv``/``ssm`` state rows and return per-slot ``conv_steps``/
        ``ssm_steps`` (the engine commits the committed slot's state).
        Returns logits for every query slot ``(B, Qmax, V)`` — callers read
        slot ``q_lens[b] - 1`` — and the updated cache with
        ``pos = ctx_lens + q_lens``.
        """
        desc = self.cache_descriptor()
        if desc is None:
            raise ValueError(
                f"no cache descriptor for family={self.cfg.family!r} "
                f"kv_cache_dtype={self.kv_cache_dtype!r}; ragged paged "
                f"step needs a pooled layout")
        if not desc.has_pages:
            return self._step_ragged_ssm(params, cache, tokens, ctx_lens,
                                         q_lens)
        cfg = self.cfg
        params = self._cast_params(params)
        h = self._embed_tokens(params, tokens)
        table = cache["block_table"]
        names = self._decoder_plane_names()
        pools = tuple(cache["pool_" + n] for n in names)

        def step_fn(ffn_kind):
            def body(carry, xs):
                hh, planes = B.step_paged_ragged_block(
                    xs[0], cfg, carry, xs[1:], table, ctx_lens, q_lens,
                    ffn_kind=ffn_kind, ep_axes=self.ep_axes)
                return hh, planes
            return body

        h, new_pools = self._scan_paged_planes(params, h, pools, step_fn)
        new_cache = {"pos": ctx_lens + q_lens, "block_table": table}
        for n, p in zip(names, new_pools):
            new_cache["pool_" + n] = p
        h = rmsnorm(params["final_ln"], h, cfg.norm_eps)
        logits = self._logits(params, h)
        return logits, new_cache

    def _step_ragged_ssm(self, params, cache, tokens, ctx_lens, q_lens):
        """Ragged multi-token SSM step: each layer scans its single-step
        mixer over the Qmax slots (state updates masked past ``q_lens``)
        and emits PER-SLOT states ``conv_steps``/``ssm_steps`` shaped
        ``(L, Qmax, B, ...)`` — slot ``i`` holds the state after absorbing
        token ``i``. The caller (serving engine) selects the committed
        slot's state per row; picking an earlier slot IS the speculative
        rollback. ``cache["conv"]``/``cache["ssm"]`` stay the step's INPUT
        states so committed == 0 rows keep them unchanged."""
        cfg = self.cfg
        params = self._cast_params(params)
        h = self._embed_tokens(params, tokens)

        def body(carry, xs):
            lp, conv_s, ssm_s = xs
            hh, conv_steps, ssm_steps = B.step_ragged_ssm_block(
                lp, cfg, carry, conv_s, ssm_s, q_lens)
            return hh, (conv_steps, ssm_steps)
        h, (conv_steps, ssm_steps) = jax.lax.scan(
            body, h, (params["blocks"], cache["conv"], cache["ssm"]),
            unroll=self.scan_unroll)
        new_cache = dict(cache)
        new_cache["pos"] = ctx_lens + q_lens
        new_cache["conv_steps"] = conv_steps
        new_cache["ssm_steps"] = ssm_steps
        h = rmsnorm(params["final_ln"], h, cfg.norm_eps)
        logits = self._logits(params, h)
        return logits, new_cache

    def step_ragged(self, params, cache, tokens, ctx_lens, q_lens):
        """The fused mixed-batch step's mirrored twin: a ragged multi-token
        step over the dense padded cache planes (``(L, B, T, *shape)`` in
        descriptor order — ``k``/``v``, int8 + scales, or MLA ``c``/
        ``kr``; SSM routes to the per-slot state scan). Same contract as
        :meth:`step_paged_ragged`; with every ``q_len == 1`` this is
        ``decode_step`` exactly."""
        desc = self.cache_descriptor()
        if desc is None:
            raise ValueError(
                f"no cache descriptor for family={self.cfg.family!r} "
                f"kv_cache_dtype={self.kv_cache_dtype!r}; ragged step "
                f"needs a pooled layout")
        if not desc.has_pages:
            return self._step_ragged_ssm(params, cache, tokens, ctx_lens,
                                         q_lens)
        cfg = self.cfg
        params = self._cast_params(params)
        h = self._embed_tokens(params, tokens)
        names = self._decoder_plane_names()
        planes = tuple(cache[n] for n in names)

        def step_fn(ffn_kind):
            def body(carry, xs):
                hh, out = B.step_ragged_block(
                    xs[0], cfg, carry, xs[1:], ctx_lens, q_lens,
                    ffn_kind=ffn_kind, ep_axes=self.ep_axes)
                return hh, out
            return body

        h, new_planes = self._scan_paged_planes(params, h, planes, step_fn)
        new_cache = dict(cache)
        new_cache["pos"] = ctx_lens + q_lens
        for n, p in zip(names, new_planes):
            new_cache[n] = p
        h = rmsnorm(params["final_ln"], h, cfg.norm_eps)
        logits = self._logits(params, h)
        return logits, new_cache

    # ---------------------------------------------------------- decode step
    def decode_step(self, params, cache, tokens, positions):
        """tokens: (B, 1) int32; positions: (B,) int32 write/query index."""
        cfg = self.cfg
        params = jax.tree.map(
            lambda a: a.astype(self.compute_dtype)
            if a.dtype in (jnp.float32, jnp.bfloat16) and a.ndim >= 1 else a,
            params)
        Bz = tokens.shape[0]
        h = self._embed_tokens(params, tokens)
        fam = cfg.family
        new_cache = dict(cache)
        new_cache["pos"] = positions + 1

        if fam in ("attn_dense", "vlm", "moe"):
            c0, c1 = (("c", "kr") if cfg.mla is not None else ("k", "v"))

            if fam == "moe":
                n_d = self.n_dense
                ck, cv = cache[c0], cache[c1]
                nk_parts, nv_parts = [], []
                if n_d:
                    def body_d(carry, xs):
                        lp, k_, v_ = xs
                        hh, (nk, nv) = B.decode_decoder_block(
                            lp, cfg, carry, (k_, v_), positions,
                            ffn_kind="dense")
                        return hh, (nk, nv)
                    h, (nkd, nvd) = jax.lax.scan(
                        body_d, h, (params["dense_blocks"],
                                    ck[:n_d], cv[:n_d]),
                        unroll=self.scan_unroll)
                    nk_parts.append(nkd)
                    nv_parts.append(nvd)

                def body_m(carry, xs):
                    lp, k_, v_ = xs
                    hh, (nk, nv) = B.decode_decoder_block(
                        lp, cfg, carry, (k_, v_), positions, ffn_kind="moe",
                        ep_axes=self.ep_axes)
                    return hh, (nk, nv)
                h, (nkm, nvm) = jax.lax.scan(
                    body_m, h, (params["moe_blocks"], ck[n_d:], cv[n_d:]),
                    unroll=self.scan_unroll)
                nk_parts.append(nkm)
                nv_parts.append(nvm)
                new_cache[c0] = jnp.concatenate(nk_parts, 0)
                new_cache[c1] = jnp.concatenate(nv_parts, 0)
            elif self.kv_cache_dtype == "int8" and cfg.mla is None:
                from repro.models import attention as attn_mod
                from repro.models.layers import rmsnorm as _rms

                def body_q8(carry, xs):
                    lp, k_, v_, ks_, vs_ = xs
                    hh = carry
                    xn = _rms(lp["ln_attn"], hh, cfg.norm_eps)
                    a, nk, nv, nks, nvs = attn_mod.attn_decode_q8(
                        lp["attn"], cfg, xn, k_, v_, ks_, vs_, positions)
                    hh = hh + cfg.residual_scale * a
                    xn = _rms(lp["ln_ffn"], hh, cfg.norm_eps)
                    from repro.models.layers import apply_ffn as _ffn
                    hh = hh + cfg.residual_scale * _ffn(
                        lp["ffn"], xn, cfg.ffn_activation)
                    return hh, (nk, nv, nks, nvs)
                h, (nk, nv, nks, nvs) = jax.lax.scan(
                    body_q8, h,
                    (params["blocks"], cache["k"], cache["v"],
                     cache["k_scale"], cache["v_scale"]),
                    unroll=self.scan_unroll)
                new_cache["k"], new_cache["v"] = nk, nv
                new_cache["k_scale"], new_cache["v_scale"] = nks, nvs
            else:
                def body_s(carry, xs):
                    lp, k_, v_ = xs
                    hh, (nk, nv) = B.decode_decoder_block(
                        lp, cfg, carry, (k_, v_), positions, ffn_kind="dense")
                    return hh, (nk, nv)
                h, (nk, nv) = jax.lax.scan(
                    body_s, h, (params["blocks"], cache[c0], cache[c1]),
                    unroll=self.scan_unroll)
                new_cache[c0], new_cache[c1] = nk, nv
        elif fam == "ssm":
            def body(carry, xs):
                lp, conv_s, ssm_s = xs
                hh, nc, ns = B.decode_ssm_block(lp, cfg, carry, conv_s, ssm_s)
                return hh, (nc, ns)
            h, (nc, ns) = jax.lax.scan(
                body, h, (params["blocks"], cache["conv"], cache["ssm"]),
                unroll=self.scan_unroll)
            new_cache["conv"], new_cache["ssm"] = nc, ns
        elif fam == "hybrid":
            n_shared = cfg.hybrid.num_shared_blocks

            def seg_body(carry, xs):
                hh, seg_idx = carry
                seg_params, lora, conv_s, ssm_s, sk, sv = xs

                def inner(c, lp_states):
                    lp, cs_, ss_ = lp_states
                    c2, nc, ns = B.decode_ssm_block(lp, cfg, c, cs_, ss_)
                    return c2, (nc, ns)
                hh, (nc, ns) = jax.lax.scan(
                    inner, hh, (seg_params, conv_s, ssm_s),
                    unroll=self.scan_unroll)
                shared = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, seg_idx % n_shared, 0, keepdims=False),
                    params["shared_blocks"])
                hh, (nsk, nsv) = B.decode_shared_block(
                    shared, lora, cfg, hh, (sk, sv), positions)
                return (hh, seg_idx + 1), (nc, ns, nsk, nsv)

            (h, _), (nc, ns, nsk, nsv) = jax.lax.scan(
                seg_body, (h, 0),
                (params["mamba_seg"], params["loras"], cache["seg_conv"],
                 cache["seg_ssm"], cache["shared_k"], cache["shared_v"]),
                unroll=self.scan_unroll)
            new_cache["seg_conv"], new_cache["seg_ssm"] = nc, ns
            new_cache["shared_k"], new_cache["shared_v"] = nsk, nsv
            if self.tail_len:
                def tail_body(c, xs):
                    lp, cs_, ss_ = xs
                    c2, ncx, nsx = B.decode_ssm_block(lp, cfg, c, cs_, ss_)
                    return c2, (ncx, nsx)
                h, (ntc, nts) = jax.lax.scan(
                    tail_body, h, (params["mamba_tail"], cache["tail_conv"],
                                   cache["tail_ssm"]),
                    unroll=self.scan_unroll)
                new_cache["tail_conv"], new_cache["tail_ssm"] = ntc, nts
        elif fam == "encdec":
            def body(carry, xs):
                lp, k_, v_, ek, ev = xs
                hh, (nk, nv) = B.decode_encdec_decoder_block(
                    lp, cfg, carry, (k_, v_, ek, ev), positions)
                return hh, (nk, nv)
            h, (nk, nv) = jax.lax.scan(
                body, h, (params["dec_blocks"], cache["k"], cache["v"],
                          cache["ek"], cache["ev"]),
                unroll=self.scan_unroll)
            new_cache["k"], new_cache["v"] = nk, nv
        else:
            raise ValueError(fam)

        h = rmsnorm(params["final_ln"], h, cfg.norm_eps)
        logits = self._logits(params, h)
        return logits, new_cache


def build_model(cfg, **kwargs) -> LM:
    return LM(cfg, **kwargs)
