"""Config-driven decoder-only transformer LM (covers 8 of the 10 assigned archs).

Features, all driven by ModelConfig:
  * GQA with arbitrary (n_heads, n_kv_heads, head_dim); optional QKV bias
    (qwen2), attention/final logit softcaps + local/global alternating layers
    (gemma2), tied embeddings, sinusoidal or rotary positions (musicgen),
    pre/post norms, (1+w) rmsnorm and embedding scaling (gemma2);
  * dense GLU FFN or routed MoE (grok-1, kimi-k2) with shared experts;
  * stub modality frontends: `stub_prefix` precomputed embeddings take the
    first positions in place of the token embeddings (musicgen audio); the
    vision stub (internvl2) takes the frozen encoder's features and maps
    them to d_model through InternVL2's trainable `mlp1` projector;
  * scan-over-layers with stacked parameters (compile-time O(1) in depth);
    the local/global pattern scans over layer *pairs* so the window is a
    static argument (no doubled attention compute);
  * optional per-block remat, query-chunked prefill attention;
  * prefill/decode paths with (L, B, Smax, KV, hd) KV caches.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models.params import Leaf
from repro.models.sharding_ctx import annotate

F32 = jnp.float32
PyTree = Any


# ----------------------------------------------------------------- params
def param_struct(cfg: ModelConfig) -> PyTree:
    d, v, nl = cfg.d_model, cfg.padded_vocab, cfg.n_layers
    hd = cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    dt = cfg.dtype

    blocks: dict[str, Leaf] = {
        "ln1": Leaf((nl, d), ("layers", "embed"), dt, "ones"),
        "wq": Leaf((nl, d, h, hd), ("layers", "embed", "heads", None), dt),
        "wk": Leaf((nl, d, kv, hd), ("layers", "embed", "kv_heads", None), dt),
        "wv": Leaf((nl, d, kv, hd), ("layers", "embed", "kv_heads", None), dt),
        "wo": Leaf((nl, h, hd, d), ("layers", "heads", None, "embed"), dt),
        "ln2": Leaf((nl, d), ("layers", "embed"), dt, "ones"),
    }
    if cfg.qkv_bias:
        blocks["bq"] = Leaf((nl, h, hd), ("layers", "heads", None), dt, "zeros")
        blocks["bk"] = Leaf((nl, kv, hd), ("layers", "kv_heads", None), dt, "zeros")
        blocks["bv"] = Leaf((nl, kv, hd), ("layers", "kv_heads", None), dt, "zeros")
    if cfg.post_norm:
        blocks["pn1"] = Leaf((nl, d), ("layers", "embed"), dt, "ones")
        blocks["pn2"] = Leaf((nl, d), ("layers", "embed"), dt, "ones")
    if cfg.moe is not None:
        m = cfg.moe
        e, f = m.n_experts, m.d_ff
        blocks["router"] = Leaf((nl, d, e), ("layers", "embed", None), "float32")
        # experts shard on the EP axis when divisible (kimi: 384 experts);
        # otherwise the divisibility fallback leaves E unsharded and the
        # "ffn" tag shards the per-expert hidden dim instead (grok: 8 experts
        # on a 16-way model axis would otherwise replicate ALL expert compute)
        blocks["we_gate"] = Leaf((nl, e, d, f), ("layers", "experts", "embed", "ffn"), dt)
        blocks["we_up"] = Leaf((nl, e, d, f), ("layers", "experts", "embed", "ffn"), dt)
        blocks["we_down"] = Leaf((nl, e, f, d), ("layers", "experts", "ffn", "embed"), dt)
        if m.n_shared_experts:
            sf = m.n_shared_experts * f
            blocks["ws_gate"] = Leaf((nl, d, sf), ("layers", "embed", "ffn"), dt)
            blocks["ws_up"] = Leaf((nl, d, sf), ("layers", "embed", "ffn"), dt)
            blocks["ws_down"] = Leaf((nl, sf, d), ("layers", "ffn", "embed"), dt)
    else:
        f = cfg.d_ff
        blocks["w_gate"] = Leaf((nl, d, f), ("layers", "embed", "ffn"), dt)
        blocks["w_up"] = Leaf((nl, d, f), ("layers", "embed", "ffn"), dt)
        blocks["w_down"] = Leaf((nl, f, d), ("layers", "ffn", "embed"), dt)

    struct = {
        "embed": Leaf((v, d), ("vocab_in", "embed"), dt, scale=0.02),
        "final_norm": Leaf((d,), ("embed",), dt, "ones"),
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        struct["head"] = Leaf((d, v), ("embed", "vocab"), dt)
    if cfg.frontend == "vision_stub":
        f = cfg.vision_feature_dim
        struct["vision_proj"] = {
            "ln_scale": Leaf((f,), (None,), dt, "ones"),
            "ln_bias": Leaf((f,), (None,), dt, "zeros"),
            "w1": Leaf((f, d), (None, "embed"), dt),
            "b1": Leaf((d,), ("embed",), dt, "zeros"),
            "w2": Leaf((d, d), ("embed", None), dt),
            "b2": Leaf((d,), (None,), dt, "zeros"),
        }
    return struct


def _is_paired(cfg: ModelConfig) -> bool:
    return (cfg.layer_pattern == "local_global" and cfg.local_window is not None
            and cfg.n_layers % 2 == 0)


# ---------------------------------------------------------------- forward
def _qkv(x, p, cfg: ModelConfig):
    # bf16-out projections: see layers.glu_mlp note (f32 outputs make the
    # whole backward f32 and double collective bytes)
    q = jnp.einsum("bsd,dkh->bskh", x, p["wq"])
    k = jnp.einsum("bsd,dkh->bskh", x, p["wk"])
    v = jnp.einsum("bsd,dkh->bskh", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return q, k, v


def _ffn(x, p, cfg: ModelConfig):
    if cfg.moe is not None:
        shared = None
        if cfg.moe.n_shared_experts:
            shared = (p["ws_gate"], p["ws_up"], p["ws_down"])
        return moe_lib.moe_ffn(x, p["router"], p["we_gate"], p["we_up"],
                               p["we_down"], cfg.moe, cfg.act, shared)
    return L.glu_mlp(x, p["w_gate"], p["w_up"], p["w_down"], cfg.act)


def _block_full(x, p, positions, cfg: ModelConfig, window: int | None,
                return_kv: bool = False):
    """One transformer block over the full sequence (train / prefill).

    `window` is STATIC (None => global attention).
    """
    h = L.apply_norm(cfg.norm, x, p["ln1"], plus_one=cfg.norm_plus_one)
    q, k, v = _qkv(h, p, cfg)
    if cfg.pos_emb == "rope":
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    q = annotate(q, "attn_q")
    k = annotate(k, "attn_kv")
    v = annotate(v, "attn_kv")
    attn = L.chunked_causal_attention(q, k, v, q_chunk=cfg.attn_q_chunk,
                                      window=window, cap=cfg.attn_softcap)
    attn = jnp.einsum("bskh,khd->bsd", attn, p["wo"])
    if cfg.post_norm:
        attn = L.apply_norm(cfg.norm, attn, p["pn1"], plus_one=cfg.norm_plus_one)
    x = annotate(x + attn, "residual")
    h = L.apply_norm(cfg.norm, x, p["ln2"], plus_one=cfg.norm_plus_one)
    ff = _ffn(h, p, cfg)
    if cfg.post_norm:
        ff = L.apply_norm(cfg.norm, ff, p["pn2"], plus_one=cfg.norm_plus_one)
    out = annotate(x + ff, "residual")
    if return_kv:
        return out, (k, v)
    return out


def _scan_blocks(x, blocks, positions, cfg: ModelConfig, remat: bool,
                 collect_kv: bool = False):
    """Scan over stacked layers; paired scan for the local/global pattern."""
    paired = _is_paired(cfg)

    if paired:
        pairs = jax.tree.map(lambda a: a.reshape((a.shape[0] // 2, 2) + a.shape[1:]),
                             blocks)

        def body(h, p2):
            p_local = jax.tree.map(lambda a: a[0], p2)
            p_global = jax.tree.map(lambda a: a[1], p2)
            if collect_kv:
                h, kv0 = _block_full(h, p_local, positions, cfg,
                                     cfg.local_window, return_kv=True)
                h, kv1 = _block_full(h, p_global, positions, cfg, None,
                                     return_kv=True)
                return h, (jnp.stack([kv0[0], kv1[0]]), jnp.stack([kv0[1], kv1[1]]))
            h = _block_full(h, p_local, positions, cfg, cfg.local_window)
            h = _block_full(h, p_global, positions, cfg, None)
            return h, None

        if remat:
            body = jax.checkpoint(body)
        x, kvs = lax.scan(body, x, pairs)
        if collect_kv:
            ck = kvs[0].reshape((-1,) + kvs[0].shape[2:])
            cv = kvs[1].reshape((-1,) + kvs[1].shape[2:])
            return x, (ck, cv)
        return x, None

    window = cfg.local_window if cfg.layer_pattern == "global" and cfg.local_window else None

    def body(h, p):
        if collect_kv:
            h, (k, v) = _block_full(h, p, positions, cfg, window, return_kv=True)
            return h, (k, v)
        return _block_full(h, p, positions, cfg, window), None

    if remat:
        body = jax.checkpoint(body)
    return lax.scan(body, x, blocks)


def vision_projector(p, feats):
    """InternVL2's ``mlp1``: LayerNorm (eps 1e-5, with bias) -> Linear f->d
    -> GELU (exact) -> Linear d->d. feats (B, P, f) -> (B, P, d)."""
    with jax.named_scope("vision_proj"):
        h = L.layer_norm(feats, p["ln_scale"], p["ln_bias"], eps=1e-5)
        h = jnp.einsum("bpf,fd->bpd", h, p["w1"]) + p["b1"].astype(h.dtype)
        h = jax.nn.gelu(h.astype(F32), approximate=False).astype(feats.dtype)
        return jnp.einsum("bpd,de->bpe", h, p["w2"]) + p["b2"].astype(h.dtype)


def _embed_inputs(params, tokens, cfg: ModelConfig, prefix_embeds=None):
    x = L.embed_lookup(params["embed"], tokens)
    if cfg.stub_prefix:
        assert prefix_embeds is not None, f"{cfg.name} needs frontend embeddings"
        p = cfg.stub_prefix
        prefix = prefix_embeds.astype(x.dtype)
        if cfg.frontend == "vision_stub":
            prefix = vision_projector(params["vision_proj"], prefix)
        x = jnp.concatenate([prefix, x[:, p:]], axis=1)
    if cfg.scale_embeddings:
        x = (x.astype(F32) * np.sqrt(cfg.d_model)).astype(x.dtype)
    if cfg.pos_emb == "sinusoidal":
        s = x.shape[1]
        x = (x.astype(F32) + L.sinusoidal_pos(jnp.arange(s), cfg.d_model)).astype(x.dtype)
    return annotate(x, "activation")


def _head(params):
    return params["head"] if "head" in params else params["embed"].T


def _hidden(params: PyTree, tokens: jax.Array, cfg: ModelConfig,
            prefix_embeds: jax.Array | None = None,
            remat: bool = False) -> jax.Array:
    x = _embed_inputs(params, tokens, cfg, prefix_embeds)
    positions = jnp.arange(x.shape[1])
    x, _ = _scan_blocks(x, params["blocks"], positions, cfg, remat)
    return L.apply_norm(cfg.norm, x, params["final_norm"],
                        plus_one=cfg.norm_plus_one)


def forward(params: PyTree, tokens: jax.Array, cfg: ModelConfig,
            prefix_embeds: jax.Array | None = None,
            remat: bool = False) -> jax.Array:
    """Teacher-forcing forward. tokens (B, S) -> logits (B, S, V) f32."""
    x = _hidden(params, tokens, cfg, prefix_embeds, remat)
    logits = L.lm_logits(x, _head(params), cap=cfg.final_softcap,
                         valid_vocab=cfg.vocab)
    return annotate(logits, "logits")


def loss_fn(params: PyTree, batch: dict, cfg: ModelConfig,
            remat: bool = False) -> tuple[jax.Array, dict]:
    x = _hidden(params, batch["tokens"], cfg,
                prefix_embeds=batch.get("prefix_embeds"), remat=remat)
    mask = None
    if cfg.stub_prefix:
        s = x.shape[1]
        mask = ((jnp.arange(s) >= cfg.stub_prefix)[None, :]
                * jnp.ones(batch["labels"].shape, F32))
    loss = L.lm_loss_chunked(x, _head(params), batch["labels"],
                             valid_vocab=cfg.vocab, chunk=cfg.ce_chunk,
                             cap=cfg.final_softcap, mask=mask)
    return loss, {"loss": loss}


# ------------------------------------------------------------------ decode
def cache_struct(cfg: ModelConfig, batch: int, max_seq: int) -> PyTree:
    hd = cfg.resolved_head_dim
    return {
        "k": Leaf((cfg.n_layers, batch, max_seq, cfg.n_kv_heads, hd),
                  ("layers", "act_batch", "act_seq", "kv_heads", None),
                  cfg.dtype, "zeros"),
        "v": Leaf((cfg.n_layers, batch, max_seq, cfg.n_kv_heads, hd),
                  ("layers", "act_batch", "act_seq", "kv_heads", None),
                  cfg.dtype, "zeros"),
    }


def prefill(params: PyTree, tokens: jax.Array, cfg: ModelConfig,
            prefix_embeds: jax.Array | None = None
            ) -> tuple[jax.Array, PyTree]:
    """Run the prompt; returns (last-position logits (B, V), KV cache)."""
    x = _embed_inputs(params, tokens, cfg, prefix_embeds)
    positions = jnp.arange(x.shape[1])
    x, (ck, cv) = _scan_blocks(x, params["blocks"], positions, cfg,
                               remat=False, collect_kv=True)
    x = L.apply_norm(cfg.norm, x, params["final_norm"], plus_one=cfg.norm_plus_one)
    logits = L.lm_logits(x[:, -1:], _head(params), cap=cfg.final_softcap,
                         valid_vocab=cfg.vocab)[:, 0]
    return logits, {"k": annotate(ck, "cache"), "v": annotate(cv, "cache")}


def _block_decode(h, p, k_l, v_l, pos, cfg: ModelConfig, window: int | None):
    hn = L.apply_norm(cfg.norm, h, p["ln1"], plus_one=cfg.norm_plus_one)
    q, k, v = _qkv(hn, p, cfg)
    if cfg.pos_emb == "rope":
        q = L.rope(q, pos[None], cfg.rope_theta)
        k = L.rope(k, pos[None], cfg.rope_theta)
    k_l = lax.dynamic_update_slice_in_dim(k_l, k.astype(k_l.dtype), pos, axis=1)
    v_l = lax.dynamic_update_slice_in_dim(v_l, v.astype(v_l.dtype), pos, axis=1)
    attn = L.decode_attention(q, k_l, v_l, pos, window=window,
                              cap=cfg.attn_softcap)
    attn = jnp.einsum("bskh,khd->bsd", attn, p["wo"])
    if cfg.post_norm:
        attn = L.apply_norm(cfg.norm, attn, p["pn1"], plus_one=cfg.norm_plus_one)
    h2 = h + attn
    hn2 = L.apply_norm(cfg.norm, h2, p["ln2"], plus_one=cfg.norm_plus_one)
    ff = _ffn(hn2, p, cfg)
    if cfg.post_norm:
        ff = L.apply_norm(cfg.norm, ff, p["pn2"], plus_one=cfg.norm_plus_one)
    return h2 + ff, k_l, v_l


def decode_step(params: PyTree, cache: PyTree, tokens: jax.Array,
                pos: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, PyTree]:
    """One decode step. tokens (B,) int32; pos scalar; cache (L,B,Smax,KV,hd).

    Returns (logits (B, V) f32, updated cache).
    """
    x = L.embed_lookup(params["embed"], tokens[:, None])  # (B, 1, D)
    if cfg.scale_embeddings:
        x = (x.astype(F32) * np.sqrt(cfg.d_model)).astype(x.dtype)
    if cfg.pos_emb == "sinusoidal":
        x = (x.astype(F32) + L.sinusoidal_pos(pos[None], cfg.d_model)).astype(x.dtype)
    x = annotate(x, "activation")

    if _is_paired(cfg):
        pairs = jax.tree.map(
            lambda a: a.reshape((a.shape[0] // 2, 2) + a.shape[1:]),
            (params["blocks"], cache["k"], cache["v"]))

        def body(h, xs):
            p2, k2, v2 = xs
            sel = lambda t, i: jax.tree.map(lambda a: a[i], t)
            h, k0, v0 = _block_decode(h, sel(p2, 0), k2[0], v2[0], pos, cfg,
                                      cfg.local_window)
            h, k1, v1 = _block_decode(h, sel(p2, 1), k2[1], v2[1], pos, cfg, None)
            return h, (jnp.stack([k0, k1]), jnp.stack([v0, v1]))

        x, (ck, cv) = lax.scan(body, x, pairs)
        ck = ck.reshape((-1,) + ck.shape[2:])
        cv = cv.reshape((-1,) + cv.shape[2:])
    else:
        def body(h, xs):
            p, k_l, v_l = xs
            h, k_l, v_l = _block_decode(h, p, k_l, v_l, pos, cfg, None)
            return h, (k_l, v_l)

        x, (ck, cv) = lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))

    x = L.apply_norm(cfg.norm, x, params["final_norm"], plus_one=cfg.norm_plus_one)
    logits = L.lm_logits(x, _head(params), cap=cfg.final_softcap,
                         valid_vocab=cfg.vocab)[:, 0]
    return logits, {"k": ck, "v": cv}
