"""Architecture registry: the 10 assigned archs (+ the paper's own models).

Each entry provides the FULL config (exact public hyper-parameters, exercised
only via the dry-run) and a `reduced()` smoke variant (same family/features,
tiny dims) that runs a real forward/train step on CPU.
"""
from __future__ import annotations

import dataclasses

from repro.configs.base import (
    LM_SHAPES,
    ModelConfig,
    MoEConfig,
    ParallelConfig,
    RWKVConfig,
    ShapeConfig,
    SSMConfig,
)

__all__ = ["ARCHS", "get", "reduced", "shapes_for", "parallel_for", "ARCH_IDS"]


ARCHS: dict[str, ModelConfig] = {
    # [dense]  hf:stabilityai/stablelm-2-12b
    "stablelm-12b": ModelConfig(
        name="stablelm-12b", family="transformer", n_layers=40, d_model=5120,
        n_heads=32, n_kv_heads=8, d_ff=13824, vocab=100352,
        act="silu", norm="layer", pos_emb="rope"),
    # [dense]  arXiv:2408.00118 — local/global alternating, logit softcaps
    "gemma2-2b": ModelConfig(
        name="gemma2-2b", family="transformer", n_layers=26, d_model=2304,
        n_heads=8, n_kv_heads=4, head_dim=256, d_ff=9216, vocab=256000,
        act="gelu", norm="rms", local_window=4096, layer_pattern="local_global",
        attn_softcap=50.0, final_softcap=30.0, tie_embeddings=True,
        post_norm=True, scale_embeddings=True, norm_plus_one=True),
    # [dense]  arXiv:2407.10671 — GQA + QKV bias
    "qwen2-72b": ModelConfig(
        name="qwen2-72b", family="transformer", n_layers=80, d_model=8192,
        n_heads=64, n_kv_heads=8, d_ff=29568, vocab=152064, qkv_bias=True),
    # [dense]  hf:Qwen/Qwen2.5-3B — GQA + QKV bias
    "qwen2.5-3b": ModelConfig(
        name="qwen2.5-3b", family="transformer", n_layers=36, d_model=2048,
        n_heads=16, n_kv_heads=2, d_ff=11008, vocab=151936, qkv_bias=True),
    # [moe]  hf:xai-org/grok-1 — 8 experts top-2
    "grok-1-314b": ModelConfig(
        name="grok-1-314b", family="transformer", n_layers=64, d_model=6144,
        n_heads=48, n_kv_heads=8, head_dim=128, d_ff=32768, vocab=131072,
        act="gelu",
        moe=MoEConfig(n_experts=8, top_k=2, d_ff=32768)),
    # [moe]  Kimi K2 — trillion-param MoE, 384 experts top-8 (+1 shared)
    "kimi-k2-1t-a32b": ModelConfig(
        name="kimi-k2-1t-a32b", family="transformer", n_layers=61, d_model=7168,
        n_heads=64, n_kv_heads=8, head_dim=128, d_ff=2048, vocab=163840,
        moe=MoEConfig(n_experts=384, top_k=8, d_ff=2048, n_shared_experts=1,
                      capacity_factor=1.0)),
    # [audio]  arXiv:2306.05284 — decoder over EnCodec tokens, stub frontend
    "musicgen-medium": ModelConfig(
        name="musicgen-medium", family="transformer", n_layers=48, d_model=1536,
        n_heads=24, n_kv_heads=24, d_ff=6144, vocab=2048,
        act="gelu", norm="layer", pos_emb="sinusoidal",
        frontend="audio_stub", stub_prefix=64),
    # [ssm]  arXiv:2404.05892 — RWKV6 "Finch", data-dependent decay
    "rwkv6-1.6b": ModelConfig(
        name="rwkv6-1.6b", family="rwkv", n_layers=24, d_model=2048,
        n_heads=32, n_kv_heads=32, d_ff=7168, vocab=65536,
        rwkv=RWKVConfig(head_dim=64), supports_500k=True),
    # [vlm]  hf:OpenGVLab/InternVL2-1B (arXiv:2404.16821) — frozen
    # InternViT-300M-448px (stubbed: its 256 pixel-shuffled 4096-wide
    # features per tile are inputs) -> mlp1 projector -> Qwen2-0.5B-Instruct
    # (GQA + QKV bias, RoPE base 1e6, untied head)
    "internvl2-1b": ModelConfig(
        name="internvl2-1b", family="transformer", n_layers=24, d_model=896,
        n_heads=14, n_kv_heads=2, d_ff=4864, vocab=151655, qkv_bias=True,
        rope_theta=1_000_000.0, frontend="vision_stub", stub_prefix=256,
        vision_feature_dim=4096),
    # [hybrid]  arXiv:2411.15242 — Mamba2 backbone + shared attention
    "zamba2-2.7b": ModelConfig(
        name="zamba2-2.7b", family="zamba", n_layers=54, d_model=2560,
        n_heads=32, n_kv_heads=32, head_dim=80, d_ff=10240, vocab=32000,
        ssm=SSMConfig(d_state=64, head_dim=64, expand=2, attn_every=6),
        supports_500k=True),
}

ARCH_IDS = tuple(ARCHS)


def get(arch_id: str) -> ModelConfig:
    if arch_id not in ARCHS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {list(ARCHS)}")
    return ARCHS[arch_id]


def reduced(arch_id: str) -> ModelConfig:
    """Tiny same-family variant for CPU smoke tests (2-4 layers, small dims)."""
    cfg = get(arch_id)
    kw: dict = dict(
        name=cfg.name + "-smoke",
        n_layers=4 if cfg.family == "zamba" else 2,
        d_model=64, n_heads=4, n_kv_heads=max(1, min(cfg.n_kv_heads, 2)),
        head_dim=16, d_ff=128, vocab=512, attn_q_chunk=32,
    )
    if cfg.family == "zamba":
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=16, head_dim=16,
                                        attn_every=2, chunk=8)
        kw["n_kv_heads"] = 4
    if cfg.family == "rwkv":
        kw["rwkv"] = dataclasses.replace(cfg.rwkv, head_dim=16, decay_lora=8,
                                         mix_lora=4, chunk=8)
        kw["n_heads"], kw["n_kv_heads"] = 4, 4
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(cfg.moe, n_experts=4,
                                        top_k=min(cfg.moe.top_k, 2), d_ff=64)
    if cfg.local_window is not None:
        kw["local_window"] = 32
    if cfg.stub_prefix:
        kw["stub_prefix"] = 8
    if cfg.vision_feature_dim:
        kw["vision_feature_dim"] = 32
    return dataclasses.replace(cfg, **kw)


def shapes_for(arch_id: str) -> tuple[ShapeConfig, ...]:
    """The assigned shape set, with long_500k gated on sub-quadratic support."""
    cfg = get(arch_id)
    return tuple(s for s in LM_SHAPES
                 if s.name != "long_500k" or cfg.supports_500k)


def skipped_shapes(arch_id: str) -> tuple[str, ...]:
    cfg = get(arch_id)
    return () if cfg.supports_500k else ("long_500k",)


# ---------------------------------------------------------- parallelism
# clients_per_pod coarsens the DFL client axis for models whose per-client
# state would not fit (see DESIGN.md §4). fsdp = 16 / clients_per_pod.
_PARALLEL: dict[str, ParallelConfig] = {
    "qwen2-72b": ParallelConfig(clients_per_pod=4, grad_accum=4),
    "grok-1-314b": ParallelConfig(clients_per_pod=2, grad_accum=4),
    "kimi-k2-1t-a32b": ParallelConfig(clients_per_pod=1, grad_accum=16),
    "stablelm-12b": ParallelConfig(clients_per_pod=8, grad_accum=4),
    # tp=8 measured best for the 2k-wide model (see EXPERIMENTS.md §Perf):
    # -19% collective, -28% memory vs tp=16
    "qwen2.5-3b": ParallelConfig(tp=8),
}


def parallel_for(arch_id: str) -> ParallelConfig:
    return _PARALLEL.get(arch_id, ParallelConfig())
