"""Config schema: model architecture, input shapes, parallelism, DFL settings.

Everything is a frozen dataclass so configs are hashable and can be closed
over by jitted functions / used as static args.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

__all__ = [
    "MoEConfig",
    "SSMConfig",
    "RWKVConfig",
    "ModelConfig",
    "ShapeConfig",
    "ParallelConfig",
    "DFLConfig",
    "LM_SHAPES",
]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden dim
    n_shared_experts: int = 0      # always-on experts (kimi-style)
    capacity_factor: float = 1.25
    router_dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block settings."""

    d_state: int = 64
    head_dim: int = 64
    expand: int = 2                # d_inner = expand * d_model
    conv_width: int = 4
    chunk: int = 256               # SSD chunk length
    attn_every: int = 6            # zamba2: shared attention after every k blocks
    n_groups: int = 1              # B/C groups


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    """RWKV6 "Finch" settings."""

    head_dim: int = 64
    decay_lora: int = 64           # rank of the data-dependent decay LoRA
    mix_lora: int = 32             # rank of the token-shift mix LoRA
    # chunked WKV evaluation length; kept short so |LOG_W_MIN|*chunk stays
    # inside the f32 exp range (see models/rwkv.py numerical-safety note)
    chunk: int = 16


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Literal["transformer", "rwkv", "zamba", "mlp", "lstm"]
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None            # default: d_model // n_heads
    act: Literal["silu", "gelu"] = "silu"
    norm: Literal["rms", "layer"] = "rms"
    qkv_bias: bool = False
    pos_emb: Literal["rope", "sinusoidal", "none"] = "rope"
    rope_theta: float = 10_000.0
    attn_softcap: float | None = None      # gemma2: 50.0
    final_softcap: float | None = None     # gemma2: 30.0
    local_window: int | None = None        # gemma2: 4096
    layer_pattern: Literal["global", "local_global"] = "global"
    tie_embeddings: bool = False
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    rwkv: RWKVConfig | None = None
    frontend: Literal["none", "audio_stub", "vision_stub"] = "none"
    stub_prefix: int = 0                   # precomputed frontend embeddings prepended
    # vision_stub: width of the frozen vision encoder's output features,
    # which the trainable projector maps to d_model (InternVL2: 4096, the
    # pixel-shuffled InternViT-300M output)
    vision_feature_dim: int = 0
    post_norm: bool = False                # gemma2: post-attn/post-ffn norms
    scale_embeddings: bool = False         # gemma2: x *= sqrt(d_model)
    norm_plus_one: bool = False            # gemma2: rmsnorm scale = (1 + w)
    dtype: str = "bfloat16"
    attn_q_chunk: int = 1024               # query-chunked prefill attention
    ce_chunk: int = 512                    # seq chunk for the fused CE loss
    # set True only for sub-quadratic families; gates the long_500k shape
    supports_500k: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def prefix_width(self) -> int:
        """Width of the precomputed frontend features (``prefix_embeds``):
        the vision encoder's features for vision_stub, else d_model."""
        return (self.vision_feature_dim if self.frontend == "vision_stub"
                else self.d_model)

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 128 so the TP axis always divides it
        (standard practice; pad logits are masked to -inf in the loss/decoder)."""
        return (self.vocab + 127) // 128 * 128

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks), used for rooflines."""
        d, v = self.d_model, self.vocab
        total = v * d  # embedding
        if not self.tie_embeddings:
            total += v * d  # output head
        hd = self.resolved_head_dim
        qd, kvd = self.n_heads * hd, self.n_kv_heads * hd
        if self.family == "rwkv":
            assert self.rwkv is not None
            r = self.rwkv
            per = (5 * d * d                    # r,k,v,g,o (time-mix projections)
                   + 2 * d * r.decay_lora       # decay LoRA
                   + 5 * 2 * d * r.mix_lora     # per-projection mix LoRAs
                   + d * self.d_ff + self.d_ff * d  # channel mix
                   + 2 * d)                     # norms
            return total + self.n_layers * per
        if self.family == "zamba":
            assert self.ssm is not None
            s = self.ssm
            d_in = s.expand * d
            per_mamba = (d * (2 * d_in + 2 * s.n_groups * s.d_state + d_in // s.head_dim)
                         + d_in * s.conv_width + d_in * d + 2 * d + d_in)
            n_attn = self.n_layers // s.attn_every
            shared = (d * (qd + 2 * kvd) + qd * d + 3 * d * self.d_ff + 2 * d)
            return total + self.n_layers * per_mamba + shared  # shared counted once
        # transformer: + the final norm, and the vision projector (LayerNorm
        # with bias, Linear f->d, Linear d->d, both with bias)
        total += d
        if self.frontend == "vision_stub":
            f = self.vision_feature_dim
            total += 2 * f + f * d + d + d * d + d
        attn = d * (qd + 2 * kvd) + qd * d
        if self.qkv_bias:
            attn += qd + 2 * kvd
        if self.moe is not None:
            m = self.moe
            ffn = (m.n_experts + m.n_shared_experts) * 3 * d * m.d_ff + d * m.n_experts
        else:
            ffn = 3 * d * self.d_ff
        per = attn + ffn + 2 * d
        return total + self.n_layers * per

    def active_param_count(self) -> int:
        """Active params per token (= param_count for dense; routed subset for MoE)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        d = self.d_model
        dense_ffn_per_layer = (m.top_k + m.n_shared_experts) * 3 * d * m.d_ff + d * m.n_experts
        full_ffn_per_layer = (m.n_experts + m.n_shared_experts) * 3 * d * m.d_ff + d * m.n_experts
        return self.param_count() - self.n_layers * (full_ffn_per_layer - dense_ffn_per_layer)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


# the assigned LM shape set (identical across the 10 archs)
LM_SHAPES: tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4_096, 256, "train"),
    ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    ShapeConfig("decode_32k", 32_768, 128, "decode"),
    ShapeConfig("long_500k", 524_288, 1, "decode"),
)


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """How one (arch x mesh) cell factorizes the device grid.

    The production mesh is (data, model) = (visible devices, 1) on a real
    host, or the dry-run's placeholder pods (16,16)=(data,model) and
    (2,16,16)=(pod,data,model); `clients_per_pod` coarsens the DFL client
    axis by regrouping data rows into (client, fsdp): client=clients_per_pod,
    fsdp=data/clients_per_pod. fsdp does ZeRO sharding of each client's
    params/momentum AND data-parallelism of the client's local batch.
    """

    clients_per_pod: int = 16
    remat: Literal["none", "block"] = "block"
    attn_mode: Literal["heads", "sequence"] = "heads"  # TP choice for attention
    # gossip executor: "ppermute_packed" (default: flat-buffer payloads, d
    # collectives/round + fused Pallas reduction), "ppermute_packed_quant"
    # (packed + int8 wire payloads, per-row-block scales riding in the wire
    # buffer), "ppermute_packed_async" (pipelined: with gossip_delay=1 the d
    # permutes ship the *previous* round's snapshot, so they depend only on
    # step inputs and overlap with the local-step scan), per-leaf
    # "ppermute"/"ppermute_quant" baselines, or the paper-naive "dense"
    # mixing einsum
    gossip_impl: Literal["dense", "ppermute", "ppermute_quant",
                         "ppermute_packed", "ppermute_packed_quant",
                         "ppermute_packed_async"] = "ppermute_packed"
    # pipelined-gossip delay (only meaningful with "ppermute_packed_async"):
    # 0 = synchronous semantics, bit-identical to "ppermute_packed"
    # (regression-pinned); 1 = one-round-delayed mixing — round t mixes the
    # in-flight snapshot of round t-1's post-local-step params, so the wire
    # transfer hides behind a full local-step scan
    gossip_delay: int = 0
    # Chebyshev multi-round gossip (repro.core.engine sub_rounds axis):
    # k >= 2 runs k gossip sub-rounds per round with Chebyshev polynomial
    # weights over the mixing matrix (second-order recurrence; coefficients
    # from the overlay's lambda via spectral.chebyshev_omegas, shipped as
    # one more donated traced operand — zero retraces). k*d collectives per
    # round; 1 = the sync engine, byte-identical HLO. Packed substrates
    # only; does not compose with gossip_delay=1, screens, or stateful
    # codecs (engine-config validation rejects those cells).
    gossip_sub_rounds: int = 1
    # wire codec override (repro.core.engine): "auto" keeps the impl
    # alias's historical codec (f32 for the plain impls, int8_block for the
    # quant impls); any codec in the engine registry (engine.CODECS) names
    # one explicitly — built-ins: "f32" / "int8" (per-buffer scale) /
    # "int8_block" (one scale per kernel row-block tile) / "topk_ef"
    # (sparse top-k with error feedback: values + lane-folded indices wire,
    # per-client EF-residual codec state threaded as a donated step
    # operand). Pipelined + quantized gossip = "ppermute_packed_async" +
    # gossip_delay=1 + gossip_codec="int8_block" (the delayed snapshot is
    # then carried AND shipped in the int8 wire format: d int8
    # collectives/round, 4x smaller donated state); with "topk_ef" the
    # carried snapshot is the ~k-fold smaller sparse wire.
    gossip_codec: str = "auto"
    # Byzantine screen over received payloads (repro.core.engine): "none"
    # trusts every wire; "norm_clip" rescales any received buffer whose norm
    # exceeds gossip_clip_tau x the receiver's own norm; "trimmed_mean"
    # drops the gossip_trim_f largest/smallest live values per coordinate
    # and renormalizes over the survivors. Screens compose with every codec
    # x timing cell through config alone — still d collectives/round.
    gossip_screen: Literal["none", "norm_clip", "trimmed_mean"] = "none"
    gossip_clip_tau: float = 3.0
    gossip_trim_f: int = 1
    # in-graph round telemetry (repro.telemetry): False keeps the step HLO
    # textually identical to an untelemetered build; True makes the step's
    # metrics dict carry a "telemetry" subtree of traced round metrics
    # (consensus residual, live in-degree, per-schedule contributor mass,
    # norm-clip counts, wire bytes — zero extra collectives, zero retraces).
    # Packed (shard_map) impls only — the per-leaf / dense baselines reject
    # it at config parse.
    gossip_telemetry: bool = False
    local_steps: int = 2          # K inside the lowered round (scan)
    use_fused_sgdm: bool = True
    grad_accum: int = 4           # microbatches per local step (memory knob)
    zero3: bool = True            # shard weights over fsdp (ZeRO-3) vs replicate
    seq_parallel: bool = False    # Megatron-SP residual sharding over TP axis
    tp: int | None = None         # TP width (None = full model axis = 16)


@dataclasses.dataclass(frozen=True)
class DFLConfig:
    """Overlay settings for the DFL round."""

    # any family registered in repro.overlay.registry: "expander", "ring",
    # "complete", "torus", "hypercube", "random_regular", "onepeer_exp",
    # "erdos_renyi", ...
    topology: str = "expander"
    degree: int = 4
    seed: int = 0
    lr: float = 0.01
    momentum: float = 0.9
    # time-varying round plan (repro.overlay.plan): per-schedule gate vector
    # shipped into the jitted step as donated data — "static", "one_peer",
    # "random_subset" (plan_k schedules/round), "throttle" (plan_fraction of
    # the pool/round). Any plan reuses one executable: gates are data.
    round_plan: str = "static"
    plan_k: int = 1
    plan_fraction: float = 0.5
    # round-level client subsampling (repro.overlay.plan.ActiveSetPlan):
    # per-client participation vector shipped into the jitted step as
    # donated data next to alive/gates — "full" (everyone, signature
    # unchanged), "random_k" (active_k clients/round), "shards"
    # (round-robin over active_shards cohorts), "stratified" (active_k
    # spread over active_shards strata). Inactive clients keep their params
    # (identity rows) and never count as stragglers: the active set
    # multiplies the alive mask but stays invisible to HealthTracker.
    active_set: str = "full"
    active_k: int = 1
    active_shards: int = 2
    # elastic runtime (launch/elastic.py): heartbeat thresholds. A client
    # missing `straggler_rounds` heartbeats is masked out of gossip for the
    # round (alive-mask step argument — zero recompiles); one missing
    # `failure_rounds` is declared dead (splice repair + one re-jit).
    straggler_rounds: int = 1
    failure_rounds: int = 3
    # Byzantine attacker harness (repro.core.failures.AttackPlan): when
    # True the jitted step takes a (2, n) per-client attack operand + a
    # PRNG key as *data* (zero retraces under attacker churn) and applies
    # it to the post-local-step params before gossip. The all-honest
    # operand is a numerical no-op, so attack-free rounds share the trace.
    byzantine: bool = False
