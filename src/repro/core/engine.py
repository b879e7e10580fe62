"""GossipEngine: ONE gossip executor assembled from three orthogonal layers.

The repo used to carry seven hand-specialized executors (per-leaf f32,
per-leaf int8, packed f32, packed int8, packed delayed, stacked, stacked
delayed) whose bodies were copy-pasted variations of the same round. Every
new lever (quantize the wire, pipeline the wire, simulate on one device)
multiplied the zoo instead of composing with it — the ROADMAP item
"pipelined + quantized gossip" could not be wired without this refactor.

The engine factors the round into:

* **WireCodec** — what travels on the wire and how it folds back into the
  mixing reduction. ``"f32"`` ships the packed buffer unchanged and reduces
  through the fused ``gossip_mix_2d`` pass on ``shard_map`` (the stacked
  rounds mix its rows with an f32 multiply-add, on the bare leaves when no
  snapshot is carried); ``"int8"`` /
  ``"int8_block"`` quantize through the Pallas quantize kernels, fold the
  f32 scale(s) INTO the shipped int8 buffer (one collective per schedule —
  ``fold_scale(s)_into_wire``), and fold each received wire into the
  accumulator through the fused ``dequant_accumulate_2d[_blockwise]``
  kernels. A codec owns encode -> ship -> fused-decode-accumulate; it never
  sees the topology.
* **timing** — ``delay=0`` (synchronous: this round's collectives carry this
  round's post-local-step buffers) or ``delay=1`` (pipelined: the
  collectives read the PREVIOUS round's snapshot, a donated step input with
  no data dependency on the local-step scan, so XLA overlaps the wire with
  compute — ``mix_dense_delayed`` semantics). The carried state is the
  codec's *wire format*, so delayed x int8 ships int8 bytes and carries a 4x
  smaller snapshot for free.
* **substrate** — where the round runs: ``"shard_map"`` (the production
  ppermute island: d collectives/round over the client mesh axes),
  ``"stacked"`` (the single-device simulator: gathers on a stacked client
  axis — the elastic runtime's path), ``"blocked"`` (the massive-client
  simulator: ``block`` clients per device in the stacked layout *under*
  shard_map — intra-device edges stay stacked gathers, cross-device edges
  ship whole per-device wire blocks via the precomputed
  :class:`~repro.core.gossip.BlockedSpec` partition, so n decouples from
  the mesh and O(10^4+) clients run on a handful of devices), ``"per_leaf"``
  (the d x n_leaves ppermute baseline), or ``"dense"`` (the paper-naive
  mixing einsum).
* **screen** — Byzantine-robust aggregation of what arrived: ``"none"``
  (trust every payload: the plain weighted reduction), ``"norm_clip"``
  (per-sender squared-norm pass over the packed wire; any received buffer
  whose norm exceeds ``clip_tau x`` the receiver's own norm is rescaled
  down onto that ball — a *payload* rescale, folded into the
  post-renormalization received weights so the alive/gates renorm is
  untouched and an all-ones clip is the exact identity), or
  ``"trimmed_mean"`` (coordinate-wise trimmed mean over the d+1 stack
  through the fused ``gossip_mix_2d_trimmed[_quant]`` kernels: per element
  the ``trim_f`` largest and smallest live values are dropped and the
  survivors renormalize — dead/gated/fixed-point senders are excluded from
  the order statistics via the same contributor weights the masked
  reduction uses). Screens are local and per-receiver: each client defends
  its own update with information it already holds; there is no reputation
  exchange and no extra collective — the wire still ships exactly d
  buffers/round.

Alive masks and round-plan gates thread through the ONE shared weight path
(:func:`repro.core.gossip.alive_weight_table` and its per-client local form)
for every combination — they are traced step data, never trace structure, so
straggler churn and per-round topologies retrace nothing.

The payoff that proves the factoring: ``delay=1 x int8`` (pipelined +
quantized) is a free composition — zero new executor code, exactly d
collectives/round of int8 wire bytes, and the same zero-retrace / splice-
repair story as every other cell of the cube. Legacy entry points
(``gossip.ppermute_mix_packed`` et al.) and legacy ``gossip_impl`` strings
all resolve here (see ``LEGACY_GOSSIP_IMPLS``); ``sync x f32 x shard_map``
lowers to HLO textually identical to the pre-refactor ``ppermute_packed``
path, and ``delay=0`` is bit-identical to sync (both pinned in tests).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import gossip, packing
from repro.core.gossip import GossipSpec
from repro.telemetry.metrics import TelemetryConfig

__all__ = [
    "CODECS",
    "SCREENS",
    "SUBSTRATES",
    "DELAY_SUBSTRATES",
    "SCREEN_SUBSTRATES",
    "STATEFUL_SUBSTRATES",
    "TELEMETRY_SUBSTRATES",
    "CHEBY_SUBSTRATES",
    "LEGACY_GOSSIP_IMPLS",
    "GossipEngineConfig",
    "GossipExecutor",
    "TopKEFCodec",
    "build_gossip_executor",
    "get_codec",
    "parse_gossip_impl",
    "register_codec",
]

PyTree = Any

SUBSTRATES = ("shard_map", "stacked", "blocked", "per_leaf", "dense")
SCREENS = ("none", "norm_clip", "trimmed_mean")
# the cells the delay and screen layers are wired for; "blocked" joins when
# its snapshot-carry and screen-norm passes land (validation names this
# tuple so every error message enumerates the same cells). Stateful codecs
# (per-client codec state, e.g. the topk_ef EF residual) ride the same two
# substrates the delay snapshot does — the state threads through the step
# exactly like the in-flight wire.
DELAY_SUBSTRATES = ("shard_map", "stacked")
SCREEN_SUBSTRATES = ("shard_map", "stacked")
STATEFUL_SUBSTRATES = ("shard_map", "stacked")
# telemetry rides "blocked" too: the metrics-only cell (consensus residual +
# in-degree) is computable from the device-local rows the blocked round
# already gathers, with ZERO extra collectives; screens (and hence clip
# counts) stay stacked/shard_map-only
TELEMETRY_SUBSTRATES = ("shard_map", "stacked", "blocked")
# Chebyshev multi-round gossip (sub_rounds > 1): the two packed substrates
# whose round bodies loop the d-collectives-per-schedule structure
CHEBY_SUBSTRATES = ("shard_map", "stacked")

# legacy ParallelConfig.gossip_impl strings -> (substrate, codec). The delay
# axis rides separately (ParallelConfig.gossip_delay); "ppermute_packed_async"
# is the only alias that accepts delay=1, and at delay=0 it IS
# "ppermute_packed" (identical engine config => textually identical HLO).
# The "blocked" substrate has NO legacy alias on purpose: it is an
# engine-config-only cell (spell it GossipEngineConfig(substrate="blocked",
# block=B)) because the production gossip_impl strings all assume one client
# per device slice, which is exactly the assumption it removes.
LEGACY_GOSSIP_IMPLS = {
    "dense": ("dense", "f32"),
    "ppermute": ("per_leaf", "f32"),
    "ppermute_quant": ("per_leaf", "int8"),
    "ppermute_packed": ("shard_map", "f32"),
    "ppermute_packed_quant": ("shard_map", "int8_block"),
    "ppermute_packed_async": ("shard_map", "f32"),
}


@dataclasses.dataclass(frozen=True)
class GossipEngineConfig:
    """Static (hashable) engine cell: substrate x codec x timing x screen.

    Attributes:
      substrate: "shard_map" | "stacked" | "blocked" | "per_leaf" | "dense".
      codec: "f32" | "int8" (per-buffer scale) | "int8_block" (one scale per
        kernel row-block tile, the tighter default wire format for quant).
      delay: 0 = synchronous, 1 = pipelined (one-round-delayed snapshot;
        shard_map | stacked only — see DELAY_SUBSTRATES).
      sub_rounds: k >= 1 gossip sub-rounds per round (the second timing
        axis). 1 (the default) is the synchronous engine, byte-identical —
        the sub-round machinery is a build-time branch, exactly like
        delay=0. k > 1 runs Chebyshev-accelerated multi-round gossip
        (shard_map | stacked — see CHEBY_SUBSTRATES): each sub-round
        reuses the round's d-collectives-per-schedule structure and fused
        reduce kernels on the SAME weight table (k*d collectives total),
        combined through the second-order recurrence
        ``x_(j+1) = omega[j] * (W x_j - x_(j-1)) + x_(j-1)`` whose
        per-sub-round ``omega`` coefficients ship as one more traced
        operand next to alive/gates (``cheby=`` — derive them from the
        overlay's lambda via :func:`repro.core.spectral.chebyshev_omegas`
        or :meth:`GossipExecutor.cheby_coeffs`; varying them retraces
        nothing). Composes with any stateless codec; delay=1 (the snapshot
        is one round stale, not one sub-round), screens (per-sub-round
        order statistics are undefined) and stateful codecs (the EF
        residual updates once per round) are rejected.
      mix_impl: kernel implementation knob threaded to the fused
        gossip_mix / quant kernels ("auto" | "pallas" | "pallas_interpret" |
        "ref").
      screen: Byzantine screen over received payloads — "none" |
        "norm_clip" | "trimmed_mean" (shard_map | stacked only — see
        SCREEN_SUBSTRATES; module docstring has the exact semantics).
      clip_tau: norm_clip threshold — a received buffer is rescaled when
        its norm exceeds ``clip_tau x`` the receiver's own norm.
      trim_f: trimmed_mean per-side drop count (clamped per coordinate so
        at least one live value always survives; 0 = renormalized mean).
      block: B, simulated clients per device — required (>= 1, dividing
        ``n_clients``) on the "blocked" substrate, must stay 0 elsewhere.
        The blocked cell runs the stacked gather/multiply-add round on a
        device-local ``(B, ...)`` slice under shard_map; cross-device
        schedule edges ship whole per-device wire blocks via the
        :class:`~repro.core.gossip.BlockedSpec` partition baked at build
        time, so an intra-heavy placement pays almost no wire.
      telemetry: None (the default — the round's HLO is textually identical
        to an untelemetered build) or a
        :class:`repro.telemetry.metrics.TelemetryConfig`, which makes the
        executor additionally return a RoundMetrics dict of traced values
        (shard_map | stacked | blocked — see TELEMETRY_SUBSTRATES; the
        blocked cell is metrics-only, measured on device-local rows).
        Metrics are outputs, never trace structure: no extra collectives,
        no retraces.
    """

    substrate: str = "shard_map"
    codec: str = "f32"
    delay: int = 0
    sub_rounds: int = 1
    mix_impl: str = "auto"
    screen: str = "none"
    clip_tau: float = 3.0
    trim_f: int = 1
    block: int = 0
    telemetry: TelemetryConfig | None = None

    def __post_init__(self):
        if self.substrate not in SUBSTRATES:
            raise ValueError(f"unknown substrate {self.substrate!r}; "
                             f"available: {', '.join(SUBSTRATES)}")
        codec_obj = get_codec(self.codec)  # raises the unknown-codec error
        if getattr(codec_obj, "stateful", False):
            if self.substrate not in STATEFUL_SUBSTRATES:
                raise ValueError(
                    f"stateful codec {self.codec!r} (per-client codec "
                    "state) runs on the "
                    f"{' | '.join(STATEFUL_SUBSTRATES)} substrates, got "
                    f"{self.substrate!r}")
            if self.screen != "none":
                raise ValueError(
                    f"screen={self.screen!r} is not wired for the stateful "
                    f"codec {self.codec!r} yet (the screened rounds do not "
                    "thread per-client codec state)")
        if self.delay not in (0, 1):
            raise ValueError(f"delay must be 0 or 1, got {self.delay}")
        if self.delay and self.substrate not in DELAY_SUBSTRATES:
            raise ValueError(
                "pipelined (delay=1) gossip runs on the "
                f"{' | '.join(DELAY_SUBSTRATES)} substrates, got "
                f"{self.substrate!r}"
                + (" (the blocked cell is not wired for a carried snapshot "
                   "yet)" if self.substrate == "blocked" else ""))
        if not isinstance(self.sub_rounds, int) or self.sub_rounds < 1:
            raise ValueError(
                f"sub_rounds must be an int >= 1, got {self.sub_rounds!r}")
        if self.sub_rounds > 1:
            if self.substrate not in CHEBY_SUBSTRATES:
                raise ValueError(
                    "Chebyshev multi-round gossip (sub_rounds > 1) runs on "
                    f"the {' | '.join(CHEBY_SUBSTRATES)} substrates, got "
                    f"{self.substrate!r}")
            if self.delay:
                raise ValueError(
                    "sub_rounds > 1 is synchronous; it does not compose "
                    "with the delayed snapshot (delay=1): the carried wire "
                    "is one ROUND stale, not one sub-round")
            if self.screen != "none":
                raise ValueError(
                    f"screen={self.screen!r} does not compose with "
                    "sub_rounds > 1 (per-sub-round order statistics are "
                    "undefined); screen the k=1 cell instead")
            if getattr(codec_obj, "stateful", False):
                raise ValueError(
                    f"stateful codec {self.codec!r} does not compose with "
                    "sub_rounds > 1 (its per-client state updates once per "
                    "round, not per sub-round)")
        if self.substrate == "per_leaf" and self.codec == "int8_block":
            raise ValueError("per-leaf payloads are not tile-aligned; use "
                             "codec='int8' for the per-leaf baseline")
        if (self.substrate == "dense"
                and not getattr(codec_obj, "identity_wire", False)):
            raise ValueError("the dense reference substrate has no wire; "
                             f"codec must be 'f32', got {self.codec!r}")
        if self.screen not in SCREENS:
            raise ValueError(f"unknown screen {self.screen!r}; "
                             f"available: {', '.join(SCREENS)}")
        if self.screen != "none" and self.substrate not in SCREEN_SUBSTRATES:
            raise ValueError(
                f"screen={self.screen!r} runs on the "
                f"{' | '.join(SCREEN_SUBSTRATES)} substrates, got "
                f"{self.substrate!r}"
                + (" (the blocked cell is not wired for screens yet)"
                   if self.substrate == "blocked" else ""))
        if self.substrate == "blocked":
            if self.block < 1:
                raise ValueError(
                    "the blocked substrate needs block >= 1 (simulated "
                    f"clients per device), got block={self.block}")
        elif self.block:
            raise ValueError(
                "block is a 'blocked'-substrate knob; substrate "
                f"{self.substrate!r} keeps block=0, got block={self.block}")
        if self.clip_tau <= 0:
            raise ValueError(f"clip_tau must be > 0, got {self.clip_tau}")
        if self.trim_f < 0:
            raise ValueError(f"trim_f must be >= 0, got {self.trim_f}")
        if self.telemetry is not None:
            if not isinstance(self.telemetry, TelemetryConfig):
                raise ValueError(
                    "telemetry must be a repro.telemetry.TelemetryConfig "
                    f"(or None), got {type(self.telemetry).__name__}")
            if self.substrate not in TELEMETRY_SUBSTRATES:
                raise ValueError(
                    "round telemetry runs on the "
                    f"{' | '.join(TELEMETRY_SUBSTRATES)} substrates, got "
                    f"{self.substrate!r}")


def parse_gossip_impl(gossip_impl: str, delay: int = 0,
                      codec: str = "auto", screen: str = "none",
                      clip_tau: float = 3.0, trim_f: int = 1,
                      telemetry: TelemetryConfig | None = None,
                      sub_rounds: int = 1,
                      ) -> GossipEngineConfig:
    """Parse a legacy ``gossip_impl`` string (+ the ``gossip_delay`` /
    ``gossip_codec`` / ``gossip_screen`` knobs) into an engine config.

    ``codec="auto"`` keeps the alias's historical codec (f32 for the plain
    impls, int8_block for the quant impls); naming a codec overrides it —
    that is how the pipelined+quantized composition is spelled:
    ``gossip_impl="ppermute_packed_async", gossip_delay=1,
    gossip_codec="int8_block"``. ``screen`` rides the same way: any packed
    alias composes with "norm_clip" / "trimmed_mean" through config alone,
    and ``telemetry`` (a :class:`TelemetryConfig`) with any packed alias.
    ``sub_rounds`` (ParallelConfig.gossip_sub_rounds) is the Chebyshev
    multi-round axis — k > 1 composes with any stateless-codec packed
    alias at delay=0.
    """
    if gossip_impl not in LEGACY_GOSSIP_IMPLS:
        raise ValueError(f"unknown gossip_impl {gossip_impl!r}; available: "
                         f"{', '.join(sorted(LEGACY_GOSSIP_IMPLS))}")
    substrate, alias_codec = LEGACY_GOSSIP_IMPLS[gossip_impl]
    if codec in (None, "auto"):
        codec = alias_codec
    if delay and gossip_impl != "ppermute_packed_async":
        raise ValueError("gossip_delay=1 requires "
                         f"gossip_impl='ppermute_packed_async', got "
                         f"{gossip_impl!r}")
    return GossipEngineConfig(substrate=substrate, codec=codec, delay=delay,
                              sub_rounds=sub_rounds, screen=screen,
                              clip_tau=clip_tau, trim_f=trim_f,
                              telemetry=telemetry)


# ------------------------------------------------------------------ codecs
def _renormalized_weights(weights, contrib):
    """The alive/gates renormalization of the fused masked kernels, computed
    on the (d+1,) scalar operands (ref ``gossip_mix`` semantics: weights
    masked by contrib, rescaled to unit mass over the live contributors,
    dead self => identity row). The norm-clip screen needs the
    renormalization OUTSIDE the kernel so the clip can multiply the
    post-renormalization received weights without entering the denominator.
    """
    w = jnp.asarray(weights, jnp.float32)
    if contrib is None:
        return w
    a = jnp.asarray(contrib, jnp.float32)
    wa = w * a
    tot = jnp.sum(wa)
    # no renormalizable mass => identity row REPLACES the renormalized term
    # (inv zeroed, so tiny fractional mass cannot double-count)
    ok = (tot > 1e-12).astype(jnp.float32)
    inv = ok / jnp.maximum(tot, 1e-12)
    a_self = a[0]
    eff = a_self * wa * inv
    return eff.at[0].add((1.0 - a_self) + a_self * (1.0 - ok))


def _clip_factors(r2, lim):
    """Norm-clip rescale factors: 1 inside the ball, sqrt(lim/r2) outside
    (so the clipped payload lands exactly ON the tau x self-norm ball)."""
    return jnp.where(r2 > lim, jnp.sqrt(lim / jnp.maximum(r2, 1e-30)), 1.0)


def _weighted_rows(w, self_rows, srcs):
    """The stacked family's weighted reduce over client-stacked rows:
    ``w[:, 0] * self_rows + w[:, 1] * srcs[0] + ... + w[:, d] *
    srcs[d - 1]``, in f32 in schedule order with the (n, d+1) table ``w``
    broadcast over the trailing axes, cast back to ``self_rows``' dtype.
    Each row term is read once; no (n, d+1, ...) stack is built. Every
    stacked-family round reduces through here, so packed buffers and bare
    leaves of the same rows mix bit for bit alike.

    Each product is rounded to f32 before it is summed. XLA:CPU lets LLVM
    fuse a multiply into the add that consumes it, and which of two
    products it fuses depends on the code around it, so a bare chain of
    multiply-adds can differ by an ulp between layouts. Adding an opaque
    +0 to each product leaves its value as it is, and leaves no add in the
    sum with a multiply to absorb."""
    with jax.named_scope("mix"):
        zero = jax.lax.optimization_barrier(jnp.zeros((), jnp.float32))
        lead = (w.shape[0],) + (1,) * (self_rows.ndim - 1)

        def term(k, rows):
            return w[:, k].reshape(lead) * rows.astype(jnp.float32) + zero

        acc = term(0, self_rows)
        for s, src in enumerate(srcs):
            acc = acc + term(1 + s, src)
        return acc.astype(self_rows.dtype)


class _F32Codec:
    """Identity wire: ship the packed buffer; on ``shard_map`` reduce via
    the fused ``gossip_mix_2d`` pass. The encode is literally the buffer, so
    the delayed snapshot is the packed fresh state. The stacked family never
    calls :meth:`reduce` for this codec: it mixes through
    :func:`_weighted_rows`, on the bare leaves when no snapshot is carried."""

    name = "f32"
    identity_wire = True   # wire IS the packed buffer (no encode/decode)
    stateful = False

    def wire_struct(self, struct: jax.ShapeDtypeStruct,
                    n_blocks: int) -> jax.ShapeDtypeStruct:
        return struct

    def encode(self, buf, *, n_blocks, block_rows, impl):
        return buf

    def decode(self, wire, dtype, *, n_blocks, block_rows):
        return wire

    def reduce(self, fresh, received, weights, contrib, *, edge_weight,
               n_blocks, block_rows, impl, sender_scale=None):
        from repro.kernels.gossip_mix import ops as mix_ops

        stack = jnp.stack([fresh] + received)
        if sender_scale is None:
            return mix_ops.gossip_mix_packed(stack, weights, contrib,
                                             block_rows=block_rows, impl=impl)
        # norm-clip: renormalize outside the kernel, then scale the received
        # weights only (column 0 untouched) — an all-ones clip is bitwise
        # the same weight vector the masked kernel would have built
        eff = _renormalized_weights(weights, contrib)
        eff = jnp.concatenate([eff[:1], eff[1:] * sender_scale])
        return mix_ops.gossip_mix_packed(stack, eff, None,
                                         block_rows=block_rows, impl=impl)

    def reduce_trimmed(self, fresh, received, u, live, *, trim, n_blocks,
                       block_rows, impl):
        from repro.kernels.gossip_mix import ops as mix_ops

        stack = jnp.stack([fresh] + received)
        return mix_ops.gossip_mix_trimmed_packed(stack, u, live, trim=trim,
                                                 block_rows=block_rows,
                                                 impl=impl)

    def wire_sqnorm(self, wire, *, n_blocks, block_rows, impl):
        from repro.kernels.gossip_mix import ops as mix_ops

        return jnp.sum(mix_ops.packed_sqnorms(wire, block_rows=block_rows,
                                              impl=impl))

    # per-leaf baseline hooks
    def encode_leaf(self, x, impl):
        return (x,)

    def decode_leaf(self, parts, dtype, impl):
        return parts[0]


class _Int8Codec:
    """int8 wire payloads: quantize through the Pallas kernels, bitcast the
    f32 scale(s) into trailing lane rows of the SAME shipped buffer (one
    collective per schedule), and fold each received wire into the
    accumulator through the fused dequant-accumulate kernels. The local term
    stays full precision, so the int8 error only enters through the (small,
    renormalized) edge weights."""

    identity_wire = False
    stateful = False

    def __init__(self, block_scales: bool):
        self.block_scales = block_scales
        self.name = "int8_block" if block_scales else "int8"

    def _tail_rows(self, n_blocks: int) -> int:
        return packing.scale_rows(n_blocks) if self.block_scales else 1

    def wire_struct(self, struct: jax.ShapeDtypeStruct,
                    n_blocks: int) -> jax.ShapeDtypeStruct:
        rows = struct.shape[0] + self._tail_rows(n_blocks)
        return jax.ShapeDtypeStruct((rows, packing.LANE), jnp.int8)

    def encode(self, buf, *, n_blocks, block_rows, impl):
        from repro.kernels.quant_gossip import ops as qops

        if self.block_scales:
            q, scales = qops.quantize_packed_blockwise(
                buf, block_rows=block_rows, impl=impl)
            return qops.fold_scales_into_wire(q, scales)
        q, scale = qops.quantize_packed(buf, block_rows=block_rows, impl=impl)
        return qops.fold_scale_into_wire(q, scale)

    def decode(self, wire, dtype, *, n_blocks, block_rows):
        """Plain dequantize (the stacked substrate's gather source); the
        shard_map substrate never materializes this — it uses the fused
        :meth:`reduce` accumulation instead."""
        from repro.kernels.quant_gossip import ops as qops

        if self.block_scales:
            q, scales = qops.split_wire_blockwise(wire, n_blocks)
            return qops.dequantize_packed_blockwise(q, scales, dtype,
                                                    block_rows=block_rows)
        q, scale = qops.split_wire(wire)
        return qops.dequantize_packed(q, scale, dtype)

    def reduce(self, fresh, received, weights, contrib, *, edge_weight,
               n_blocks, block_rows, impl, sender_scale=None):
        from repro.kernels.quant_gossip import ops as qops

        c = edge_weight
        if contrib is None:
            self_scale = weights[0]
            recv_w = [None] * len(received)
        else:
            a_self, src_a = contrib[0], contrib[1:]
            wa0 = weights[0] * a_self
            tot = wa0 + c * jnp.sum(src_a)
            # no renormalizable mass => identity row REPLACES the
            # renormalized term (inv zeroed, so tiny fractional mass cannot
            # double-count)
            ok = (tot > 1e-12).astype(jnp.float32)
            inv = ok / jnp.maximum(tot, 1e-12)
            self_scale = (a_self * wa0 * inv + (1.0 - a_self)
                          + a_self * (1.0 - ok))
            recv_w = [a_self * src_a[k] * inv for k in range(len(received))]
        if sender_scale is not None:
            # norm-clip folds into the per-sender weight operand of the
            # fused dequant-accumulate — post-renormalization, so the
            # alive/gates denominator above is untouched
            recv_w = [sender_scale[k] if a is None else a * sender_scale[k]
                      for k, a in enumerate(recv_w)]
        acc = self_scale.astype(fresh.dtype) * fresh
        for rwire, a in zip(received, recv_w):
            if self.block_scales:
                rq, rs = qops.split_wire_blockwise(rwire, n_blocks)
                acc = qops.dequant_accumulate_packed_blockwise(
                    rq, rs, c, acc, a, block_rows=block_rows, impl=impl)
            else:
                rq, rs = qops.split_wire(rwire)
                acc = qops.dequant_accumulate_packed(
                    rq, rs, c, acc, a, block_rows=block_rows, impl=impl)
        return acc

    def reduce_trimmed(self, fresh, received, u, live, *, trim, n_blocks,
                       block_rows, impl):
        from repro.kernels.gossip_mix import ops as mix_ops
        from repro.kernels.quant_gossip import ops as qops

        if self.block_scales:
            pairs = [qops.split_wire_blockwise(w, n_blocks)
                     for w in received]
            scales = jnp.stack([s for _, s in pairs])          # (d, n_blocks)
        else:
            pairs = [qops.split_wire(w) for w in received]
            scales = jnp.stack([s.reshape(1) for _, s in pairs])  # (d, 1)
        qstack = jnp.stack([q for q, _ in pairs])
        return mix_ops.gossip_mix_trimmed_quant_packed(
            fresh, qstack, scales, u, live, trim=trim,
            block_rows=block_rows, impl=impl)

    def wire_sqnorm(self, wire, *, n_blocks, block_rows, impl):
        from repro.kernels.gossip_mix import ops as mix_ops
        from repro.kernels.quant_gossip import ops as qops

        # decoded-payload norm straight off the int8 wire: per-block
        # sum(q^2) x scale^2 (exact for what the mix would dequantize)
        if self.block_scales:
            q, scales = qops.split_wire_blockwise(wire, n_blocks)
            part = mix_ops.packed_sqnorms(q.astype(jnp.float32),
                                          block_rows=block_rows, impl=impl)
            return jnp.sum(part * scales.astype(jnp.float32) ** 2)
        q, scale = qops.split_wire(wire)
        part = mix_ops.packed_sqnorms(q.astype(jnp.float32),
                                      block_rows=block_rows, impl=impl)
        return scale.astype(jnp.float32) ** 2 * jnp.sum(part)

    # per-leaf baseline hooks (per-tensor scale; no tile alignment)
    def encode_leaf(self, x, impl):
        from repro.kernels.quant_gossip import ops as qops

        return qops.quantize_int8(x, impl=impl)

    def decode_leaf(self, parts, dtype, impl):
        from repro.kernels.quant_gossip import ops as qops

        return qops.dequantize_int8(parts[0], parts[1], dtype, impl=impl)


class TopKEFCodec:
    """Sparse top-k wire with error feedback — the first STATEFUL codec.

    The WireCodec contract grows three optional hooks for codecs that carry
    per-client state across rounds (all declared via class attrs / methods,
    never via executor special-casing):

    * ``stateful = True`` — the executor threads a per-buffer state operand
      through the round and returns the updated state right after the delay
      snapshot (a donated step input, exactly like the in-flight wire);
    * ``state_struct(struct, n_blocks)`` — the per-client state layout for
      one packed buffer (here: an f32 residual shaped like the payload);
    * ``init_state(struct)`` — the priming value (zeros: nothing dropped
      yet); :meth:`GossipExecutor.init_codec_state` maps it over the pack
      spec (with the client axis in front on the stacked substrate, so a
      splice repair remaps the state by the same old2new row take as the
      params and the in-flight snapshot).

    Encode is ``ef_compress`` on the packed ``(rows, 128)`` buffer: add the
    residual, keep the k = max(1, floor(k_fraction * rows * 128)) largest-
    magnitude entries, remember what was dropped. The wire is the k f32
    values with their k int32 flat indices lane-folded into ONE int8 buffer
    (:func:`repro.kernels.quant_gossip.ops.fold_topk_into_wire`), so each
    schedule still ships a single collective of ~8k bytes — ~2 *
    k_fraction of the dense f32 wire. Reduce folds each received wire into
    the accumulator through the fused scatter-accumulate Pallas kernel
    (``scatter_accumulate_2d``), one dense HBM pass per wire like the int8
    path. The self row stays the FRESH full-precision buffer everywhere, so
    sparsification error only enters through the received edges (and is
    re-injected next round by the sender's residual).
    """

    identity_wire = False
    stateful = True

    def __init__(self, k_fraction: float, name: str = "topk_ef"):
        if not 0.0 < float(k_fraction) <= 1.0:
            raise ValueError("k_fraction must be in (0, 1], got "
                             f"{k_fraction}")
        self.k_fraction = float(k_fraction)
        self.name = name

    def k_for(self, rows: int) -> int:
        """ef_compress's k on a (rows, LANE) packed buffer."""
        return max(1, int(self.k_fraction * rows * packing.LANE))

    def wire_struct(self, struct: jax.ShapeDtypeStruct,
                    n_blocks: int) -> jax.ShapeDtypeStruct:
        rows = packing.topk_wire_rows(self.k_for(struct.shape[0]))
        return jax.ShapeDtypeStruct((rows, packing.LANE), jnp.int8)

    def state_struct(self, struct: jax.ShapeDtypeStruct,
                     n_blocks: int) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(struct.shape, jnp.float32)

    def init_state(self, struct: jax.ShapeDtypeStruct) -> jax.Array:
        return jnp.zeros(struct.shape, jnp.float32)

    def encode(self, buf, *, n_blocks, block_rows, impl, state):
        from repro.core import compression
        from repro.kernels.quant_gossip import ops as qops

        y = buf.astype(jnp.float32) + state
        vals, idx = compression.topk_sparsify(y, self.k_for(buf.shape[0]))
        dense = (jnp.zeros(y.size, jnp.float32).at[idx].set(vals)
                 .reshape(y.shape))
        return qops.fold_topk_into_wire(vals, idx), y - dense

    def decode(self, wire, dtype, *, n_blocks, block_rows):
        """Scatter back to dense (the stacked substrate's gather source);
        the shard_map substrate never materializes this — it uses the fused
        :meth:`reduce` scatter-accumulation instead."""
        from repro.kernels.quant_gossip import ops as qops

        rows = n_blocks * block_rows
        vals, idx = qops.split_topk_wire(wire, self.k_for(rows))
        dense = jnp.zeros(rows * packing.LANE, jnp.float32).at[idx].set(vals)
        return dense.reshape(rows, packing.LANE).astype(dtype)

    def reduce(self, fresh, received, weights, contrib, *, edge_weight,
               n_blocks, block_rows, impl, sender_scale=None):
        from repro.kernels.quant_gossip import ops as qops

        c = edge_weight
        if contrib is None:
            self_scale = weights[0]
            recv_w = [None] * len(received)
        else:
            a_self, src_a = contrib[0], contrib[1:]
            wa0 = weights[0] * a_self
            tot = wa0 + c * jnp.sum(src_a)
            # no renormalizable mass => identity row REPLACES the
            # renormalized term (same fallback as the int8 reduce)
            ok = (tot > 1e-12).astype(jnp.float32)
            inv = ok / jnp.maximum(tot, 1e-12)
            self_scale = (a_self * wa0 * inv + (1.0 - a_self)
                          + a_self * (1.0 - ok))
            recv_w = [a_self * src_a[k] * inv for k in range(len(received))]
        if sender_scale is not None:
            recv_w = [sender_scale[k] if a is None else a * sender_scale[k]
                      for k, a in enumerate(recv_w)]
        k_top = self.k_for(n_blocks * block_rows)
        acc = self_scale.astype(fresh.dtype) * fresh
        for rwire, a in zip(received, recv_w):
            vals, idx = qops.split_topk_wire(rwire, k_top)
            acc = qops.scatter_accumulate_packed(
                vals, idx, c, acc, a, block_rows=block_rows, impl=impl)
        return acc

    def wire_sqnorm(self, wire, *, n_blocks, block_rows, impl):
        from repro.kernels.quant_gossip import ops as qops

        vals, _ = qops.split_topk_wire(wire,
                                       self.k_for(n_blocks * block_rows))
        return jnp.sum(vals.astype(jnp.float32) ** 2)
    # no reduce_trimmed / encode_leaf hooks: screens and the per-leaf
    # baseline are rejected for stateful codecs at config validation.


# ------------------------------------------------------------ registry
# Codecs plug in by NAME: config validation, the trainers' front door, the
# legacy-knob shims and the wire-byte accounting all consult this registry,
# so a new codec (including out-of-tree ones) never edits the engine body.
_CODECS: dict[str, Any] = {}
CODECS: tuple[str, ...] = ()


def register_codec(name: str, codec) -> Any:
    """Register a WireCodec instance under ``name`` (last write wins).

    ``codec`` follows the duck-typed WireCodec contract (wire_struct /
    encode / decode / reduce / wire_sqnorm, plus the optional stateful
    hooks — see :class:`TopKEFCodec`). After registration the name is valid
    anywhere a codec is spelled: ``GossipEngineConfig(codec=name)``, the
    trainers' ``engine=`` front door, and the benches' wire accounting.
    """
    global CODECS
    if not name or not isinstance(name, str):
        raise ValueError(f"codec name must be a non-empty string, got "
                         f"{name!r}")
    _CODECS[name] = codec
    CODECS = tuple(_CODECS)
    return codec


def get_codec(name: str):
    """Public codec lookup (benches/tests derive wire shapes from it)."""
    if name not in _CODECS:
        raise ValueError(f"unknown codec {name!r}; available: "
                         f"{', '.join(CODECS)}")
    return _CODECS[name]


register_codec("f32", _F32Codec())
register_codec("int8", _Int8Codec(block_scales=False))
register_codec("int8_block", _Int8Codec(block_scales=True))
register_codec("topk_ef", TopKEFCodec(k_fraction=0.01))


# --------------------------------------------------------------- executor
@dataclasses.dataclass(frozen=True)
class GossipExecutor:
    """One assembled gossip round. Call signature by timing:

    * sync: ``executor(tree, alive=..., gates=...) -> mixed_tree``
    * delayed: ``executor(tree, state=..., alive=..., gates=...) ->
      (mixed_tree, new_state)`` where ``state`` is the codec-wire snapshot
      of the previous round (prime it with :meth:`init_state`).

    A STATEFUL codec (``codec.stateful``, e.g. ``topk_ef``'s EF residual)
    adds one more threaded operand: pass ``codec_state=...`` (prime it with
    :meth:`init_codec_state`) and the updated per-buffer state tuple is
    returned right AFTER the delay snapshot (``(mixed, new_codec_state)``
    sync, ``(mixed, new_state, new_codec_state)`` delayed). Like the
    snapshot, codec state is step data in the codec's ``state_struct``
    layout — donated, remapped through splice repair by the same old2new
    row compaction, never trace structure.

    With ``config.sub_rounds = k > 1`` (Chebyshev multi-round gossip) the
    call takes one more traced operand: ``cheby=...``, the (k,) f32
    per-sub-round coefficient vector (host-side source:
    :meth:`cheby_coeffs`, which reads the baked ``spec.lam``). Like
    alive/gates it is data — recomputing it after a splice repair or
    sweeping it across rounds retraces nothing. The k=1 cell takes no such
    operand and IS the sync engine (build-time branch, delay=0 style).

    With ``config.telemetry`` set, a RoundMetrics dict of traced values is
    appended as the LAST element of the return tuple (``(mixed, metrics)``
    sync, ``(mixed, new_state, metrics)`` delayed); :meth:`metrics_structs`
    declares its exact key set and shapes. Telemetry never changes the
    collectives or the trace structure — ``telemetry=None`` builds lower to
    HLO textually identical to pre-telemetry anchors.

    ``tree`` is the client-local shard pytree on the ``shard_map`` /
    ``per_leaf`` substrates (call inside the island), the client-stacked
    pytree on ``stacked`` / ``dense``, and the device-local ``(block, ...)``
    stacked slice on ``blocked`` (call inside the island over a 1-D client
    device axis; a ``P(axis)`` sharding of the stacked tree IS that slice).
    ``alive`` / ``gates`` are traced data on the packed substrates — on
    ``blocked`` they stay full-length replicated ``(n,)`` / ``(S,)``
    vectors, the executor slices its own device's rows (``per_leaf`` and
    ``dense``-with-gates follow the legacy conventions: per-leaf ignores
    both).
    """

    config: GossipEngineConfig
    spec: GossipSpec
    axis_names: Any = None
    pack_spec: packing.PackSpec | None = None
    blocked: gossip.BlockedSpec | None = None

    @property
    def delayed(self) -> bool:
        return self.config.delay == 1

    @property
    def codec(self):
        return _CODECS[self.config.codec]

    @property
    def stateful(self) -> bool:
        """Whether this executor threads per-client codec state."""
        return bool(getattr(self.codec, "stateful", False))

    def __call__(self, tree: PyTree, *, state=None, codec_state=None,
                 alive=None, gates=None, cheby=None):
        cfg = self.config
        if self.delayed and state is None:
            raise ValueError("delayed executor needs the carried snapshot "
                             "(prime it with init_state)")
        if self.stateful and codec_state is None:
            raise ValueError(f"codec {cfg.codec!r} is stateful and needs "
                             "its per-client codec state (prime it with "
                             "init_codec_state)")
        if not self.stateful and codec_state is not None:
            raise ValueError(f"codec {cfg.codec!r} carries no codec state; "
                             "drop the codec_state operand")
        if cfg.sub_rounds > 1 and cheby is None:
            raise ValueError(
                f"sub_rounds={cfg.sub_rounds} needs the (sub_rounds,) "
                "per-sub-round Chebyshev coefficient operand (build it "
                "with cheby_coeffs / spectral.chebyshev_omegas)")
        if cfg.sub_rounds == 1 and cheby is not None:
            raise ValueError(
                "cheby coefficients are a sub_rounds > 1 operand; the "
                "sub_rounds=1 cell is the sync engine — drop the operand")
        with jax.named_scope("dfl.gossip"):
            if cfg.substrate == "dense":
                return gossip.mix_dense(
                    tree, gossip.gated_mixing_matrix(self.spec, gates, alive))
            if cfg.substrate == "per_leaf":
                return self._per_leaf_round(tree)
            if cfg.substrate == "stacked":
                if cfg.sub_rounds > 1:
                    return self._stacked_round_cheby(tree, alive, gates, cheby)
                return self._stacked_round(tree, state, codec_state, alive,
                                           gates)
            if cfg.substrate == "blocked":
                return self._blocked_round(tree, alive, gates)
            if cfg.sub_rounds > 1:
                return self._shard_map_round_cheby(tree, alive, gates, cheby)
            return self._shard_map_round(tree, state, codec_state, alive, gates)

    # ------------------------------------------------- pipelined state
    def init_state(self, tree: PyTree) -> tuple[jax.Array, ...]:
        """Prime the pipeline: the codec-wire snapshot of ``tree`` (round 0
        then mixes the initial params as its delayed snapshot — the
        ``mix_dense_delayed`` y_{-1} := x_0 convention). The snapshot layout
        depends only on the parameter structure, never on the topology, so
        a splice repair remaps it by the same old2new row compaction as the
        params."""
        cfg, codec = self.config, self.codec

        def enc(x, b, pack_spec):
            kw = dict(n_blocks=pack_spec.buffer_blocks(b),
                      block_rows=pack_spec.block_rows, impl=cfg.mix_impl)
            if self.stateful:
                # prime against a zero residual; the priming residual is
                # discarded (init_codec_state owns the carried zeros) — the
                # y_{-1} := x_0 snapshot is the one EF-unfed wire
                wire, _ = codec.encode(
                    x, state=jnp.zeros(x.shape, jnp.float32), **kw)
                return wire
            return codec.encode(x, **kw)

        if cfg.substrate == "stacked":
            pack_spec = self.pack_spec or gossip._stacked_pack_spec(tree)
            bufs = jax.vmap(lambda t: packing.pack_tree(t, pack_spec))(tree)
            return tuple(
                jax.vmap(lambda x, b=b: enc(x, b, pack_spec))(buf)
                for b, buf in enumerate(bufs))
        pack_spec = self.pack_spec or packing.make_pack_spec(tree)
        return tuple(
            enc(buf, b, pack_spec)
            for b, buf in enumerate(packing.pack_tree(tree, pack_spec)))

    def state_structs(self) -> tuple[jax.ShapeDtypeStruct, ...]:
        """Per-device wire shapes of the carried snapshot (requires a baked
        ``pack_spec``) — what the production step declares as its donated
        in-flight argument."""
        if self.pack_spec is None:
            raise ValueError("state_structs needs a baked pack_spec")
        ps, codec = self.pack_spec, self.codec
        return tuple(
            codec.wire_struct(ps.buffer_struct(b), ps.buffer_blocks(b))
            for b in range(ps.n_buffers))

    # ------------------------------------------------- codec state
    def init_codec_state(self, tree: PyTree) -> tuple[jax.Array, ...]:
        """Prime the per-client codec state (``codec.init_state`` per packed
        buffer — the topk_ef EF residual starts at zeros: nothing dropped
        yet). On the stacked substrate the client axis rides in front, so a
        splice repair remaps this state by the same old2new row take as the
        params and the in-flight snapshot."""
        cfg, codec = self.config, self.codec
        if not self.stateful:
            raise ValueError(f"codec {cfg.codec!r} carries no codec state")
        if cfg.substrate == "stacked":
            pack_spec = self.pack_spec or gossip._stacked_pack_spec(tree)
            n = jax.tree.leaves(tree)[0].shape[0]
            return tuple(
                jnp.zeros((n,) + st.shape, st.dtype)
                for st in (codec.state_struct(pack_spec.buffer_struct(b),
                                              pack_spec.buffer_blocks(b))
                           for b in range(pack_spec.n_buffers)))
        pack_spec = self.pack_spec or packing.make_pack_spec(tree)
        return tuple(
            codec.init_state(pack_spec.buffer_struct(b))
            for b in range(pack_spec.n_buffers))

    def codec_state_structs(self) -> tuple[jax.ShapeDtypeStruct, ...]:
        """Per-device codec-state shapes (requires a baked ``pack_spec``) —
        what the production step declares as its donated codec-state
        argument."""
        if self.pack_spec is None:
            raise ValueError("codec_state_structs needs a baked pack_spec")
        if not self.stateful:
            raise ValueError(f"codec {self.config.codec!r} carries no "
                             "codec state")
        ps, codec = self.pack_spec, self.codec
        return tuple(
            codec.state_struct(ps.buffer_struct(b), ps.buffer_blocks(b))
            for b in range(ps.n_buffers))

    # ------------------------------------------------- cheby coefficients
    def cheby_coeffs(self):
        """Host-side (sub_rounds,) f32 Chebyshev coefficient vector for the
        baked spec's lambda(M) — the value the ``cheby=`` operand ships.
        Recompute after a splice repair (the rebuilt executor carries the
        new spec.lam); the shape only depends on ``config.sub_rounds``, so
        the refreshed values never retrace."""
        from repro.core import spectral

        return spectral.chebyshev_omegas(self.spec.lam,
                                         self.config.sub_rounds)

    # ----------------------------------------------------- telemetry
    def metrics_structs(self) -> dict:
        """ShapeDtypeStructs of the RoundMetrics this executor returns —
        the key set is fixed by (telemetry, screen, substrate) at build
        time ({} when telemetry is off). Stacked metrics are client-stacked
        arrays; shard_map metrics are per-DEVICE locals (the caller's
        island sums them host-side — see repro.telemetry.metrics); blocked
        metrics are the device-local (block,)-leading rows (an island
        out_spec over the client device axis concatenates them back to the
        stacked layout)."""
        tel = self.config.telemetry
        if tel is None:
            return {}
        out = {}
        if self.config.substrate in ("stacked", "blocked"):
            n = (self.config.block if self.config.substrate == "blocked"
                 else self.spec.n_clients)
            n_sched = len(self.spec.recv_from)
            if tel.consensus:
                out["resid_sqnorm"] = jax.ShapeDtypeStruct((n,), jnp.float32)
            if tel.degree:
                out["in_degree"] = jax.ShapeDtypeStruct((n,), jnp.float32)
                out["sched_contrib"] = jax.ShapeDtypeStruct((n, n_sched),
                                                            jnp.float32)
            if tel.clip and self.config.screen == "norm_clip":
                out["clipped"] = jax.ShapeDtypeStruct((n,), jnp.int32)
        else:  # shard_map
            n_sched = len(gossip._live_schedules(self.spec))
            if tel.consensus:
                out["resid_sqnorm"] = jax.ShapeDtypeStruct((), jnp.float32)
            if tel.degree:
                out["in_degree"] = jax.ShapeDtypeStruct((), jnp.float32)
                out["sched_contrib"] = jax.ShapeDtypeStruct((n_sched,),
                                                            jnp.float32)
            if tel.clip and self.config.screen == "norm_clip":
                out["clip_recv"] = jax.ShapeDtypeStruct((), jnp.int32)
        return out

    def wire_bytes_per_round(self) -> int:
        """EXACT wire bytes one client ships per round: one codec wire per
        live schedule per packed buffer PER SUB-ROUND, from the same
        ``wire_struct`` shapes the collectives move (requires a baked
        ``pack_spec``; the dense reference substrate has no wire => 0).
        ``sub_rounds=k`` multiplies the wire k-fold — the cost side of the
        Chebyshev rounds-to-threshold trade the benches measure."""
        if self.config.substrate == "dense":
            return 0
        if self.pack_spec is None:
            raise ValueError("wire_bytes_per_round needs a baked pack_spec")
        if self.config.substrate == "per_leaf":
            raise ValueError("per-leaf wires are per-tensor, not packed; "
                             "wire accounting covers the packed substrates")
        ps, codec = self.pack_spec, self.codec
        per_sched = 0
        for b in range(ps.n_buffers):
            st = codec.wire_struct(ps.buffer_struct(b), ps.buffer_blocks(b))
            per_sched += math.prod(st.shape) * jnp.dtype(st.dtype).itemsize
        return (len(gossip._live_schedules(self.spec)) * per_sched
                * self.config.sub_rounds)

    def _sq(self, pack_spec):
        """Whole-buffer squared-norm closure through the fused per-block
        pass (the telemetry consensus metric's accumulator)."""
        from repro.kernels.gossip_mix import ops as mix_ops

        def sq(x):
            return jnp.sum(mix_ops.packed_sqnorms(
                x.astype(jnp.float32), block_rows=pack_spec.block_rows,
                impl=self.config.mix_impl))

        return sq

    # ---------------------------------------------------- substrates
    def _shard_map_round(self, tree, state, cstate, alive, gates):
        cfg, codec, spec = self.config, self.codec, self.spec
        tel = cfg.telemetry
        pack_spec = self.pack_spec or packing.make_pack_spec(tree)
        idx = gossip._client_index(self.axis_names)
        live = gossip._live_schedules(spec)
        perms = [p for _, p, _, _ in live]
        weights = gossip._local_raw_weights(spec, idx, len(perms), gates)
        # the trimmed screen ALWAYS builds the contributor vector: fixed
        # points deliver zeros on this substrate and must stay invisible to
        # the order statistics even with no alive/gates overlay
        contrib = (None if alive is None and gates is None
                   and cfg.screen != "trimmed_mean"
                   else gossip._local_contrib_vec(spec, idx, live, alive,
                                                  gates))
        # telemetry reads contributor mass through its OWN vector when the
        # reduce path runs contrib-less — forcing one into codec.reduce
        # would change the lowered arithmetic (renorm ops), and telemetry
        # must never touch the mixing HLO
        tcontrib = None
        if tel is not None:
            tcontrib = (contrib if contrib is not None
                        else gossip._local_contrib_vec(spec, idx, live,
                                                       alive, gates))
        if cfg.screen == "norm_clip":
            return self._shard_map_round_clipped(tree, state, weights,
                                                 contrib, pack_spec, perms,
                                                 tcontrib)
        if cfg.screen == "trimmed_mean":
            trim_u = jnp.maximum(weights, 0.0) * contrib
            trim_live = (contrib > 0.0).astype(jnp.float32)
        metrics = {}
        if tel is not None and tel.degree:
            metrics["in_degree"] = jnp.sum(tcontrib[1:])
            metrics["sched_contrib"] = tcontrib[1:]
        resid = jnp.float32(0.0)
        sq = self._sq(pack_spec)
        out_bufs, new_state, new_cstate = [], [], []
        for b, buf in enumerate(packing.pack_tree(tree, pack_spec)):
            n_blocks = pack_spec.buffer_blocks(b)
            if self.stateful:
                # the codec updates its per-client state exactly once per
                # round, at encode; with delay the permutes still read the
                # carried snapshot while the fresh wire becomes next
                # round's snapshot (sparse pipelined gossip: the donated
                # in-flight buffer IS the ~k-fold smaller codec wire)
                wire_fresh, res = codec.encode(
                    buf, n_blocks=n_blocks, block_rows=pack_spec.block_rows,
                    impl=cfg.mix_impl, state=cstate[b])
                new_cstate.append(res)
                wire = state[b] if cfg.delay else wire_fresh
                if cfg.delay:
                    new_state.append(wire_fresh)
            elif cfg.delay:
                # the permutes read the carried snapshot (a step input): no
                # dep on the local-step scan, so the scheduler can start
                # them at program entry and hide the wire behind compute
                wire = state[b]
                new_state.append(codec.encode(
                    buf, n_blocks=n_blocks, block_rows=pack_spec.block_rows,
                    impl=cfg.mix_impl))
            else:
                wire = codec.encode(buf, n_blocks=n_blocks,
                                    block_rows=pack_spec.block_rows,
                                    impl=cfg.mix_impl)
            # all ppermutes issued before the reduction so XLA can overlap
            received = [jax.lax.ppermute(wire, self.axis_names, perm=p)
                        for p in perms]
            if tel is not None and tel.consensus:
                # consensus proxy over THIS shard: what each neighbor wire
                # dequantizes to, against the local fresh buffer
                for s, rwire in enumerate(received):
                    dec = codec.decode(rwire, buf.dtype, n_blocks=n_blocks,
                                       block_rows=pack_spec.block_rows)
                    resid = resid + tcontrib[1 + s] * sq(
                        dec.astype(jnp.float32) - buf.astype(jnp.float32))
            if cfg.screen == "trimmed_mean":
                out_bufs.append(codec.reduce_trimmed(
                    buf, received, trim_u, trim_live, trim=cfg.trim_f,
                    n_blocks=n_blocks, block_rows=pack_spec.block_rows,
                    impl=cfg.mix_impl))
            else:
                out_bufs.append(codec.reduce(
                    buf, received, weights, contrib,
                    edge_weight=float(spec.edge_weight), n_blocks=n_blocks,
                    block_rows=pack_spec.block_rows, impl=cfg.mix_impl))
        if tel is not None and tel.consensus:
            metrics["resid_sqnorm"] = resid
        mixed = packing.unpack_tree(tuple(out_bufs), pack_spec)
        ret = (mixed,)
        if cfg.delay:
            ret = ret + (tuple(new_state),)
        if self.stateful:
            ret = ret + (tuple(new_cstate),)
        if tel is not None:
            ret = ret + (metrics,)
        return ret[0] if len(ret) == 1 else ret

    def _shard_map_round_clipped(self, tree, state, weights, contrib,
                                 pack_spec, perms, tcontrib=None):
        """norm_clip needs whole-model norms, so the round splits into an
        encode+permute pass (all collectives still issued up front — the
        wire is byte-identical to the unscreened round), one tiny norm
        reduction per wire, and the per-buffer fused reduce with the clip
        folded into the received weight operands."""
        from repro.kernels.gossip_mix import ops as mix_ops

        cfg, codec, spec = self.config, self.codec, self.spec
        tel = cfg.telemetry
        fresh = list(packing.pack_tree(tree, pack_spec))
        wires, new_state = [], []
        s2 = jnp.float32(0.0)
        for b, buf in enumerate(fresh):
            n_blocks = pack_spec.buffer_blocks(b)
            if cfg.delay:
                wire = state[b]
                new_state.append(codec.encode(
                    buf, n_blocks=n_blocks, block_rows=pack_spec.block_rows,
                    impl=cfg.mix_impl))
            else:
                wire = codec.encode(buf, n_blocks=n_blocks,
                                    block_rows=pack_spec.block_rows,
                                    impl=cfg.mix_impl)
            wires.append(wire)
            s2 = s2 + jnp.sum(mix_ops.packed_sqnorms(
                buf, block_rows=pack_spec.block_rows, impl=cfg.mix_impl))
        received = [[jax.lax.ppermute(wire, self.axis_names, perm=p)
                     for p in perms] for wire in wires]
        r2 = [sum(codec.wire_sqnorm(received[b][k],
                                    n_blocks=pack_spec.buffer_blocks(b),
                                    block_rows=pack_spec.block_rows,
                                    impl=cfg.mix_impl)
                  for b in range(len(fresh)))
              for k in range(len(perms))]
        clip = (_clip_factors(jnp.stack(r2), cfg.clip_tau ** 2 * s2)
                if r2 else jnp.zeros((0,), jnp.float32))
        metrics = {}
        if tel is not None:
            if tel.degree:
                metrics["in_degree"] = jnp.sum(tcontrib[1:])
                metrics["sched_contrib"] = tcontrib[1:]
            if tel.consensus:
                sq = self._sq(pack_spec)
                resid = jnp.float32(0.0)
                for b, buf in enumerate(fresh):
                    for k in range(len(perms)):
                        dec = codec.decode(
                            received[b][k], buf.dtype,
                            n_blocks=pack_spec.buffer_blocks(b),
                            block_rows=pack_spec.block_rows)
                        resid = resid + tcontrib[1 + k] * sq(
                            dec.astype(jnp.float32)
                            - buf.astype(jnp.float32))
                metrics["resid_sqnorm"] = resid
            if tel.clip:
                # LOCAL per-receiver count of incoming wires this client
                # clipped (a per-sender count here would need a reverse
                # collective; the stacked substrate has the global view)
                metrics["clip_recv"] = jnp.sum(
                    ((clip < 1.0) & (tcontrib[1:] > 0.0)).astype(jnp.int32))
        out_bufs = [
            codec.reduce(buf, received[b], weights, contrib,
                         edge_weight=float(spec.edge_weight),
                         n_blocks=pack_spec.buffer_blocks(b),
                         block_rows=pack_spec.block_rows, impl=cfg.mix_impl,
                         sender_scale=clip)
            for b, buf in enumerate(fresh)]
        mixed = packing.unpack_tree(tuple(out_bufs), pack_spec)
        ret = (mixed,)
        if cfg.delay:
            ret = ret + (tuple(new_state),)
        if tel is not None:
            ret = ret + (metrics,)
        return ret[0] if len(ret) == 1 else ret

    def _shard_map_round_cheby(self, tree, alive, gates, cheby):
        """Chebyshev multi-round gossip (sub_rounds = k > 1), shard_map.

        The traced ``cheby`` operand carries the (k,) per-sub-round weights
        (:func:`repro.core.spectral.chebyshev_omegas`) — plain data, so a
        splice repair's refreshed lambda never retraces. Each sub-round
        reuses the sync round's exact d-ppermute + fused-reduce structure
        (k*d collectives per round, HLO-counted by the anchor tests) and the
        second-order combine

            x^(j+1) = cheby[j] * (W x^(j) - x^(j-1)) + x^(j-1)

        with x^(-1) := x^(0) runs in f32 on the packed buffers. Weights /
        contributor vectors are computed once and reused every sub-round —
        the same W each application, exactly the ``mixing.chebyshev_mix``
        dense oracle. Telemetry (when on) measures the FIRST sub-round —
        the wires the k=1 cell would ship — so metrics stay comparable
        across the sub_rounds axis."""
        cfg, codec, spec = self.config, self.codec, self.spec
        tel = cfg.telemetry
        pack_spec = self.pack_spec or packing.make_pack_spec(tree)
        idx = gossip._client_index(self.axis_names)
        live = gossip._live_schedules(spec)
        perms = [p for _, p, _, _ in live]
        weights = gossip._local_raw_weights(spec, idx, len(perms), gates)
        contrib = (None if alive is None and gates is None
                   else gossip._local_contrib_vec(spec, idx, live, alive,
                                                  gates))
        tcontrib = None
        if tel is not None:
            tcontrib = (contrib if contrib is not None
                        else gossip._local_contrib_vec(spec, idx, live,
                                                       alive, gates))
        omg = jnp.asarray(cheby, jnp.float32)
        metrics = {}
        if tel is not None and tel.degree:
            metrics["in_degree"] = jnp.sum(tcontrib[1:])
            metrics["sched_contrib"] = tcontrib[1:]
        resid = jnp.float32(0.0)
        sq = self._sq(pack_spec)
        out_bufs = []
        for b, buf in enumerate(packing.pack_tree(tree, pack_spec)):
            n_blocks = pack_spec.buffer_blocks(b)
            x_prev = buf.astype(jnp.float32)
            x_cur = x_prev
            for j in range(cfg.sub_rounds):
                xj = x_cur.astype(buf.dtype)
                wire = codec.encode(xj, n_blocks=n_blocks,
                                    block_rows=pack_spec.block_rows,
                                    impl=cfg.mix_impl)
                received = [jax.lax.ppermute(wire, self.axis_names, perm=p)
                            for p in perms]
                if j == 0 and tel is not None and tel.consensus:
                    for s, rwire in enumerate(received):
                        dec = codec.decode(rwire, buf.dtype,
                                           n_blocks=n_blocks,
                                           block_rows=pack_spec.block_rows)
                        resid = resid + tcontrib[1 + s] * sq(
                            dec.astype(jnp.float32)
                            - xj.astype(jnp.float32))
                y = codec.reduce(
                    xj, received, weights, contrib,
                    edge_weight=float(spec.edge_weight), n_blocks=n_blocks,
                    block_rows=pack_spec.block_rows,
                    impl=cfg.mix_impl).astype(jnp.float32)
                # dead self => y == x^(j) (identity fallback), and the
                # recurrence fixes the whole orbit: dead clients keep params
                x_next = omg[j] * (y - x_prev) + x_prev
                x_prev, x_cur = x_cur, x_next
            out_bufs.append(x_cur.astype(buf.dtype))
        if tel is not None and tel.consensus:
            metrics["resid_sqnorm"] = resid
        mixed = packing.unpack_tree(tuple(out_bufs), pack_spec)
        if tel is not None:
            return mixed, metrics
        return mixed

    def _stacked_round(self, tree, state, cstate, alive, gates):
        cfg, codec, spec = self.config, self.codec, self.spec
        tel = cfg.telemetry
        pack_spec = self.pack_spec or gossip._stacked_pack_spec(tree)
        if cfg.screen != "none":
            return self._stacked_round_screened(tree, state, alive, gates,
                                                pack_spec)
        w = (gossip._static_weight_table(spec)
             if alive is None and gates is None
             else gossip.alive_weight_table(spec, alive, gates))
        gathers = [jnp.asarray(rf) for rf in spec.recv_from]
        # an identity wire with no snapshot needs no packed layout: the
        # round gathers and mixes the leaves themselves
        leafwise = (codec.identity_wire and not self.stateful
                    and not cfg.delay)
        fresh, sq, rebuild = self._stacked_rows(tree, pack_spec, leafwise)
        metrics, tcontrib = self._stacked_metrics_init(alive, gates)
        resid = jnp.zeros((spec.n_clients,), jnp.float32)
        out_bufs, new_state, new_cstate = [], [], []
        for b, buf in enumerate(fresh):
            n_blocks = None if leafwise else pack_spec.buffer_blocks(b)

            def enc(x, b=b):
                return codec.encode(x, n_blocks=n_blocks,
                                    block_rows=pack_spec.block_rows,
                                    impl=cfg.mix_impl)

            def dec(x, n_blocks=n_blocks, dtype=buf.dtype):
                return codec.decode(x, dtype, n_blocks=n_blocks,
                                    block_rows=pack_spec.block_rows)

            if self.stateful:
                # per-client encode updates the codec state exactly once
                # per round; with delay the gathers read the carried
                # snapshot while the fresh wire becomes next round's
                wire, res = jax.vmap(
                    lambda x, r, b=b: codec.encode(
                        x, n_blocks=n_blocks,
                        block_rows=pack_spec.block_rows,
                        impl=cfg.mix_impl, state=r))(buf, cstate[b])
                new_cstate.append(res)
                src = jax.vmap(dec)(state[b] if cfg.delay else wire)
                if cfg.delay:
                    new_state.append(wire)
            elif codec.identity_wire:
                src = state[b] if cfg.delay else buf
            else:
                wire = state[b] if cfg.delay else jax.vmap(enc)(buf)
                src = jax.vmap(dec)(wire)
            # self row stays the FRESH full-precision buffer; only the
            # gathered neighbor rows go through the codec / the snapshot
            srcs = [jnp.take(src, idx, axis=0) for idx in gathers]
            out_bufs.append(_weighted_rows(w, buf, srcs))
            if tel is not None and tel.consensus:
                for s, g in enumerate(srcs):
                    resid = resid + tcontrib[:, 1 + s] * sq(
                        g.astype(jnp.float32) - buf.astype(jnp.float32))
            if cfg.delay and not self.stateful:
                new_state.append(buf if codec.identity_wire
                                 else jax.vmap(enc)(buf))
        if tel is not None and tel.consensus:
            metrics["resid_sqnorm"] = resid
        mixed = rebuild(out_bufs)
        ret = (mixed,)
        if cfg.delay:
            ret = ret + (tuple(new_state),)
        if self.stateful:
            ret = ret + (tuple(new_cstate),)
        if tel is not None:
            ret = ret + (metrics,)
        return ret[0] if len(ret) == 1 else ret

    def _stacked_rows(self, tree, pack_spec, leafwise):
        """What a stacked round mixes: the client-stacked tree's own leaves
        when ``leafwise``, else its vmapped ``(n, rows, 128)`` pack buffers.
        Returns ``(rows, sq, rebuild)``: the arrays, the per-client squared
        norm of an f32 array shaped like one of them, and the map from the
        mixed arrays back to the tree."""
        if leafwise:
            leaves, treedef = jax.tree.flatten(tree)

            def sq(x):
                return jnp.sum(jnp.square(x).reshape(x.shape[0], -1), axis=1)

            return leaves, sq, lambda out: jax.tree.unflatten(treedef, out)
        fresh = jax.vmap(lambda t: packing.pack_tree(t, pack_spec))(tree)
        return (fresh, jax.vmap(self._sq(pack_spec)),
                lambda out: jax.vmap(
                    lambda bs: packing.unpack_tree(bs, pack_spec))(tuple(out)))

    def _stacked_metrics_init(self, alive, gates):
        """(metrics dict seeded with the degree metrics, contributor table)
        for a stacked telemetry build — (empty, None) when telemetry is off
        so the call sites stay single-line."""
        tel = self.config.telemetry
        if tel is None:
            return {}, None
        _, tcontrib = gossip.raw_contrib_tables(self.spec, alive, gates)
        metrics = {}
        if tel.degree:
            metrics["in_degree"] = jnp.sum(tcontrib[:, 1:], axis=1)
            metrics["sched_contrib"] = tcontrib[:, 1:]
        return metrics, tcontrib

    def _stacked_round_cheby(self, tree, alive, gates, cheby):
        """Chebyshev multi-round gossip (sub_rounds = k > 1), stacked.

        Same contract as :meth:`_shard_map_round_cheby` on the client-
        stacked substrate: k gather + multiply-add applications of the one
        weight table (computed once — the same W each sub-round), the
        second-order combine in f32, telemetry measured on the first
        sub-round. An identity wire mixes the bare leaves (no pack). The f32
        cell is the dense-oracle reference: it matches
        ``mixing.chebyshev_mix(x, gossip.gated_mixing_matrix(spec, gates,
        alive), cheby)`` to float tolerance."""
        cfg, codec, spec = self.config, self.codec, self.spec
        tel = cfg.telemetry
        pack_spec = self.pack_spec or gossip._stacked_pack_spec(tree)
        w = (gossip._static_weight_table(spec)
             if alive is None and gates is None
             else gossip.alive_weight_table(spec, alive, gates))
        gathers = [jnp.asarray(rf) for rf in spec.recv_from]
        leafwise = codec.identity_wire
        fresh, sq, rebuild = self._stacked_rows(tree, pack_spec, leafwise)
        metrics, tcontrib = self._stacked_metrics_init(alive, gates)
        resid = jnp.zeros((spec.n_clients,), jnp.float32)
        omg = jnp.asarray(cheby, jnp.float32)
        out_bufs = []
        for b, buf in enumerate(fresh):
            n_blocks = None if leafwise else pack_spec.buffer_blocks(b)

            def enc(x, n_blocks=n_blocks):
                return codec.encode(x, n_blocks=n_blocks,
                                    block_rows=pack_spec.block_rows,
                                    impl=cfg.mix_impl)

            def dec(x, n_blocks=n_blocks, dtype=buf.dtype):
                return codec.decode(x, dtype, n_blocks=n_blocks,
                                    block_rows=pack_spec.block_rows)

            x_prev = buf.astype(jnp.float32)
            x_cur = x_prev
            for j in range(cfg.sub_rounds):
                xj = x_cur.astype(buf.dtype)
                # self row stays the current full-precision iterate; only
                # the gathered neighbor rows go through the codec wire
                src = (xj if codec.identity_wire
                       else jax.vmap(dec)(jax.vmap(enc)(xj)))
                srcs = [jnp.take(src, g, axis=0) for g in gathers]
                # an f32 self row keeps y in f32 for the combine below
                y = _weighted_rows(w, xj.astype(jnp.float32), srcs)
                if j == 0 and tel is not None and tel.consensus:
                    for s, g in enumerate(srcs):
                        resid = resid + tcontrib[:, 1 + s] * sq(
                            g.astype(jnp.float32) - xj.astype(jnp.float32))
                x_next = omg[j] * (y - x_prev) + x_prev
                x_prev, x_cur = x_cur, x_next
            out_bufs.append(x_cur.astype(buf.dtype))
        if tel is not None and tel.consensus:
            metrics["resid_sqnorm"] = resid
        mixed = rebuild(out_bufs)
        if tel is not None:
            return mixed, metrics
        return mixed

    def _stacked_round_screened(self, tree, state, alive, gates, pack_spec):
        """Screened stacked round. The gather sources (decoded codec wires /
        the delayed snapshot) are materialized for every buffer first so the
        norm-clip screen can compare whole-model norms; the per-buffer mix
        then runs with either the clip-scaled weight table (norm_clip: the
        same multiply-add as the plain round, :func:`_weighted_rows`, so an
        all-ones clip is bitwise identical) or the vmapped trimmed-mean
        kernel over the (n, d+1, rows, 128) stack (trimmed_mean: its order
        statistics need every value of an element at once).

        Under telemetry, the norm_clip cells emit per-SENDER ``clipped``
        counts of receivers that clipped them this round — the suspicion
        signal :class:`repro.core.failures.HealthTracker` accumulates."""
        from repro.kernels.gossip_mix import ops as mix_ops

        cfg, codec, spec = self.config, self.codec, self.spec
        tel = cfg.telemetry
        if cfg.screen == "norm_clip" and not codec.identity_wire:
            return self._stacked_round_clipped_quant(tree, state, alive,
                                                     gates, pack_spec)
        gathers = [jnp.asarray(rf) for rf in spec.recv_from]
        fresh = jax.vmap(lambda t: packing.pack_tree(t, pack_spec))(tree)
        srcs, new_state = [], []
        for b, buf in enumerate(fresh):
            n_blocks = pack_spec.buffer_blocks(b)

            def enc(x, n_blocks=n_blocks):
                return codec.encode(x, n_blocks=n_blocks,
                                    block_rows=pack_spec.block_rows,
                                    impl=cfg.mix_impl)

            if codec.identity_wire:
                src = state[b] if cfg.delay else buf
            else:
                wire = state[b] if cfg.delay else jax.vmap(enc)(buf)
                src = jax.vmap(
                    lambda x, n_blocks=n_blocks, dtype=buf.dtype:
                    codec.decode(x, dtype, n_blocks=n_blocks,
                                 block_rows=pack_spec.block_rows))(wire)
            srcs.append(src)
            if cfg.delay:
                new_state.append(buf if codec.identity_wire
                                 else jax.vmap(enc)(buf))
        metrics, tcontrib = self._stacked_metrics_init(alive, gates)
        if cfg.screen == "norm_clip":
            w = (gossip._static_weight_table(spec)
                 if alive is None and gates is None
                 else gossip.alive_weight_table(spec, alive, gates))

            def sq(x):
                return jnp.sum(mix_ops.packed_sqnorms(
                    x, block_rows=pack_spec.block_rows, impl=cfg.mix_impl))

            s2 = sum(jax.vmap(sq)(buf) for buf in fresh)        # (n,)
            r2_src = sum(jax.vmap(sq)(src) for src in srcs)     # (n,)
            lim = jnp.float32(cfg.clip_tau) ** 2 * s2
            clip = jnp.stack([_clip_factors(r2_src[g], lim)
                              for g in gathers], axis=1)        # (n, S)
            # clip multiplies the post-renormalization received columns
            # only — the table already carries the alive/gates renorm and
            # the dead-self identity fallback, both untouched here
            eff = jnp.concatenate([w[:, :1], w[:, 1:] * clip], axis=1)
            if tel is not None and tel.clip:
                counts = jnp.zeros(spec.n_clients, jnp.int32)
                for s, g in enumerate(gathers):
                    flag = ((clip[:, s] < 1.0)
                            & (w[:, 1 + s] > 0.0)).astype(jnp.int32)
                    counts = counts.at[g].add(flag)
                metrics["clipped"] = counts

            def mixer(buf, recv):
                return _weighted_rows(eff, buf, recv)
        else:  # trimmed_mean
            raw, contrib = gossip.raw_contrib_tables(spec, alive, gates)
            trim_u = jnp.maximum(raw, 0.0) * contrib
            trim_live = (contrib > 0.0).astype(jnp.float32)

            def mixer(buf, recv):
                return jax.vmap(
                    lambda st, uu, ll: mix_ops.gossip_mix_trimmed_packed(
                        st, uu, ll, trim=cfg.trim_f,
                        block_rows=pack_spec.block_rows,
                        impl=cfg.mix_impl))(
                    jnp.stack([buf] + recv, axis=1), trim_u,
                    trim_live).astype(buf.dtype)
        resid = jnp.zeros((spec.n_clients,), jnp.float32)
        vsq = jax.vmap(self._sq(pack_spec))
        out_bufs = []
        for b, buf in enumerate(fresh):
            # self row stays the FRESH full-precision buffer; only the
            # gathered neighbor rows go through the codec / the snapshot
            recv = [jnp.take(srcs[b], idx, axis=0) for idx in gathers]
            out_bufs.append(mixer(buf, recv))
            if tel is not None and tel.consensus:
                for s, g in enumerate(recv):
                    resid = resid + tcontrib[:, 1 + s] * vsq(
                        g.astype(jnp.float32) - buf.astype(jnp.float32))
        if tel is not None and tel.consensus:
            metrics["resid_sqnorm"] = resid
        mixed = jax.vmap(lambda bs: packing.unpack_tree(bs, pack_spec))(
            tuple(out_bufs))
        ret = (mixed,)
        if cfg.delay:
            ret = ret + (tuple(new_state),)
        if tel is not None:
            ret = ret + (metrics,)
        return ret[0] if len(ret) == 1 else ret

    def _stacked_round_clipped_quant(self, tree, state, alive, gates,
                                     pack_spec):
        """Fused quantized norm_clip on the stacked substrate: the int8
        wires are GATHERED, never decoded — the clip norms come straight off
        the wire (``wire_sqnorm``: per-block sum(q^2) x scale^2, exact for
        what the mix would dequantize) and each receiver folds its received
        wires through the same per-wire fused ``dequant_accumulate_2d``
        pass the shard_map cell uses, with the clip riding the per-sender
        weight operand. One arithmetic path for the quantized norm_clip
        screen across both packed substrates; only trimmed_mean still
        decodes-then-gathers here (its order statistics need the whole
        dequantized stack — see the ROADMAP design record)."""
        from repro.kernels.gossip_mix import ops as mix_ops

        cfg, codec, spec = self.config, self.codec, self.spec
        tel = cfg.telemetry
        gathers = [jnp.asarray(rf) for rf in spec.recv_from]
        fresh = jax.vmap(lambda t: packing.pack_tree(t, pack_spec))(tree)
        wires, new_state = [], []
        s2 = jnp.zeros((spec.n_clients,), jnp.float32)
        r2 = jnp.zeros((spec.n_clients,), jnp.float32)
        for b, buf in enumerate(fresh):
            n_blocks = pack_spec.buffer_blocks(b)

            def enc(x, n_blocks=n_blocks):
                return codec.encode(x, n_blocks=n_blocks,
                                    block_rows=pack_spec.block_rows,
                                    impl=cfg.mix_impl)

            wire = state[b] if cfg.delay else jax.vmap(enc)(buf)
            wires.append(wire)
            if cfg.delay:
                new_state.append(jax.vmap(enc)(buf))
            s2 = s2 + jax.vmap(lambda x: jnp.sum(mix_ops.packed_sqnorms(
                x, block_rows=pack_spec.block_rows,
                impl=cfg.mix_impl)))(buf)
            r2 = r2 + jax.vmap(
                lambda x, n_blocks=n_blocks: codec.wire_sqnorm(
                    x, n_blocks=n_blocks, block_rows=pack_spec.block_rows,
                    impl=cfg.mix_impl))(wire)
        lim = jnp.float32(cfg.clip_tau) ** 2 * s2                    # (n,)
        clip = (jnp.stack([_clip_factors(r2[g], lim) for g in gathers],
                          axis=1)
                if gathers else jnp.zeros((spec.n_clients, 0), jnp.float32))
        # pre-renormalization tables: codec.reduce applies the same
        # per-client renorm + dead-self identity fallback as the shard_map
        # cell (fixed points stay invisible through the contrib zeros)
        raw, contrib = gossip.raw_contrib_tables(spec, alive, gates)
        metrics = {}
        if tel is not None:
            if tel.degree:
                metrics["in_degree"] = jnp.sum(contrib[:, 1:], axis=1)
                metrics["sched_contrib"] = contrib[:, 1:]
            if tel.clip:
                w = gossip.alive_weight_table(spec, alive, gates)
                counts = jnp.zeros(spec.n_clients, jnp.int32)
                for s, g in enumerate(gathers):
                    flag = ((clip[:, s] < 1.0)
                            & (w[:, 1 + s] > 0.0)).astype(jnp.int32)
                    counts = counts.at[g].add(flag)
                metrics["clipped"] = counts
            if tel.consensus:
                # the consensus proxy is the ONE telemetry metric this cell
                # pays real extra compute for: the fused path never decodes
                # the gathered wires, so residuals dequantize them here
                vsq = jax.vmap(self._sq(pack_spec))
                resid = jnp.zeros((spec.n_clients,), jnp.float32)
                for b, buf in enumerate(fresh):
                    n_blocks = pack_spec.buffer_blocks(b)
                    dec = jax.vmap(
                        lambda x, n_blocks=n_blocks, dtype=buf.dtype:
                        codec.decode(x, dtype, n_blocks=n_blocks,
                                     block_rows=pack_spec.block_rows))(
                        wires[b])
                    for s, g in enumerate(gathers):
                        resid = resid + contrib[:, 1 + s] * vsq(
                            jnp.take(dec, g, axis=0).astype(jnp.float32)
                            - buf.astype(jnp.float32))
                metrics["resid_sqnorm"] = resid
        out_bufs = []
        for b, buf in enumerate(fresh):
            n_blocks = pack_spec.buffer_blocks(b)
            recv = [jnp.take(wires[b], g, axis=0) for g in gathers]

            def red(fb, rw, cv, cl, *rs, n_blocks=n_blocks):
                return codec.reduce(
                    fb, list(rs), rw, cv,
                    edge_weight=float(spec.edge_weight), n_blocks=n_blocks,
                    block_rows=pack_spec.block_rows, impl=cfg.mix_impl,
                    sender_scale=cl)

            out_bufs.append(jax.vmap(red)(buf, raw, contrib, clip, *recv)
                            .astype(buf.dtype))
        mixed = jax.vmap(lambda bs: packing.unpack_tree(bs, pack_spec))(
            tuple(out_bufs))
        ret = (mixed,)
        if cfg.delay:
            ret = ret + (tuple(new_state),)
        if tel is not None:
            ret = ret + (metrics,)
        return ret[0] if len(ret) == 1 else ret

    def _blocked_round(self, tree, alive, gates):
        """The massive-client round: ``tree`` is this device's (block, ...)
        stacked slice inside a shard_map island over the 1-D client device
        axis. Intra-block edges are plain stacked gathers; every cross-block
        partial device permutation in ``self.blocked.transfers`` ships ONE
        whole (block, rows, 128) wire buffer via ppermute, and each client
        gathers its source row out of the [local + received] candidate stack
        through the static ``gather_flat`` table (sliced to this device by
        ``axis_index``). The final weighted reduction is the stacked
        substrate's multiply-add (:func:`_weighted_rows`) over the
        device-local rows of the SAME ``alive_weight_table`` — f32 cells are
        bit-identical to the stacked reference on the same overlay, and
        alive / active-set / gate churn stays plain data.

        Telemetry (when on) reads the device-local (block,) rows of the
        contributor table and measures residuals off the ALREADY-gathered
        candidate rows — zero extra collectives, asserted by the HLO
        guards in tests/test_telemetry.py. The island's out_spec over the
        client device axis concatenates the per-device rows back to the
        (n,)-stacked layout."""
        cfg, codec, spec = self.config, self.codec, self.spec
        tel = cfg.telemetry
        bs = self.blocked
        pack_spec = self.pack_spec or gossip._stacked_pack_spec(tree)
        b_sz = bs.block
        row0 = gossip._client_index(self.axis_names) * b_sz
        w = gossip.alive_weight_table(spec, alive, gates)       # (n, S+1)
        w_local = jax.lax.dynamic_slice(w, (row0, 0), (b_sz, w.shape[1]))
        idx_tab = jnp.asarray(bs.gather_flat, jnp.int32)        # (S, n)
        fresh = jax.vmap(lambda t: packing.pack_tree(t, pack_spec))(tree)
        metrics, tcontrib_local = {}, None
        if tel is not None:
            _, tcontrib = gossip.raw_contrib_tables(spec, alive, gates)
            tcontrib_local = jax.lax.dynamic_slice(
                tcontrib, (row0, 0), (b_sz, tcontrib.shape[1]))
            if tel.degree:
                metrics["in_degree"] = jnp.sum(tcontrib_local[:, 1:], axis=1)
                metrics["sched_contrib"] = tcontrib_local[:, 1:]
        resid = jnp.zeros((b_sz,), jnp.float32)
        vsq = jax.vmap(self._sq(pack_spec))
        out_bufs = []
        for b, buf in enumerate(fresh):
            n_blocks = pack_spec.buffer_blocks(b)

            def enc(x, n_blocks=n_blocks):
                return codec.encode(x, n_blocks=n_blocks,
                                    block_rows=pack_spec.block_rows,
                                    impl=cfg.mix_impl)

            wire = buf if codec.identity_wire else jax.vmap(enc)(buf)
            # all whole-block permutes issued before any gather so XLA can
            # overlap the wire; devices outside a partial permutation
            # receive zeros, which no gather table entry ever points at
            received = [jax.lax.ppermute(wire, self.axis_names, perm=list(t))
                        for t in bs.transfers]
            cand = jnp.concatenate([wire[None]] + [r[None] for r in received],
                                   axis=0)
            flat = cand.reshape((bs.n_transfers + 1) * b_sz, *wire.shape[1:])
            if not codec.identity_wire:
                flat = jax.vmap(
                    lambda x, n_blocks=n_blocks, dtype=buf.dtype:
                    codec.decode(x, dtype, n_blocks=n_blocks,
                                 block_rows=pack_spec.block_rows))(flat)
            srcs = [jnp.take(flat,
                             jax.lax.dynamic_slice(idx_tab[s], (row0,),
                                                   (b_sz,)), axis=0)
                    for s in range(spec.degree)]
            # self row stays the FRESH full-precision buffer; only the
            # gathered neighbor rows go through the codec wire
            out_bufs.append(_weighted_rows(w_local, buf, srcs))
            if tel is not None and tel.consensus:
                # residuals off the already-gathered rows: the telemetry
                # build ships the exact same permutes as the metrics-off one
                for s, g in enumerate(srcs):
                    resid = resid + tcontrib_local[:, 1 + s] * vsq(
                        g.astype(jnp.float32) - buf.astype(jnp.float32))
        if tel is not None and tel.consensus:
            metrics["resid_sqnorm"] = resid
        mixed = jax.vmap(lambda bso: packing.unpack_tree(bso, pack_spec))(
            tuple(out_bufs))
        if tel is not None:
            return mixed, metrics
        return mixed

    def _per_leaf_round(self, tree):
        cfg, codec, spec = self.config, self.codec, self.spec
        idx = gossip._client_index(self.axis_names)
        self_w = jnp.asarray(spec.self_weights)[idx]
        perms = [list(pairs) for pairs in spec.perms if len(pairs) > 0]

        def _mix(x):
            parts = codec.encode_leaf(x, cfg.mix_impl)
            received = [
                codec.decode_leaf(
                    tuple(jax.lax.ppermute(part, self.axis_names, perm=p)
                          for part in parts), x.dtype, cfg.mix_impl)
                for p in perms
            ]
            out = self_w.astype(x.dtype) * x
            c = jnp.asarray(spec.edge_weight, dtype=x.dtype)
            for r in received:
                out = out + c * r
            return out

        return jax.tree.map(_mix, tree)


def build_gossip_executor(config: GossipEngineConfig, spec: GossipSpec, *,
                          axis_names=None,
                          pack_spec: packing.PackSpec | None = None
                          ) -> GossipExecutor:
    """Assemble one gossip executor from an engine cell.

    ``axis_names`` names the client mesh axis/axes and is required on the
    ``shard_map`` / ``per_leaf`` / ``blocked`` substrates (the executor is
    called inside the fully-manual island; for ``blocked`` the axis indexes
    DEVICES, each holding ``config.block`` clients); the stacked / dense
    substrates run on a client-stacked pytree and ignore it. Pass
    ``pack_spec`` (built host-side from shape structs — the PER-CLIENT
    slice spec on stacked/blocked) to bake the packed layout into the
    jitted step; it is derived from the tree at call time otherwise. On
    ``blocked`` the schedule partition (:func:`gossip.make_blocked_spec`)
    is baked here, host-side, once per (spec, block).
    """
    if (config.substrate in ("shard_map", "per_leaf", "blocked")
            and axis_names is None):
        raise ValueError(f"substrate {config.substrate!r} runs inside "
                         "shard_map and needs axis_names")
    blocked = (gossip.make_blocked_spec(spec, config.block)
               if config.substrate == "blocked" else None)
    return GossipExecutor(config=config, spec=spec, axis_names=axis_names,
                          pack_spec=pack_spec, blocked=blocked)
