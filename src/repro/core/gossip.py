"""Gossip semantics: specs, dense oracles, and the legacy executor surface.

This module owns the *meaning* of a mixing round `w <- M w`:

* :class:`GossipSpec` — the static, hashable round description baked into
  the jitted step (schedules as ppermute pairs + recv_from gather tables +
  Chow weights);
* the dense oracles (``mix_dense``, ``mix_dense_masked``,
  ``mix_dense_gated``, ``mix_dense_delayed``) and the gather reference
  ``mix_schedules`` — the ground truth every executor is tested against;
* the ONE shared weight path (:func:`alive_weight_table` /
  :func:`gated_mixing_matrix` and the per-client local forms
  ``_local_raw_weights`` / ``_local_contrib_vec``) that turns traced
  ``alive`` masks and per-schedule ``gates`` into renormalized mixing
  weights for every variant.

The executors themselves are assembled by :mod:`repro.core.engine` from
three orthogonal layers — WireCodec (f32 / int8 / int8_block wire format)
x timing (sync / one-round-delayed pipeline) x substrate (shard_map
ppermute island / stacked simulator / per-leaf baseline / dense) — and the
seven pre-engine entry points below (``ppermute_mix``,
``ppermute_mix_quantized``, ``ppermute_mix_packed``,
``ppermute_mix_packed_quantized``, ``ppermute_mix_packed_delayed``,
``mix_packed_stacked``, ``mix_packed_stacked_delayed``) are thin aliases
that each name one engine cell. New compositions (e.g. pipelined +
quantized: ``delay=1 x int8``) need no new executor code — build them with
``engine.build_gossip_executor`` directly.

Failure awareness (paper §5.2) lives on the packed paths: the packed
executors (and the stacked :func:`mix_packed_stacked` simulator counterpart)
take an optional *traced* ``alive`` vector with :func:`mix_dense_masked`
semantics — dead clients neither send nor update, survivors renormalize over
their live in-degree. Because the mask is a step argument rather than spec
structure, straggler churn never retraces the jitted step (see
``alive_weight_table``); the per-leaf ppermute baselines and
``mix_schedules`` deliberately do NOT take a mask (use the packed paths).

Time-varying overlays (the overlay lab, :mod:`repro.overlay.plan`) ride the
same design: the packed executors take an optional traced ``gates`` vector —
one float per *schedule* — that multiplies each schedule's edge weight before
the very same renormalization. A gate of 0 removes the schedule from the
round's mixing matrix (its ppermute still runs and contributes weight zero),
so one-peer rotation, random schedule subsets, and throttled rounds are all
plain data through one executable. Gates compose with ``alive``: contributor
weight = gate[schedule] x alive[sender]. For 0/1 gates the fused reduction
matches :func:`mix_dense_gated` bit-for-bit in f32 on one-peer rounds (see
its docstring for the exact scope; 0/1 factors are exact in floating point).

Pipelined (one-round-delayed) gossip rides on top of the packed engine: the
``*_delayed`` executors mix this round's *fresh* local-step output with the
**previous** round's packed snapshot, carried across rounds as donated step
state. Because the snapshot is a step *input*, its d ppermutes have no data
dependency on the local-step scan and XLA's latency-hiding scheduler can run
the wire transfer under the whole scan — per-round wall-clock becomes
max(compute, comm) instead of compute + comm (asynchronous decentralized SGD
in the style of overlap-SGP). :func:`mix_dense_delayed` is the dense oracle
pinning the semantics; ``gossip_delay=0`` keeps the synchronous executors
untouched (bit-identical).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.topology import Overlay

__all__ = [
    "GossipSpec",
    "make_gossip_spec",
    "BlockedSpec",
    "make_blocked_spec",
    "alive_weight_table",
    "raw_contrib_tables",
    "gated_mixing_matrix",
    "mix_dense",
    "mix_dense_masked",
    "mix_dense_gated",
    "mix_dense_delayed",
    "mix_schedules",
    "mix_packed_stacked",
    "mix_packed_stacked_delayed",
    "pack_state_stacked",
    "ppermute_mix",
    "ppermute_mix_quantized",
    "ppermute_mix_packed",
    "ppermute_mix_packed_delayed",
    "ppermute_mix_packed_quantized",
]

PyTree = Any


@dataclasses.dataclass(frozen=True)
class GossipSpec:
    """Static gossip description (hashable => usable as a jit static arg).

    Attributes:
      n_clients: number of clients on the gossip axis.
      perms: per schedule, a tuple of (src, dst) pairs for ppermute — i.e.
        data flows src -> dst, where dst's mixing row has weight edge_weight at
        column src. Fixed points are excluded here and folded into self_weights.
      recv_from: per schedule, tuple of length n_clients: recv_from[s][i] is
        the client whose params client i receives under schedule s (i itself
        for fixed points). Used by the stacked-gather executor.
      self_weights: per-client diagonal weight (w0 + edge_weight * #fixed).
      edge_weight: the uniform Chow edge weight c.
      lam: lambda(M) of the mixing matrix (for reports).
      live_masks: per schedule, tuple of 0/1 per client: 1 iff the client
        receives from a *different* client under that schedule (i.e. it is not
        a fixed point). Derived host-side from recv_from so the stacked-gather
        executor never recomputes ``idx != arange(n)`` per (leaf x schedule).
    """

    n_clients: int
    perms: tuple[tuple[tuple[int, int], ...], ...]
    recv_from: tuple[tuple[int, ...], ...]
    self_weights: tuple[float, ...]
    edge_weight: float
    lam: float
    live_masks: tuple[tuple[int, ...], ...] | None = None

    def __post_init__(self):
        if self.live_masks is None:
            masks = tuple(
                tuple(int(src != i) for i, src in enumerate(rf))
                for rf in self.recv_from)
            object.__setattr__(self, "live_masks", masks)

    @property
    def degree(self) -> int:
        return len(self.perms)

    def fixed_masks_np(self) -> np.ndarray:
        """(S, n) 0/1: schedule s has a fixed point at client i (host-side)."""
        if self.degree == 0:
            return np.zeros((0, self.n_clients), np.float32)
        return 1.0 - np.asarray(self.live_masks, np.float32)

    def base_self_weights_np(self) -> np.ndarray:
        """(n,) self weights *without* the fixed-point edge folding — the w0
        each gated fixed point's c must be re-added to (gate pathway)."""
        fixed_counts = self.fixed_masks_np().sum(axis=0)
        return (np.asarray(self.self_weights, np.float32)
                - np.float32(self.edge_weight) * fixed_counts)


def make_gossip_spec(overlay: Overlay, theta: float | None = None) -> GossipSpec:
    """Bake an Overlay + Chow weights into a static GossipSpec."""
    w = overlay.chow_weights(theta)
    n = overlay.n
    perms = []
    recv_from = []
    fixed_counts = np.zeros(n, dtype=np.int64)
    for s in overlay.schedules:
        pairs = tuple(
            (int(s[i]), int(i)) for i in range(n) if int(s[i]) != i
        )  # i receives FROM s[i]: src=s[i], dst=i
        perms.append(pairs)
        recv_from.append(tuple(int(s[i]) for i in range(n)))
        fixed_counts += (s == np.arange(n)).astype(np.int64)
    self_w = tuple(float(w.self_weight + w.edge_weight * fixed_counts[i]) for i in range(n))
    return GossipSpec(
        n_clients=n,
        perms=tuple(perms),
        recv_from=tuple(recv_from),
        self_weights=self_w,
        edge_weight=float(w.edge_weight),
        lam=float(w.lam),
    )


# ---------------------------------------------------- blocked schedule split
@dataclasses.dataclass(frozen=True)
class BlockedSpec:
    """Static plan for the ``blocked`` substrate: n = n_devices x block
    clients, client ``i`` living on device ``i // block`` at stacked row
    ``i % block`` (hashable => usable as a jit static arg).

    Each overlay schedule is partitioned at build time into its intra-block
    part (a gather on the device-local stacked axis — free) and its
    cross-block part. A cross-block schedule's device-level demand graph
    ("device d needs device s's wire block") decomposes into *partial device
    permutations*; each becomes ONE ``ppermute`` of the whole per-device
    ``(block, rows, 128)`` wire buffer. The unit of transfer is the block,
    not the client: a schedule whose cross edges touch one neighbor device
    costs exactly one collective regardless of how many of its B clients
    cross (on a 2-device mesh every cross schedule is a single swap, so the
    collective count in HLO equals the number of cross-block schedules).

    Attributes:
      block: B, clients per device.
      n_devices: n_clients // block.
      transfers: flat tuple over ALL schedules' partial permutations —
        ``transfers[t]`` is the ppermute pair list ``((src_dev, dst_dev),
        ...)``. Not deduplicated across schedules (XLA CSE merges identical
        ppermutes of the same wire post-lowering; keeping them per-schedule
        keeps the slot bookkeeping local).
      schedule_transfers: per schedule, the global transfer ids it owns
        (empty for intra-block schedules).
      gather_flat: (S, n) int: for schedule s and client i, the flat index
        ``slot * block + src_row`` into the candidate stack
        ``concat([local_wire] + received_wires)`` reshaped to
        ``((T+1) * block, rows, 128)`` — slot 0 is the device's own wire,
        slot t+1 the block received by global transfer t.
    """

    block: int
    n_devices: int
    transfers: tuple[tuple[tuple[int, int], ...], ...]
    schedule_transfers: tuple[tuple[int, ...], ...]
    gather_flat: tuple[tuple[int, ...], ...]

    @property
    def n_transfers(self) -> int:
        return len(self.transfers)

    @property
    def cross_schedules(self) -> int:
        """How many schedules have at least one cross-block edge."""
        return sum(1 for t in self.schedule_transfers if t)


def _partition_demand(edges: list[tuple[int, int]]
                      ) -> list[tuple[tuple[int, int], ...]]:
    """Greedy split of a device-level demand edge set into partial
    permutations (no device sends or receives twice within one part)."""
    parts: list[list[tuple[int, int]]] = []
    for s, d in sorted(edges):
        for part in parts:
            if all(s != ps and d != pd for ps, pd in part):
                part.append((s, d))
                break
        else:
            parts.append([(s, d)])
    return [tuple(p) for p in parts]


def make_blocked_spec(spec: GossipSpec, block: int) -> BlockedSpec:
    """Partition a GossipSpec's schedules for B-clients-per-device execution.

    Host-side, O(S * n). Requires ``block`` to divide ``n_clients``; the
    resulting plan assumes row-major client placement (client i on device
    ``i // block``), which is what a ``P("clients")`` sharding of the stacked
    axis produces under shard_map.
    """
    n, b = spec.n_clients, int(block)
    if b < 1 or n % b:
        raise ValueError(
            f"blocked substrate needs block >= 1 dividing n_clients; got "
            f"block={block} for n_clients={n}")
    transfers: list[tuple[tuple[int, int], ...]] = []
    schedule_transfers: list[tuple[int, ...]] = []
    gather_flat: list[tuple[int, ...]] = []
    for rf in spec.recv_from:
        demand = sorted({(src // b, i // b)
                         for i, src in enumerate(rf) if src // b != i // b})
        parts = _partition_demand(list(demand))
        ids = tuple(range(len(transfers), len(transfers) + len(parts)))
        # slot of each cross (src_dev, dst_dev) pair within THIS schedule
        slot_of = {pair: 1 + ids[t] for t, part in enumerate(parts)
                   for pair in part}
        row = []
        for i, src in enumerate(rf):
            pair = (src // b, i // b)
            slot = 0 if pair[0] == pair[1] else slot_of[pair]
            row.append(slot * b + src % b)
        transfers.extend(parts)
        schedule_transfers.append(ids)
        gather_flat.append(tuple(row))
    return BlockedSpec(
        block=b,
        n_devices=n // b,
        transfers=tuple(transfers),
        schedule_transfers=tuple(schedule_transfers),
        gather_flat=tuple(gather_flat),
    )


# ----------------------------------------------------------------- executors
def mix_dense(tree: PyTree, m: jax.Array | np.ndarray) -> PyTree:
    """Reference: out_c = sum_d M[c, d] x_d over the leading (client) axis."""
    m = jnp.asarray(m)

    def _mix(x):
        flat = x.reshape(x.shape[0], -1)
        out = jnp.einsum("cd,df->cf", m.astype(flat.dtype), flat,
                         precision=jax.lax.Precision.HIGHEST)
        return out.reshape(x.shape)

    return jax.tree.map(_mix, tree)


def mix_dense_masked(tree: PyTree, m: jax.Array | np.ndarray,
                     alive: jax.Array | np.ndarray) -> PyTree:
    """Failure-aware dense mixing (paper §5.2 semantics).

    Dead clients neither send nor update. Each surviving row renormalizes over
    its alive in-neighbors (incl. itself); dead rows keep their parameters.
    """
    m = jnp.asarray(m, dtype=jnp.float32)
    alive = jnp.asarray(alive, dtype=jnp.float32)
    masked = m * alive[None, :]  # zero dead senders
    row = masked.sum(axis=1, keepdims=True)
    renorm = masked / jnp.maximum(row, 1e-12)
    # dead receivers: identity row (they keep their params)
    eye = jnp.eye(m.shape[0], dtype=jnp.float32)
    eff = alive[:, None] * renorm + (1.0 - alive[:, None]) * eye
    return mix_dense(tree, eff)


def alive_weight_table(spec: GossipSpec, alive: jax.Array | None,
                       gates: jax.Array | None = None) -> jax.Array:
    """Renormalized mixing weights under (traced) alive + gate vectors:
    (n, S+1).

    Column 0 is the self weight, column 1+s the weight applied to the payload
    received under schedule s. Rows match ``mix_dense_gated`` exactly: each
    schedule's edge weight is scaled by its gate, dead senders are zeroed,
    each surviving row renormalizes over its gated alive in-neighborhood
    (incl. itself), and dead receivers get the identity row. A gated fixed
    point re-enters the self weight through the gate (the full-permutation
    convention: gate g_s scales P_s including its diagonal), so gating a
    schedule off is exactly removing it from the overlay. Both vectors are
    data, not structure — recomputing this table every round costs a few
    n x (S+1) vector ops and never retraces the step.
    """
    n, s_count = spec.n_clients, spec.degree
    alive = (jnp.ones(n, jnp.float32) if alive is None
             else jnp.asarray(alive, jnp.float32))
    if gates is None:
        self_w = jnp.asarray(spec.self_weights, jnp.float32)
        gates = jnp.ones(s_count, jnp.float32)
    else:
        gates = jnp.asarray(gates, jnp.float32)
        fixed = jnp.asarray(spec.fixed_masks_np())
        # clamp: dense overlays can have a *negative* Chow self weight
        # (w0 = 1 - c*S < 0 when lam_max(L) < 2S/(1+theta)); a gated subset
        # of such a row has no valid renormalization, so the gated path
        # projects onto the nonnegative (lazy) variant
        self_w = jnp.maximum(
            jnp.asarray(spec.base_self_weights_np())
            + spec.edge_weight * jnp.sum(gates[:, None] * fixed, axis=0), 0.0)
    cols = [spec.edge_weight * gates[s] * jnp.asarray(mask, jnp.float32)
            * jnp.take(alive, jnp.asarray(rf))
            for s, (rf, mask) in enumerate(zip(spec.recv_from,
                                               spec.live_masks))]
    ws = (jnp.stack(cols, axis=1) if cols else jnp.zeros((n, 0), jnp.float32))
    wa = jnp.concatenate([(self_w * alive)[:, None], ws], axis=1)
    tot = jnp.sum(wa, axis=1)
    # rows with no renormalizable mass (everything gated off / clamped
    # away) fall back to the identity INSTEAD of the renormalized weights
    # (inv is zeroed, not eps-clamped, so near-zero fractional mass cannot
    # leak a second, non-stochastic copy of the row on top of the fallback)
    ok = tot > 1e-12
    inv = jnp.where(ok, 1.0 / jnp.maximum(tot, 1e-12), 0.0)
    eff = alive[:, None] * wa * inv[:, None]
    fallback = (1.0 - alive) + alive * (1.0 - ok)
    return eff.at[:, 0].add(fallback)


def raw_contrib_tables(spec: GossipSpec, alive: jax.Array | None,
                       gates: jax.Array | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Stacked-substrate mirror of ``_local_raw_weights`` /
    ``_local_contrib_vec``: the pre-renormalization pieces of
    :func:`alive_weight_table`, vectorized over clients.

    Returns ``(raw, contrib)``, both (n, S+1). ``raw`` holds the unnormalized
    Chow weights — column 0 the (gated-clamped) self weight, columns 1+s the
    uniform edge weight c. ``contrib`` holds the per-contributor
    participation weights — column 0 the client's own liveness, column 1+s
    ``gate_s x live_mask_s x sender-liveness`` (zero at fixed points, so a
    schedule that delivers nothing is invisible). The trimmed-mean screen
    consumes these directly: ``contrib > 0`` decides who enters the order
    statistics and ``max(raw, 0) * contrib`` weighs the survivors; the
    renormalized product reproduces :func:`alive_weight_table` rows (minus
    the identity-fallback fold, which screens re-apply themselves).
    """
    n, s_count = spec.n_clients, spec.degree
    alive_v = (jnp.ones(n, jnp.float32) if alive is None
               else jnp.asarray(alive, jnp.float32))
    if gates is None:
        self_w = jnp.asarray(spec.self_weights, jnp.float32)
        gates_v = jnp.ones(s_count, jnp.float32)
    else:
        gates_v = jnp.asarray(gates, jnp.float32)
        fixed = jnp.asarray(spec.fixed_masks_np())
        # same clamp as alive_weight_table: a gated subset of a negative-w0
        # row projects onto the nonnegative (lazy) variant
        self_w = jnp.maximum(
            jnp.asarray(spec.base_self_weights_np())
            + spec.edge_weight * jnp.sum(gates_v[:, None] * fixed, axis=0),
            0.0)
    raw = jnp.concatenate(
        [self_w[:, None],
         jnp.full((n, s_count), spec.edge_weight, jnp.float32)], axis=1)
    cols = [gates_v[s] * jnp.asarray(mask, jnp.float32)
            * jnp.take(alive_v, jnp.asarray(rf))
            for s, (rf, mask) in enumerate(zip(spec.recv_from,
                                               spec.live_masks))]
    contrib = jnp.concatenate(
        [alive_v[:, None]] + [c[:, None] for c in cols], axis=1)
    return raw, contrib


def gated_mixing_matrix(spec: GossipSpec, gates: jax.Array | None = None,
                        alive: jax.Array | None = None) -> jax.Array:
    """Effective (row-stochastic) n x n mixing matrix under gates + alive.

    The dense oracle for the gated/masked packed executors: rows are the
    :func:`alive_weight_table` weights scattered to their sender columns, so
    for 0/1 gates and masks the scalar weights match the fused kernels'
    renormalization bit-for-bit in f32 (same op order, and 0/1 factors are
    exact). Traceable — ``gates``/``alive`` stay step data under jit.
    """
    n = spec.n_clients
    table = alive_weight_table(spec, alive, gates)
    m = jnp.zeros((n, n), jnp.float32)
    idx = jnp.arange(n)
    m = m.at[idx, idx].set(table[:, 0])
    for s, rf in enumerate(spec.recv_from):
        m = m.at[idx, jnp.asarray(rf)].add(table[:, 1 + s])
    return m


def mix_dense_gated(tree: PyTree, spec: GossipSpec,
                    gates: jax.Array | None = None,
                    alive: jax.Array | None = None) -> PyTree:
    """Dense reference for time-varying (gated) + failure-masked mixing.

    The reduction is an explicit multiply-then-sum (not a dot/einsum, whose
    FMA accumulation rounds differently), so with 0/1 gates and masks the
    packed executors reproduce this oracle **bit-for-bit in f32 whenever a
    row has at most two live contributors** (one-peer rotation: self + one
    sender; the remaining terms are exact zeros and f32 addition is
    commutative). With three or more live contributors the dense row (sender
    order) and the stacked round (schedule order) sum in different orders and
    may differ in the last ulp — compare with allclose there.
    """
    m = gated_mixing_matrix(spec, gates, alive)

    def _mix(x):
        flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
        out = jnp.sum(m[:, :, None] * flat[None, :, :], axis=1)
        return out.astype(x.dtype).reshape(x.shape)

    return jax.tree.map(_mix, tree)


def mix_dense_delayed(fresh: PyTree, delayed: PyTree, spec: GossipSpec,
                      gates: jax.Array | None = None,
                      alive: jax.Array | None = None) -> PyTree:
    """Dense oracle for one-round-delayed (pipelined) gossip.

    Row i combines its own **fresh** value (this round's post-local-step
    params) with its neighbors' **delayed** values (their post-local-step
    params from the *previous* round, the in-flight snapshot)::

        out_i = w_i0 * fresh_i + sum_s w_i,1+s * delayed[recv_from[s][i]]

    with the exact :func:`alive_weight_table` weights — the self column
    (incl. folded fixed-point edge weight) always applies to the fresh value,
    matching the packed executors where fixed-point schedules deliver zeros.
    With ``delayed == fresh`` this is the synchronous gated/masked mixing,
    so delay is purely a data-staleness change, never a weight change. The
    reduction is an explicit multiply-then-sum in schedule order, so for 0/1
    gates/masks it matches the packed delayed executors with the same
    bit-for-bit scope as :func:`mix_dense_gated`.
    """
    table = alive_weight_table(spec, alive, gates)
    gathers = [jnp.asarray(rf) for rf in spec.recv_from]

    def _mix(xf, xd):
        ff = xf.reshape(xf.shape[0], -1).astype(jnp.float32)
        fd = xd.reshape(xd.shape[0], -1).astype(jnp.float32)
        out = table[:, 0][:, None] * ff
        for s, idx in enumerate(gathers):
            out = out + table[:, 1 + s][:, None] * jnp.take(fd, idx, axis=0)
        return out.astype(xf.dtype).reshape(xf.shape)

    return jax.tree.map(_mix, fresh, delayed)


def _static_weight_table(spec: GossipSpec) -> jax.Array:
    """All-alive weight table (host-side constant): (n, S+1)."""
    w0 = np.asarray(spec.self_weights, np.float32)[:, None]
    if spec.degree == 0:
        return jnp.asarray(w0)
    ws = np.stack([spec.edge_weight * np.asarray(m, np.float32)
                   for m in spec.live_masks], axis=1)
    return jnp.asarray(np.concatenate([w0, ws], axis=1))


def mix_schedules(tree: PyTree, spec: GossipSpec) -> PyTree:
    """Stacked-axis executor of the schedule decomposition (gather-based).

    out = self_weights * x + c * sum_s [recv_from[s] != id] * x[recv_from[s]]
    — fixed points contribute nothing here because their weight is already
    folded into self_weights (same arithmetic as the ppermute path, so this
    serves as its oracle).
    """
    self_w = jnp.asarray(spec.self_weights)
    # per-schedule gather indices and live masks, built once (host-side spec
    # data), shared across every leaf instead of recomputed per (leaf x sched)
    gathers = [(jnp.asarray(rf), jnp.asarray(mask, jnp.float32))
               for rf, mask in zip(spec.recv_from, spec.live_masks)]

    def _mix(x):
        w = self_w.astype(x.dtype).reshape((-1,) + (1,) * (x.ndim - 1))
        out = w * x
        for idx, mask in gathers:
            live = mask.astype(x.dtype).reshape((-1,) + (1,) * (x.ndim - 1))
            out = out + jnp.asarray(spec.edge_weight, dtype=x.dtype) * live * jnp.take(
                x, idx, axis=0)
        return out

    return jax.tree.map(_mix, tree)


def mix_packed_stacked(tree: PyTree, spec: GossipSpec,
                       alive: jax.Array | None = None, *,
                       gates: jax.Array | None = None,
                       pack_spec: packing.PackSpec | None = None) -> PyTree:
    """Stacked-axis packed executor — the simulator counterpart of
    :func:`ppermute_mix_packed` and the mixing path of the elastic runtime.

    Each schedule becomes one gather on the stacked axis of every leaf, and
    each leaf's weighted reduction is one f32 multiply-add over its own row
    and its gathered neighbour rows, with no packed copy of the state and
    no ``(n, S+1, ...)`` stack. With ``alive`` (a *traced* ``(n,)`` 0/1 vector)
    the reduction uses the renormalized masked weights of
    :func:`alive_weight_table`, so straggler-set changes are plain data and
    never retrace the enclosing jit; ``gates`` (a traced per-schedule float
    vector, :mod:`repro.overlay.plan`) makes the round time-varying the same
    way — one-peer rotation and schedule subsets are weight changes, not new
    executables.

    Engine cell: ``stacked x f32 x sync`` (:mod:`repro.core.engine`).
    """
    from repro.core import engine as engine_lib

    ex = engine_lib.build_gossip_executor(
        engine_lib.GossipEngineConfig(substrate="stacked", codec="f32"),
        spec, pack_spec=pack_spec)
    return ex(tree, alive=alive, gates=gates)


def _stacked_pack_spec(tree: PyTree) -> packing.PackSpec:
    """PackSpec of the client-stacked tree's per-client slice."""
    return packing.make_stacked_pack_spec(tree)


def pack_state_stacked(tree: PyTree,
                       pack_spec: packing.PackSpec | None = None
                       ) -> tuple[jax.Array, ...]:
    """Pack a client-stacked pytree into per-dtype ``(n, rows, 128)``
    snapshot buffers — the in-flight state of the delayed (pipelined) gossip
    round. Used once to prime the pipeline (round 0 mixes the *initial*
    params as its delayed snapshot) and by the delayed executors every round.
    The layout depends only on the parameter structure, never on the
    topology, so a splice repair remaps the snapshot by the same ``old2new``
    row permutation as the params (see ``launch/elastic.py``)."""
    if pack_spec is None:
        pack_spec = _stacked_pack_spec(tree)
    return jax.vmap(lambda t: packing.pack_tree(t, pack_spec))(tree)


def mix_packed_stacked_delayed(tree: PyTree,
                               snapshot: tuple[jax.Array, ...],
                               spec: GossipSpec,
                               alive: jax.Array | None = None, *,
                               gates: jax.Array | None = None,
                               pack_spec: packing.PackSpec | None = None
                               ) -> tuple[PyTree, tuple[jax.Array, ...]]:
    """Stacked-axis pipelined gossip: the simulator / elastic-runtime
    counterpart of :func:`ppermute_mix_packed_delayed`.

    ``tree`` is this round's fresh post-local-step state; ``snapshot`` is the
    previous round's :func:`pack_state_stacked` output (what is "on the
    wire"). Each schedule gathers from the *snapshot* while the self term
    stays fresh — :func:`mix_dense_delayed` semantics, with the same
    alive/gates weight table as the synchronous path. Returns the mixed tree
    and the new snapshot (this round's packed fresh state), to be carried as
    step state. With ``snapshot == pack_state_stacked(tree)`` the result is
    bit-identical to :func:`mix_packed_stacked` (the same f32 multiply-add
    over the same rows, on the packed buffers here and on the leaves there).

    Engine cell: ``stacked x f32 x delayed`` (:mod:`repro.core.engine`).
    """
    from repro.core import engine as engine_lib

    ex = engine_lib.build_gossip_executor(
        engine_lib.GossipEngineConfig(substrate="stacked", codec="f32",
                                      delay=1),
        spec, pack_spec=pack_spec)
    return ex(tree, state=snapshot, alive=alive, gates=gates)


def _client_index(axis_names: str | tuple[str, ...]) -> jax.Array:
    """Flattened client index over (possibly) multiple mesh axes, row-major."""
    if isinstance(axis_names, str):
        return jax.lax.axis_index(axis_names)
    idx = jax.lax.axis_index(axis_names[0])
    for name in axis_names[1:]:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def ppermute_mix(tree: PyTree, spec: GossipSpec,
                 axis_names: str | tuple[str, ...]) -> PyTree:
    """Production gossip: one collective-permute per schedule (call in shard_map).

    Every leaf holds the *local shard* of the local client's value; the client
    axis is the mesh axis/axes in ``axis_names``. All ppermutes are issued
    before any sums so XLA can overlap them.

    Engine cell: ``per_leaf x f32 x sync`` (:mod:`repro.core.engine`).
    """
    from repro.core import engine as engine_lib

    ex = engine_lib.build_gossip_executor(
        engine_lib.GossipEngineConfig(substrate="per_leaf", codec="f32"),
        spec, axis_names=axis_names)
    return ex(tree)


def ppermute_mix_quantized(tree: PyTree, spec: GossipSpec,
                           axis_names: str | tuple[str, ...]) -> PyTree:
    """Beyond-paper: gossip with int8-quantized payloads (4x/2x fewer ICI bytes).

    Each leaf is symmetrically quantized per-tensor to int8 with an f32 scale;
    neighbors dequantize before the weighted sum. The *local* term stays full
    precision, so quantization error only enters through the (small) edge
    weights.

    Engine cell: ``per_leaf x int8 x sync`` (:mod:`repro.core.engine`).
    """
    from repro.core import engine as engine_lib

    ex = engine_lib.build_gossip_executor(
        engine_lib.GossipEngineConfig(substrate="per_leaf", codec="int8"),
        spec, axis_names=axis_names)
    return ex(tree)


# ------------------------------------------------------- packed executors
def _live_schedules(spec: GossipSpec):
    """(schedule idx, perm pairs, recv_from, live_mask) for schedules with
    any exchange (the index keys this schedule's entry in a gate vector)."""
    return [(s, list(pairs), rf, mask)
            for s, (pairs, rf, mask) in enumerate(zip(spec.perms,
                                                      spec.recv_from,
                                                      spec.live_masks))
            if len(pairs) > 0]


def _local_raw_weights(spec: GossipSpec, idx: jax.Array, n_live: int,
                       gates: jax.Array | None = None) -> jax.Array:
    """This client's *unnormalized* Chow weights (w0, c, ..., c): (d+1,).

    With ``gates``, the self weight follows the full-permutation convention:
    each schedule's fixed-point contribution c re-scales by its gate, so
    gating a schedule off removes it from the mixing matrix entirely.
    """
    if gates is None:
        self_w = jnp.asarray(spec.self_weights)[idx].astype(jnp.float32)
    else:
        fixed = jnp.asarray(spec.fixed_masks_np())
        # clamped like alive_weight_table: a gated subset of a negative-w0
        # row projects onto the nonnegative (lazy) variant
        self_w = jnp.maximum(
            jnp.asarray(spec.base_self_weights_np())[idx]
            + spec.edge_weight
            * jnp.sum(jnp.asarray(gates, jnp.float32) * fixed[:, idx]), 0.0)
    return jnp.concatenate([
        self_w[None], jnp.full((n_live,), spec.edge_weight, jnp.float32)])


def _local_contrib_vec(spec: GossipSpec, idx: jax.Array, live,
                       alive: jax.Array | None,
                       gates: jax.Array | None) -> jax.Array:
    """Per-contributor weights for the renormalized fused reduction: (d+1,).

    Entry 0 is this client's own liveness; entry 1+k the k-th schedule's
    gate x sender-liveness (zero at fixed points). Renormalization over the
    gated live in-degree happens inside the fused kernel. The sender's
    liveness is a *gather from the replicated alive vector* via the static
    recv_from table, and the gate a gather from the replicated gate vector —
    neither costs extra collectives.
    """
    one = jnp.float32(1.0)
    alive = None if alive is None else jnp.asarray(alive, jnp.float32)
    gates = None if gates is None else jnp.asarray(gates, jnp.float32)
    srcs = []
    for s, _, rf, mask in live:
        v = jnp.asarray(mask, jnp.float32)[idx]
        if gates is not None:
            v = gates[s] * v
        if alive is not None:
            v = v * alive[jnp.asarray(rf)[idx]]
        srcs.append(v)
    return jnp.stack([one if alive is None else alive[idx]] + srcs)


def ppermute_mix_packed(tree: PyTree, spec: GossipSpec,
                        axis_names: str | tuple[str, ...], *,
                        pack_spec: packing.PackSpec | None = None,
                        mix_impl: str = "auto",
                        alive: jax.Array | None = None,
                        gates: jax.Array | None = None) -> PyTree:
    """Packed production gossip: d collectives/round, one fused HBM reduction.

    The client-local pytree packs into one lane-aligned flat buffer per dtype
    (:mod:`repro.core.packing`); each schedule then permutes the *whole*
    buffer in a single ``lax.ppermute`` — d collectives per round regardless
    of leaf count, vs d x n_leaves for :func:`ppermute_mix`. Self + the d
    received buffers stack to ``(d+1, rows, 128)`` and reduce in **one** HBM
    pass through the fused ``gossip_mix_2d`` Pallas kernel (interpret/ref off
    TPU). Fixed-point schedules deliver zeros (ppermute semantics), which the
    kernel's weighted sum absorbs — same arithmetic as the per-leaf path.

    ``alive`` (a traced, replicated ``(n_clients,)`` 0/1 vector) makes the
    round failure-aware with :func:`mix_dense_masked` semantics: dead senders
    are masked out of the reduction (their weight gathers to zero from the
    replicated vector — no extra collectives), each survivor renormalizes
    over its live in-degree inside the fused kernel, and a dead client keeps
    its own parameters. Because ``alive`` is data, straggler churn never
    retraces the step.

    ``gates`` (a traced, replicated per-schedule float vector,
    :mod:`repro.overlay.plan`) makes the round *time-varying* through the
    identical mechanism: each schedule's contributor weight scales by its
    gate before the in-kernel renormalization, so one-peer rotation,
    schedule subsets, and throttled rounds reuse this one executable with
    zero retraces. All d ppermutes still run — a gated-off schedule's
    payload lands with weight exactly 0 — keeping liveness AND the round
    plan out of trace structure.

    Pass ``pack_spec`` (built host-side from shape structs) to bake the
    layout into the jitted step; it is derived from ``tree`` otherwise.

    Engine cell: ``shard_map x f32 x sync`` (:mod:`repro.core.engine`) —
    pinned to lower to HLO textually identical to the pre-refactor body.
    """
    from repro.core import engine as engine_lib

    ex = engine_lib.build_gossip_executor(
        engine_lib.GossipEngineConfig(substrate="shard_map", codec="f32",
                                      mix_impl=mix_impl),
        spec, axis_names=axis_names, pack_spec=pack_spec)
    return ex(tree, alive=alive, gates=gates)


def ppermute_mix_packed_delayed(tree: PyTree,
                                state_bufs: tuple[jax.Array, ...],
                                spec: GossipSpec,
                                axis_names: str | tuple[str, ...], *,
                                pack_spec: packing.PackSpec | None = None,
                                mix_impl: str = "auto",
                                alive: jax.Array | None = None,
                                gates: jax.Array | None = None
                                ) -> tuple[PyTree, tuple[jax.Array, ...]]:
    """Pipelined packed gossip (``gossip_delay=1``): d collectives/round on
    the *previous* round's snapshot, overlapped with this round's compute.

    ``state_bufs`` is the carried in-flight state: the per-device packed
    buffers of last round's post-local-step shard tree (this function's
    second return value, primed with the initial params). Each schedule
    ppermutes the **snapshot**, not the fresh buffer — the permutes' operand
    is a step *input*, so they have no data dependency on the local-step
    scan that produced ``tree`` and XLA's async collectives
    (permute-start/permute-done) run the wire transfer under the scan. The
    fused ``gossip_mix_2d`` reduction then combines the fresh self buffer
    with the d delayed received buffers using the *identical* raw-weight /
    alive / gates operands as :func:`ppermute_mix_packed` — delay changes
    which round's bytes are on the wire, never the mixing weights
    (:func:`mix_dense_delayed` is the oracle). Feeding
    ``state_bufs == pack_tree(tree)`` reproduces the synchronous executor
    bit-for-bit, which is the delay=0 regression anchor.

    Returns ``(mixed tree, new state_bufs)`` where the new state is this
    round's fresh packed buffers (what round t+1 will mix).

    Engine cell: ``shard_map x f32 x delayed`` (:mod:`repro.core.engine`).
    """
    from repro.core import engine as engine_lib

    ex = engine_lib.build_gossip_executor(
        engine_lib.GossipEngineConfig(substrate="shard_map", codec="f32",
                                      delay=1, mix_impl=mix_impl),
        spec, axis_names=axis_names, pack_spec=pack_spec)
    return ex(tree, state=state_bufs, alive=alive, gates=gates)


def ppermute_mix_packed_quantized(tree: PyTree, spec: GossipSpec,
                                  axis_names: str | tuple[str, ...], *,
                                  pack_spec: packing.PackSpec | None = None,
                                  impl: str = "auto",
                                  alive: jax.Array | None = None,
                                  gates: jax.Array | None = None,
                                  block_scales: bool = True) -> PyTree:
    """Packed gossip with int8 wire payloads (4x/2x fewer ICI bytes).

    The packed buffer quantizes once through the Pallas quantize kernel,
    and the f32 scales are **folded into the shipped int8 buffer** as
    trailing lane rows (:func:`~repro.kernels.quant_gossip.ops.
    fold_scales_into_wire`), so each schedule ships exactly **one**
    collective — d per round, down from the 2d payload+scale pairs this
    path used to issue. Every received wire buffer splits back into
    (int8 payload, scales) with static slices and folds into the
    accumulator through the fused ``dequant_accumulate_2d`` kernel family
    (dequant + scale + add in one HBM pass per neighbor). The local term
    stays full precision, so the int8 error only enters through the
    (small) edge weights.

    ``block_scales`` (default) quantizes with **one scale per row-block
    kernel tile** instead of per buffer: a tile of small-magnitude
    parameters (norm gains, biases) no longer inherits the quantization
    step of the buffer-wide amax, which closes the PR-1 follow-up. The
    scales ride the same wire buffer (32 per lane row), so the collective
    count is unchanged; ``block_scales=False`` keeps the PR-3 per-buffer
    format.

    ``alive`` has :func:`mix_dense_masked` semantics and ``gates``
    (per-schedule floats) the time-varying semantics, both exactly as in
    :func:`ppermute_mix_packed`: the renormalizing denominator is a handful
    of scalar ops, the self term is rescaled up front, and each sender's
    renormalized gate x alive weight rides into its fused
    dequant-accumulate pass — masked or gated rounds do the same HBM
    traffic as plain ones.

    Engine cell: ``shard_map x int8_block x sync`` (``int8`` with
    ``block_scales=False``; :mod:`repro.core.engine`).
    """
    from repro.core import engine as engine_lib

    ex = engine_lib.build_gossip_executor(
        engine_lib.GossipEngineConfig(
            substrate="shard_map",
            codec="int8_block" if block_scales else "int8", mix_impl=impl),
        spec, axis_names=axis_names, pack_spec=pack_spec)
    return ex(tree, alive=alive, gates=gates)
