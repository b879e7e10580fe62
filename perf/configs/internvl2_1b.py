"""InternVL2-1B under DFedAvgM, one client per chip, gossip on a ring.

The timed path is the production step: ``launch.steps.build_train_step``
on ``launch.mesh.make_production_mesh()`` (one client per chip through
``derive_dfl_mesh``), and every round is one call of its ``step_fn``: the
K local steps of every client with the fused sgdm kernel, then the
``shard_map`` gossip engine (d collective permutes of the packed buffer
into the mix kernel). Each round's batch is drawn on the device from
(seed, round) by a jitted function outside the step, and the round's mean
loss is fetched to the host, as a trainer that logs does. Weights come
from the seed, every client from one draw, with the QKV biases, norm
scales and projector biases drawn away from zero and one so that a
reference without them differs.

Set-up drives rounds 0-2 through ``step_fn`` itself; after the window the
plain reference (``internvl2_1b_ref``, float32 at ``HIGHEST``) repeats them
from the same weights and batches on its own ring, drawn as the paper's
section 4 draws one.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from perf import compare
from perf.configs import char_lstm, char_lstm_ref
from perf.configs import internvl2_1b_ref as ref

CHECKED_ROUNDS = 3
LANE, PACK_BLOCK_ROWS, SGDM_BLOCK_ROWS = 128, 256, 256
# kernel instruction names in the device trace (``name=`` of the calls)
MIX_KERNEL = r"^gossip_mix(_alive)?\b"
SGDM_KERNEL = r"^fused_sgdm\b"


def model_config(cfg: dict):
    """The program's registry entry with this configuration's widths."""
    from repro.configs import registry
    return dataclasses.replace(
        registry.get(cfg["arch"]), n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_kv_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["d_ff"], vocab=cfg["vocab"], rope_theta=cfg["rope_theta"],
        qkv_bias=cfg["qkv_bias"], stub_prefix=cfg["image_tokens"],
        vision_feature_dim=cfg["vision_feature_dim"], dtype=cfg["dtype"])


def padded_vocab(cfg: dict) -> int:
    return (cfg["vocab"] + 127) // 128 * 128


def layout(cfg: dict) -> dict:
    """One client's parameter tree in the program's layout:
    {path: (shape, mean, std)}."""
    d, f, v = cfg["d_model"], cfg["d_ff"], padded_vocab(cfg)
    n, h, kv, hd = (cfg["n_layers"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    fv = cfg["vision_feature_dim"]
    return {
        "embed": ((v, d), 0.0, 0.02),
        "head": ((d, v), 0.0, d ** -0.5),
        "final_norm": ((d,), 1.0, 0.1),
        "blocks/ln1": ((n, d), 1.0, 0.1),
        "blocks/ln2": ((n, d), 1.0, 0.1),
        "blocks/wq": ((n, d, h, hd), 0.0, d ** -0.5),
        "blocks/wk": ((n, d, kv, hd), 0.0, d ** -0.5),
        "blocks/wv": ((n, d, kv, hd), 0.0, d ** -0.5),
        "blocks/wo": ((n, h, hd, d), 0.0, (h * hd) ** -0.5),
        "blocks/bq": ((n, h, hd), 0.0, 0.5),
        "blocks/bk": ((n, kv, hd), 0.0, 0.5),
        "blocks/bv": ((n, kv, hd), 0.0, 0.5),
        "blocks/w_gate": ((n, d, f), 0.0, d ** -0.5),
        "blocks/w_up": ((n, d, f), 0.0, d ** -0.5),
        "blocks/w_down": ((n, f, d), 0.0, f ** -0.5),
        "vision_proj/ln_scale": ((fv,), 1.0, 0.1),
        "vision_proj/ln_bias": ((fv,), 0.0, 0.1),
        "vision_proj/w1": ((fv, d), 0.0, fv ** -0.5),
        "vision_proj/b1": ((d,), 0.0, 0.1),
        "vision_proj/w2": ((d, d), 0.0, d ** -0.5),
        "vision_proj/b2": ((d,), 0.0, 0.1),
    }


def draw_weights(cfg: dict, key, n_clients: int = 0):
    """One client's weights in the configuration's dtype from ``key``
    (traced); with ``n_clients``
    every client holds that same draw on a leading axis."""
    import jax
    import jax.numpy as jnp

    out: dict = {}
    for i, (path, (shape, mean, std)) in enumerate(sorted(layout(cfg).items())):
        w = (mean + std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                            jnp.float32)).astype(cfg["dtype"])
        if n_clients:
            w = jnp.broadcast_to(w, (n_clients,) + shape)
        *heads, leaf = path.split("/")
        node = out
        for k in heads:
            node = node.setdefault(k, {})
        node[leaf] = w
    return out


# ------------------------------------------------------------ counts
def tokens_per_round(cfg: dict, t: dict) -> int:
    """Label positions of all clients in a round (the text positions)."""
    return (t["clients"] * t["local_steps"] * t["batch"]
            * (t["seq"] - cfg["image_tokens"]))


def flops_per_round(cfg: dict, t: dict) -> float:
    """Model FLOPs of a round over all clients, forward and backward, no
    recompute: 6 x the block matmul parameters x every position, 6 x the
    head x the label positions, 6 x the projector's matmuls x the image
    positions, and attention's two products (3 x 2 x S^2 x heads x hd a
    layer and sequence: the causal half of 2 x 2 x S^2 x heads x hd)."""
    d, f, n = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    qd = cfg["n_heads"] * cfg["head_dim"]
    kvd = cfg["n_kv_heads"] * cfg["head_dim"]
    s, img = t["seq"], cfg["image_tokens"]
    seqs = t["clients"] * t["local_steps"] * t["batch"]
    block = d * (qd + 2 * kvd) + qd * d + 3 * d * f
    proj = cfg["vision_feature_dim"] * d + d * d
    return float(6 * n * block * seqs * s
                 + 6 * d * cfg["vocab"] * seqs * (s - img)
                 + 6 * proj * seqs * img
                 + 3 * 2 * s * s * qd * n * seqs)


def _padded(size: int, block_rows: int) -> int:
    tile = block_rows * LANE
    return (size + tile - 1) // tile * tile


def client_elements(cfg: dict) -> list[int]:
    return [math.prod(shape) for shape, _, _ in layout(cfg).values()]


def mix_kernel_round(cfg: dict, t: dict) -> tuple[int, float, float]:
    """(calls, bytes, FLOPs) of the mix kernel on one chip in a round, from
    its operand shapes: the (d+1, rows, 128) stack of the packed bf16
    buffer read, the (rows, 128) result written, (d+1, 1) f32 weights and
    alive vectors; a multiply and an add per contributor and element."""
    k = t["degree"] + 1
    elems = _padded(sum(client_elements(cfg)), PACK_BLOCK_ROWS)
    nbytes = (k + 1) * elems * 2 + 2 * k * 4
    return 1, float(nbytes), float(2 * k * elems)


def sgdm_kernel_round(cfg: dict, t: dict) -> tuple[int, float, float]:
    """(calls, bytes, FLOPs) of the fused sgdm kernel on one chip in a
    round: one call per leaf per local step, each reading w, v and g and
    writing w and v, (rows, 128) bf16 padded to 256-row tiles, plus its
    (1, 2) f32 scalars; 4 FLOPs an element (beta v, lr g, their
    difference, w + v)."""
    elems = [_padded(e, SGDM_BLOCK_ROWS) for e in client_elements(cfg)]
    k = t["local_steps"]
    return (k * len(elems), float(k * sum(5 * 2 * e + 8 for e in elems)),
            float(k * 4 * sum(elems)))


KERNELS = {"gossip_mix": (MIX_KERNEL, mix_kernel_round),
           "fused_sgdm": (SGDM_KERNEL, sgdm_kernel_round)}


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed32: int, spans):
        self.cfg, self.t, self.seed32, self.spans = cfg, traffic, seed32, spans
        # a program without the projector or the biases stops here
        self.model = model_config(cfg)
        self.tokens_per_round = tokens_per_round(cfg, traffic)
        self.flops_per_round = flops_per_round(cfg, traffic)

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.configs.base import DFLConfig, ParallelConfig, ShapeConfig
        from repro.launch import mesh as mesh_lib
        from repro.launch import steps
        from repro.models.params import Leaf

        cfg, t = self.cfg, self.t
        with self.spans.span("setup.build"):
            mesh = mesh_lib.make_production_mesh()
            n = mesh.shape["data"]
            if n != t["clients"]:
                raise ValueError(f"the cell holds one client per chip: "
                                 f"{t['clients']} clients, {n} devices")
            shape = ShapeConfig("cell", t["seq"], n * t["batch"], "train")
            par = ParallelConfig(
                clients_per_pod=n, tp=1, local_steps=t["local_steps"],
                grad_accum=t["grad_accum"], gossip_codec=t["codec"],
                gossip_delay=t["delay"])
            dfl = DFLConfig(topology=t["topology"], degree=t["degree"],
                            seed=t["overlay_seed"], lr=t["lr"],
                            momentum=t["momentum"])
            st = steps.build_train_step(self.model, shape, mesh, par, dfl)
        p_shard, b_shard = st.in_shardings[0], st.in_shardings[1]
        repl = NamedSharding(st.dfl_mesh, PartitionSpec())
        self.program_adjacency = char_lstm.schedule_adjacency(
            st.overlay.schedules, n)
        d = st.gossip_spec.degree
        bspec = st.input_specs["batch"]
        key = jax.random.key(self.seed32)

        weights = jax.jit(lambda k: draw_weights(cfg, k, n),
                          out_shardings=p_shard)

        def draw_batch(key, rnd):
            k1, k2 = jax.random.split(jax.random.fold_in(key, rnd))
            tok = bspec["tokens"].shape
            seq = jax.random.randint(k1, tok[:-1] + (tok[-1] + 1,), 0,
                                     cfg["vocab"], jnp.int32)
            pe = bspec["prefix_embeds"]
            return {"tokens": seq[..., :-1], "labels": seq[..., 1:],
                    "prefix_embeds": jax.random.normal(
                        k2, pe.shape, jnp.float32).astype(pe.dtype)}

        batch_fn = jax.jit(draw_batch, out_shardings=b_shard)
        ones = jax.jit(lambda: (jnp.ones((n,), jnp.float32),
                                jnp.ones((d,), jnp.float32)),
                       out_shardings=(repl, repl))
        lr = jax.device_put(jnp.float32(t["lr"]), repl)
        bkey = jax.random.fold_in(key, 1)

        def one_round(params, rnd):
            batch = batch_fn(bkey, jnp.int32(rnd))
            params, met = st.step_fn(params, batch, lr, *ones())
            return params, float(met["loss"]), batch

        with self.spans.span("setup.weights"):
            p0 = weights(key)
            params = weights(key)
            jax.block_until_ready(params)
        is_leaf = lambda x: isinstance(x, Leaf)  # noqa: E731
        want = jax.tree.map(lambda l: (l.shape, l.dtype), st.param_struct,
                            is_leaf=is_leaf)
        got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), p0)
        if got != want:
            raise ValueError("the program's parameter tree differs from the "
                             "configuration's layout")
        self.fed, losses = [], []
        with self.spans.span("setup.checked_rounds"):
            for rnd in range(CHECKED_ROUNDS):
                params, loss, batch = one_round(params, rnd)
                losses.append(loss)
                self.fed.append(jax.device_get(batch))
                if rnd == 0:
                    d1 = compare.leaf_diff_norms(params, p0)
            self.got = {"losses": losses, "step1": d1,
                        "step3": compare.leaf_diff_norms(params, p0)}
        del p0
        self.params, self.one_round = params, one_round

    def rounds(self, on_round) -> None:
        """Runs rounds through ``step_fn`` until ``on_round``, called as each
        round starts, raises."""
        import jax

        params, self.params = self.params, None
        rnd = CHECKED_ROUNDS
        try:
            while True:
                on_round()
                params, _, _ = self.one_round(params, rnd)
                rnd += 1
        finally:  # the check after the window needs the chips' memory
            jax.tree.map(lambda a: a.delete(), params)

    def release(self) -> None:
        self.params = self.one_round = None

    # ------------------------------------------------------------- check
    def reference(self, fault: str | None = None, low: bool = False) -> dict:
        """What the reference, from the same weights and batches on its own
        ring, gives for the numbers that ``setup`` took. ``fault`` plants
        one of ``internvl2_1b_ref.FAULTS``; ``low`` computes the softmax and
        the cross-entropy in bfloat16 (the control)."""
        import jax

        cfg, t = self.cfg, self.t
        key = jax.random.key(self.seed32)
        one = jax.jit(lambda k: draw_weights(cfg, k))

        def p0_fn(device):
            return one(jax.device_put(key, device))

        with jax.default_matmul_precision("highest"):
            return ref.run(p0_fn, self.fed, self.mixing(), CHECKED_ROUNDS,
                           cfg["vocab"], cfg["rope_theta"], t["lr"],
                           t["momentum"], t["grad_accum"], fault, low)

    def overlay(self) -> np.ndarray:
        t = self.t
        return char_lstm_ref.overlay_adjacency(t["topology"], t["clients"],
                                               t["degree"], t["overlay_seed"])

    def mixing(self) -> np.ndarray:
        return char_lstm_ref.chow_mixing(self.overlay())

    def check(self) -> dict:
        """{name: value} of each number compared: the program's readings
        against the float32 reference's, and the count of entries (ordered
        client pairs) in which the program's ring and the reference's
        differ."""
        edges = int(np.count_nonzero(self.program_adjacency != self.overlay()))
        return dict(self.gaps(self.got, self.reference()),
                    overlay_edges_differing=edges)

    gaps = staticmethod(char_lstm.Cell.gaps)


def build(cfg: dict, traffic: dict, seed32: int, spans) -> Cell:
    return Cell(cfg, traffic, seed32, spans)
