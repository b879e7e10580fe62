"""The paper's char-LSTM under DFedAvgM on the stacked substrate.

The timed path is the train CLI's: ``repro.launch.train.build_char_lm``
builds the ``SimTrainer``, and every round goes through ``SimTrainer.run``
with a ``batch_fn`` made as the CLI makes it (the program's
``TokenBatcher`` over its bundled corpus, then ``jnp.asarray``). The
overlay is the deployment's and stays fixed (``overlay_seed``; its mixing
weights are constants of the compiled round, so a graph drawn from the
run's seed would compile anew in every run); the reference draws it anew
from that seed and compares it with the program's, edge by edge. The data
and the weights come from the run's seed. The harness makes the weights,
drives the first rounds through that same call for the check, and hands
the same trainer to the window.
"""
from __future__ import annotations

import numpy as np

from perf import compare
from perf.configs import char_lstm_ref as ref

CHECKED_ROUNDS = 3


def flops_per_token(cfg: dict) -> float:
    """Model FLOPs of one training position, forward and backward (3x the
    forward's matrix products; no recompute, elementwise work left out)."""
    e, h, v, n = cfg["d_embed"], cfg["d_hidden"], cfg["vocab"], cfg["n_layers"]
    h_in = max(e, h)
    forward = 2 * e * h_in + 2 * n * (h_in + h) * 4 * h + 2 * h * v
    return 3.0 * forward


def layout(cfg: dict) -> dict:
    """The program's parameter tree of one client: {path: (shape, std)};
    std 0 means zeros."""
    e, h, v, n = cfg["d_embed"], cfg["d_hidden"], cfg["vocab"], cfg["n_layers"]
    h_in = max(e, h)
    return {
        "embed": ((v, e), 0.05),
        "proj_in": ((e, h_in), e ** -0.5),
        "layers/wx": ((n, h_in, 4 * h), h_in ** -0.5),
        "layers/wh": ((n, h, 4 * h), h ** -0.5),
        "layers/b": ((n, 4 * h), 0.0),
        "head": ((h, v), h ** -0.5),
    }


def make_weights(cfg: dict, n_clients: int, seed32: int):
    """Every client's f32 weights, made on the device in one jitted call:
    all clients start from one draw (the DFL convention)."""
    import jax
    import jax.numpy as jnp

    lay = layout(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (path, (shape, std)) in enumerate(sorted(lay.items())):
            w = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                   jnp.float32) * std)
            w = jnp.broadcast_to(w, (n_clients,) + shape)
            node = out
            *heads, leaf = path.split("/")
            for k in heads:
                node = node.setdefault(k, {})
            node[leaf] = w
        return out

    return make(jax.random.key(seed32))


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed32: int, spans):
        self.cfg, self.t, self.seed32, self.spans = cfg, traffic, seed32, spans
        t = traffic
        self.tokens_per_round = (t["clients"] * t["local_steps"] * t["batch"]
                                 * t["seq"])
        self.flops_per_round = self.tokens_per_round * flops_per_token(cfg)

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.data import federated, pipeline, shakespeare
        from repro.launch import train

        t, seed = self.t, self.seed32
        with self.spans.span("setup.build"):
            app = train.build_char_lm(
                n_clients=t["clients"], topology=t["topology"],
                degree=t["degree"], local_steps=t["local_steps"],
                batch=t["batch"], seq=t["seq"], lr=t["lr"],
                momentum=t["momentum"], seed=t["overlay_seed"],
                gossip_codec=t["codec"])
            self.trainer = app.trainer
            toks, _ = shakespeare.corpus()
            batcher = pipeline.TokenBatcher(
                tokens=toks, spans=federated.span_split(
                    len(toks), t["clients"], seed=seed),
                batch_size=t["batch"], seq_len=t["seq"],
                local_steps=t["local_steps"], seed=seed)

        def batch_fn(rnd):
            b = batcher.round_batches(rnd)
            return {"tokens": jnp.asarray(b["tokens"]),
                    "labels": jnp.asarray(b["labels"])}

        self.batch_fn = batch_fn
        with self.spans.span("setup.weights"):
            p0 = make_weights(self.cfg, t["clients"], seed)
            jax.block_until_ready(p0)
        same = (jax.tree.structure(p0) == jax.tree.structure(app.params)
                and all(a.shape == b.shape and a.dtype == b.dtype
                        for a, b in zip(jax.tree.leaves(p0),
                                        jax.tree.leaves(app.params))))
        if not same:
            raise ValueError("the program's parameter tree differs from the "
                             "configuration's layout")
        del app
        self.program_adjacency = schedule_adjacency(
            self.trainer.overlay.schedules, t["clients"])
        self.fed: list = []

        def feed(rnd):
            with self.spans.span("batch_fn"):
                b = self.batch_fn(rnd)
            if len(self.fed) < CHECKED_ROUNDS:
                self.fed.append(b)
            return b

        self.lr = lambda rnd: t["lr"]
        with self.spans.span("setup.checked_rounds"):
            p1, h1 = self.trainer.run(p0, feed, 1, self.lr)
            d1 = compare.leaf_diff_norms(p1, p0)
            p3, h3 = self.trainer.run(p1, feed, CHECKED_ROUNDS, self.lr,
                                      start_round=1)
            self.got = {"losses": [h["train_loss"] for h in h1 + h3],
                        "step1": d1, "step3": compare.leaf_diff_norms(p3, p0)}
        self.params = p3

    def rounds(self, on_round) -> None:
        """Runs rounds through ``SimTrainer.run`` until ``on_round``, called
        as each round starts, raises."""
        def feed(rnd):
            on_round()
            with self.spans.span("batch_fn"):
                return self.batch_fn(rnd)

        params, self.params = self.params, None
        self.trainer.run(params, feed, 1 << 40, self.lr,
                         start_round=CHECKED_ROUNDS)

    def release(self) -> None:
        self.trainer = self.batch_fn = self.params = None

    # ------------------------------------------------------------- check
    def reference(self, dtype: str = "float32", fault: str | None = None
                  ) -> dict:
        """What the reference, run in ``dtype`` from the same weights and
        feed on its own overlay, gives for the numbers that ``setup`` took.
        ``fault`` plants one of the faults the limits must catch:
        ``unchanged`` (each round returns the state it was given),
        ``half_batch`` (the loss over the first half of each batch) or
        ``no_mix`` (the gossip step left out)."""
        import jax
        import jax.numpy as jnp

        t = self.t
        toks = [np.asarray(b["tokens"]) for b in self.fed]
        labs = [np.asarray(b["labels"]) for b in self.fed]
        for x, y in zip(toks, labs):  # the feed is next-character windows
            if not np.array_equal(x[..., 1:], y[..., :-1]):
                raise ValueError("the feed's labels are not its tokens "
                                 "shifted by one")
        dt = jnp.dtype(dtype)
        m = jnp.asarray(ref.chow_mixing(self.overlay()), jnp.float32)
        if fault == "no_mix":
            m = jnp.eye(t["clients"], dtype=jnp.float32)
        if fault == "half_batch":
            half = t["batch"] // 2
            toks = [x[:, :, :half] for x in toks]
            labs = [y[:, :, :half] for y in labs]
        p0 = make_weights(self.cfg, t["clients"], self.seed32)
        with jax.default_matmul_precision(
                "highest" if dt == jnp.float32 else "default"):
            out = {"grads0": compare.client_norms(
                       ref.first_gradients(p0, toks[0], labs[0])),
                   "losses": []}
            step = ref.make_round(t["lr"], t["momentum"])
            p = jax.tree.map(lambda x: x.astype(dt), p0)
            for r in range(CHECKED_ROUNDS):
                p_in = p
                p, losses = step(p, toks[r], labs[r], m)
                if fault == "unchanged":
                    p = p_in
                out["losses"].append(float(jnp.mean(losses)))
                if r == 0:
                    out["step1"] = compare.leaf_diff_norms(p, p0)
            out["step3"] = compare.leaf_diff_norms(p, p0)
        return out

    def overlay(self) -> np.ndarray:
        t = self.t
        return ref.overlay_adjacency(t["topology"], t["clients"],
                                     t["degree"], t["overlay_seed"])

    def check(self) -> dict:
        """{name: value} of each number compared: the program's readings
        against the float32 reference's, and the count of entries (ordered
        client pairs) in which the program's overlay and the reference's
        differ."""
        edges = int(np.count_nonzero(self.program_adjacency != self.overlay()))
        return dict(self.gaps(self.got, self.reference()),
                    overlay_edges_differing=edges)

    @staticmethod
    def gaps(got: dict, want: dict) -> dict:
        keep = compare.moving_leaves(want["grads0"].ravel())
        out = {"loss_gap": compare.rel_gap(got["losses"], want["losses"])}
        for k in ("step1", "step3"):
            out[k + "_client_change_gap"] = compare.worst_leaf_gap(
                got[k].ravel(), want[k].ravel(), keep)
        return out


def schedule_adjacency(schedules, n: int) -> np.ndarray:
    """Multigraph adjacency of an overlay given as permutations of [n]
    (schedule s sends client i to s[i]; fixed points exchange nothing)."""
    a = np.zeros((n, n))
    for s in schedules:
        for i, j in enumerate(np.asarray(s)):
            if i != j:
                a[i, j] += 1.0
    return a


def build(cfg: dict, traffic: dict, seed32: int, spans) -> Cell:
    return Cell(cfg, traffic, seed32, spans)
