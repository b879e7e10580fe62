"""Elastic runtime integration: stragglers, permanent failure repair, resume.

The elastic trainer now rides the packed gossip engine: the alive mask is a
traced step argument (straggler churn must cause ZERO retraces) and repairs
return the real survivor permutation (per-client state must follow its
owner through the index compaction).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import dfedavg, engine, failures, gossip
from repro.core.topology import expander_overlay
from repro.launch.elastic import ElasticTrainer
from repro.launch.train import SimTrainer


def quad_loss(params, batch):
    loss = jnp.mean(jnp.square(params["w"] - batch["target"]))
    return loss, {}


def _batches(targets, k):
    return {"target": jnp.broadcast_to(targets[:, None],
                                       (targets.shape[0], k, targets.shape[1]))}


def test_elastic_full_lifecycle(tmp_path):
    """Train -> straggler round -> permanent failure -> repair -> resume."""
    n, dim = 12, 4
    r = np.random.default_rng(0)
    targets = jnp.asarray(r.standard_normal((n, dim)), jnp.float32)
    cfg = dfedavg.DFedAvgMConfig(local_steps=2, lr=0.3, momentum=0.5)
    trainer = ElasticTrainer(
        overlay=expander_overlay(n, 4, seed=0), loss_fn=quad_loss, dcfg=cfg,
        ckpt=CheckpointManager(str(tmp_path), save_every=1),
        straggler_rounds=1, failure_rounds=2)
    params = {"w": jnp.zeros((n, dim))}

    # rounds 0-1: all healthy
    for rnd in range(2):
        params, _, _ = trainer.observe_heartbeats(np.ones(n), params)
        params, _losses = trainer.step(params, _batches(targets, 2), 0.3)
        trainer.checkpoint(rnd, params)
    assert trainer.n_clients == n

    # rounds 2-3: client 5 misses heartbeats -> straggler, then dead
    alive = np.ones(n)
    alive[5] = 0
    params, _, old2new = trainer.observe_heartbeats(alive, params)  # straggler
    assert trainer.n_clients == n and old2new is None
    params, _losses = trainer.step(params, _batches(targets, 2), 0.3)

    params, _, old2new = trainer.observe_heartbeats(alive, params)  # dead
    assert trainer.n_clients == n - 1
    assert trainer.repairs and trainer.repairs[0]["dead"] == [5]
    assert params["w"].shape[0] == n - 1
    # the REAL survivor permutation, not an identity map
    assert old2new is not None and old2new[5] == -1
    np.testing.assert_array_equal(
        old2new, np.asarray([0, 1, 2, 3, 4, -1, 5, 6, 7, 8, 9, 10]))

    surv_targets = jnp.concatenate([targets[:5], targets[6:]])
    params, _losses = trainer.step(params, _batches(surv_targets, 2), 0.3)
    trainer.checkpoint(3, params)
    assert bool(jnp.isfinite(params["w"]).all())

    # crash-resume: restore survivors' state from checkpoint
    m = CheckpointManager(str(tmp_path))
    restored, meta = m.restore({"w": jnp.zeros((n - 1, dim))})
    assert meta["n_clients"] == n - 1
    np.testing.assert_allclose(restored["w"], params["w"], rtol=1e-6)


def test_straggler_round_keeps_progress():
    """Straggler rounds must not corrupt the healthy clients' consensus."""
    n, dim = 8, 3
    targets = jnp.zeros((n, dim))
    cfg = dfedavg.DFedAvgMConfig(local_steps=1, lr=0.5, momentum=0.0)
    trainer = ElasticTrainer(overlay=expander_overlay(n, 4, seed=1),
                             loss_fn=quad_loss, dcfg=cfg,
                             straggler_rounds=1, failure_rounds=99)
    params = {"w": jnp.ones((n, dim))}
    alive = np.ones(n)
    alive[0] = 0
    for rnd in range(6):
        params, _, _ = trainer.observe_heartbeats(alive, params)
        params, _ = trainer.step(params, _batches(targets, 1), 0.5)
    # healthy clients converge toward 0 despite the dead neighbor
    healthy = params["w"][1:]
    assert float(jnp.max(jnp.abs(healthy))) < 0.2


def test_straggler_churn_zero_retrace():
    """Any straggler pattern must reuse ONE jitted executable (tentpole
    claim: alive is a step argument, not trace structure)."""
    n, dim = 10, 3
    targets = jnp.zeros((n, dim))
    cfg = dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.0)
    trainer = ElasticTrainer(overlay=expander_overlay(n, 4, seed=0),
                             loss_fn=quad_loss, dcfg=cfg,
                             straggler_rounds=1, failure_rounds=99)
    params = {"w": jnp.ones((n, dim))}
    rng = np.random.default_rng(0)
    for rnd in range(8):
        # different straggler set every round, incl. recoveries + all-healthy
        alive = (rng.random(n) > 0.3).astype(np.float32)
        if rnd == 3:
            alive[:] = 1.0
        params, _, old2new = trainer.observe_heartbeats(alive, params)
        assert old2new is None
        params, _ = trainer.step(params, _batches(targets, 1), 0.2)
    assert trainer.n_traces == 1, trainer.n_traces


def test_repair_retraces_exactly_once():
    """Membership changes re-jit exactly once; the rounds around them don't."""
    n, dim = 10, 3
    targets = jnp.zeros((n, dim))
    cfg = dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.0)
    trainer = ElasticTrainer(overlay=expander_overlay(n, 4, seed=0),
                             loss_fn=quad_loss, dcfg=cfg,
                             straggler_rounds=1, failure_rounds=2)
    params = {"w": jnp.ones((n, dim))}
    alive = np.ones(n)
    for _ in range(3):
        params, _, _ = trainer.observe_heartbeats(alive, params)
        params, _ = trainer.step(params, _batches(targets, 1), 0.2)
    assert trainer.n_traces == 1
    alive[4] = 0  # miss 2 heartbeats -> dead at the second observe
    params, _, _ = trainer.observe_heartbeats(alive, params)
    params, _ = trainer.step(params, _batches(targets, 1), 0.2)
    params, _, old2new = trainer.observe_heartbeats(alive, params)
    assert old2new is not None and trainer.n_clients == n - 1
    targets2 = jnp.zeros((n - 1, dim))
    for _ in range(3):
        params, _, _ = trainer.observe_heartbeats(np.ones(n - 1), params)
        params, _ = trainer.step(params, _batches(targets2, 1), 0.2)
    assert trainer.n_traces == 2, trainer.n_traces  # one per membership


def test_old2new_remaps_client_state_through_death():
    """Regression (was: identity old2new): per-client state must follow its
    owner through the survivor compaction, incl. caller-held state."""
    n, dim = 12, 4
    r = np.random.default_rng(1)
    targets = jnp.asarray(r.standard_normal((n, dim)), jnp.float32)
    cfg = dfedavg.DFedAvgMConfig(local_steps=1, lr=0.1, momentum=0.5)
    trainer = ElasticTrainer(overlay=expander_overlay(n, 4, seed=0),
                             loss_fn=quad_loss, dcfg=cfg,
                             straggler_rounds=1, failure_rounds=2)
    # tag each client's params + an "optimizer state" with its owner id
    params = {"w": jnp.tile(jnp.arange(n, dtype=jnp.float32)[:, None],
                            (1, dim))}
    opt_state = {"slot": jnp.arange(n, dtype=jnp.float32) * 100.0}

    alive = np.ones(n)
    alive[[3, 7]] = 0
    trainer.health.observe(alive)  # first miss: stragglers
    params2, opt2, old2new = trainer.observe_heartbeats(alive, params,
                                                        opt_state)
    assert old2new is not None
    survivors = [i for i in range(n) if i not in (3, 7)]
    np.testing.assert_array_equal(np.asarray(params2["w"][:, 0]),
                                  np.asarray(survivors, np.float32))
    np.testing.assert_array_equal(np.asarray(opt2["slot"]),
                                  np.asarray(survivors, np.float32) * 100.0)
    # the map itself: survivors compacted in order, dead -> -1
    expect = -np.ones(n, np.int64)
    expect[survivors] = np.arange(n - 2)
    np.testing.assert_array_equal(old2new, expect)
    # training continues on the survivors
    surv_targets = jnp.asarray(np.asarray(targets)[survivors])
    params2, _ = trainer.step(params2, _batches(surv_targets, 1), 0.1)
    assert params2["w"].shape[0] == n - 2


def test_health_counters_survive_repair():
    """Regression: a survivor mid-way to straggler/death keeps its missed
    count through the repair remap (was: fresh tracker dropped it)."""
    n = 8
    cfg = dfedavg.DFedAvgMConfig(local_steps=1, lr=0.1, momentum=0.0)
    trainer = ElasticTrainer(overlay=expander_overlay(n, 4, seed=0),
                             loss_fn=quad_loss, dcfg=cfg,
                             straggler_rounds=1, failure_rounds=3)
    params = {"w": jnp.zeros((n, 2))}
    # client 2 dies (3 misses); client 6 is mid-flight (2 misses so far)
    alive = np.ones(n)
    alive[2] = 0
    trainer.health.observe(alive)
    trainer.health.observe(alive)
    alive[6] = 0
    params, _, old2new = trainer.observe_heartbeats(alive, params)
    assert old2new is not None and old2new[2] == -1
    new6 = old2new[6]
    assert trainer.health.missed[new6] == 1         # carried, not reset
    assert new6 in trainer.health.stragglers()
    # one more miss for (old) client 6 -> it is declared dead, solely
    # because its pre-repair counter survived the remap
    alive2 = np.ones(n - 1)
    alive2[new6] = 0
    trainer.health.observe(alive2)
    trainer.health.observe(alive2)
    assert new6 in trainer.health.dead()


def test_elastic_packed_matches_dense_masked_reference():
    """Acceptance: a scripted FailurePlan through the (packed) elastic
    trainer matches a manual loop using the mix_dense_masked oracle."""
    n, dim = 10, 5
    r = np.random.default_rng(2)
    targets = jnp.asarray(r.standard_normal((n, dim)), jnp.float32)
    cfg = dfedavg.DFedAvgMConfig(local_steps=2, lr=0.3, momentum=0.5)
    overlay = expander_overlay(n, 4, seed=3)
    trainer = ElasticTrainer(overlay=overlay, loss_fn=quad_loss, dcfg=cfg,
                             straggler_rounds=1, failure_rounds=99)
    plan = failures.FailurePlan(
        n_clients=n, events=((2, (1,)), (4, (6, 8))))  # stragglers only

    params = {"w": jnp.zeros((n, dim))}
    ref = {"w": jnp.zeros((n, dim))}
    mix_mat = overlay.mixing_matrix()

    def local(p, b):
        def client(pc, bc):
            v = jax.tree.map(jnp.zeros_like, pc)
            pc, _, loss = dfedavg.local_round(pc, v, bc, quad_loss, cfg,
                                             lr=0.3)
            return pc, loss
        return jax.vmap(client)(p, b)

    for rnd in range(6):
        mask = plan.alive_mask(rnd)
        params, _, _ = trainer.observe_heartbeats(mask, params)
        batches = _batches(targets, 2)
        params, _ = trainer.step(params, batches, 0.3)
        ref, _ = local(ref, batches)
        ref = gossip.mix_dense_masked(ref, mix_mat, mask)
        np.testing.assert_allclose(np.asarray(params["w"]),
                                   np.asarray(ref["w"]),
                                   rtol=2e-5, atol=2e-5)
    assert trainer.n_traces == 1


def test_delayed_zero_retrace_under_churn_and_plan():
    """Pipelined trainer (gossip_delay=1): straggler churn AND an active
    one-peer round plan must reuse ONE executable — the in-flight snapshot
    is step state, never trace structure."""
    from repro.overlay.plan import OnePeerPlan

    n, dim = 10, 3
    targets = jnp.zeros((n, dim))
    cfg = dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.0)
    trainer = ElasticTrainer(overlay=expander_overlay(n, 4, seed=0),
                             loss_fn=quad_loss, dcfg=cfg,
                             straggler_rounds=1, failure_rounds=99,
                             engine=engine.GossipEngineConfig(
                                 substrate="stacked", delay=1),
                             plan=OnePeerPlan())
    params = {"w": jnp.ones((n, dim))}
    rng = np.random.default_rng(0)
    for rnd in range(8):
        alive = (rng.random(n) > 0.3).astype(np.float32)
        if rnd == 3:
            alive[:] = 1.0
        params, _, old2new = trainer.observe_heartbeats(alive, params)
        assert old2new is None
        params, _ = trainer.step(params, _batches(targets, 1), 0.2)
    assert trainer.n_traces == 1, trainer.n_traces


def test_delayed_trainer_matches_dense_delayed_reference():
    """Acceptance: the pipelined trainer under scripted straggler churn
    matches a manual loop with the mix_dense_delayed oracle — the delayed
    snapshot is the previous round's post-local-step state, primed with the
    initial params."""
    n, dim = 10, 5
    r = np.random.default_rng(2)
    targets = jnp.asarray(r.standard_normal((n, dim)), jnp.float32)
    cfg = dfedavg.DFedAvgMConfig(local_steps=2, lr=0.3, momentum=0.5)
    overlay = expander_overlay(n, 4, seed=3)
    trainer = ElasticTrainer(overlay=overlay, loss_fn=quad_loss, dcfg=cfg,
                             straggler_rounds=1, failure_rounds=99,
                             engine=engine.GossipEngineConfig(
                                 substrate="stacked", delay=1))
    params = {"w": jnp.asarray(r.standard_normal((n, dim)), jnp.float32)}
    ref = {"w": params["w"]}
    snap = {"w": params["w"]}          # y_{-1} := initial params
    spec = trainer.spec

    def local(p, b):
        def client(pc, bc):
            v = jax.tree.map(jnp.zeros_like, pc)
            pc, _, loss = dfedavg.local_round(pc, v, bc, quad_loss, cfg,
                                              lr=0.3)
            return pc, loss
        return jax.vmap(client)(p, b)

    rng = np.random.default_rng(0)
    for rnd in range(6):
        mask = (rng.random(n) > 0.25).astype(np.float32)
        if mask.sum() < 2:
            mask[:] = 1.0
        params, _, _ = trainer.observe_heartbeats(mask, params)
        batches = _batches(targets, 2)
        params, _ = trainer.step(params, batches, 0.3)
        w, _ = local(ref, batches)
        ref = gossip.mix_dense_delayed(w, snap, spec, None,
                                       jnp.asarray(mask))
        snap = w
        np.testing.assert_allclose(np.asarray(params["w"]),
                                   np.asarray(ref["w"]),
                                   rtol=2e-5, atol=2e-5)
    assert trainer.n_traces == 1


def test_delayed_inflight_survives_repair():
    """The in-flight snapshot must follow the survivors through splice
    repair by the same old2new row compaction as the params (and the step
    after the repair must run on the remapped snapshot)."""
    n, dim = 12, 4
    r = np.random.default_rng(1)
    targets = jnp.asarray(r.standard_normal((n, dim)), jnp.float32)
    cfg = dfedavg.DFedAvgMConfig(local_steps=2, lr=0.1, momentum=0.5)
    trainer = ElasticTrainer(overlay=expander_overlay(n, 4, seed=0),
                             loss_fn=quad_loss, dcfg=cfg,
                             straggler_rounds=1, failure_rounds=2,
                             engine=engine.GossipEngineConfig(
                                 substrate="stacked", delay=1))
    params = {"w": jnp.asarray(r.standard_normal((n, dim)), jnp.float32)}
    params, _ = trainer.step(params, _batches(targets, 2), 0.1)  # primes
    alive = np.ones(n)
    alive[5] = 0
    params, _, old2new = trainer.observe_heartbeats(alive, params)
    assert old2new is None                       # straggler, not dead yet
    params, _ = trainer.step(params, _batches(targets, 2), 0.1)
    pre = [np.asarray(b) for b in trainer._inflight]
    params, _, old2new = trainer.observe_heartbeats(alive, params)  # dead
    assert old2new is not None and old2new[5] == -1
    survivors = np.arange(n) != 5
    for b_pre, b_post in zip(pre, trainer._inflight):
        assert np.asarray(b_post).shape[0] == n - 1
        np.testing.assert_array_equal(np.asarray(b_post), b_pre[survivors])
    surv_targets = jnp.concatenate([targets[:5], targets[6:]])
    params, _ = trainer.step(params, _batches(surv_targets, 2), 0.1)
    assert params["w"].shape[0] == n - 1
    assert bool(jnp.isfinite(params["w"]).all())
    assert trainer.n_traces == 2                 # one re-jit per membership


def test_failure_plan_and_masks():
    plan = failures.sample_failures(20, 0.2, at_round=5, seed=0)
    assert len(plan.dead_at(4)) == 0
    assert len(plan.dead_at(5)) == 4
    mask = plan.alive_mask(10)
    assert mask.sum() == 16


class TestAttackPlan:
    def test_round_vector_semantics(self):
        plan = failures.AttackPlan(6, events=(
            (0, (1,), "sign_flip", 2.0),
            (3, (4,), "scale", 5.0),
            (5, (1,), "noise", 0.7)))
        v0 = plan.round_vector(0)
        assert v0.shape == (2, 6)
        assert v0[0, 1] == -2.0 and v0[1, 1] == 0.0     # sign_flip: -mag
        assert np.all(v0[0, [0, 2, 3, 4, 5]] == 1.0)    # honest: identity
        v3 = plan.round_vector(3)
        assert v3[0, 4] == 5.0                          # scale joins
        v5 = plan.round_vector(5)
        assert v5[0, 1] == 1.0 and v5[1, 1] == 0.7      # later event overrides
        assert set(plan.attackers_at(2)) == {1}
        assert set(plan.attackers_at(4)) == {1, 4}

    def test_all_honest_vector_is_identity_on_apply(self):
        plan = failures.AttackPlan(4, events=((10, (2,), "scale", 3.0),))
        tree = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(
            (4, 3, 2)), jnp.float32)}
        key = np.array([0, 0], np.uint32)
        out = failures.apply_attack(tree, jnp.asarray(plan.round_vector(0)),
                                    jnp.asarray(key))
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))

    def test_apply_attack_modes(self):
        r = np.random.default_rng(3)
        tree = {"w": jnp.asarray(r.standard_normal((5, 4)), jnp.float32)}
        key = jnp.asarray(np.array([7, 1], np.uint32))
        plan = failures.AttackPlan(5, events=((0, (2,), "sign_flip", 10.0),
                                              (0, (4,), "noise", 2.0)))
        out = failures.apply_attack(tree, jnp.asarray(plan.round_vector(0)),
                                    key)
        w, ow = np.asarray(tree["w"]), np.asarray(out["w"])
        np.testing.assert_allclose(ow[2], -10.0 * w[2], rtol=1e-6)
        np.testing.assert_array_equal(ow[[0, 1, 3]], w[[0, 1, 3]])
        assert not np.allclose(ow[4], w[4])  # noise perturbed
        # same key reproduces, different round key differs
        out2 = failures.apply_attack(tree, jnp.asarray(plan.round_vector(0)),
                                     key)
        np.testing.assert_array_equal(np.asarray(out2["w"]), ow)

    def test_sample_attackers(self):
        plan = failures.sample_attackers(12, 3, mode="scale", magnitude=4.0,
                                         at_round=2, seed=1)
        assert plan.n_clients == 12 and len(plan.attackers_at(2)) == 3
        assert plan.attackers_at(1) == set()
        assert plan.events[0][2] == "scale"


def test_attacker_churn_and_screen_zero_retrace():
    """Tentpole retrace guard: an AttackPlan whose attacker set CHANGES
    mid-run plus an active screen must reuse ONE executable — the (2, n)
    attack vector and the PRNG key are step data, never trace structure."""
    n, dim = 10, 3
    targets = jnp.zeros((n, dim))
    cfg = dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.0)
    plan = failures.AttackPlan(n, events=(
        (1, (2,), "sign_flip", 5.0),
        (3, (7,), "scale", 10.0),
        (5, (2,), "noise", 1.0)))          # mode changes too
    rng = np.random.default_rng(0)
    for screen, kw in (("norm_clip", {"clip_tau": 3.0}),
                       ("trimmed_mean", {"trim_f": 1})):
        trainer = ElasticTrainer(overlay=expander_overlay(n, 4, seed=0),
                                 loss_fn=quad_loss, dcfg=cfg,
                                 straggler_rounds=1, failure_rounds=99,
                                 engine=engine.GossipEngineConfig(
                                     substrate="stacked", screen=screen,
                                     **kw),
                                 attack_plan=plan)
        params = {"w": jnp.ones((n, dim))}
        for rnd in range(7):
            alive = (rng.random(n) > 0.2).astype(np.float32)  # churn too
            params, _, old2new = trainer.observe_heartbeats(alive, params)
            assert old2new is None
            params, _ = trainer.step(params, _batches(targets, 1), 0.2)
        assert trainer.n_traces == 1, (screen, trainer.n_traces)
        assert bool(jnp.isfinite(params["w"]).all())


def test_quarantine_evicts_attackers_through_splice_repair():
    """norm_clip telemetry -> suspicion -> quarantine -> the SAME splice
    repair as heartbeat death, with suspicion counters carried through
    old2new and attack-plan columns compacted to the survivors."""
    n, dim = 12, 4
    r = np.random.default_rng(0)
    targets = jnp.zeros((n, dim))
    cfg = dfedavg.DFedAvgMConfig(local_steps=2, lr=0.05, momentum=0.9)
    plan = failures.AttackPlan(n, events=((0, (3, 7), "sign_flip", 30.0),))
    trainer = ElasticTrainer(overlay=expander_overlay(n, 4, seed=1),
                             loss_fn=quad_loss, dcfg=cfg,
                             straggler_rounds=1, failure_rounds=99,
                             engine=engine.GossipEngineConfig(
                                 substrate="stacked", screen="norm_clip",
                                 clip_tau=3.0),
                             attack_plan=plan, quarantine_rounds=3)
    params = {"w": jnp.asarray(r.standard_normal((n, dim)) * 0.1,
                               jnp.float32)}
    repaired_at = None
    for rnd in range(6):
        params, _, old2new = trainer.observe_heartbeats(
            np.ones(trainer.n_clients), params)
        if old2new is not None:
            repaired_at = rnd
            break
        params, _ = trainer.step(
            params, _batches(jnp.zeros((trainer.n_clients, dim)), 2), 0.05)
    # every receiver of 3/7 clips them every round -> suspicion hits the
    # threshold after quarantine_rounds rounds and the repair fires
    assert repaired_at == 3, repaired_at
    assert trainer.repairs[-1]["dead"] == [3, 7]
    assert trainer.repairs[-1]["quarantined"] == [3, 7]
    assert trainer.n_clients == n - 2
    assert params["w"].shape[0] == n - 2
    # suspicion counters followed the survivors through old2new
    assert old2new[3] == -1 and old2new[7] == -1
    survivors = np.asarray(old2new) >= 0
    assert np.all(trainer.health.suspicion < trainer.quarantine_rounds)
    # attack columns compacted: the evicted attackers' scripts are gone
    np.testing.assert_array_equal(trainer._attack_cols,
                                  np.arange(n)[survivors])
    # post-repair rounds run clean (one re-jit for the membership change)
    params, _ = trainer.step(
        params, _batches(jnp.zeros((n - 2, dim)), 2), 0.05)
    assert trainer.n_traces == 2, trainer.n_traces
    assert bool(jnp.isfinite(params["w"]).all())


def test_suspicion_carried_through_remap():
    """A straggling-but-not-quarantined suspect keeps its counter at its
    compacted index when an unrelated client dies."""
    tracker = failures.HealthTracker(8, straggler_rounds=1, failure_rounds=2,
                                     quarantine_rounds=5)
    tracker.observe_suspicion(np.asarray([0, 0, 0, 0, 0, 2, 0, 1]))
    tracker.observe_suspicion(np.asarray([0, 0, 0, 0, 0, 1, 0, 0]))
    np.testing.assert_array_equal(tracker.suspicion,
                                  [0, 0, 0, 0, 0, 2, 0, 1])
    old2new = np.asarray([0, 1, -1, 2, 3, 4, 5, 6])  # client 2 dies
    remapped = tracker.remap(old2new)
    np.testing.assert_array_equal(remapped.suspicion,
                                  [0, 0, 0, 0, 2, 0, 1])
    assert list(remapped.suspects()) == []


# ----------------------------------------- one simulator round, two trainers
def _two_leaf_loss(params, batch):
    loss = (jnp.mean(jnp.square(params["w"] - batch["target"]))
            + jnp.mean(jnp.square(params["b"])))
    return loss, {}


PARITY_CELLS = {
    "stacked_f32": {},
    "int8_block_delay1": dict(codec="int8_block", delay=1),
    "topk_ef": dict(codec="topk_ef"),
    "topk_ef_delay1": dict(codec="topk_ef", delay=1),
    "sub_rounds2": dict(sub_rounds=2),
    "trimmed_mean": dict(screen="trimmed_mean"),
    "blocked8": dict(substrate="blocked", block=8),
}


@pytest.mark.parametrize("cell", list(PARITY_CELLS))
def test_sim_and_elastic_trainers_run_the_same_round(cell):
    """SimTrainer.run and ElasticTrainer.step, given one engine cell, the
    same params and the same batches, agree bitwise after three rounds,
    each on one trace."""
    n, dim = 8, 32
    cfg = engine.GossipEngineConfig(**{"substrate": "stacked",
                                       **PARITY_CELLS[cell]})
    r = np.random.default_rng(4)
    p0 = {"w": jnp.asarray(r.standard_normal((n, dim)), jnp.float32),
          "b": jnp.asarray(r.standard_normal((n, 3)), jnp.float32)}
    batches = _batches(jnp.asarray(r.standard_normal((n, dim)),
                                   jnp.float32), 2)

    def make(cls):
        return cls(overlay=expander_overlay(n, 4, seed=0),
                   loss_fn=_two_leaf_loss,
                   dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.2,
                                               momentum=0.5),
                   engine=cfg)

    sim, el = make(SimTrainer), make(ElasticTrainer)
    p_sim, _ = sim.run(p0, lambda rnd: batches, 3, lambda rnd: 0.2)
    p_el = p0
    for _ in range(3):
        p_el, _ = el.step(p_el, batches, 0.2)
    for k in p0:
        np.testing.assert_array_equal(np.asarray(p_sim[k]),
                                      np.asarray(p_el[k]))
    assert sim.tracer.count == 1 and el.n_traces == 1


@pytest.mark.parametrize("cls", [SimTrainer, ElasticTrainer])
def test_trainers_reject_a_shard_map_engine(cls):
    with pytest.raises(ValueError, match=r"runs the stacked \| blocked"):
        cls(overlay=expander_overlay(8, 4, seed=0), loss_fn=quad_loss,
            dcfg=dfedavg.DFedAvgMConfig(local_steps=1, lr=0.2, momentum=0.0),
            engine=engine.GossipEngineConfig(substrate="shard_map"))


# ------------------------------------------- the host loop's batch ahead
def _sim_trainer(cell="stacked_f32"):
    return SimTrainer(overlay=expander_overlay(8, 4, seed=0),
                      loss_fn=_two_leaf_loss,
                      dcfg=dfedavg.DFedAvgMConfig(local_steps=2, lr=0.2,
                                                  momentum=0.5),
                      engine=engine.GossipEngineConfig(
                          **{"substrate": "stacked", **PARITY_CELLS[cell]}))


def _recording_batch_fn(fail_at=None):
    """A batch function of the round index alone that records its calls."""
    calls = []

    def batch_fn(rnd):
        calls.append(rnd)
        if rnd == fail_at:
            raise RuntimeError(f"no batch for round {rnd}")
        r = np.random.default_rng((7, rnd))
        return _batches(jnp.asarray(r.standard_normal((8, 32)),
                                    jnp.float32), 2)

    return batch_fn, calls


def _p0():
    r = np.random.default_rng(4)
    return {"w": jnp.asarray(r.standard_normal((8, 32)), jnp.float32),
            "b": jnp.asarray(r.standard_normal((8, 3)), jnp.float32)}


def test_batch_fn_called_once_per_round_in_order():
    """run(p, fn, 1) then run(p, fn, 4, start_round=1) asks for rounds
    0-3, once each and in order, and never for round 4: a caller that
    keeps the first three batches it is fed keeps rounds 0-2."""
    sim = _sim_trainer()
    batch_fn, calls = _recording_batch_fn()
    kept = []

    def feed(rnd):
        b = batch_fn(rnd)
        if len(kept) < 3:
            kept.append(rnd)
        return b

    p1, h1 = sim.run(_p0(), feed, 1, lambda rnd: 0.2)
    _, h4 = sim.run(p1, feed, 4, lambda rnd: 0.2, start_round=1)
    assert calls == [0, 1, 2, 3]
    assert kept == [0, 1, 2]
    assert [h["round"] for h in h1 + h4] == [0, 1, 2, 3]


@pytest.mark.parametrize("cell", ["stacked_f32", "int8_block_delay1"])
def test_run_equals_chained_one_round_calls(cell):
    """run(p, fn, 4), which builds each batch a round ahead, gives params
    and history bitwise equal to four one-round calls, which never do; the
    delayed int8 cell carries its in-flight snapshot between rounds."""
    p0 = _p0()
    batch_fn, _ = _recording_batch_fn()
    p_run, h_run = _sim_trainer(cell).run(p0, batch_fn, 4, lambda rnd: 0.2)
    sim, p_one, h_one = _sim_trainer(cell), p0, []
    for rnd in range(4):
        p_one, h = sim.run(p_one, batch_fn, rnd + 1, lambda r: 0.2,
                           start_round=rnd)
        h_one += h
    for k in p0:
        np.testing.assert_array_equal(np.asarray(p_run[k]),
                                      np.asarray(p_one[k]))
    assert h_run == h_one


def _spy_on_spans(monkeypatch):
    """Records each host span train.py opens, as its name or, for a step
    span, (name, step), in a list it returns."""
    from repro.launch import train
    events = []
    real = train.span

    def spy(name, step=None):
        events.append(name if step is None else (name, step))
        return real(name, step)

    monkeypatch.setattr(train, "span", spy)
    return events


def test_batch_fn_error_leaves_run_with_the_round_unsynced(monkeypatch):
    """An exception raised by batch_fn(r + 1) propagates out of run at
    once: round r was dispatched, its sync and record never ran."""
    names = _spy_on_spans(monkeypatch)
    batch_fn, calls = _recording_batch_fn(fail_at=2)
    with pytest.raises(RuntimeError, match="no batch for round 2"):
        _sim_trainer().run(_p0(), batch_fn, 5, lambda rnd: 0.2)
    assert calls == [0, 1, 2]
    last = len(names) - names[::-1].index(("dfl.round", 1))
    assert names[last:] == ["dfl.batch", "dfl.operands", "dfl.dispatch",
                            "sim.batch_ahead"]


def test_run_builds_the_next_batch_between_dispatch_and_sync(monkeypatch):
    """Within each round but the last the host spans run dfl.dispatch ->
    sim.batch_ahead -> dfl.sync, and the batch built there is the next
    round's; every round keeps its dfl.batch span, which builds the batch
    only in a call's first round."""
    events = _spy_on_spans(monkeypatch)
    batch_fn, calls = _recording_batch_fn()

    def feed(rnd):
        events.append(("batch_fn", rnd))
        return batch_fn(rnd)

    _sim_trainer().run(_p0(), feed, 3, lambda rnd: 0.2)
    leaves = ["dfl.operands", "dfl.dispatch"]
    tail = ["dfl.sync", "dfl.record"]
    assert events == [
        ("dfl.round", 0), "dfl.batch", ("batch_fn", 0), *leaves,
        "sim.batch_ahead", ("batch_fn", 1), *tail,
        ("dfl.round", 1), "dfl.batch", *leaves,
        "sim.batch_ahead", ("batch_fn", 2), *tail,
        ("dfl.round", 2), "dfl.batch", *leaves, *tail]
    assert calls == [0, 1, 2]
