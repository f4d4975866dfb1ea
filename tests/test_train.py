"""Train loop tests — step semantics, convergence on learnable data,
gradient accumulation (covers the reference's train() drivers, SURVEY.md
§2.11-2.12, as pure functions)."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_resnet_tensorflow_tpu.data import learnable_synthetic_iterator
from distributed_resnet_tensorflow_tpu.train import Trainer, cross_entropy_loss
from distributed_resnet_tensorflow_tpu.utils.config import get_preset


def _tiny_cfg(**overrides):
    cfg = get_preset("smoke")
    cfg.model.compute_dtype = "float32"
    cfg.model.resnet_size = 8
    cfg.model.num_classes = 4
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    cfg.optimizer.schedule = "constant"
    cfg.optimizer.learning_rate = 0.05
    for k, v in overrides.items():
        cfg.override(k, v)
    return cfg


def test_cross_entropy_loss():
    logits = jnp.asarray([[10.0, 0.0], [0.0, 10.0]])
    labels = jnp.asarray([0, 1])
    assert float(cross_entropy_loss(logits, labels)) < 1e-3
    # label smoothing raises the floor
    smoothed = float(cross_entropy_loss(logits, labels, label_smoothing=0.1))
    assert smoothed > 0.1


def test_train_step_runs_and_metrics():
    cfg = _tiny_cfg()
    tr = Trainer(cfg)
    tr.init_state()
    it = learnable_synthetic_iterator(16, 8, 4)
    state, m = tr.train(it, num_steps=2)
    assert int(state.step) == 2
    for key in ("loss", "cross_entropy", "precision", "learning_rate",
                "grad_norm"):
        assert key in m
    assert np.isfinite(float(m["loss"]))


def test_loss_decreases_on_learnable_data():
    """Tiny convergence test — the e2e correctness oracle the reference only
    had via its continuous evaluator (SURVEY.md §4.3)."""
    cfg = _tiny_cfg()
    tr = Trainer(cfg)
    tr.init_state()
    it = learnable_synthetic_iterator(16, 8, 4, seed=3)
    losses = []
    step_fn = tr.jitted_train_step()
    from distributed_resnet_tensorflow_tpu.parallel.sharding import shard_batch
    for i in range(30):
        batch = shard_batch(next(it), tr.mesh)
        tr.state, m = step_fn(tr.state, batch)
        losses.append(float(m["cross_entropy"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.8, losses


def test_weight_decay_in_loss():
    """Reference adds L2 over trainable kernels to the loss
    (resnet_model.py:78-86): loss > cross_entropy when wd > 0."""
    cfg = _tiny_cfg()
    cfg.optimizer.weight_decay = 0.01
    tr = Trainer(cfg)
    tr.init_state()
    it = learnable_synthetic_iterator(16, 8, 4)
    state, m = tr.train(it, num_steps=1)
    assert float(m["loss"]) > float(m["cross_entropy"])


def test_loss_weight_decay_hand_computed():
    """Both decay modes against hand-computed 0.5*rate*Σ‖w‖² values."""
    from distributed_resnet_tensorflow_tpu.train.optimizers import (
        loss_weight_decay)
    params = {
        "Dense": {"kernel": jnp.asarray([[1.0, 2.0], [3.0, 4.0]]),  # Σsq=30
                  "bias": jnp.asarray([1.0, 1.0])},                  # Σsq=2
        "BatchNorm": {"scale": jnp.asarray([2.0]),                   # Σsq=4
                      "bias": jnp.asarray([3.0])},                   # Σsq=9
    }
    rate = 0.1
    # kernels-only (default): just the 2-D kernel
    assert np.isclose(float(loss_weight_decay(params, rate)), 0.5 * rate * 30)
    # reference-faithful: ALL trainables incl. BN scale/bias and biases
    # (reference resnet_model.py:85-86)
    assert np.isclose(float(loss_weight_decay(params, rate, all_params=True)),
                      0.5 * rate * (30 + 2 + 4 + 9))
    assert loss_weight_decay(params, 0.0) == 0.0


@pytest.mark.slow  # re-tiered out of the 870s tier-1; runs in the full (unfiltered) suite
@pytest.mark.heavy
def test_decay_all_params_config_increases_loss():
    """optimizer.decay_all_params=True adds BN/bias L2 on top of kernels."""
    def run(decay_all):
        cfg = _tiny_cfg()
        cfg.optimizer.weight_decay = 0.01
        cfg.optimizer.decay_all_params = decay_all
        tr = Trainer(cfg)
        tr.init_state(seed=0)
        it = learnable_synthetic_iterator(16, 8, 4, seed=5)
        _, m = tr.train(it, num_steps=1)
        return float(m["loss"]), float(m["cross_entropy"])

    loss_k, ce_k = run(False)
    loss_a, ce_a = run(True)
    assert np.isclose(ce_k, ce_a, rtol=1e-6)  # same init, same data
    # BN scales init to 1.0, so all-params decay is strictly larger
    assert loss_a > loss_k


@pytest.mark.heavy
def test_grad_accum_matches_big_batch():
    """2 microbatches of 8 == one batch of 16 (grads averaged). Uses the
    BN-free logistic model where the equivalence is exact; with BN the
    microbatch moments legitimately differ from full-batch moments."""
    it = learnable_synthetic_iterator(16, 8, 4, seed=7)
    batch = next(it)

    def build(accum):
        cfg = _tiny_cfg()
        cfg.model.name = "logistic"
        cfg.model.num_classes = 4
        cfg.model.input_size = 8 * 8 * 3
        cfg.train.grad_accum_steps = accum
        tr = Trainer(cfg)
        tr.init_state(seed=0)
        return tr

    tr_a, tr_b = build(1), build(2)
    sa, ma = tr_a._train_step(tr_a.state, {k: jnp.asarray(v) for k, v in batch.items()})
    sb, mb = tr_b._train_step(tr_b.state, {k: jnp.asarray(v) for k, v in batch.items()})
    pa = jax.tree_util.tree_leaves(sa.params)
    pb = jax.tree_util.tree_leaves(sb.params)
    for a, b in zip(pa, pb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    assert np.isclose(float(ma["cross_entropy"]), float(mb["cross_entropy"]),
                      rtol=1e-5)


@pytest.mark.heavy
# re-tiered out of the 870s tier-1 (ISSUE 17, ~13s: a full two-trainer
# A/B against the optax oracle). The fused-xent kernel keeps its own
# unit pins in tier-1 (test_ops) and the fused path trains in
# test_loss_decreases_on_learnable_data; the full (unfiltered) suite
# runs the end-to-end oracle.
@pytest.mark.slow
def test_fused_xent_train_step_matches_optax():
    """train.fused_xent=interpret (Pallas kernel, CPU interpreter) produces
    the same step as the optax path — including gradients, via the custom
    VJP — on the sharded 8-device mesh (shard_map route)."""
    def run(mode):
        cfg = _tiny_cfg()
        cfg.train.fused_xent = mode
        tr = Trainer(cfg)
        tr.init_state(seed=0)
        it = learnable_synthetic_iterator(16, 8, 4, seed=11)
        state, m = tr.train(it, num_steps=2)
        return state, m

    sa, ma = run("off")
    sb, mb = run("interpret")
    assert np.isclose(float(ma["cross_entropy"]), float(mb["cross_entropy"]),
                      rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(sa.params),
                    jax.tree_util.tree_leaves(sb.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_fused_xent_auto_resolves_off_cpu():
    """auto → optax on CPU (kernel only compiles on TPU)."""
    from distributed_resnet_tensorflow_tpu.train.loop import make_ce_fn
    import jax.numpy as jnp
    ce = make_ce_fn(0.0, "auto", None)
    logits = jnp.asarray([[2.0, 0.0], [0.0, 2.0]])
    labels = jnp.asarray([0, 1])
    expected = float(cross_entropy_loss(logits, labels))
    assert np.isclose(float(ce(logits, labels)), expected, rtol=1e-6)


def test_evaluate():
    cfg = _tiny_cfg()
    tr = Trainer(cfg)
    tr.init_state()
    it = learnable_synthetic_iterator(16, 8, 4)
    out = tr.evaluate(it, num_batches=3)
    assert out["count"] == 48
    assert 0.0 <= out["precision"] <= 1.0


@pytest.mark.heavy
def test_lars_optimizer_runs():
    cfg = _tiny_cfg()
    cfg.optimizer.name = "lars"
    cfg.optimizer.schedule = "cosine"
    cfg.optimizer.warmup_steps = 2
    cfg.optimizer.total_steps = 10
    tr = Trainer(cfg)
    tr.init_state()
    it = learnable_synthetic_iterator(16, 8, 4)
    state, m = tr.train(it, num_steps=2)
    assert np.isfinite(float(m["loss"]))


def test_adamw_decoupled_decay():
    """AdamW (the transformer-family presets' optimizer) takes decay inside
    the optimizer: loss == cross_entropy even at wd > 0 (no loss-side L2),
    yet a decayed kernel shrinks under zero gradients while masked params
    (bias, pos_embed) do not."""
    cfg = _tiny_cfg()
    cfg.optimizer.name = "adamw"
    cfg.optimizer.weight_decay = 0.1
    tr = Trainer(cfg)
    tr.init_state()
    it = learnable_synthetic_iterator(16, 8, 4)
    state, m = tr.train(it, num_steps=2)
    assert np.isfinite(float(m["loss"]))
    assert float(m["loss"]) == pytest.approx(float(m["cross_entropy"]))

    # the decay itself, isolated: zero gradients, one update — decayed
    # kernels shrink by ~lr*wd, masked leaves (bias, pos_embed) are frozen
    from distributed_resnet_tensorflow_tpu.train.optimizers import (
        create_optimizer)
    tx = create_optimizer(cfg.optimizer, lambda step: 0.01)
    params = {"Dense_0": {"kernel": jnp.ones((4, 4)),
                          "bias": jnp.ones((4,))},
              "pos_embed": jnp.ones((1, 3, 4))}
    opt_state = tx.init(params)
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    updates, _ = tx.update(grads, opt_state, params)
    new = optax.apply_updates(params, updates)
    assert float(jnp.max(jnp.abs(new["Dense_0"]["kernel"]))) < 1.0
    assert float(jnp.min(new["Dense_0"]["bias"])) == 1.0
    assert float(jnp.min(new["pos_embed"])) == 1.0


def test_adamw_rejects_decay_all_params():
    """decay_all_params is the loss-side reference-parity switch; decoupled
    optimizers must refuse it loudly rather than silently ignore it."""
    cfg = _tiny_cfg()
    cfg.optimizer.name = "adamw"
    cfg.optimizer.decay_all_params = True
    with pytest.raises(ValueError, match="decay_all_params"):
        Trainer(cfg)


def test_evaluate_with_masked_batches():
    """Masked eval counts only real examples."""
    cfg = _tiny_cfg()
    tr = Trainer(cfg)
    tr.init_state()
    it = learnable_synthetic_iterator(16, 8, 4)

    def masked(it):
        for b in it:
            b = dict(b)
            b["mask"] = np.concatenate(
                [np.ones(12, np.float32), np.zeros(4, np.float32)])
            yield b

    out = tr.evaluate(masked(it), num_batches=2)
    assert out["count"] == 24


def test_steps_per_loop_matches_sequential():
    """K fused steps (lax.scan) == K sequential steps (logistic, exact)."""
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        shard_stacked_batch)
    it = learnable_synthetic_iterator(8, 8, 4, seed=9)
    batches = [next(it) for _ in range(4)]

    def build(spl):
        cfg = _tiny_cfg()
        cfg.model.name = "logistic"
        cfg.model.num_classes = 4
        cfg.model.input_size = 8 * 8 * 3
        cfg.train.batch_size = 8
        cfg.train.steps_per_loop = spl
        tr = Trainer(cfg)
        tr.init_state(seed=0)
        return tr

    tr_seq = build(1)
    step_fn = tr_seq.jitted_train_step()
    from distributed_resnet_tensorflow_tpu.parallel.sharding import shard_batch
    for b in batches:
        tr_seq.state, m_seq = step_fn(tr_seq.state, shard_batch(b, tr_seq.mesh))

    tr_fused = build(4)
    stacked = {k: np.stack([b[k] for b in batches]) for k in batches[0]}
    multi = tr_fused.jitted_multi_step(4)
    tr_fused.state, m_fused = multi(
        tr_fused.state, shard_stacked_batch(stacked, tr_fused.mesh))

    assert int(tr_seq.state.step) == int(tr_fused.state.step) == 4
    for a, b in zip(jax.tree_util.tree_leaves(tr_seq.state.params),
                    jax.tree_util.tree_leaves(tr_fused.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    assert np.isclose(float(m_seq["loss"]), float(m_fused["loss"]), rtol=1e-5)


@pytest.mark.heavy
def test_trainer_train_with_steps_per_loop_and_tail():
    """num_steps not a multiple of steps_per_loop: tail runs unfused."""
    cfg = _tiny_cfg()
    cfg.train.steps_per_loop = 3
    tr = Trainer(cfg)
    tr.init_state()
    hook_steps = []
    it = learnable_synthetic_iterator(16, 8, 4)
    state, m = tr.train(it, num_steps=7,
                        hooks=(lambda s, st, mm: hook_steps.append(s),))
    assert int(state.step) == 7
    assert hook_steps == [3, 6, 7]


def test_segmented_tail_remainder_no_skip():
    """Segmented training with a fused-loop tail must not discard the
    remainder of the pre-stacked group at the segment boundary: a k=3 run
    split 4+4 must see the same batch sequence as an unfused 8-step run
    (exact on the BN-free model)."""
    def build(spl):
        cfg = _tiny_cfg()
        cfg.model.name = "logistic"
        cfg.model.num_classes = 4
        cfg.model.input_size = 8 * 8 * 3
        cfg.train.steps_per_loop = spl
        tr = Trainer(cfg)
        tr.init_state(seed=0)
        return tr

    tr_a = build(1)
    tr_a.train(learnable_synthetic_iterator(16, 8, 4, seed=21), num_steps=8)

    tr_b = build(3)
    it = learnable_synthetic_iterator(16, 8, 4, seed=21)
    tr_b.train(it, num_steps=4)                  # fused 3 + tail 1 (banks 2)
    tr_b.train(it, num_steps=8, start_step=4)    # remainder 2 + fused 3 - ...
    assert int(tr_b.state.step) == 8
    for a, b in zip(jax.tree_util.tree_leaves(tr_a.state.params),
                    jax.tree_util.tree_leaves(tr_b.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow  # re-tiered out of the 870s tier-1; runs in the full (unfiltered) suite
def test_finite_stream_ends_training_k1():
    """Same contract on the k==1 (unfused) path: exhaustion ends training
    cleanly instead of leaking StopIteration out of Trainer.train."""
    cfg = _tiny_cfg()
    cfg.train.steps_per_loop = 1
    tr = Trainer(cfg)
    tr.init_state()
    src = learnable_synthetic_iterator(16, 8, 4)
    finite = iter([next(src) for _ in range(5)])
    state, m = tr.train(finite, num_steps=100)
    assert int(state.step) == 5
    assert m is not None and np.isfinite(float(m["loss"]))


def test_finite_stream_ends_training_at_last_full_group():
    """A deliberately truncated input ends training cleanly (the reference's
    serial path stopped on input exhaustion too, SURVEY.md §3.5)."""
    cfg = _tiny_cfg()
    cfg.train.steps_per_loop = 3
    tr = Trainer(cfg)
    tr.init_state()
    src = learnable_synthetic_iterator(16, 8, 4)
    finite = iter([next(src) for _ in range(7)])
    state, m = tr.train(finite, num_steps=100)
    assert int(state.step) == 6  # 2 full groups; the partial 7th is dropped
    assert m is not None and np.isfinite(float(m["loss"]))


def test_detach_device_dataset_restores_config_augment():
    """attach forces device-side augmentation (raw uint8 needs it); detach
    must restore the config-resolved choice or streamed host-standardized
    input would be augmented twice."""
    cfg = _tiny_cfg()
    cfg.data.dataset = "cifar10"
    cfg.data.device_augment = "off"   # CPU: config resolves to host augment
    tr = Trainer(cfg)
    tr.init_state()
    assert tr._aug_fn is None
    imgs = np.zeros((64, 8, 8, 3), np.uint8)
    lbls = np.zeros((64,), np.int32)
    tr.attach_device_dataset(imgs, lbls)
    assert tr._aug_fn is not None
    tr.detach_device_dataset()
    assert tr._aug_fn is None


def test_threaded_stacker_close_stops_worker():
    """Closing the stacker generator must terminate its worker thread
    (otherwise every replaced prefetcher leaks a parked thread + batches)."""
    import threading
    import time as _time
    from distributed_resnet_tensorflow_tpu.data.device_prefetch import (
        threaded_stacker)

    def gen():
        i = 0
        while True:
            yield {"x": np.full((2,), i)}
            i += 1

    existing = set(threading.enumerate())
    it = threaded_stacker(gen(), 3, depth=1)
    first = next(it)
    assert first["x"].shape == (3, 2)
    workers = [t for t in threading.enumerate()
               if t not in existing and "stacker" in t.name]
    assert len(workers) == 1
    it.close()
    workers[0].join(3)
    assert not workers[0].is_alive()


def test_segmented_training_does_not_skip_batches():
    """Repeated train() calls over ONE shared iterator must consume batches
    contiguously despite the device-prefetch lookahead."""
    cfg = _tiny_cfg()
    cfg.model.name = "logistic"
    cfg.model.num_classes = 4
    cfg.model.input_size = 8 * 8 * 3
    tr = Trainer(cfg)
    tr.init_state(seed=0)

    consumed = []

    def tracking_iter():
        i = 0
        it = learnable_synthetic_iterator(16, 8, 4, seed=1)
        while True:
            consumed.append(i)
            i += 1
            yield next(it)

    it = tracking_iter()
    tr.train(it, num_steps=3)
    tr.train(it, num_steps=6, start_step=3)
    # 9 steps total; the staging pipeline may hold transfer_depth (2)
    # queued device batches, one in the worker hand-off, and up to two in
    # the transfer thread's issue window beyond that
    assert len(consumed) <= 9 + 5


def test_loss_decreases_with_group_norm():
    """The BN-free contract trains: same convergence oracle as the BN path
    (VERDICT r4 #1 — the GroupNorm escape hatch must exist AND learn)."""
    cfg = _tiny_cfg()
    cfg.model.norm = "group"
    tr = Trainer(cfg)
    tr.init_state()
    assert not tr.state.batch_stats  # stateless contract
    it = learnable_synthetic_iterator(16, 8, 4, seed=3)
    losses = []
    step_fn = tr.jitted_train_step()
    from distributed_resnet_tensorflow_tpu.parallel.sharding import shard_batch
    for i in range(30):
        batch = shard_batch(next(it), tr.mesh)
        tr.state, m = step_fn(tr.state, batch)
        losses.append(float(m["cross_entropy"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.8, losses


@pytest.mark.heavy
def test_loss_decreases_with_frozen_bn():
    """The frozen-BN fine-tune contract also trains from scratch (stats
    pinned at init 0/1 — a learned affine)."""
    cfg = _tiny_cfg()
    cfg.model.norm = "frozen"
    tr = Trainer(cfg)
    tr.init_state()
    # snapshot to numpy: the jitted step donates the state buffers
    before = [np.asarray(x)
              for x in jax.tree_util.tree_leaves(tr.state.batch_stats)]
    it = learnable_synthetic_iterator(16, 8, 4, seed=3)
    losses = []
    step_fn = tr.jitted_train_step()
    from distributed_resnet_tensorflow_tpu.parallel.sharding import shard_batch
    for i in range(30):
        batch = shard_batch(next(it), tr.mesh)
        tr.state, m = step_fn(tr.state, batch)
        losses.append(float(m["cross_entropy"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.8, losses
    after = jax.tree_util.tree_leaves(tr.state.batch_stats)
    for b, a in zip(before, after):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))


@pytest.mark.slow  # re-tiered out of the 870s tier-1; runs in the full (unfiltered) suite
def test_group_norm_warmupless_high_lr_warns(caplog):
    """The measured GroupNorm plateau (docs/perf_norm_r5.md) warns at
    TRAIN time when the RESOLVED schedule starts high (probing the
    schedule, not raw config fields — piecewise ignores learning_rate and
    constant ignores warmup_steps); an effective warmup stays silent, and
    merely constructing a Trainer (the evaluator does) never warns."""
    import logging
    from distributed_resnet_tensorflow_tpu.data import (
        learnable_synthetic_iterator)
    cfg = _tiny_cfg()
    cfg.model.norm = "group"
    # piecewise starting at 0.1 — learning_rate field deliberately low to
    # prove the probe reads the schedule, not the raw field
    cfg.optimizer.schedule = "piecewise"
    cfg.optimizer.learning_rate = 0.001
    cfg.optimizer.boundaries = (50,)
    cfg.optimizer.values = (0.1, 0.01)
    with caplog.at_level(logging.WARNING):
        tr = Trainer(cfg)
    assert not any("plateau" in r.message for r in caplog.records)
    with caplog.at_level(logging.WARNING):
        tr.train(learnable_synthetic_iterator(16, 8, 4), num_steps=1)
    assert any("plateau" in r.message for r in caplog.records)
    caplog.clear()
    # effective warmup: schedule starts low -> silent
    cfg2 = _tiny_cfg()
    cfg2.model.norm = "group"
    cfg2.optimizer.schedule = "warmup_piecewise"
    cfg2.optimizer.warmup_steps = 500
    cfg2.optimizer.warmup_start = 0.01
    cfg2.optimizer.boundaries = (600,)
    cfg2.optimizer.values = (0.1, 0.01)
    tr2 = Trainer(cfg2)
    with caplog.at_level(logging.WARNING):
        tr2.train(learnable_synthetic_iterator(16, 8, 4), num_steps=1)
    assert not any("plateau" in r.message for r in caplog.records)


def test_exactly_one_transfer_per_training_batch(monkeypatch):
    """Acceptance contract: the hot path issues EXACTLY one host→device
    transfer per training batch (the coalesced stager's single batched
    device_put), counted via a wrapper around the one issue point."""
    from distributed_resnet_tensorflow_tpu.parallel import sharding as sh
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        CoalescedStager)

    calls = []
    real = sh._issue_device_put
    monkeypatch.setattr(sh, "_issue_device_put",
                        lambda arrays, devices:
                        calls.append(1) or real(arrays, devices))

    # k=1 path: N batches -> N transfer issues
    cfg = _tiny_cfg()
    cfg.data.coalesced_transfer = "on"   # auto resolves off on CPU
    tr = Trainer(cfg)
    assert isinstance(tr._put_batch, CoalescedStager)
    tr.init_state()
    src = learnable_synthetic_iterator(16, 8, 4)
    finite = iter([next(src) for _ in range(5)])
    state, _ = tr.train(finite, num_steps=100)
    assert int(state.step) == 5
    assert len(calls) == 5

    # fused path: 6 batches at k=3 -> 2 stacked groups -> 2 transfer issues
    calls.clear()
    cfg = _tiny_cfg()
    cfg.data.coalesced_transfer = "on"
    cfg.train.steps_per_loop = 3
    tr = Trainer(cfg)
    tr.init_state()
    finite = iter([next(src) for _ in range(6)])
    state, _ = tr.train(finite, num_steps=100)
    assert int(state.step) == 6
    assert len(calls) == 2


def test_evaluate_partial_stream_single_process():
    """Pipelined evaluate keeps the exhaustion contract: a one-pass stream
    shorter than num_batches returns metrics over what was consumed
    (single-process; multi-process raises to avoid the collective
    deadlock)."""
    cfg = _tiny_cfg()
    tr = Trainer(cfg)
    tr.init_state()
    src = learnable_synthetic_iterator(16, 8, 4)
    out = tr.evaluate(iter([next(src) for _ in range(2)]), num_batches=5)
    assert out["count"] == 32


def test_evaluate_closes_staging_thread():
    """Each evaluate() call must stop its staging thread on return —
    a polling evaluator would otherwise leak one thread per round."""
    import threading
    import time
    cfg = _tiny_cfg()
    tr = Trainer(cfg)
    tr.init_state()
    it = learnable_synthetic_iterator(16, 8, 4)
    before = {t for t in threading.enumerate()}
    tr.evaluate(it, num_batches=2)
    deadline = time.time() + 5
    while time.time() < deadline:
        leaked = [t for t in threading.enumerate() if t not in before
                  and "drt-device-stage" in t.name and t.is_alive()]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, leaked


def _step_program_cfg(family):
    cfg = _tiny_cfg()
    if family == "vit":
        cfg.model.name = "vit"
        cfg.model.vit_patch_size = 4
        cfg.model.vit_dim = 32
        cfg.model.vit_depth = 2
        cfg.model.vit_heads = 2
        cfg.optimizer.name = "adam"
        cfg.optimizer.learning_rate = 1e-3
        cfg.optimizer.weight_decay = 0.0
    return cfg


def _run_step_program(program, cfg, mesh):
    """Four optimizer steps through one of the Trainer's four train-step
    programs: the single-step programs dispatched four times, the fused
    ones once over the stacked group. Returns (last loss, params)."""
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        shard_batch, shard_stacked_batch)
    k = 4
    rng = np.random.RandomState(5)
    tr = Trainer(cfg, mesh=mesh)
    tr.init_state(seed=0)
    if program.startswith("jitted_index"):
        tr.attach_device_dataset(
            rng.randint(0, 256, (64, 8, 8, 3)).astype(np.uint8),
            rng.randint(0, 4, (64,)).astype(np.int32))
        group = {"idx": rng.randint(0, 64, (k, 16)).astype(np.int32)}
        put_one, put_group = tr._put_idx, tr._put_idx_multi
    else:
        group = {"images": rng.randn(k, 16, 8, 8, 3).astype(np.float32),
                 "labels": rng.randint(0, 4, (k, 16)).astype(np.int32)}
        put_one = lambda b: shard_batch(b, mesh)  # noqa: E731
        put_group = lambda b: shard_stacked_batch(b, mesh)  # noqa: E731
    fn = getattr(tr, program)()
    if "multi" in program:
        state, m = fn(tr.state, put_group(group))
    else:
        state = tr.state
        for i in range(k):
            state, m = fn(state, put_one({n: v[i] for n, v in group.items()}))
    assert int(state.step) == k
    return float(m["loss"]), jax.tree_util.tree_map(np.asarray, state.params)


@pytest.mark.parametrize("program,family", [
    ("jitted_train_step", "resnet"), ("jitted_train_step", "vit"),
    ("jitted_multi_step", "resnet"), ("jitted_multi_step", "vit"),
    ("jitted_index_step", "resnet"), ("jitted_index_step", "vit"),
    # found by this case when it was written (PR 31), on the CPU's eight
    # virtual devices: from the second iteration of the rolled scan on, the
    # gradient lacks the loss's L2 term (grad_norm 3.19652 where one device
    # and the same mesh under train.scan_unroll=4 read 3.19875, and
    # optimizer.weight_decay=0 reads 3.19651 everywhere). The streamed
    # fused step, the single index step and AdamW's ViT all agree with one
    # device, and so does this program over 2 and 4 shards: only 8 shards
    # of two examples each show it. No benchmark cell fuses index steps;
    # ROADMAP has the debt.
    pytest.param("jitted_index_multi_step", "resnet",
                 marks=pytest.mark.xfail(strict=True, reason=(
                     "the rolled index scan on a mesh drops the L2 "
                     "gradient after its first iteration"))),
    ("jitted_index_multi_step", "vit"),
])
def test_step_program_on_a_mesh_matches_one_device(mesh8, program, family):
    """Each of the four programs a training run can dispatch (a streamed
    batch or a device dataset's indices, one step or a fused group of
    four: the four jit sites ``exchange_compiler_options`` reaches) trains
    the same parameters over eight data shards as on one device."""
    from distributed_resnet_tensorflow_tpu.parallel import create_mesh
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig
    one = create_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    loss1, params1 = _run_step_program(program, _step_program_cfg(family), one)
    loss8, params8 = _run_step_program(program, _step_program_cfg(family),
                                       mesh8)
    assert np.isclose(loss1, loss8, rtol=1e-4), (loss1, loss8)
    for a, b in zip(jax.tree_util.tree_leaves(params1),
                    jax.tree_util.tree_leaves(params8)):
        np.testing.assert_allclose(b, a, rtol=2e-3, atol=5e-4)


# ---------------------------------------------------------------------------
# cadence hooks read device metrics one call late; train() flushes (PR 32)
# ---------------------------------------------------------------------------

def _logistic_trainer(k):
    cfg = _tiny_cfg()
    cfg.model.name = "logistic"
    cfg.model.input_size = 8 * 8 * 3
    cfg.train.steps_per_loop = k
    tr = Trainer(cfg)
    tr.init_state(seed=0)
    return tr


def _stream(batches=None):
    """The learnable stream, endless or cut after ``batches``."""
    src = learnable_synthetic_iterator(16, 8, 4, seed=5)
    return src if batches is None else iter([next(src)
                                             for _ in range(batches)])


# how train() comes to its return -> (batches in the stream, num_steps, stop
# after which hook call, the steps whose line must be there on return)
_ENDS = {
    1: {"num_steps": (None, 4, None, [2, 4]),
        "stop_fn": (None, 50, 2, [2]),
        "exhausted": (4, 100, None, [2, 4])},
    3: {"num_steps": (None, 6, None, [3, 6]),
        "stop_fn": (None, 60, 1, [3]),
        "exhausted": (7, 100, None, [3, 6]),
        # 2 fused groups, then one unfused step of a third
        "tail": (None, 7, None, [3, 6, 7])},
}


@pytest.mark.parametrize("k,end", [(k, end) for k in _ENDS for end in _ENDS[k]])
def test_train_flushes_late_hooks_on_every_normal_return(k, end):
    """The last cadence line is printed before train() returns, under its
    own step and with that step's loss, however the loop ends."""
    from distributed_resnet_tensorflow_tpu.train.hooks import LoggingHook
    batches, num_steps, stop_after, want = _ENDS[k][end]
    tr = _logistic_trainer(k)
    lines, calls = [], []
    hooks = (LoggingHook(every_steps={1: 2, 3: 3}[k] if end != "tail" else 1,
                         print_fn=lines.append),
             lambda s, st, m: calls.append((s, m["loss"])))
    state, m = tr.train(
        _stream(batches), num_steps=num_steps, hooks=hooks,
        stop_fn=(lambda: len(calls) >= stop_after) if stop_after else None)
    assert int(state.step) == want[-1]
    loss_at = {s: f"{float(v):.4f}" for s, v in calls}
    assert [ln.split()[:4] for ln in lines] == [
        ["step", str(s), "loss", loss_at[s]] for s in want]


@pytest.mark.parametrize("k", [1, 3])
def test_nan_in_the_last_cadence_dispatch_raises_out_of_train(k):
    """No later hook call would read it: the flush does, before train()
    returns a state nobody checked."""
    from distributed_resnet_tensorflow_tpu.resilience import faultinject
    from distributed_resnet_tensorflow_tpu.train.hooks import NanGuardHook
    tr = _logistic_trainer(k)
    n = 2 * k
    stream = faultinject.inject_nan(_stream(), at_batch=n)
    with pytest.raises(NanGuardHook.NanLossError, match=f"at step {n}$"):
        tr.train(stream, num_steps=n, hooks=(NanGuardHook(every_steps=k),))


@pytest.mark.parametrize("k", [1, 3])
def test_late_read_comes_after_the_next_dispatch_was_entered(k):
    """The point of reading late: when the loop waits for the kept step's
    values, the following train.step has been enqueued. A recording step
    in the compiled one's place shows the order; the span counts the
    reads."""
    from distributed_resnet_tensorflow_tpu.train.hooks import (
        LoggingHook, NanGuardHook)
    from distributed_resnet_tensorflow_tpu.utils.metrics import input_stages
    tr = _logistic_trainer(k)
    events = []

    def fake_step(state, batch):
        events.append("dispatch")
        return state, {"loss": jnp.asarray(float(len(events)))}
    tr._jitted_train = tr._jitted_multi = fake_step
    hooks = (NanGuardHook(every_steps=2 * k),
             LoggingHook(every_steps=2 * k,
                         print_fn=lambda s: events.append(s.split()[1])))
    input_stages.reset()
    tr.train(_stream(), num_steps=4 * k, hooks=hooks)
    # dispatches end at k, 2k, 3k, 4k; 2k is read after the third was
    # entered, 4k by the flush
    assert events == ["dispatch", "dispatch", "dispatch", str(2 * k),
                      "dispatch", str(4 * k)]
    assert input_stages.snapshot()["train.hook_read"]["count"] == 4


@pytest.mark.parametrize("k,step_s,waits", [(3, 0.8, 4), (1, 1.0, 4), (1, 0.05, 2)])
def test_the_loop_waits_for_the_dispatch_its_lead_back(k, step_s, waits, monkeypatch):
    """The loop's lead is device memory (a dispatch's group and outputs are
    allocated when it is enqueued) and what a stop_fn waits behind: after
    sending dispatch n the fused loop waits for n - 2, and so does the
    one-step loop until it has waited twice; from the time between it
    takes its lead as STEP_LEAD_SECONDS of device work: 2 steps of a
    second, 40 of 50 ms (more than are ever sent here: it waits no more)."""
    from distributed_resnet_tensorflow_tpu.train import loop
    from distributed_resnet_tensorflow_tpu.utils.metrics import input_stages
    assert (loop.FUSED_DISPATCH_LEAD, loop.STEP_LEAD_SECONDS) == (2, 2.0)
    lead = 2
    tr = _logistic_trainer(k)
    events = []
    clock = [100.0]
    monkeypatch.setattr(loop.time, "perf_counter", lambda: clock[0])

    class Sent:  # a leaf jax.block_until_ready asks to block
        def __init__(self, n):
            self.n = n

        def block_until_ready(self):
            clock[0] += step_s
            events.append(f"waited {self.n}")

    def fake_step(state, batch):
        n = sum(e.startswith("sent") for e in events) + 1
        events.append(f"sent {n}")
        return state, {"loss": Sent(n)}
    tr._jitted_multi = tr._jitted_train = fake_step
    input_stages.reset()
    tr.train(_stream(), num_steps=(lead + 4) * k)
    want = [f"sent {n}" for n in range(1, lead + 1)]
    for n in range(1, 5):  # from dispatch lead + 1 on, one wait a dispatch
        want += [f"sent {lead + n}"] + [f"waited {n}"] * (n <= waits)
    assert events == want
    assert input_stages.snapshot()["train.lead_wait"]["count"] == waits
