"""Low-precision hot paths (parallel/precision.py + serve variants;
docs/precision.md).

The load-bearing claims, pinned on the virtual 8-device mesh:

  * with ``train.precision=off`` NOTHING changes: the policy resolves
    to None, the model keeps its configured compute dtype — and runs are
    bitwise deterministic (the off path is byte-for-byte the pre-policy step; no
    policy code touches it);
  * the bf16 step is allclose to the f32 oracle at the documented
    tolerances on dp AND dp_fsdp, for momentum and LAMB, with and
    without ZeRO-1 — while every persisted leaf stays an f32 MASTER;
  * checkpoints are policy-agnostic: an f32-master checkpoint written
    under a bf16 policy restores bit-exactly into an off-policy trainer
    (and vice versa), including the per-host sharded layout and the
    serving hot swap of a bf16 variant;
  * serving variants are strict: unknown variants and wrong request
    dtypes are rejected loudly; a bf16 variant bucket answers requests
    close to the f32 variant and hot swaps rebuild every variant.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_resnet_tensorflow_tpu.parallel import create_mesh
from distributed_resnet_tensorflow_tpu.parallel.precision import (
    check_master_dtypes, precision_stats, resolve_precision,
    resolve_serve_variants)
from distributed_resnet_tensorflow_tpu.train import Trainer
from distributed_resnet_tensorflow_tpu.utils.config import (MeshConfig,
                                                            get_preset)

#: documented bf16-vs-f32 tolerances (docs/precision.md): after a few
#: optimizer steps the cast paths agree with the f32 oracle to bf16
#: rounding amplified through the loss curvature — elementwise within
#: (rtol, atol), globally within a relative-L2 drift bound. LAMB's
#: layer-wise trust ratio rescales whole layers, so its elementwise tail
#: is wider at the same (tiny) global drift; its tests also pin the LR
#: to a sane LAMB range (the default 0.1 is a momentum number — at that
#: LR even two f32 runs with different reduction orders diverge).
BF16_TOL = {"momentum": dict(rtol=0.12, atol=5e-2),
            "lamb": dict(rtol=0.2, atol=0.15)}
BF16_REL_L2 = 0.05
#: loss agreement after a few steps (the trajectory-parity check)
BF16_LOSS_ATOL = 5e-2


def _assert_bf16_close(on, off, opt, m_on, m_off):
    np.testing.assert_allclose(on, off, **BF16_TOL[opt])
    drift = np.linalg.norm(on - off) / max(np.linalg.norm(off), 1e-9)
    assert drift < BF16_REL_L2, f"relative L2 drift {drift:.4f}"
    assert abs(float(m_off["loss"]) - float(m_on["loss"])) < BF16_LOSS_ATOL
    # short-horizon top-1 parity on the training batch itself
    assert abs(float(m_off["precision"]) -
               float(m_on["precision"])) <= 0.25


def _tiny_cfg(**kw):
    cfg = get_preset("smoke")
    cfg.model.compute_dtype = "float32"
    cfg.model.resnet_size = 8
    cfg.model.num_classes = 4
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    cfg.optimizer.schedule = "constant"
    cfg.checkpoint.save_every_secs = 0.0
    for k, v in kw.items():
        cfg.override(k, v)
    return cfg


def _fixed_batches(n=3, bs=16, size=8, classes=4):
    rng = np.random.RandomState(7)
    imgs = rng.randn(n, bs, size, size, 3).astype(np.float32)
    labs = rng.randint(0, classes, (n, bs)).astype(np.int32)
    return [{"images": imgs[i], "labels": labs[i]} for i in range(n)]


def _flat_params(state):
    return np.concatenate([np.asarray(l, np.float32).ravel() for l in
                           jax.tree_util.tree_leaves(state.params)])


def _train(mesh_cfg, batches, **kw):
    cfg = _tiny_cfg(**kw)
    tr = Trainer(cfg, mesh=create_mesh(mesh_cfg))
    tr.init_state()
    state, metrics = tr.train(iter(list(batches)), num_steps=len(batches))
    return tr, state, _flat_params(state), metrics


# ---------------------------------------------------------------------------
# the off path: bit-identical, policy-free (the acceptance pin)
# ---------------------------------------------------------------------------

def test_precision_off_is_policy_free_and_deterministic(devices):
    """train.precision=off must leave NO policy machinery on the step:
    the resolver returns None, the model keeps the configured compute
    dtype, and two identical runs are BITWISE equal — together with the
    resolver being the only entry point, this pins the off path to the
    pre-policy (PR 11) step."""
    cfg = _tiny_cfg()
    assert cfg.train.precision == "off"
    assert resolve_precision(cfg) is None
    batches = _fixed_batches()
    tr, _, a, m1 = _train(MeshConfig(data=8), batches)
    assert not tr.precision_active
    assert tr.model.dtype == jnp.float32  # configured dtype untouched
    _, _, b, m2 = _train(MeshConfig(data=8), batches)
    np.testing.assert_array_equal(a, b)
    assert float(m1["loss"]) == float(m2["loss"])


def test_fp16_step_refused_with_reason():
    cfg = _tiny_cfg()
    cfg.train.precision = "fp16"
    with pytest.raises(ValueError, match="loss scaling"):
        resolve_precision(cfg)
    cfg.train.precision = "maybe"
    with pytest.raises(ValueError, match="unknown"):
        resolve_precision(cfg)


# ---------------------------------------------------------------------------
# bf16 step vs the f32 oracle (the acceptance claim)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mesh_cfg,opt,zero1", [
    (MeshConfig(data=8), "momentum", "off"),
    (MeshConfig(data=4, fsdp=2), "momentum", "off"),
    # lamb_zero1 legs re-tiered out of the 870s tier-1 (ISSUE 13): the
    # momentum legs pin the bf16-vs-f32 oracle; the LAMB×ZeRO-1
    # composition re-runs it with the heaviest optimizer and stays in
    # the full (unfiltered) suite
    pytest.param(MeshConfig(data=8), "lamb", "on",
                 marks=pytest.mark.slow),
    pytest.param(MeshConfig(data=4, fsdp=2), "lamb", "on",
                 marks=pytest.mark.slow),
], ids=["momentum-dp", "momentum-dp_fsdp", "lamb_zero1-dp",
        "lamb_zero1-dp_fsdp"])
def test_bf16_step_allclose_vs_f32_oracle(mesh_cfg, opt, zero1):
    """bf16 activations/matmuls over f32 masters vs the all-f32 oracle:
    params allclose at the documented tolerance, loss trajectory within
    BF16_LOSS_ATOL after a few steps, and every float state leaf still a
    float32 MASTER (the checkpoint contract)."""
    batches = _fixed_batches()
    kw = {"optimizer.name": opt}
    if opt == "lamb":
        kw.update({"optimizer.weight_decay": "1e-4",
                   "optimizer.learning_rate": "0.02"})
    if zero1 == "on":
        kw.update({"optimizer.zero1": "on",
                   "optimizer.zero1_min_size": "16"})
    _, _, off, m0 = _train(mesh_cfg, batches, **kw)
    tr, st, on, m1 = _train(mesh_cfg, batches, **kw,
                            **{"train.precision": "bf16"})
    assert tr.precision_active
    assert tr.model.dtype == jnp.bfloat16  # the policy override landed
    _assert_bf16_close(on, off, opt, m1, m0)
    # masters: every float leaf of params AND optimizer state is f32
    check_master_dtypes(st.params)
    for leaf in jax.tree_util.tree_leaves(st.opt_state):
        if hasattr(leaf, "dtype") and \
                jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32


@pytest.mark.parametrize("opt,zero1", [
    # the lamb leg re-tiered out of the 870s tier-1 (ISSUE 13); the
    # momentum_zero1 leg stays as the cheap remaining-matrix pin
    pytest.param("lamb", "off", marks=pytest.mark.slow),
    ("momentum", "on"),
], ids=["lamb", "momentum_zero1"])
def test_bf16_step_allclose_remaining_matrix_dp(opt, zero1):
    """The other half of the (optimizer × zero1) matrix on dp — lamb
    without ZeRO-1, momentum with — so every pairing is covered."""
    batches = _fixed_batches()
    kw = {"optimizer.name": opt}
    if opt == "lamb":
        kw.update({"optimizer.weight_decay": "1e-4",
                   "optimizer.learning_rate": "0.02"})
    if zero1 == "on":
        kw.update({"optimizer.zero1": "on",
                   "optimizer.zero1_min_size": "16"})
    _, _, off, m0 = _train(MeshConfig(data=8), batches, **kw)
    _, _, on, m1 = _train(MeshConfig(data=8), batches, **kw,
                          **{"train.precision": "bf16"})
    _assert_bf16_close(on, off, opt, m1, m0)


# ---------------------------------------------------------------------------
# checkpoints stay policy-agnostic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sharded", ["off", "on"], ids=["single", "sharded"])
def test_f32_master_checkpoint_roundtrip_under_bf16_policy(tmp_path,
                                                           sharded,
                                                           devices):
    """Train under the bf16 policy, save, restore into an OFF-policy
    trainer: every restored leaf is f32 and bit-equal — the checkpoint
    never learns what policy wrote it. Covers the per-host sharded
    layout too (checkpoint/shards.py)."""
    from distributed_resnet_tensorflow_tpu.checkpoint import (
        CheckpointManager)
    batches = _fixed_batches(n=2)
    kw = {"train.precision": "bf16"}
    if sharded == "on":
        kw["checkpoint.sharded"] = "on"
    tr, st, flat, _ = _train(MeshConfig(data=8), batches, **kw)
    d = os.path.join(str(tmp_path), "ckpt")
    mngr = CheckpointManager(d, async_save=False, sharded=sharded)
    mngr.save(2, st, force=True)
    mngr.close()
    # restore into a policy-OFF trainer (same shapes)
    cfg2 = _tiny_cfg()
    tr2 = Trainer(cfg2, mesh=create_mesh(MeshConfig(data=8)))
    tr2.init_state()
    mngr2 = CheckpointManager(d, async_save=False, sharded=sharded)
    restored, rstep = mngr2.restore(tr2.state)
    mngr2.close()
    assert rstep == 2
    check_master_dtypes(restored.params)
    np.testing.assert_array_equal(_flat_params(restored), flat)
    # the reverse direction (off-written → bf16-policy trainer) is the
    # same bytes into the same f32 abstract state — covered by the
    # master-dtype guard in Trainer.init_state + this equality


# ---------------------------------------------------------------------------
# serving variants
# ---------------------------------------------------------------------------

def _serve_cfg(tmp_path, **kw):
    cfg = _tiny_cfg(**kw)
    cfg.data.eval_batch_size = 8        # one bucket: [8]
    cfg.log_root = str(tmp_path)
    cfg.checkpoint.directory = os.path.join(str(tmp_path), "ckpt")
    cfg.checkpoint.async_save = False
    cfg.serve.max_queue_delay_ms = 20.0
    cfg.serve.poll_interval_secs = 0.2
    return cfg


def test_resolve_serve_variants_strict():
    cfg = _tiny_cfg()
    assert resolve_serve_variants(cfg) == ("f32",)
    cfg.serve.variants = ("bf16", "f32", "bf16")
    assert resolve_serve_variants(cfg) == ("bf16", "f32")  # deduped, ordered
    cfg.serve.variants = ("int8",)  # weight-only quantized serving
    assert resolve_serve_variants(cfg) == ("int8",)
    cfg.serve.variants = ("int4",)
    with pytest.raises(ValueError, match="int4"):
        resolve_serve_variants(cfg)
    # CLI override coercion keeps string tuples as strings
    cfg2 = _tiny_cfg()
    cfg2.override("serve.variants", "f32,bf16")
    assert cfg2.serve.variants == ("f32", "bf16")


#: pinned parity bound for the int8 weight-only variant vs the f32
#: variant on the same params (docs/precision.md): per-output-channel
#: symmetric quantization keeps serving logits within this relative L2
INT8_PARITY_REL_L2 = 0.05


def test_int8_quantizer_roundtrip_bound():
    """Per-channel symmetric int8: dequantized weights sit within half a
    quantization step of the original, per OUTPUT channel — the static
    half of the serving parity bound."""
    from distributed_resnet_tensorflow_tpu.parallel.precision import (
        INT8_QMAX, dequantize_params, quantize_leaf_int8)
    rng = np.random.RandomState(0)
    w = (rng.randn(3, 3, 8, 16) * rng.rand(16) * 3).astype(np.float32)
    q = quantize_leaf_int8(w)
    assert q["int8_q"].dtype == jnp.int8 and q["int8_scale"].shape == (16,)
    deq = dequantize_params({"k": q})["k"]
    step = np.asarray(q["int8_scale"])
    assert np.all(np.abs(np.asarray(deq) - w) <= step / 2 + 1e-7)
    # scales are per-channel maxima / 127
    np.testing.assert_allclose(
        step, np.abs(w).max(axis=(0, 1, 2)) / float(INT8_QMAX), rtol=1e-6)


def test_int8_variant_serves_within_parity_bound(tmp_path, devices):
    """The int8 weight-only serving variant: kernels live int8-at-rest
    (a real ~4× cut on quantized leaves), biases/norm leaves stay f32,
    AOT warm covers the variant (no serve-time compile), and its logits
    stay within the pinned parity bound of the f32 variant."""
    from distributed_resnet_tensorflow_tpu.serve.server import (
        InferenceServer)
    cfg = _serve_cfg(tmp_path)
    cfg.serve.variants = ("f32", "int8")
    server = InferenceServer(cfg)
    server.start(start_threads=False)
    leaves = jax.tree_util.tree_leaves(server._states["int8"].params)
    int8_bytes = sum(int(l.size) for l in leaves if l.dtype == jnp.int8)
    f32_bytes = sum(int(l.size) * 4 for l in leaves
                    if l.dtype == jnp.float32)
    assert int8_bytes > 0 and int8_bytes > 4 * f32_bytes, \
        (int8_bytes, f32_bytes)  # the kernels really are int8 at rest
    rng = np.random.RandomState(0)
    img = rng.randn(8, 8, 3).astype(np.float32)
    fut32 = server.submit(img, variant="f32")
    fut8 = server.submit(img, variant="int8")
    served = 0
    while served < 2:
        served += server.service_once(block_secs=0.5)
    row32, _ = fut32.result(timeout=5)
    row8, _ = fut8.result(timeout=5)
    rel = np.linalg.norm(row8 - row32) / (np.linalg.norm(row32) + 1e-9)
    assert rel < INT8_PARITY_REL_L2, rel
    assert server.cache.serve_time_compiles == 0
    server.close()


@pytest.mark.heavy
def test_bf16_variant_serves_and_hot_swap_rebuilds(tmp_path, devices):
    """A (bucket, bf16) variant answers requests close to the f32
    variant; unknown variants and wrong dtypes are rejected loudly; and
    a hot swap rebuilds EVERY variant from the new f32 masters (the bf16
    copy must never serve a stale checkpoint)."""
    from distributed_resnet_tensorflow_tpu.checkpoint import (
        CheckpointManager)
    from distributed_resnet_tensorflow_tpu.serve.server import (
        InferenceServer)
    cfg = _serve_cfg(tmp_path)
    cfg.serve.variants = ("f32", "bf16")
    server = InferenceServer(cfg)
    server.start(start_threads=False)
    assert server.variants == ("f32", "bf16")
    # the bf16 variant's weight copy is genuinely bf16
    bf_leaves = jax.tree_util.tree_leaves(server._states["bf16"].params)
    assert all(l.dtype == jnp.bfloat16 for l in bf_leaves
               if jnp.issubdtype(l.dtype, jnp.floating))
    check_master_dtypes(server._states["f32"].params)

    rng = np.random.RandomState(0)
    img = rng.randn(8, 8, 3).astype(np.float32)
    fut32 = server.submit(img)                      # default = f32
    fut16 = server.submit(img, variant="bf16")
    served = 0
    while served < 2:
        served += server.service_once(block_secs=0.5)
    row32, _ = fut32.result(timeout=5)
    row16, _ = fut16.result(timeout=5)
    # two dispatches: the variant change splits the group
    assert server.batcher.batches == 2
    np.testing.assert_allclose(row16, row32, rtol=0.1, atol=0.1)
    assert not np.array_equal(row16, row32)  # genuinely bf16 compute
    # per-variant latency keys (the (batch, variant) breakdown)
    keys = set(server.latency.summary_ms())
    assert {"bucket_8", "bucket_8_bf16"} <= keys
    # strict validation: unknown variant, wrong dtype
    with pytest.raises(ValueError, match="variant"):
        server.submit(img, variant="int8")
    with pytest.raises(ValueError, match="dtype"):
        server.submit((img * 255).astype(np.uint8))
    # zero request-time compiles: warm covered every (bucket, variant)
    assert server.cache.serve_time_compiles == 0

    # hot swap: publish rescaled params; BOTH variants must rebuild
    st = server.trainer.state

    def host(x):
        return np.asarray(x)

    params = jax.tree_util.tree_map(lambda x: host(x) * 0.5, st.params)
    st2 = st.replace(step=np.asarray(7, np.int32), params=params,
                     batch_stats=jax.tree_util.tree_map(host,
                                                        st.batch_stats),
                     opt_state=jax.tree_util.tree_map(host, st.opt_state))
    mngr = CheckpointManager(cfg.checkpoint.directory, async_save=False)
    mngr.save(7, st2, force=True)
    mngr.close()
    assert server.swapper.poll_once() is not None
    server.service_once()                     # boundary hook applies it
    assert server.serving_step == 7
    f32_now = np.asarray(jax.tree_util.tree_leaves(
        server._states["f32"].params)[0])
    bf16_now = jax.tree_util.tree_leaves(server._states["bf16"].params)[0]
    assert bf16_now.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(bf16_now, np.float32), f32_now, rtol=0.01, atol=1e-3)
    server.close()
    assert server.dropped == 0


def test_f32_variant_stays_full_precision_under_bf16_policy(tmp_path,
                                                            devices):
    """A serving config that carries train.precision=bf16 (the
    large-batch presets do) must still serve the f32 VARIANT in full
    precision: the trainer's own predict step computes in the policy
    dtype, so the cache needs a dedicated f32-compute program — without
    it both variants silently compute bf16 and the f32 oracle contract
    is broken (review finding, pinned here)."""
    from distributed_resnet_tensorflow_tpu.serve.server import (
        InferenceServer)
    cfg = _serve_cfg(tmp_path, **{"train.precision": "bf16"})
    cfg.serve.variants = ("f32", "bf16")
    cfg.serve.warm_buckets = False     # inspect programs, skip compiles
    server = InferenceServer(cfg)
    server.start(start_threads=False)  # builds the lazy variant states
    assert server.trainer.precision_active
    # the cache's f32 entry is NOT the trainer's policy-cast step
    assert server.cache._predicts["f32"] is not \
        server.trainer._predict_step
    rng = np.random.RandomState(0)
    batch = {"images": rng.randn(1, 8, 8, 3).astype(np.float32)}
    f32_logits = np.asarray(server.cache._predicts["f32"](
        server._states["f32"], batch))
    bf16_logits = np.asarray(server.cache._predicts["bf16"](
        server._states["bf16"], batch))
    policy_logits = np.asarray(server.trainer._predict_step(
        server._states["f32"], batch))
    # f32 variant ≠ the bf16-compute outputs; bf16 variant ≈ the policy
    assert not np.array_equal(f32_logits, bf16_logits)
    np.testing.assert_allclose(bf16_logits, policy_logits, rtol=0.05,
                               atol=0.05)
    server.close()


# ---------------------------------------------------------------------------
# telemetry: the precision row
# ---------------------------------------------------------------------------

def test_precision_event_row(tmp_path, devices):
    from distributed_resnet_tensorflow_tpu.train.hooks import PrecisionHook
    from distributed_resnet_tensorflow_tpu.utils.metrics import (
        MetricsWriter, read_metrics)
    precision_stats.reset()
    batches = _fixed_batches(n=2)
    cfg = _tiny_cfg(**{"train.precision": "bf16"})
    tr = Trainer(cfg, mesh=create_mesh(MeshConfig(data=8)))
    assert tr.precision_active
    tr.init_state()
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False)
    tr.train(iter(batches), num_steps=2,
             hooks=(PrecisionHook(w, every_steps=1),))
    w.close()
    prows = [r for r in read_metrics(str(tmp_path))
             if r.get("event") == "precision"]
    assert len(prows) == 1        # one row per resolved policy
    assert prows[0]["policy"] == "bf16"
    assert prows[0]["compute_dtype"] == "bfloat16"
    assert prows[0]["master_dtype"] == "float32"
    assert prows[0]["master_param_bytes"] > 0


def test_precision_events_registered():
    from distributed_resnet_tensorflow_tpu.telemetry.tracer import (
        SPAN_CATALOG)
    from distributed_resnet_tensorflow_tpu.utils.metrics import (
        EVENT_SCHEMAS)
    assert EVENT_SCHEMAS["precision"]["fields"]
    assert "serve.variant_build" in SPAN_CATALOG


# ---------------------------------------------------------------------------
# presets
# ---------------------------------------------------------------------------

def test_large_batch_presets_carry_the_bf16_recipe():
    """The arXiv:1811.05233 recipe shape rides the large-batch presets:
    a bf16 step; the accuracy-replay presets stay f32-off (the oracle)."""
    for name in ("imagenet_resnet50_lars32k", "imagenet_resnet50_lars4k",
                 "imagenet_resnet50_lamb4k"):
        cfg = get_preset(name)
        assert cfg.train.precision == "bf16", name
        assert resolve_precision(cfg) is not None
    for name in ("cifar10_resnet50", "imagenet_resnet50", "smoke"):
        cfg = get_preset(name)
        assert cfg.train.precision == "off", name
