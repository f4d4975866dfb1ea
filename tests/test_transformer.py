"""ViT model family tests — attention-based models through the same
Trainer/config path as the ResNets."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_resnet_tensorflow_tpu.models import VisionTransformer, create_model
from distributed_resnet_tensorflow_tpu.utils.config import ModelConfig, get_preset


def test_vit_shapes_and_dtype():
    model = VisionTransformer(num_classes=10, patch_size=4, dim=32, depth=2,
                              num_heads=2, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x)
    logits = model.apply(variables, x)
    assert logits.shape == (2, 10) and logits.dtype == jnp.float32


def test_vit_attention_impls_agree():
    """dense and blockwise attention give the same model output."""
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 16, 3), jnp.float32)
    outs = []
    for impl in ("dense", "blockwise"):
        model = VisionTransformer(num_classes=4, patch_size=4, dim=32,
                                  depth=1, num_heads=2, dtype=jnp.float32,
                                  attention_impl=impl)
        variables = model.init(jax.random.PRNGKey(0), x)
        outs.append(np.asarray(model.apply(variables, x)))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-5, atol=2e-5)


def test_vit_invalid_configs():
    x = jnp.zeros((1, 30, 30, 3))
    with pytest.raises(ValueError):
        VisionTransformer(patch_size=4).init(jax.random.PRNGKey(0), x)
    x2 = jnp.zeros((1, 32, 32, 3))
    with pytest.raises(ValueError):
        VisionTransformer(dim=30, num_heads=4).init(jax.random.PRNGKey(0), x2)


def test_vit_trains_through_trainer():
    from distributed_resnet_tensorflow_tpu.data import learnable_synthetic_iterator
    from distributed_resnet_tensorflow_tpu.train import Trainer
    cfg = get_preset("smoke")
    cfg.model.name = "vit"
    cfg.model.num_classes = 4
    cfg.model.compute_dtype = "float32"
    cfg.model.vit_dim = 32
    cfg.model.vit_depth = 1
    cfg.model.vit_heads = 2
    cfg.data.image_size = 8
    cfg.train.batch_size = 16
    cfg.optimizer.name = "adam"
    cfg.optimizer.schedule = "constant"
    cfg.optimizer.learning_rate = 1e-3
    cfg.optimizer.weight_decay = 0.0
    tr = Trainer(cfg)
    tr.init_state()
    it = learnable_synthetic_iterator(16, 8, 4, seed=2)
    losses = []
    from distributed_resnet_tensorflow_tpu.parallel import shard_batch
    step = tr.jitted_train_step()
    for _ in range(25):
        tr.state, m = step(tr.state, shard_batch(next(it), tr.mesh))
        losses.append(float(m["cross_entropy"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_create_model_vit_factory():
    cfg = ModelConfig(name="vit", num_classes=10, compute_dtype="float32")
    m = create_model(cfg, "cifar10")
    assert isinstance(m, VisionTransformer)


def _mesh(**axes):
    from distributed_resnet_tensorflow_tpu.parallel import create_mesh
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig
    return create_mesh(MeshConfig(**axes))


def _small_vit(impl, mesh=None):
    return VisionTransformer(num_classes=4, patch_size=4, dim=32, depth=2,
                             num_heads=4, dtype=jnp.float32,
                             attention_impl=impl, mesh=mesh)


@pytest.mark.heavy
def test_vit_ring_matches_dense_full_model():
    """Sequence parallelism as a MODEL feature: ring attention + seq-sharded
    tokens through the full ViT must reproduce the dense model's logits AND
    parameter gradients (VERDICT r1 item 7)."""
    mesh = _mesh(data=2, sequence=4)
    x = jnp.asarray(np.random.RandomState(0).randn(4, 16, 16, 3), jnp.float32)
    labels = jnp.asarray([0, 1, 2, 3])

    dense = _small_vit("dense")
    ring = _small_vit("ring", mesh=mesh)
    variables = dense.init(jax.random.PRNGKey(0), x)

    def loss(model):
        def fn(params, x):
            logits = model.apply({"params": params}, x)
            onehot = jax.nn.one_hot(labels, 4)
            return -(jax.nn.log_softmax(logits) * onehot).sum(), logits
        return fn

    (ld, logits_d), grads_d = jax.jit(
        jax.value_and_grad(loss(dense), has_aux=True))(variables["params"], x)
    (lr, logits_r), grads_r = jax.jit(
        jax.value_and_grad(loss(ring), has_aux=True))(variables["params"], x)
    np.testing.assert_allclose(np.asarray(logits_r), np.asarray(logits_d),
                               rtol=2e-5, atol=2e-5)
    assert np.isclose(float(lr), float(ld), rtol=1e-5)
    for gd, gr in zip(jax.tree_util.tree_leaves(grads_d),
                      jax.tree_util.tree_leaves(grads_r)):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   rtol=2e-4, atol=2e-5)


def test_vit_tensor_parallel_matches_unsharded():
    """Megatron-style tensor parallelism (qkv/proj/mlp over `tensor`) must
    be numerically invisible: same logits as the unsharded model, with the
    kernels actually sharded in the train state (VERDICT r1 item 8)."""
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        param_sharding_rule, tree_param_shardings)
    mesh = _mesh(data=2, tensor=4)
    x = jnp.asarray(np.random.RandomState(1).randn(4, 16, 16, 3), jnp.float32)

    plain = _small_vit("dense")
    tp = _small_vit("dense", mesh=mesh)
    variables = plain.init(jax.random.PRNGKey(0), x)

    # the rule shards the four block projections over `tensor`
    shardings = tree_param_shardings(variables["params"], mesh)
    flat = {"/".join(str(p) for p in path): s for path, s in
            jax.tree_util.tree_flatten_with_path(shardings)[0]}
    qkv = [s for name, s in flat.items() if "qkv" in name and "kernel" in name]
    assert qkv and all("tensor" in str(s.spec) for s in qkv)
    proj = [s for name, s in flat.items() if "proj" in name and "kernel" in name]
    assert proj and all("tensor" in str(s.spec) for s in proj)

    # sharded params + constrained activations == unsharded numerics
    sharded_params = jax.device_put(variables["params"], shardings)
    out_plain = np.asarray(jax.jit(
        lambda p, x: plain.apply({"params": p}, x))(variables["params"], x))
    out_tp = np.asarray(jax.jit(
        lambda p, x: tp.apply({"params": p}, x))(sharded_params, x))
    np.testing.assert_allclose(out_tp, out_plain, rtol=2e-5, atol=2e-5)


def test_vit_flash_runs_per_shard_under_a_mesh():
    """The Pallas kernel cannot be partitioned by GSPMD (on the chip a
    batch-sharded jit of it refuses to lower — found by chip_smoke on four
    chips, PR 21), so under a mesh it runs per shard inside a full-manual
    shard_map, batch over data and heads over tensor: same logits and
    parameter gradients as dense attention on one device."""
    mesh = _mesh(data=2, tensor=4)
    x = jnp.asarray(np.random.RandomState(2).randn(4, 16, 16, 3), jnp.float32)
    labels = jnp.asarray([0, 1, 2, 3])
    dense = _small_vit("dense")
    flash = _small_vit("flash_interpret", mesh=mesh)
    variables = dense.init(jax.random.PRNGKey(0), x)

    def loss(model):
        def fn(p):
            logits = model.apply({"params": p}, x)
            return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(4), labels])
        return fn

    ld, gd = jax.jit(jax.value_and_grad(loss(dense)))(variables["params"])
    jaxpr = str(jax.make_jaxpr(loss(flash))(variables["params"]))
    assert "shard_map" in jaxpr
    lf, gf = jax.jit(jax.value_and_grad(loss(flash)))(variables["params"])
    np.testing.assert_allclose(float(lf), float(ld), rtol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_vit_ring_routed_through_trainer():
    """mesh.sequence > 1 + attention_impl=auto resolves to ring and trains
    end-to-end through the Trainer."""
    from distributed_resnet_tensorflow_tpu.data import (
        learnable_synthetic_iterator)
    from distributed_resnet_tensorflow_tpu.train import Trainer
    cfg = get_preset("smoke")
    cfg.model.name = "vit"
    cfg.model.num_classes = 4
    cfg.model.compute_dtype = "float32"
    cfg.model.vit_dim = 32
    cfg.model.vit_depth = 1
    cfg.model.vit_heads = 2
    cfg.model.attention_impl = "auto"
    cfg.data.image_size = 8       # 4 tokens with patch 4... use seq=2
    cfg.train.batch_size = 8
    cfg.mesh.data = 4
    cfg.mesh.sequence = 2
    cfg.optimizer.weight_decay = 0.0
    tr = Trainer(cfg)
    assert tr.model.attention_impl == "ring"
    tr.init_state()
    state, m = tr.train(learnable_synthetic_iterator(8, 8, 4), num_steps=2)
    assert int(state.step) == 2
    assert np.isfinite(float(m["loss"]))


def test_dead_mesh_axes_rejected():
    from distributed_resnet_tensorflow_tpu.train import Trainer
    cfg = get_preset("smoke")
    cfg.mesh.data = 4
    cfg.mesh.tensor = 2
    with pytest.raises(ValueError, match="tensor"):
        Trainer(cfg)
    # expert has no consumer in ANY model family yet
    cfg2 = get_preset("smoke")
    cfg2.model.name = "vit"
    cfg2.mesh.data = 4
    cfg2.mesh.expert = 2
    with pytest.raises(ValueError, match="expert"):
        Trainer(cfg2)
    # pipeline for a non-transformer model is rejected
    cfg3 = get_preset("smoke")
    cfg3.mesh.data = 4
    cfg3.mesh.pipeline = 2
    with pytest.raises(ValueError, match="pipeline"):
        Trainer(cfg3)
