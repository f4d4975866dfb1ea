"""The FLOP functions against the parameter shapes of the two presets."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.flops import resnet_v2 as rn_flops, vit as vit_flops
from benchmark.harness import spec
from benchmark.reference import resnet_v2, vit


def _config(name):
    with open(os.path.join(spec.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_vit_macs_are_the_kernels_times_the_tokens_plus_attention():
    model = _config("vit_l16_224")["model"]
    shapes = vit._shapes(model)
    tokens = (model["image_size"] // model["vit_patch_size"]) ** 2
    kernels = sum(int(jnp.prod(jnp.asarray(s))) for n, s in shapes.items()
                  if n.endswith("kernel") and not n.startswith("head"))
    attention = model["vit_depth"] * 2 * tokens * tokens * model["vit_dim"]
    head = model["vit_dim"] * model["num_classes"]
    assert vit_flops.forward_macs(model) == kernels * tokens + attention + head
    # 304 M parameters, 367 GFLOP a training example (PR 22's readings imply 367)
    assert sum(int(jnp.prod(jnp.asarray(s))) for s in shapes.values()) == \
        pytest.approx(304e6, rel=0.01)
    assert vit_flops.train_flops_per_example(model) == pytest.approx(367.4e9, rel=1e-3)


def test_resnet50_macs_match_the_published_count_and_the_parameters():
    model = _config("resnet50_v2_imagenet")["model"]
    shapes = resnet_v2._shapes(model)
    assert sum(int(jnp.prod(jnp.asarray(s))) for s in shapes.values()) == \
        pytest.approx(25.55e6, rel=0.01)
    # He et al. give 3.8e9 for the v1 network with the stride on the first
    # 1x1; with it on the 3x3, as here and in the TF official model, 4.1e9
    assert rn_flops.forward_macs(model) == pytest.approx(4.09e9, rel=0.01)
    assert rn_flops.train_flops_per_example(model) == pytest.approx(24.5e9, rel=0.01)


@pytest.mark.parametrize("size", [64, 96])
def test_resnet_macs_agree_with_xla_on_the_reference_forward(size):
    model = dict(_config("resnet50_v2_imagenet")["model"], image_size=size)
    params = jax.eval_shape(lambda k: resnet_v2.init_params(k, model), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, size, size, 3), jnp.float32)
    # the scanned blocks are counted once by XLA: unroll them for the count
    blocks = dict(resnet_v2.BLOCKS)
    cost = jax.jit(lambda p, a: resnet_v2.logits(p, a, model)).lower(params, x) \
        .compile().cost_analysis()
    scanned = sum(n - 2 for n in blocks[50])  # bodies XLA does not repeat
    assert scanned == 8
    macs = rn_flops.forward_macs(model)
    # between the count with every scan body once and the full count
    assert cost["flops"] / 2 < macs * 1.02
    assert cost["flops"] / 2 > macs * 0.3


def test_the_program_has_the_leaves_the_flops_are_counted_from():
    """The reference's shapes are the program's: same leaves, same sizes."""
    from distributed_resnet_tensorflow_tpu.models import create_model
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset
    for name, ref, dataset in (("resnet50_v2_imagenet", resnet_v2, "imagenet"),
                               ("vit_l16_224", vit, "synthetic")):
        config = _config(name)
        cfg = get_preset(config["preset"])
        model = create_model(cfg.model, dataset)
        size = config["model"]["image_size"]
        tree = jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, size, size, 3)),
                                                   train=False), jax.random.PRNGKey(0))
        theirs = {"/".join(str(getattr(k, "key", k)) for k in path): leaf.shape
                  for path, leaf in jax.tree_util.tree_flatten_with_path(tree["params"])[0]}
        ours = ref._shapes(config["model"])
        paths = ref.program_paths(config["model"])
        assert {paths[n]: tuple(s) for n, s in ours.items()} == theirs
