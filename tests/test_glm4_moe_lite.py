"""The latent-attention family (model.name="glm4_moe_lite": models/transformer
.CausalDecoder of DecoderBlocks whose attention is LatentAttention, the
multi-token-prediction block of PredictionBlock, sigmoid top-k SwiGLU
experts in models/moe.DroplessMoe) against its plain reference
(benchmark/reference/glm4_moe_lite.py) and against itself, at sizes a CPU
holds, in float32; the flash kernels in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check
from benchmark.reference import follow
from benchmark.reference import glm4_moe_lite as ref
from distributed_resnet_tensorflow_tpu.models import transformer
from distributed_resnet_tensorflow_tpu.models.moe import DroplessMoe
from distributed_resnet_tensorflow_tpu.ops.attention import Mask
from distributed_resnet_tensorflow_tpu.ops.pallas.flash_attention import (
    _BLOCK_TABLES, _pick_blocks)
from distributed_resnet_tensorflow_tpu.parallel.mesh import create_mesh
from distributed_resnet_tensorflow_tpu.telemetry.tracer import SCOPE_CATALOG
from distributed_resnet_tensorflow_tpu.train.loop import Trainer
from distributed_resnet_tensorflow_tpu.utils.config import get_preset

MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 32,
    "layer_types": ["full_attention"] * 3, "num_dense_layers": 1, "rope_theta": 1e6,
    "rms_norm_eps": 1e-5, "intermediate_size": 96, "moe_intermediate_size": 32,
    "experts_published": 8, "experts_held": [0, 4], "num_experts_per_tok": 2,
    "num_shared_experts": 1, "route_scale": 1.8, "load_balance_coeff": 0.001,
    "mup_enabled": False, "vocab_held": 50, "num_nextn_predict_layers": 1,
    "mtp_loss_weight": 0.3, "seq_len": 40, "compute_dtype": "float32"}
OPTIMIZER = {"name": "adamw", "learning_rate": 3e-4, "weight_decay": 0.1,
             "b1": 0.9, "b2": 0.999, "eps": 1e-8, "schedule": "constant"}
CONFIG = {"family": "glm4_moe_lite", "model": MODEL, "optimizer": OPTIMIZER,
          "start_step": 0}
ROWS = 2
#: the reference's names where the program's config has the source's
PROGRAM_KEY = {"seq_len": "data.seq_len", "experts_published": "model.num_experts"}
#: float32 against float32, the same operations in another order (the
#: head's chunks, the walk's sorted windows against a dense sum, the flash
#: kernels' online softmax): rounding alone reads about 1e-6 relative; a
#: bfloat16 operand anywhere reads 1e-3 and over
LOSS_RTOL = 1e-5
GRAD_GAP = 2e-4


@pytest.fixture(scope="module", autouse=True)
def short_chunks():
    """Chunks of the loss small enough that a batch takes several."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(transformer, "LOSS_CHUNK", 16)
        yield


def tiny_cfg(impl: str = "dense", **overrides):
    cfg = get_preset("glm47_flash_share8")
    for key, value in MODEL.items():
        cfg.override(PROGRAM_KEY.get(key, f"model.{key}"), value)
    for key, value in {"model.attention_impl": impl, "train.batch_size": ROWS,
                       "optimizer.schedule": "constant", "mesh.data": 1,
                       **overrides}.items():
        cfg.override(key, value)
    return cfg


def tiny_trainer(impl: str = "dense", **overrides) -> Trainer:
    cfg = tiny_cfg(impl, **overrides)
    trainer = Trainer(cfg, mesh=create_mesh(cfg.mesh, devices=jax.devices()[:1]))
    trainer.init_state(0)
    return trainer


def to_tree(flat, model=MODEL):
    tree = {}
    for name, path in ref.program_paths(model).items():
        node = tree
        *dirs, leaf = path.split("/")
        for d in dirs:
            node = node.setdefault(d, {})
        node[leaf] = flat[name]
    return tree


def to_flat(tree, model=MODEL):
    out = {}
    for name, path in ref.program_paths(model).items():
        node = tree
        for d in path.split("/"):
            node = node[d]
        out[name] = node
    return out


def batches(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    p = np.arange(1, MODEL["vocab_held"] + 1, dtype=np.float64) ** -0.7
    return [{"tokens": rng.choice(MODEL["vocab_held"], (ROWS, MODEL["seq_len"] + 1),
                                  p=p / p.sum()).astype(np.int32)} for _ in range(n)]


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(follow.init_key(11), MODEL)


@pytest.fixture(scope="module")
def dense_trainer():
    return tiny_trainer()


def grad_gaps(mine, theirs):
    """Per leaf: |mine − theirs| over the larger of the reference's norm of
    that leaf and of the median leaf."""
    scale = np.median([float(jnp.linalg.norm(g)) for g in theirs.values()])
    return {n: float(jnp.linalg.norm(mine[n] - g)) / max(float(jnp.linalg.norm(g)), scale)
            for n, g in theirs.items()}


def test_the_programs_leaves_are_the_references(dense_trainer, weights):
    mine = jax.tree_util.tree_map(lambda a: a.shape, dense_trainer.state.params)
    assert mine == jax.tree_util.tree_map(lambda a: a.shape, to_tree(weights))
    names = set(ref.program_paths(MODEL))
    # no head norms, gate or post-norms; one dense layer, two expert layers
    # and one prediction block, whose expert layer the rule moves too
    assert not any("q_norm" in n or "gate_proj" in n or "post_" in n for n in names)
    assert {"layer0.mlp.gate", "layer1.moe.router_bias", "layer2.attn.kv_b_proj",
            "mtp0.eh_proj", "mtp0.enorm.scale", "mtp0.hnorm.scale", "mtp0.norm.scale",
            "mtp0.moe.router_bias", "mtp0.attn.q_a_norm.scale"} <= names
    assert ref.ruled_leaves(MODEL) == ["layer1.moe.router_bias", "layer2.moe.router_bias",
                                       "mtp0.moe.router_bias"]


def _attention_module(impl: str, model=MODEL):
    m = model
    return transformer.LatentAttention(
        m["num_attention_heads"], m["q_lora_rank"], m["kv_lora_rank"],
        m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"], m["rope_theta"],
        m["rms_norm_eps"], jnp.float32, impl)


def _attention_params(weights, prefix="layer1."):
    tree = to_tree(weights)
    return tree[prefix.rstrip(".")]["attn"], {
        n[len(prefix):]: v for n, v in weights.items() if n.startswith(prefix + "attn.")}


@pytest.mark.parametrize("impl", ["dense", "flash_interpret"])
def test_latent_attention_matches_the_reference_forward_and_back(weights, impl):
    """The module alone, on a sequence of 40 positions: output and the
    gradient of every leaf and of the input against the reference's
    ``latent_attention``."""
    module = _attention_module(impl)
    params, flat = _attention_params(weights)
    x = jax.random.normal(jax.random.PRNGKey(3), (ROWS, 40, 64))
    r = jax.random.normal(jax.random.PRNGKey(4), (ROWS, 40, 64))

    def mine(p, x):
        return jnp.sum(module.apply({"params": p}, x) * r)

    def theirs(p, x):
        return sum(jnp.sum(ref.latent_attention(x[i], p, MODEL, lambda a: a) * r[i])
                   for i in range(ROWS))
    with jax.default_matmul_precision("highest"):
        out = module.apply({"params": params}, x)
        want = jnp.stack([ref.latent_attention(x[i], flat, MODEL, lambda a: a)
                          for i in range(ROWS)])
        (gp, gx) = jax.grad(mine, (0, 1))(params, x)
        (hp, hx) = jax.grad(theirs, (0, 1))(flat, x)
    np.testing.assert_allclose(out, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    np.testing.assert_allclose(gx, hx, atol=5e-5 * float(jnp.max(jnp.abs(hx))))
    gp = {"attn." + "/".join(str(getattr(k, "key", k)) for k in path[:-1]).replace("/", ".")
          + ("" if path[-1].key == "kernel" else "." + path[-1].key): leaf
          for path, leaf in jax.tree_util.tree_flatten_with_path(gp)[0]}
    assert set(gp) == set(hp)
    gaps = grad_gaps(gp, hp)
    assert max(gaps.values()) < GRAD_GAP, gaps


def _seen(module, params, x, positions=None):
    """What the module hands the kernels, and their output by head."""
    got = {}
    real = transformer.causal_attention

    def spy(q, k, v, mask, impl, mesh=None):
        got.update(q=q, k=k, v=v)
        got["o"] = real(q, k, v, mask, impl, mesh)
        return got["o"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(transformer, "causal_attention", spy)
        module.apply({"params": params}, x, positions)
    return got


def test_rotary_turns_the_last_rope_dimensions_and_one_key_serves_every_head(weights):
    """The rotary key is ONE vector a token: moving the key/value latent's
    rotary columns moves the last d_r dimensions of every head's key (and
    nothing else of any key) and every head's output; moving one head's
    rotary query columns moves that head's output alone. Rotary turns the
    last d_r dimensions only: with positions shifted, a query's first d_n
    dimensions are what they were, its last d_r are not."""
    module = _attention_module("dense")
    params, _ = _attention_params(weights)
    nope, rope, heads = MODEL["qk_nope_head_dim"], MODEL["qk_rope_head_dim"], 4
    rkv = MODEL["kv_lora_rank"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, 64))
    base = _seen(module, params, x)
    kva = params["kv_a_proj"]["kernel"]
    moved_key = dict(params, kv_a_proj={"kernel": kva.at[:, rkv:].multiply(1.5)})
    got = _seen(module, moved_key, x)
    np.testing.assert_array_equal(got["k"][..., :nope], base["k"][..., :nope])
    np.testing.assert_array_equal(got["q"], base["q"])
    for h in range(heads):
        assert not np.allclose(got["k"][:, :, h, nope:], base["k"][:, :, h, nope:])
        assert not np.allclose(got["o"][:, 1:, h], base["o"][:, 1:, h]), h
    np.testing.assert_array_equal(got["k"][:, :, 0, nope:], got["k"][:, :, 3, nope:])
    qb = params["q_b_proj"]["kernel"]
    head2 = slice(2 * (nope + rope) + nope, 3 * (nope + rope))
    got = _seen(module, dict(params, q_b_proj={"kernel": qb.at[:, head2].multiply(3.0)}), x)
    for h in range(heads):
        assert np.allclose(got["o"][:, :, h], base["o"][:, :, h]) == (h != 2), h
    shifted = _seen(module, params, x, jnp.arange(40) + 7)
    for part in ("q", "k"):
        np.testing.assert_array_equal(shifted[part][..., :nope], base[part][..., :nope])
        assert not np.allclose(shifted[part][..., nope:], base[part][..., nope:])


def test_the_flash_path_refuses_unequal_value_and_key_head_sizes(weights):
    """The kernels take one head size: a v_head_dim other than qk_nope +
    qk_rope is refused there, by name; the dense twin runs it."""
    model = dict(MODEL, v_head_dim=16)
    x = jnp.ones((1, 16, 64))
    for impl in ("flash", "flash_interpret"):
        with pytest.raises(ValueError, match="v_head_dim 16 is not qk_nope_head_dim 24"):
            _attention_module(impl, model).init(jax.random.PRNGKey(0), x)
    out, _ = _attention_module("dense", model).init_with_output(jax.random.PRNGKey(0), x)
    assert out.shape == (1, 16, 64)


def test_a_latent_preset_that_leaves_a_width_unset_is_refused():
    """The config's latent widths default to 0, which latent attention
    refuses by name, so a preset that forgets one fails where it would
    otherwise train at some other width."""
    cfg = get_preset("glm47_flash_share8").model
    assert min(cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
               cfg.qk_rope_head_dim, cfg.v_head_dim) > 0
    from distributed_resnet_tensorflow_tpu.utils.config import ModelConfig
    fresh = ModelConfig()
    assert (fresh.q_lora_rank, fresh.kv_lora_rank, fresh.qk_nope_head_dim,
            fresh.qk_rope_head_dim, fresh.v_head_dim) == (0, 0, 0, 0, 0)
    x = jnp.ones((1, 16, 64))
    with pytest.raises(ValueError, match="kv_lora_rank, v_head_dim not set"):
        _attention_module("dense", dict(MODEL, kv_lora_rank=0, v_head_dim=0)).init(
            jax.random.PRNGKey(0), x)


def losses(trainer, tokens, model=MODEL):
    objective = trainer.model.objective()

    def mine(params):
        ce, metrics, _, _, counts = objective.forward(
            trainer.model.apply, {"params": params, "batch_stats": {}},
            {"tokens": jnp.asarray(tokens)})
        return ce, (metrics, counts)

    def theirs(flat):
        total, counts = ref.loss_sum(flat, ref.examples({"tokens": tokens}, 0, 0),
                                     jnp.ones((ROWS,)), model)
        return total / ROWS, counts
    return mine, theirs


@pytest.mark.parametrize("impl", ["dense", "flash_interpret"])
def test_loss_and_gradients_match_the_reference(dense_trainer, weights, impl):
    """The whole decoder with its prediction block: L_main + 0.3 L_mtp and
    the gradient of every leaf, the routing counts of every expert layer
    (the prediction block's as ``mtp0``)."""
    trainer = dense_trainer if impl == "dense" else tiny_trainer(impl)
    tokens = batches(1)[0]["tokens"]
    mine, theirs = losses(trainer, tokens)
    with jax.default_matmul_precision("highest"):
        (a, (metrics, counts)), grads_a = jax.value_and_grad(mine, has_aux=True)(
            to_tree(weights))
        (b, ref_counts), grads_b = jax.value_and_grad(theirs, has_aux=True)(weights)
    assert abs(float(a) - float(b)) < LOSS_RTOL * abs(float(b))
    gaps = grad_gaps(to_flat(grads_a), grads_b)
    assert max(gaps.values()) < GRAD_GAP, max(gaps.items(), key=lambda kv: kv[1])
    assert sorted(counts) == ["layer1", "layer2", "mtp0"]
    for layer, c in counts.items():
        np.testing.assert_array_equal(c, ref_counts[f"{layer}.moe.router_bias"])
    assert float(metrics["moe_windows"]) == 1.0
    assert 0.0 <= float(metrics["mtp_precision"]) <= 1.0


def test_the_prediction_term_is_three_tenths_of_its_loss_and_reaches_layer0(
        dense_trainer, weights):
    """``mtp_loss`` is the reference's L_mtp (the total at weight 0.3 less
    the total at weight 0, over 0.3), the objective adds 0.3 of it to the
    main loss, and its own gradient reaches the first layer."""
    tokens = batches(1, seed=4)[0]["tokens"]
    mine, _ = losses(dense_trainer, tokens)
    _, main_only = losses(dense_trainer, tokens, dict(MODEL, num_nextn_predict_layers=0))
    _, theirs = losses(dense_trainer, tokens)
    with jax.default_matmul_precision("highest"):
        total, (metrics, _) = mine(to_tree(weights))
        want_total = theirs(weights)[0]
        want_main = main_only({n: v for n, v in weights.items() if not n.startswith("mtp")})[0]
        out = dense_trainer.model.apply({"params": to_tree(weights)},
                                        jnp.asarray(tokens[:, :-1]), train=True,
                                        targets=jnp.asarray(tokens[:, 1:]))
        grads = jax.grad(lambda p: dense_trainer.model.apply(
            {"params": p}, jnp.asarray(tokens[:, :-1]), train=True,
            targets=jnp.asarray(tokens[:, 1:]))["mtp_loss"])(to_tree(weights))
    want_mtp = (float(want_total) - float(want_main)) / 0.3
    assert float(metrics["mtp_loss"]) == pytest.approx(want_mtp, rel=1e-4)
    assert float(total) == pytest.approx(float(out["loss"]) + 0.3 * float(out["mtp_loss"]),
                                         rel=1e-6)
    assert float(total) == pytest.approx(float(want_total), rel=LOSS_RTOL)
    assert float(jnp.linalg.norm(grads["layer0"]["attn"]["q_a_proj"]["kernel"])) > 0
    assert float(jnp.linalg.norm(grads["layer0"]["mlp"]["up"]["kernel"])) > 0
    assert float(jnp.linalg.norm(grads["embed"]["embedding"])) > 0


def test_the_last_position_of_the_prediction_block_weighs_nothing(dense_trainer, weights):
    """The prediction block's last position reads the row's last id and
    would predict one past the row: it weighs 0. Where that id occurs
    nowhere else in the batch its embedding row gets no gradient at all
    (the main loss has it as a target only, the head's business); it would
    with a weight of 1."""
    tokens = batches(1, seed=5)[0]["tokens"].copy()
    tokens[tokens == 49] = 48
    tokens[:, -1] = 49
    mine, _ = losses(dense_trainer, tokens)
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: mine(p)[0])(to_tree(weights))
    row = grads["embed"]["embedding"][49]
    assert float(jnp.max(jnp.abs(row))) == 0.0
    assert float(jnp.max(jnp.abs(grads["embed"]["embedding"][48]))) > 0


def test_three_steps_with_the_rule_match_the_references_walk(weights):
    """Through ``Trainer.train``: AdamW on the matrices, no decay on norms,
    embedding and biases, the router-bias rule after every update on the
    two expert layers and the prediction block's (the kernels' path is
    the loss test's)."""
    trainer = tiny_trainer()
    trainer.state = trainer.state.replace(
        params=jax.tree_util.tree_map(jnp.copy, to_tree(weights)))
    fed = batches(3, seed=1)
    got = {"loss": {}}

    def record(step, state, metrics):
        got["loss"][step] = float(metrics["loss"])
        if step == 1:
            got["moment"] = jax.tree_util.tree_map(
                float, follow.norms_and_probes(to_flat(state.opt_state[0].mu), 11))
            got["bias"] = float(jnp.max(jnp.abs(state.params["mtp0"]["moe"]["router_bias"])))
        if step == 3:
            p0 = ref.init_params(follow.init_key(11), MODEL)
            got["change"] = jax.tree_util.tree_map(float, follow.norms_and_probes(
                {n: v - p0[n] for n, v in to_flat(state.params).items()}, 11))
    with jax.default_matmul_precision("highest"):
        trainer.train(iter(fed), num_steps=3, hooks=(record,))
    theirs = follow.follow(CONFIG, 11, fed, [1, 2, 3])
    numbers, where = check.compare(got, theirs)
    assert max(numbers.values()) < 2e-3, (numbers, where)
    # the rule moved the prediction block's bias by its rate in the first step
    assert got["bias"] == pytest.approx(MODEL["load_balance_coeff"])
    assert set(ref.ruled_leaves(MODEL)) <= set(theirs["ruled"])


def test_the_rule_after_the_update_moves_the_prediction_blocks_bias(dense_trainer):
    """``NextTokenObjective.after_update`` reads every routing layer's
    counts, ``mtp0`` among them, and moves no other leaf."""
    objective = dense_trainer.model.objective()
    params = dense_trainer.state.params
    counts = {name: jnp.arange(8.0) for name in ("layer1", "layer2", "mtp0")}
    moved = objective.after_update(params, counts)
    want = params["mtp0"]["moe"]["router_bias"] + 0.001 * jnp.sign(3.5 - jnp.arange(8.0))
    np.testing.assert_array_equal(moved["mtp0"]["moe"]["router_bias"], want)
    np.testing.assert_array_equal(moved["mtp0"]["moe"]["router"]["kernel"],
                                  params["mtp0"]["moe"]["router"]["kernel"])
    np.testing.assert_array_equal(moved["layer0"]["mlp"]["up"]["kernel"],
                                  params["layer0"]["mlp"]["up"]["kernel"])


def test_no_decay_on_norms_embedding_and_biases(dense_trainer):
    from distributed_resnet_tensorflow_tpu.train.optimizers import _non_bn_mask
    mask = to_flat(_non_bn_mask(dense_trainer.state.params))
    for name, leaf in ref.init_params(follow.init_key(0), MODEL).items():
        assert bool(mask[name]) == ref.decayed(name, leaf), name
    assert mask["mtp0.eh_proj"] and not mask["mtp0.enorm.scale"]


def _moe_layer(held):
    return DroplessMoe(8, held, 2, 32, 32, 1.8, jnp.float32, "sigmoid_bias", "swiglu")


def test_the_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Two chips each holding four of the 8 experts: their held parts, and
    the shared expert counted once, sum to the reference's layer of all 8;
    every share's counts are the uncut layer's."""
    whole_layer = _moe_layer((0, 8))
    params = whole_layer.init(jax.random.PRNGKey(0), jnp.zeros((8, 64)))["params"]
    params = dict(params, router_bias=0.3 * jax.random.normal(jax.random.PRNGKey(5), (8,)))
    x = jax.random.normal(jax.random.PRNGKey(2), (96, 64))
    whole, counts = whole_layer.apply({"params": params}, x)
    s = params["shared"]
    shared = (jax.nn.silu(x @ s["gate"]["kernel"]) * (x @ s["up"]["kernel"])) \
        @ s["down"]["kernel"]
    total = None
    for lo in (0, 4):
        share = dict(params, experts={n: v[lo:lo + 4] for n, v in params["experts"].items()})
        part, c = _moe_layer((lo, lo + 4)).apply({"params": share}, x)
        np.testing.assert_array_equal(c, counts)
        total = part if total is None else total + part - shared
    flat = {"moe.router": params["router"]["kernel"], "moe.router_bias": params["router_bias"],
            **{f"moe.experts.{n}": v for n, v in params["experts"].items()},
            **{f"moe.shared.{n}": v["kernel"] for n, v in params["shared"].items()}}
    model = dict(MODEL, experts_held=[0, 8])
    with jax.default_matmul_precision("highest"):
        want, ref_counts = ref._moe(x, flat, model, lambda a: a)
    np.testing.assert_allclose(total, want, atol=5e-5)
    np.testing.assert_allclose(whole, want, atol=5e-5)
    np.testing.assert_array_equal(counts, ref_counts)
    assert float(jnp.sum(counts)) == 96 * 2


def test_the_start_puts_a_tokens_choices_on_different_chips():
    """The reference's initialiser lays a router's 64 columns in periods of
    the 8 experts held here: a token's four choices fall on four different
    chips' ranges for most tokens, and the held range gets about the half
    of the tokens that a balanced router sends it."""
    model = dict(MODEL, hidden_size=256, experts_published=64, experts_held=[0, 8],
                 num_experts_per_tok=4)
    params = ref.init_params(follow.init_key(3), model)
    x = jax.random.normal(jax.random.PRNGKey(1), (2048, 256))
    sel, _ = ref.route(x, params["layer1.moe.router"], params["layer1.moe.router_bias"], model)
    chips = np.asarray(sel) // 8
    distinct = np.mean([len(set(r)) == 4 for r in chips])
    assert distinct > 0.9
    held = np.mean(np.asarray(sel) < 8) * 4
    assert held == pytest.approx(0.5, abs=0.05)


@pytest.mark.parametrize("d,mask,want", [
    (64, Mask("causal"), (512, 512)), (64, Mask("causal", 2048), (512, 512)),
    (128, Mask("causal"), (1024, 1024)), (128, Mask("causal", 2048), (512, 512)),
    (96, Mask("causal"), (512, 512)), (112, Mask("causal"), (1024, 1024)),
    (256, Mask("causal"), None), (256, Mask("causal", 2048), None)])
def test_the_block_tables_row_by_head_size(d, mask, want):
    """Head sizes up to 96 read the 64 row, up to 128 the 128 row, up to
    256 the new 256 row (at 4,096 positions); a window at a head size
    with no row of its kind reads the causal row."""
    got = _pick_blocks(4096, d, mask)
    assert got == (want or _BLOCK_TABLES[256, False][-1][1])


def test_the_block_tables_rows_are_pinned():
    """Every row that was there gives what it gave; the 256 row is one row
    of its head size for every T; a head size past 256 has no row."""
    tables = _BLOCK_TABLES
    assert tables[64, False] == ((4096, (512, 512)), (8192, (1024, 1024)))
    assert tables[128, False] == ((2048, (512, 512)), (8192, (1024, 1024)))
    assert tables[128, True] == ((8192, (512, 512)),)
    assert tables[128, "block_diffusion"] == ((4096, (512, 512)), (16384, (1024, 1024)))
    assert len(tables[256, False]) == 1
    for t in (1024, 8192):
        assert _pick_blocks(t, 128) == ((512, 512) if t <= 2048 else (1024, 1024))
        assert _pick_blocks(t, 64) == ((512, 512) if t <= 4096 else (1024, 1024))
    with pytest.raises(ValueError, match="head size 320"):
        _pick_blocks(8192, 320)


def test_the_new_scopes_are_registered_where_they_are_read():
    assert SCOPE_CATALOG["latent"].under == "attention"
    assert SCOPE_CATALOG["latent"].read_by == "mla_latent_ms"
    assert SCOPE_CATALOG["mtp"].under == "forward"
    assert SCOPE_CATALOG["mtp"].read_by == "mtp_ms"


def test_the_step_counts_and_logs_the_prediction_blocks_loss(dense_trainer):
    """One step through ``Trainer.train``: the metrics carry ``mtp_loss``
    and ``mtp_precision`` beside ``precision``, and ``LoggingHook`` reads
    them."""
    from distributed_resnet_tensorflow_tpu.train.hooks import LoggingHook
    seen, lines = {}, []
    hook = LoggingHook(1, batch_size=ROWS, print_fn=lines.append)
    trainer = tiny_trainer()
    trainer.train(iter(batches(1, seed=7)), num_steps=1,
                  hooks=(hook, lambda step, state, metrics: seen.update(metrics)))
    hook.flush()
    assert {"mtp_loss", "mtp_precision", "precision", "moe_windows"} <= set(seen)
    assert float(seen["mtp_loss"]) > 0
    assert set(hook._reads(seen)) >= {"mtp_loss", "mtp_precision"}


@pytest.fixture(scope="module")
def gradient_paths(dense_trainer):
    """The scope path of every operation in the lowered gradient of the tiny
    decoder's loss with its prediction block, taken under ``forward`` as
    the step takes it."""
    import re
    mine, _ = losses(dense_trainer, batches(1)[0]["tokens"])
    text = jax.jit(jax.grad(jax.named_scope("forward")(mine), has_aux=True)).lower(
        dense_trainer.state.params).as_text(debug_info=True)
    return {name.rsplit("/", 1)[0] for name in re.findall(r'loc\("(jit\([^"]+)"', text)
            if "/" in name}


@pytest.mark.parametrize("components", [("attention", "latent"), ("attention", "core"),
                                        ("mtp",), ("mtp", "lm_head"), ("mtp", "moe")])
def test_the_lowered_gradient_holds_the_scopes_the_readers_read(gradient_paths, components):
    """``mla_latent_ms`` reads ``attention/latent`` and ``mtp_ms`` ``mtp``
    (benchmark/flops/afmoe.scope_seconds): each names operations of the
    first forward pass, of the block's recomputation and of the backward
    pass; the prediction block's head, expert layer and attention lie
    under ``mtp`` (the head's chunks outside the block's recomputation,
    made again inside its own backward loop)."""
    from benchmark.flops import afmoe
    from benchmark.tools import step_parts
    passes = {step_parts.which_pass(p) for p in gradient_paths
              if afmoe.scope_seconds({"scope_s": {p: 1.0}}, *components)}
    want = {"forward", "backward"} if "lm_head" in components else \
        {"forward", "recomputed", "backward"}
    assert passes == want, (components, passes)
    # the main stack's latent parts are outside mtp, the block's inside
    latent = [p for p in gradient_paths
              if afmoe.scope_seconds({"scope_s": {p: 1.0}}, "attention", "latent")]
    assert any("mtp0" in p for p in latent) and any("layer0" in p for p in latent)


def test_no_registered_scope_is_a_flax_modules_name(dense_trainer):
    """``moe`` and ``lm_head`` name a scope and the module it is wrapped
    around, as in the other families; ``latent`` and ``mtp`` name no module
    (the prediction block's module is ``mtp0``)."""
    modules = {str(getattr(k, "key", k)) for path, _ in
               jax.tree_util.tree_flatten_with_path(dense_trainer.state.params)[0]
               for k in path}
    ours = {name for name, s in SCOPE_CATALOG.items() if s.origin == "scope"}
    assert {"attn", "q_a_proj", "kv_b_proj", "mtp0", "eh_proj", "experts"} <= modules
    assert ours & modules == {"moe", "lm_head"}
