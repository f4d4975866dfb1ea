"""The hybrid decoder family (model.name="nemotron_h": models/transformer
.CausalDecoder of MixerBlocks, models/mamba.Mamba2 over the chunked scan of
ops/ssd.py, relu² experts in models/moe.DroplessMoe) against its plain
reference (benchmark/reference/nemotron_h.py, the recurrence a position at
a time) and against itself, at sizes a CPU holds; the flash kernels in
interpret mode."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check
from benchmark.reference import follow
from benchmark.reference import nemotron_h as ref
from distributed_resnet_tensorflow_tpu.models import moe, transformer
from distributed_resnet_tensorflow_tpu.models.mamba import causal_conv
from distributed_resnet_tensorflow_tpu.models.moe import DroplessMoe
from distributed_resnet_tensorflow_tpu.ops.ssd import chunked_scan, scan_census
from distributed_resnet_tensorflow_tpu.parallel.mesh import create_mesh
from distributed_resnet_tensorflow_tpu.telemetry.tracer import SCOPE_CATALOG
from distributed_resnet_tensorflow_tpu.train.loop import Trainer
from distributed_resnet_tensorflow_tpu.utils.config import get_preset

MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": ["mamba", "moe", "mamba", "full_attention", "moe"],
    "rms_norm_eps": 1e-5, "num_dense_layers": 0, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "experts_published": 16,
    "experts_held": [4, 8], "num_experts_per_tok": 3, "num_shared_experts": 1,
    "route_scale": 2.5, "load_balance_coeff": 0.001, "mup_enabled": False,
    "vocab_held": 50, "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16, "seq_len": 40,
    "compute_dtype": "float32"}
#: Δ's start, which both initialisers read (the program's defaults)
INIT = {"time_step_min": 1e-3, "time_step_max": 0.1, "time_step_floor": 1e-4}
REF_MODEL = {**MODEL, **INIT}
OPTIMIZER = {"name": "adamw", "learning_rate": 3e-4, "weight_decay": 0.1,
             "b1": 0.9, "b2": 0.999, "eps": 1e-8, "schedule": "constant"}
CONFIG = {"family": "nemotron_h", "model": REF_MODEL, "optimizer": OPTIMIZER,
          "start_step": 0}
ROWS = 2
#: the reference's names where the program's config has the source's
PROGRAM_KEY = {"seq_len": "data.seq_len", "experts_published": "model.num_experts"}


@pytest.fixture(scope="module", autouse=True)
def short_chunks():
    """Chunks of the loss small enough that a batch takes several."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(transformer, "LOSS_CHUNK", 16)
        yield


def tiny_trainer(impl: str = "dense", **overrides) -> Trainer:
    cfg = get_preset("nemotron3_nano_share16")
    for key, value in MODEL.items():
        cfg.override(PROGRAM_KEY.get(key, f"model.{key}"), value)
    for key, value in {"model.attention_impl": impl, "train.batch_size": ROWS,
                       "optimizer.schedule": "constant", "mesh.data": 1,
                       **overrides}.items():
        cfg.override(key, value)
    trainer = Trainer(cfg, mesh=create_mesh(cfg.mesh, devices=jax.devices()[:1]))
    trainer.init_state(0)
    return trainer


def to_tree(flat):
    tree = {}
    for name, path in ref.program_paths(MODEL).items():
        node = tree
        *dirs, leaf = path.split("/")
        for d in dirs:
            node = node.setdefault(d, {})
        node[leaf] = flat[name]
    return tree


def to_flat(tree):
    out = {}
    for name, path in ref.program_paths(MODEL).items():
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
    return ref.init_params(follow.init_key(11), REF_MODEL)


@pytest.fixture(scope="module")
def dense_trainer():
    return tiny_trainer()


def test_the_programs_leaves_are_the_references(dense_trainer, weights):
    mine = jax.tree_util.tree_map(lambda a: a.shape, dense_trainer.state.params)
    assert mine == jax.tree_util.tree_map(lambda a: a.shape, to_tree(weights))
    names = set(ref.program_paths(MODEL))
    # no head norms, gate or rotary in attention; relu² experts have no gate
    assert not any("q_norm" in n or "gate" in n or "post_" in n for n in names)
    assert {"layer0.mamba.A_log", "layer1.moe.router_bias", "layer1.moe.shared.up",
            "layer3.attn.o_proj"} <= names


def losses(trainer, tokens):
    objective = trainer.model.objective()

    def mine(params):
        ce, metrics, _, _, counts = objective.forward(
            trainer.model.apply, {"params": params, "batch_stats": {}},
            {"tokens": jnp.asarray(tokens)})
        return ce, (metrics, counts)

    def theirs(flat):
        total, counts = ref.loss_sum(flat, ref.examples({"tokens": tokens}, 0, 0),
                                     jnp.ones((ROWS,)), REF_MODEL)
        return total / ROWS, counts
    return mine, theirs


@pytest.mark.parametrize("impl", ["dense", "flash_interpret"])
def test_loss_logits_and_gradients_match_the_reference(dense_trainer, weights, impl):
    trainer = dense_trainer if impl == "dense" else tiny_trainer(impl)
    tokens = batches(1)[0]["tokens"]
    mine, theirs = losses(trainer, tokens)
    with jax.default_matmul_precision("highest"):
        (a, (metrics, counts)), grads_a = jax.value_and_grad(mine, has_aux=True)(
            to_tree(weights))
        (b, ref_counts), grads_b = jax.value_and_grad(theirs, has_aux=True)(weights)
        logits = trainer.model.apply({"params": to_tree(weights)}, jnp.asarray(tokens[:, :-1]))
        want = jnp.stack([ref.sequence_logits(weights, row[:-1], REF_MODEL)[0]
                          for row in jnp.asarray(tokens)])
    assert abs(float(a) - float(b)) < 1e-5 * abs(float(b))
    np.testing.assert_allclose(logits, want, atol=2e-4 * float(jnp.max(jnp.abs(want))))
    grads_a = to_flat(grads_a)
    scale = np.median([float(jnp.linalg.norm(g)) for g in grads_b.values()])
    for name, g in grads_b.items():
        gap = float(jnp.linalg.norm(grads_a[name] - g)) / max(float(jnp.linalg.norm(g)), scale)
        assert gap < 2e-4, (name, gap)
    assert sorted(counts) == ["layer1", "layer4"]
    for layer, c in counts.items():
        np.testing.assert_array_equal(c, ref_counts[f"{layer}.moe.router_bias"])
    # 2 routing layers x 80 tokens x 3 choices x 4 of 16 experts
    assert 0 < float(metrics["moe_assignments_held"]) < ROWS * 40 * 3
    assert float(metrics["moe_windows"]) == 1.0


def test_three_steps_with_the_rule_match_the_references_walk(weights):
    """Through ``Trainer.train``: AdamW on the matrices, no decay on norms,
    embedding, biases, dt_bias, A_log and D, the router-bias rule after
    every update."""
    trainer = tiny_trainer("flash_interpret")
    trainer.state = trainer.state.replace(
        params=jax.tree_util.tree_map(jnp.copy, to_tree(weights)))
    fed = batches(3, seed=1)
    got = {"loss": {}}

    def record(step, state, metrics):
        got["loss"][step] = float(metrics["loss"])
        if step == 1:
            got["moment"] = jax.tree_util.tree_map(
                float, follow.norms_and_probes(to_flat(state.opt_state[0].mu), 11))
        if step == 3:
            p0 = ref.init_params(follow.init_key(11), REF_MODEL)
            got["change"] = jax.tree_util.tree_map(float, follow.norms_and_probes(
                {n: v - p0[n] for n, v in to_flat(state.params).items()}, 11))
    with jax.default_matmul_precision("highest"):
        trainer.train(iter(fed), num_steps=3, hooks=(record,))
    theirs = follow.follow(CONFIG, 11, fed, [1, 2, 3])
    numbers, where = check.compare(got, theirs)
    assert max(numbers.values()) < 2e-3, (numbers, where)
    assert ref.ruled_leaves(REF_MODEL) == ["layer1.moe.router_bias", "layer4.moe.router_bias"]
    assert set(ref.ruled_leaves(REF_MODEL)) <= set(theirs["ruled"])


def test_no_decay_on_the_mixers_own_vectors(dense_trainer):
    from distributed_resnet_tensorflow_tpu.train.optimizers import _non_bn_mask
    mask = to_flat(_non_bn_mask(dense_trainer.state.params))
    for name, leaf in ref.init_params(follow.init_key(0), REF_MODEL).items():
        assert bool(mask[name]) == ref.decayed(name, leaf), name
    assert mask["layer0.mamba.conv_weight"] and not mask["layer0.mamba.A_log"]


@pytest.mark.parametrize("low,high,floor,want", [(0.05, 0.05, 1e-4, 0.05),
                                                 (1e-5, 1e-5, 1e-4, 1e-4)])
def test_the_mixers_step_size_starts_where_the_config_says(low, high, floor, want):
    """dt_bias is the softplus inverse of a Δ drawn on [time_step_min,
    time_step_max] and floored at time_step_floor, from the config."""
    trainer = tiny_trainer(**{"model.time_step_min": low, "model.time_step_max": high,
                              "model.time_step_floor": floor})
    for layer in ("layer0", "layer2"):
        dt_bias = trainer.state.params[layer]["mamba"]["dt_bias"]
        np.testing.assert_allclose(jax.nn.softplus(dt_bias), want, rtol=1e-4)


# -- the chunked scan (ops/ssd.py) against the recurrence a position at a time --

def _scan_inputs(t, b=2, h=4, p=8, g=2, n=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, h)) - 1.0)
    a = -jnp.exp(jax.random.normal(k[2], (h,)))
    return x, dt, a, jax.random.normal(k[3], (b, t, g, n)), jax.random.normal(k[4], (b, t, g, n))


def _recurrence(x, dt, a, b, c):
    return jnp.stack([ref.recurrence(x[i], dt[i], a, b[i], c[i]) for i in range(x.shape[0])])


SCAN_CASES = {"one_chunk": (16, 16), "four_chunks": (64, 16), "ragged_tail": (45, 16),
              "chunk_longer_than_t": (10, 16), "chunk_of_one": (7, 1)}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_the_chunked_scan_is_the_recurrence_forward_and_back(case):
    """Value and vector-Jacobian product of every input against the
    recurrence a position at a time: one chunk, a state carried across
    three boundaries, a last chunk that T does not fill, a chunk longer
    than the sequence, chunks of one position."""
    t, chunk = SCAN_CASES[case]
    inputs = _scan_inputs(t)
    r = jax.random.normal(jax.random.PRNGKey(9), inputs[0].shape)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda *a: chunked_scan(*a, chunk, jnp.float32), *inputs)
        want, vjp_ref = jax.vjp(_recurrence, *inputs)
        grads, grads_ref = vjp(r), vjp_ref(r)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=2e-5 * scale)
    for name, g, h in zip(("x", "dt", "a", "b", "c"), grads, grads_ref):
        np.testing.assert_allclose(g, h, atol=5e-5 * float(jnp.max(jnp.abs(h))), err_msg=name)


def test_the_state_reaches_across_chunks():
    """Zeroing the recurrence's state at each chunk's start (the walk's
    fault) moves every chunk after the first and leaves the first alone."""
    inputs = _scan_inputs(48)
    with jax.default_matmul_precision("highest"):
        sound = chunked_scan(*inputs, 16, jnp.float32)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(ref, "STATE_RESET_EVERY", 16)
            reset = _recurrence(*inputs)
    np.testing.assert_allclose(reset[:, :16], sound[:, :16], atol=1e-4)
    for start in (16, 32):
        gap = float(jnp.max(jnp.abs(reset[:, start:start + 16] - sound[:, start:start + 16])))
        assert gap > 1e-2, start


def test_the_scan_in_bfloat16_stays_near_the_float32_scan():
    inputs = _scan_inputs(64)
    exact = chunked_scan(*inputs, 16, jnp.float32)
    low = chunked_scan(*inputs, 16, jnp.bfloat16)
    assert low.dtype == jnp.float32
    err = float(jnp.linalg.norm(low - exact) / jnp.linalg.norm(exact))
    assert 1e-4 < err < 2e-2


@pytest.mark.parametrize("t,chunk,want", [
    (8192, 128, (64, 2 * 64, 64 * 4 * 2 * 64 * 64 * 128)),
    (45, 16, (3, 2 * 4, 3 * 4 * 2 * 4 * 8 * 16))])
def test_the_scan_census_counts_by_hand(t, chunk, want):
    """Chunks, decay tiles a chunk and the states kept for the backward
    pass; at a CPU's size, against the states the scan does keep."""
    heads, groups, head_dim, state = (64, 8, 64, 128) if t == 8192 else (4, 2, 8, 16)
    got = scan_census(2, t, heads, groups, head_dim, state, chunk)
    assert (got["chunks"], got["tiles"], got["state_bytes"]) == want
    if t < 100:
        inputs = _scan_inputs(t, h=heads, p=head_dim, g=groups, n=state)
        _, vjp = jax.vjp(lambda *a: chunked_scan(*a, chunk, jnp.float32), *inputs)
        kept = [r for r in jax.tree_util.tree_leaves(vjp) if r.shape == (
            got["chunks"], 2, groups, heads // groups, head_dim, state)]
        assert len(kept) == 1 and kept[0].nbytes == got["state_bytes"]


def test_the_convolution_is_causal_from_the_sequences_start():
    """Output t reads inputs t-3..t and nothing later; the first three read
    zeros before the start."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jnp.arange(6.0)
    out = causal_conv(x, w, bias)
    for t in range(10):
        want = bias + sum(w[3 - j] * x[0, t - j] for j in range(4) if t - j >= 0)
        np.testing.assert_allclose(out[0, t], want, atol=1e-5)
    moved = causal_conv(x.at[0, 6].add(1.0), w, bias)
    np.testing.assert_array_equal(moved[0, :6], out[0, :6])
    assert not np.allclose(moved[0, 6:], out[0, 6:])


# -- the relu² experts through the walk (models/moe.held_experts_sum) --

def _per_expert_sum(x, sel, w, up, down, lo):
    """Σ over chosen held experts of weight × relu² expert, a plain loop."""
    out = jnp.zeros_like(x)
    for e in range(up.shape[0]):
        weight = jnp.sum(jnp.where(sel == lo + e, w, 0.0), axis=1)
        out = out + weight[:, None] * (jnp.square(jax.nn.relu(x @ up[e])) @ down[e])
    return out


@pytest.mark.parametrize("bound", [4096, 32])
def test_the_relu2_walk_is_the_per_expert_loop(bound, monkeypatch):
    """One window and several: value and every gradient of the walk over
    two kernels against a loop over the held experts."""
    _walk_against_the_loop(bound, monkeypatch)


@pytest.mark.parametrize("tile,products", [(16, (32, 32)), (8, (24, 16)), (4, (24, 12))])
@pytest.mark.parametrize("bound", [4096, 32])
def test_the_padded_relu2_walk_is_the_per_expert_loop(bound, tile, products, monkeypatch):
    """The walk hands the grouped products each width that ``PRODUCT_TILE``
    does not divide padded to a multiple of twice it (24 x 12 runs as
    32 x 32 under a tile of 16, as 24 x 16 under 8 and as itself under 4);
    value and every gradient come back in the parameters' widths and are
    the loop's, in one window and several."""
    monkeypatch.setattr(moe, "PRODUCT_TILE", tile)
    assert moe.product_widths(24, 12) == products
    _walk_against_the_loop(bound, monkeypatch, products)


def _ragged_dot_widths(fn, *args):
    """(k, n) of every grouped product in ``fn``'s program, nested ones too."""
    found = set()

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("ragged_dot"):
                found.add((eqn.invars[0].aval.shape[1], eqn.outvars[0].aval.shape[-1]))
            for param in eqn.params.values():
                for sub in param if isinstance(param, (tuple, list)) else [param]:
                    sub = getattr(sub, "jaxpr", sub)  # a closed jaxpr's own
                    if hasattr(sub, "eqns"):
                        visit(sub)
    visit(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _walk_against_the_loop(bound, monkeypatch, products=None):
    n, k, held, published, lo = 96, 3, 4, 16, 4
    monkeypatch.setattr(moe, "WINDOW_TOKENS", bound)
    keys = jax.random.split(jax.random.PRNGKey(bound), 6)
    sel = jax.random.randint(keys[0], (n, k), 0, published)
    x = jax.random.normal(keys[1], (n, 24))
    w = jax.random.uniform(keys[2], (n, k)) + 0.1
    up = jax.random.normal(keys[3], (held, 24, 12)) / 5
    down = jax.random.normal(keys[4], (held, 12, 24)) / 3
    r = jax.random.normal(keys[5], (n, 24))

    def walked(x, w, up, down):
        return moe.held_experts_sum(x, sel, w, None, up, down, lo, published, jnp.float32)
    got, windows = jax.jit(walked)(x, w, up, down)
    np.testing.assert_allclose(got, _per_expert_sum(x, sel, w, up, down, lo), atol=2e-5)
    live = int(jnp.sum((sel >= lo) & (sel < lo + held)))
    assert float(windows) == -(-live // moe.walk_rows(n, k, held, published))
    grad = jax.grad(lambda *a: jnp.sum(walked(*a)[0] * r), argnums=(0, 1, 2, 3))
    got = grad(x, w, up, down)
    want = jax.grad(lambda *a: jnp.sum(_per_expert_sum(a[0], sel, *a[1:], lo) * r),
                    argnums=(0, 1, 2, 3))(x, w, up, down)
    for name, a, b in zip(("dx", "dw", "up", "down"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=5e-5 * float(jnp.max(jnp.abs(b))), err_msg=name)
    if products is not None:
        assert _ragged_dot_widths(grad, x, w, up, down) == {products, products[::-1]}


@pytest.mark.parametrize("published,padded", [
    ((2688, 1856), (3072, 2048)), ((2048, 1024), (2048, 1024)), ((2048, 768), (2048, 768)),
    ((2816, 1280), (2816, 1280)), ((64, 32), (512, 512)), ((257, 512), (512, 512))])
def test_the_product_widths_pad_what_no_tile_divides(published, padded):
    """Nemotron's 2,688 x 1,856 runs as 3,072 x 2,048; the SwiGLU cells'
    widths (Trinity 2,048 x 1,024, SDAR 2,048 x 768) are multiples of 256
    already and are left as they are, as is any multiple of 256 that is
    none of 512."""
    assert moe.product_widths(*published) == padded


def test_the_pad_leaves_the_layers_parameters_and_gradients_as_published():
    """64 x 32 experts run their products at 512 x 512: the parameters, and
    the gradient the layer hands the optimizer, keep 64 and 32."""
    layer = _moe_layer((4, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 64))
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    assert moe.product_widths(64, 32) == (512, 512)
    assert params["experts"]["up"].shape == (4, 64, 32)
    assert params["experts"]["down"].shape == (4, 32, 64)
    grads = jax.grad(lambda p: jnp.sum(layer.apply({"params": p}, x)[0]))(params)
    shapes = partial(jax.tree_util.tree_map, lambda a: a.shape)
    assert shapes(grads) == shapes(params)
    assert float(jnp.linalg.norm(grads["experts"]["up"])) > 0


@pytest.mark.parametrize("tile,want", [(256, "512x512 from 64x32"), (16, "as published")])
def test_the_resolved_line_states_the_product_widths(dense_trainer, tile, want, monkeypatch):
    """``Trainer.resolutions`` says whether the pad engaged: the tiny model's
    64 x 32 experts are padded under the shipped tile and not under 16."""
    monkeypatch.setattr(moe, "PRODUCT_TILE", tile)
    assert dense_trainer.resolutions()["moe.product_widths"] == want


def _moe_layer(held, experts=16):
    return DroplessMoe(experts, held, 6, 32, 48, 2.5, jnp.float32, "sigmoid_bias", "relu2")


def test_the_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Sixteen chips each holding one of 16 experts: their held parts, and
    the shared expert counted once, sum to the reference's layer of all 16;
    every share's counts are the uncut layer's. Relu² has no gate leaf."""
    whole_layer = _moe_layer((0, 16))
    params = whole_layer.init(jax.random.PRNGKey(0), jnp.zeros((8, 64)))["params"]
    assert set(params) == {"router", "router_bias", "experts", "shared"}
    assert set(params["experts"]) == {"up", "down"} and set(params["shared"]) == {"up", "down"}
    params = dict(params, router_bias=0.3 * jax.random.normal(jax.random.PRNGKey(5), (16,)))
    x = jax.random.normal(jax.random.PRNGKey(2), (96, 64))
    whole, counts = whole_layer.apply({"params": params}, x)
    total = None
    for lo in range(16):
        share = dict(params, experts={n: v[lo:lo + 1] for n, v in params["experts"].items()})
        part, c = _moe_layer((lo, lo + 1)).apply({"params": share}, x)
        np.testing.assert_array_equal(c, counts)
        # each share adds the shared expert: count it once
        total = part if total is None else total + part - _shared(params, x)
    flat = {"moe.router": params["router"]["kernel"], "moe.router_bias": params["router_bias"],
            **{f"moe.experts.{n}": v for n, v in params["experts"].items()},
            **{f"moe.shared.{n}": v["kernel"] for n, v in params["shared"].items()}}
    model = dict(REF_MODEL, experts_held=[0, 16], num_experts_per_tok=6)
    with jax.default_matmul_precision("highest"):
        want, ref_counts = ref._moe(x, flat, model, lambda a: a)
    np.testing.assert_allclose(total, want, atol=5e-5)
    np.testing.assert_allclose(whole, want, atol=5e-5)
    np.testing.assert_array_equal(counts, ref_counts)
    assert float(jnp.sum(counts)) == 96 * 6


def _shared(params, x):
    s = params["shared"]
    return jnp.square(jax.nn.relu(x @ s["up"]["kernel"])) @ s["down"]["kernel"]


def test_the_start_puts_a_tokens_choices_on_different_chips():
    """The reference's initialiser lays a router's 128 columns in periods
    of the 8 experts held here: a token's six choices fall on six of the
    sixteen chips' ranges, at most one on each for most tokens (not where
    two of its best columns stand within the jitter), and the held range
    takes 6/16 of the tokens whatever the seed."""
    model = dict(REF_MODEL, experts_published=128, num_experts_per_tok=6,
                 experts_held=[0, 8])
    for seed in (3, 4):
        router = ref.init_params(follow.init_key(seed), model)["layer1.moe.router"]
        x = jax.random.normal(jax.random.PRNGKey(seed), (4096, 64))
        sel, _ = ref.route(x, router, jnp.zeros((128,)), model)
        per_chip = np.stack([np.bincount(row // 8, minlength=16) for row in np.asarray(sel)])
        assert (per_chip.max(axis=1) == 1).mean() > 0.85
        held = float(per_chip[:, 0].mean())
        assert abs(held - 6 / 16) < 0.05, (seed, held)


# -- scopes the traces are read by --

@pytest.fixture(scope="module")
def gradient_paths(dense_trainer):
    """The scope path of every operation in the lowered gradient of the tiny
    decoder's loss, taken under ``forward`` as the step takes it."""
    import re
    mine, _ = losses(dense_trainer, batches(1)[0]["tokens"])
    text = jax.jit(jax.grad(jax.named_scope("forward")(mine), has_aux=True)).lower(
        dense_trainer.state.params).as_text(debug_info=True)
    return {name.rsplit("/", 1)[0] for name in re.findall(r'loc\("(jit\([^"]+)"', text)
            if "/" in name}


MAMBA_PARTS = sorted(f"mamba/{name}" for name, s in SCOPE_CATALOG.items()
                     if s.under == "mamba")


@pytest.mark.parametrize("row", MAMBA_PARTS + ["mamba"])
def test_the_lowered_gradient_holds_every_part_of_the_mixer(gradient_paths, row):
    """The mixer and its three parts name operations of the first forward
    pass, of the block's recomputation and of the backward pass, so that
    ``mamba_ms``, ``ssd_roofline`` and ``step_parts.py`` find their time."""
    from benchmark.tools import step_parts
    passes = {step_parts.which_pass(p) for p in gradient_paths
              if step_parts.row_of(p, SCOPE_CATALOG) == row}
    assert passes == {"forward", "recomputed", "backward"}, (row, passes)


def test_no_registered_scope_is_a_flax_modules_name(dense_trainer):
    """``moe``, ``lm_head`` and ``mamba`` name a scope and the module it is
    wrapped around: one thing twice; no other scope takes a module's name."""
    modules = {str(getattr(k, "key", k)) for path, _ in
               jax.tree_util.tree_flatten_with_path(dense_trainer.state.params)[0]
               for k in path}
    ours = {name for name, s in SCOPE_CATALOG.items() if s.origin == "scope"}
    assert {"mamba", "in_proj", "shared", "experts", "attn"} <= modules
    assert ours & modules == {"moe", "lm_head", "mamba"}


def test_a_one_mixer_block_refuses_a_kind_it_has_no_mixer_for():
    cfg = get_preset("nemotron3_nano_share16").model
    cfg.layer_types = ("sliding_attention",)
    block = transformer.MixerBlock(cfg, 0)
    with pytest.raises(ValueError, match="mamba, moe or full_attention"):
        block.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size)))


def test_the_family_trains_through_main(tmp_path):
    """``main.py train`` with the preset, cut to a CPU's size by --set."""
    from distributed_resnet_tensorflow_tpu import main as cli
    args = ["--preset", "nemotron3_nano_share16", "--set", f"log_root={tmp_path}",
            "--set", "train.train_steps=3", "--set", "train.log_every_steps=1",
            "--set", "checkpoint.save_every_secs=0", "--set", "train.batch_size=8",
            "--set", "model.attention_impl=dense"]
    for key, value in MODEL.items():
        if isinstance(value, list):
            value = ",".join(str(v) for v in value)
        args += ["--set", PROGRAM_KEY.get(key, f"model.{key}") + f"={value}"]
    assert cli.main(args) in (0, None)
