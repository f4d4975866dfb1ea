"""The decoder family (model.name="afmoe": models/transformer.CausalDecoder,
models/moe.DroplessMoe, the window/grouped flash kernels) against its plain
reference (benchmark/reference/afmoe.py) and against itself, at sizes a CPU
holds; Pallas kernels in interpret mode at a few hundred tokens."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check
from benchmark.reference import afmoe as ref
from benchmark.reference import follow
from distributed_resnet_tensorflow_tpu.models import moe, transformer
from distributed_resnet_tensorflow_tpu.models.moe import DroplessMoe
from distributed_resnet_tensorflow_tpu.models.transformer import (
    GroupedAttention, causal_attention)
from distributed_resnet_tensorflow_tpu.ops.attention import attention
from distributed_resnet_tensorflow_tpu.ops.pallas.flash_attention import (
    flash_attention)
from distributed_resnet_tensorflow_tpu.parallel.mesh import create_mesh
from distributed_resnet_tensorflow_tpu.telemetry.tracer import SCOPE_CATALOG
from distributed_resnet_tensorflow_tpu.train.loop import Trainer
from distributed_resnet_tensorflow_tpu.train.optimizers import _non_bn_mask
from distributed_resnet_tensorflow_tpu.utils.config import get_preset

MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16,
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention"],
    "sliding_window": 24, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "num_dense_layers": 1, "intermediate_size": 96, "moe_intermediate_size": 32,
    "experts_published": 16, "experts_held": [4, 8], "num_experts_per_tok": 4,
    "num_shared_experts": 1, "route_scale": 2.826, "load_balance_coeff": 0.001,
    "mup_enabled": True, "vocab_held": 50, "compute_dtype": "float32",
    "seq_len": 64}
OPTIMIZER = {"name": "adamw", "learning_rate": 3e-4, "weight_decay": 0.1,
             "b1": 0.9, "b2": 0.999, "eps": 1e-8, "schedule": "constant"}
CONFIG = {"family": "afmoe", "model": MODEL, "optimizer": OPTIMIZER, "start_step": 0}
ROWS = 4
#: the reference's names where the program's config has the source's
PROGRAM_KEY = {"seq_len": "data.seq_len", "experts_published": "model.num_experts"}


@pytest.fixture(scope="module", autouse=True)
def several_chunks():
    """Chunks small enough that 256 tokens make several of each."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "TOKEN_CHUNK", 64)
        mp.setattr(transformer, "LOSS_CHUNK", 32)
        yield


def tiny_trainer(impl: str = "dense", **overrides) -> Trainer:
    cfg = get_preset("trinity_mini_share8")
    for key, value in MODEL.items():
        cfg.override(PROGRAM_KEY.get(key, f"model.{key}"), value)
    for key, value in {"model.attention_impl": impl, "train.batch_size": ROWS,
                       "optimizer.schedule": "constant", "mesh.data": 1,
                       **overrides}.items():
        cfg.override(key, value)
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


def test_the_programs_leaves_are_the_references(weights):
    trainer = tiny_trainer()
    mine = jax.tree_util.tree_map(lambda a: a.shape, trainer.state.params)
    assert mine == jax.tree_util.tree_map(lambda a: a.shape, to_tree(weights))


@pytest.mark.parametrize("impl", ["dense", "flash_interpret"])
def test_loss_and_gradients_match_the_reference(weights, impl):
    trainer = tiny_trainer(impl)
    batch = batches(1)[0]
    objective = trainer.model.objective()

    def mine(params):
        ce, _, _, _, counts = objective.forward(
            trainer.model.apply, {"params": params, "batch_stats": {}},
            {"tokens": jnp.asarray(batch["tokens"])})
        return ce, counts

    def theirs(flat):
        total, counts = ref.loss_sum(flat, {"tokens": jnp.asarray(batch["tokens"])},
                                     jnp.ones((ROWS,)), MODEL)
        return total / ROWS, counts
    with jax.default_matmul_precision("highest"):
        (a, counts_a), grads_a = jax.value_and_grad(mine, has_aux=True)(to_tree(weights))
        (b, counts_b), grads_b = jax.value_and_grad(theirs, has_aux=True)(weights)
    assert abs(float(a) - float(b)) < 1e-5 * abs(float(b))
    for i in (1, 2):
        np.testing.assert_array_equal(counts_a[f"layer{i}"],
                                      counts_b[f"layer{i}.moe.router_bias"])
    grads_a = to_flat(grads_a)
    scale = np.median([float(jnp.linalg.norm(g)) for g in grads_b.values()])
    for name, g in grads_b.items():
        gap = float(jnp.linalg.norm(grads_a[name] - g)) / max(float(jnp.linalg.norm(g)), scale)
        assert gap < 2e-4, (name, gap)
    for name in ref.ruled_leaves(MODEL):  # the bias: chosen by, never trained by the loss
        assert float(jnp.max(jnp.abs(grads_a[name]))) == 0.0


def test_three_steps_with_the_rule_match_the_references_walk(weights):
    """Through ``Trainer.train``: AdamW on the matrices, no decay on norms,
    embedding and biases, the rule after every update."""
    trainer = tiny_trainer("flash_interpret")
    state = trainer.state
    trainer.state = state.replace(params=to_tree(weights))
    fed = batches(3)
    seen = {"loss": {}}

    def record(step, state, metrics):
        seen["loss"][step] = float(metrics["loss"])
        if step == 1:
            mu = state.opt_state[0].mu
            seen["moment"] = jax.tree_util.tree_map(
                float, follow.norms_and_probes(to_flat(mu), 11))
        if step == 3:
            p0 = ref.init_params(follow.init_key(11), MODEL)
            seen["change"] = jax.tree_util.tree_map(float, follow.norms_and_probes(
                {n: v - p0[n] for n, v in to_flat(state.params).items()}, 11))
            seen["bias"] = {n: np.asarray(v) for n, v in to_flat(state.params).items()
                            if n in ref.RULED}
    with jax.default_matmul_precision("highest"):
        trainer.train(iter(fed), num_steps=3, hooks=(record,))
    theirs = follow.follow(CONFIG, 11, fed, [1, 2, 3])
    numbers, where = check.compare(seen, theirs)
    assert max(numbers.values()) < 2e-3, (numbers, where)
    for name, bias in seen["bias"].items():  # moved, by steps of the rule's rate
        steps = np.abs(bias) / MODEL["load_balance_coeff"]
        assert bias.any() and np.allclose(steps, np.round(steps), atol=1e-3), name


def test_the_bias_is_outside_the_decay_mask_and_the_embedding_too(weights):
    mask = to_flat(_non_bn_mask(to_tree(weights)))
    assert {n for n, kept in mask.items() if kept} == \
        {n for n, v in weights.items() if ref.decayed(n, v)}
    assert not mask["embed"] and not mask["layer1.moe.router_bias"]
    assert mask["lm_head"] and mask["layer1.moe.experts.down"]


# -- attention: each form against the other ---------------------------------

@pytest.mark.parametrize("t,heads,kv,window,bq,bk", [
    (256, 4, 4, None, 64, 64), (256, 8, 2, None, 64, 128), (256, 8, 2, 96, 64, 32),
    (200, 8, 2, 50, 64, 32), (256, 4, 1, 64, 32, 128), (384, 8, 1, 128, 128, 128)])
def test_flash_kernels_match_their_twin(t, heads, kv, window, bq, bk):
    """Forward and the three gradients: window in the mask and in the
    skipping, grouped heads without copies, dK/dV summed over a group."""
    key = jax.random.PRNGKey(t + heads)
    q = jax.random.normal(jax.random.fold_in(key, 1), (2, t, heads, 32))
    k = jax.random.normal(jax.random.fold_in(key, 2), (2, t, kv, 32))
    v = jax.random.normal(jax.random.fold_in(key, 3), (2, t, kv, 32))

    def flash(q, k, v):
        return flash_attention(q, k, v, True, True, bq, bk, window)

    def twin(q, k, v):
        return attention(q, k, v, True, window)
    np.testing.assert_allclose(flash(q, k, v), twin(q, k, v), atol=2e-6)
    wrt = (0, 1, 2)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), wrt)(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(twin(*a))), wrt)(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("window", [None, 24])
def test_the_twin_matches_the_references_blocks_of_queries(window):
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 96, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 96, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 96, 2, 16))
    want = ref._attention_core(q[0], k[0], v[0], window, lambda a: a)
    got = attention(q, k, v, True, window)[0].reshape(96, -1)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_a_window_without_causal_is_refused():
    q = jnp.zeros((1, 32, 2, 16))
    with pytest.raises(ValueError, match="causal"):
        attention(q, q, q, False, 8)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, False, True, 0, 0, 8)


@pytest.mark.parametrize("kind,moves", [("full_attention", False),
                                        ("sliding_attention", True)])
def test_rotary_positions_on_window_layers_only(kind, moves):
    """With the same keys in view, the last position's output does not
    depend on the ORDER of the earlier tokens in a full layer (no positional
    term at all) and does in a window layer (rotary positions)."""
    layer = GroupedAttention(4, 2, 16, kind, 64, 10000.0, 1e-5, jnp.float32, "dense")
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 64))
    params = layer.init(jax.random.PRNGKey(6), x)
    shuffled = jnp.concatenate([x[:, :31][:, ::-1], x[:, 31:]], axis=1)
    a = layer.apply(params, x)[0, -1]
    b = layer.apply(params, shuffled)[0, -1]
    assert (float(jnp.max(jnp.abs(a - b))) > 1e-3) == moves


# -- the expert layer -------------------------------------------------------

def _moe_layer(held, experts=16):
    return DroplessMoe(experts, held, 4, 32, 32, 2.826, jnp.float32)


def _moe_params(key, experts=16):
    layer = _moe_layer((0, experts))
    x = jnp.zeros((8, 64))
    return layer.init(key, x)["params"]


def _share(params, lo, hi):
    p = copy.copy(dict(params))
    p["experts"] = {n: v[lo:hi] for n, v in params["experts"].items()}
    return p


def test_the_shares_add_up():
    """Eight layers holding experts 0-1 ... 14-15, the shared expert counted
    once, sum to the uncut layer of all 16; and each share's counts are the
    uncut layer's (every chip routes over all the published experts)."""
    params = _moe_params(jax.random.PRNGKey(0))
    params["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    x = jax.random.normal(jax.random.PRNGKey(2), (96, 64))
    whole, counts = _moe_layer((0, 16)).apply({"params": params}, x)
    shared = _moe_layer((0, 16)).apply(
        {"params": dict(params, experts=jax.tree_util.tree_map(jnp.zeros_like,
                                                               params["experts"]))}, x)[0]
    total = shared
    for lo in range(0, 16, 2):
        part, c = _moe_layer((lo, lo + 2)).apply({"params": _share(params, lo, lo + 2)}, x)
        np.testing.assert_array_equal(c, counts)
        total = total + (part - shared)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert float(jnp.sum(counts)) == 96 * 4


@pytest.mark.parametrize("chunk", [32, 96])
def test_dropless_under_a_router_forced_onto_one_expert(chunk, monkeypatch):
    """Every token's first choice is expert 5: no capacity, no token left
    out; the layer is the reference's, and expert 5 took all 96."""
    params = _moe_params(jax.random.PRNGKey(3))
    params["router_bias"] = jnp.zeros((16,)).at[5].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (96, 64))
    monkeypatch.setattr(moe, "TOKEN_CHUNK", chunk)
    got, counts = _moe_layer((4, 8)).apply(
        {"params": _share(params, 4, 8)}, x)
    model = dict(MODEL, experts_held=[4, 8])
    flat = {"moe.router": params["router"]["kernel"], "moe.router_bias": params["router_bias"],
            **{f"moe.experts.{n}": v[4:8] for n, v in params["experts"].items()},
            **{f"moe.shared.{n}": v["kernel"] for n, v in params["shared"].items()}}
    want, want_counts = ref._moe(x, flat, model, lambda a: a)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(counts, want_counts)
    assert float(counts[5]) == 96


# -- the windowed walk of the sorted assignments (models/moe.held_experts_sum) --

@pytest.mark.parametrize("n,k,held,published,rows", [
    (4096, 8, 16, 128, 8192),    # the cells: a quarter of the buffer
    (4096, 8, 128, 128, 32768),  # every published expert held: the buffer
    (96, 4, 2, 16, 128),         # twice 48, up to a tile of 128 rows
    (96, 4, 16, 16, 384)])
def test_the_window_is_the_shapes_and_nothing_else(n, k, held, published, rows,
                                                   monkeypatch):
    """Twice the expected load in whole tiles, at most the buffer; no
    argument beside the four shapes, no module attribute, no environment."""
    import inspect
    assert moe.window_rows(n, k, held, published) == rows
    assert list(inspect.signature(moe.window_rows).parameters) == [
        "n", "k", "held", "published"]
    assert set(moe.window_rows.__code__.co_names) <= {"min"}
    monkeypatch.setattr(moe, "TOKEN_CHUNK", 7)
    monkeypatch.setenv("MOE_WINDOW_ROWS", "1")
    assert moe.window_rows(n, k, held, published) == rows


def _per_token_sum(x, sel, w, gate, up, down, lo):
    """The held experts' part of the routed sum, a token and a choice at a
    time, float32: what ``held_experts_sum`` has to give."""
    def choice(xt, e, wt):
        here = jnp.logical_and(e >= lo, e < lo + gate.shape[0])
        at = jnp.clip(e - lo, 0, gate.shape[0] - 1)
        h = jax.nn.silu(xt @ gate[at]) * (xt @ up[at])
        return jnp.where(here, wt, 0.0) * (h @ down[at])
    token = lambda xt, es, ws: jnp.sum(jax.vmap(  # noqa: E731
        lambda e, wt: choice(xt, e, wt))(es, ws), axis=0)
    return jax.vmap(token)(x, sel, w)


def _choices(n, k, held, published, lo, live, seed):
    """(n, k) distinct choices a token of which exactly ``live`` in all fall
    on the held range [lo, lo + held)."""
    rng = np.random.default_rng(seed)
    here = np.arange(lo, lo + held)
    absent = np.setdiff1d(np.arange(published), here)
    sel = np.empty((n, k), np.int32)
    for t in range(n):
        c = live // n + (t < live % n)
        row = np.concatenate([rng.choice(here, c, replace=False),
                              rng.choice(absent, k - c, replace=False)])
        sel[t] = rng.permutation(row)
    return jnp.asarray(sel)


WALKS = {  # (n, k, held, published, lo), live assignments: rows are 128 but in the last
    "none": ((96, 4, 4, 32, 8), 0),
    "the_expected_eighth": ((96, 4, 4, 32, 8), 48),
    "exactly_a_window": ((96, 4, 4, 32, 8), 128),
    "a_window_and_a_row": ((96, 4, 4, 32, 8), 129),
    "every_assignment": ((96, 4, 4, 32, 8), 384),
    "all_held_one_window": ((96, 4, 16, 16, 0), 384)}


@pytest.mark.parametrize("case", list(WALKS))
def test_the_windowed_sum_is_the_per_token_sum(case):
    """Value, dx, dw and the three kernels' gradients against the plain
    per-token sum at held loads of nothing, the expected share, exactly a
    window, a row more (two windows) and everything (every window); the
    windows walked are ceil(live / rows), none at no load."""
    (n, k, held, published, lo), live = WALKS[case]
    rows = moe.window_rows(n, k, held, published)
    assert rows == (384 if case == "all_held_one_window" else 128)
    keys = jax.random.split(jax.random.PRNGKey(live), 6)
    sel = _choices(n, k, held, published, lo, live, seed=live)
    assert int(jnp.sum((sel >= lo) & (sel < lo + held))) == live
    x = jax.random.normal(keys[0], (n, 24))
    w = jax.random.uniform(keys[1], (n, k)) + 0.1
    gate, up = (jax.random.normal(key, (held, 24, 12)) / 5 for key in keys[2:4])
    down = jax.random.normal(keys[4], (held, 12, 24)) / 3
    r = jax.random.normal(keys[5], (n, 24))

    def walked(*leaves):
        return moe.held_experts_sum(leaves[0], sel, *leaves[1:], lo, published, n,
                                    jnp.float32)
    got, windows = jax.jit(walked)(x, w, gate, up, down)
    np.testing.assert_allclose(got, _per_token_sum(x, sel, w, gate, up, down, lo),
                               atol=2e-5)
    assert float(windows) == -(-live // rows)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(walked(*a)[0] * r),
                           argnums=(0, 1, 2, 3, 4)))(x, w, gate, up, down)
    want = jax.grad(lambda *a: jnp.sum(_per_token_sum(a[0], sel, *a[1:], lo) * r),
                    argnums=(0, 1, 2, 3, 4))(x, w, gate, up, down)
    for name, a, b in zip(("dx", "dw", "gate", "up", "down"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("chunk", [32, 96])
def test_the_windows_walked_are_a_mean_over_the_chunks(chunk, monkeypatch):
    """``walked`` returns, beside what ``__call__`` returns, the windows the
    held experts walked: ceil(a chunk's live assignments / rows), a mean
    over the chunks. Expert 5 is forced on every token, so every chunk
    holds more than its expected share."""
    params = _moe_params(jax.random.PRNGKey(3))
    params["router_bias"] = jnp.zeros((16,)).at[5].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (96, 64))
    monkeypatch.setattr(moe, "TOKEN_CHUNK", chunk)
    layer, share = _moe_layer((4, 8)), {"params": _share(params, 4, 8)}
    out, counts, walked = layer.apply(share, x, method="walked")
    sel, _, _ = moe.biased_topk_route(x, params["router"]["kernel"],
                                      params["router_bias"], 4, 2.826)
    live = np.sum((np.asarray(sel) >= 4) & (np.asarray(sel) < 8),
                  axis=1).reshape(96 // chunk, chunk).sum(axis=1)
    rows = moe.window_rows(chunk, 4, 4, 16)
    assert rows == {32: 128, 96: 256}[chunk] and live.min() >= chunk
    assert float(walked) == np.mean(-(-live // rows))
    again, same = layer.apply(share, x)
    np.testing.assert_array_equal(out, again)
    np.testing.assert_array_equal(counts, same)


@pytest.fixture(scope="module")
def gradient_paths():
    """The scope path of every operation in the lowered gradient of the tiny
    decoder's loss, taken under ``forward`` as the step takes it."""
    import re
    trainer = tiny_trainer()
    tokens = jnp.asarray(batches(1)[0]["tokens"])

    @jax.named_scope("forward")
    def loss(params):
        return trainer.model.apply({"params": params}, tokens[:, :-1], train=True,
                                   targets=tokens[:, 1:])["loss"]
    text = jax.jit(jax.grad(loss)).lower(trainer.state.params).as_text(debug_info=True)
    # an operation's name starts at the jitted function; the other strings
    # are files and the pieces of a called function's own name
    return {name.rsplit("/", 1)[0] for name in re.findall(r'loc\("(jit\([^"]+)"', text)
            if "/" in name}


def test_the_expert_layers_operations_carry_the_scopes_the_traces_are_read_by(gradient_paths):
    """``moe_ms`` and the experts' rooflines sum the device time of the
    operations whose scope path holds ``moe`` (and ``experts``) as
    components (benchmark/flops/afmoe.scope_seconds): the walk's loops,
    forward and backward, have to sit under them, and the router under
    ``moe`` and ``route``."""
    from benchmark.flops import afmoe
    paths = gradient_paths
    walk = [p for p in paths if "while/body" in p and "experts" in p]
    assert any("transpose(" in p for p in walk) and any("transpose(" not in p for p in walk)
    for path in walk:
        assert afmoe.scope_seconds({"scope_s": {path: 1.0}}, "moe", "experts") == 1.0, path
    assert afmoe.scope_seconds({"scope_s": dict.fromkeys(paths, 1.0)}, "moe", "route")


#: the registered scopes that lie inside a decoder block's attention and
#: expert layer, as the rows ``benchmark/tools/step_parts.py`` prints
PARTS = sorted(f"{s.under}/{name}" for name, s in SCOPE_CATALOG.items()
               if s.origin == "scope" and s.under.split("/")[0] in ("attention", "moe"))


@pytest.mark.parametrize("row", PARTS)
def test_the_lowered_gradient_holds_every_registered_part(gradient_paths, row):
    """Each part of attention and of the walk names operations of the first
    forward pass, of the recomputation (``rematted_computation``) and of the
    backward pass (``transpose(``), so that a reader by scope and
    ``step_parts.py`` find its time in a trace; the kernels' gradient carry
    is the backward walk's alone."""
    from benchmark.tools import step_parts
    passes = {step_parts.which_pass(p) for p in gradient_paths
              if step_parts.row_of(p, SCOPE_CATALOG) == row}
    want = {"backward"} if row.endswith("/carry") else {"forward", "recomputed", "backward"}
    assert passes == want, (row, passes)


def test_no_registered_scope_is_a_flax_modules_name():
    """A reader matches path components by name: a ``jax.named_scope`` that
    took a module's name (``gate``, ``router``) would read that module in.
    ``moe`` and ``lm_head`` name a scope and the module it is wrapped
    around: one thing twice."""
    trainer = tiny_trainer()
    modules = {str(getattr(k, "key", k)) for path, _ in
               jax.tree_util.tree_flatten_with_path(trainer.state.params)[0] for k in path}
    ours = {name for name, s in SCOPE_CATALOG.items() if s.origin == "scope"}
    assert {"experts", "shared", "router", "gate", "attn", "q_proj"} <= modules
    assert ours & modules == {"moe", "lm_head"}
    assert {n for n, s in SCOPE_CATALOG.items() if s.origin == "module"} <= modules


def test_the_decoder_trains_through_main(tmp_path):
    """``main.py train`` with the preset, cut to a CPU's size by --set."""
    from distributed_resnet_tensorflow_tpu import main as cli
    args = ["--preset", "trinity_mini_share8", "--set", f"log_root={tmp_path}",
            "--set", "train.train_steps=4", "--set", "train.log_every_steps=2",
            "--set", "checkpoint.save_every_secs=0", "--set", "train.batch_size=8",
            "--set", "model.attention_impl=dense"]
    for key, value in MODEL.items():
        if isinstance(value, list):
            value = ",".join(str(v) for v in value)
        args += ["--set", PROGRAM_KEY.get(key, f"model.{key}") + f"={value}"]
    assert cli.main(args) in (0, None)
