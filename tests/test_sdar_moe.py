"""The block-diffusion decoder family (model.name="sdar_moe":
models/transformer.CausalDecoder under BlockDiffusionObjective, softmax
routing in models/moe.DroplessMoe, the flash kernels under the
block-diffusion ``Mask``) against its plain reference
(benchmark/reference/sdar_moe.py) and against itself, at sizes a CPU holds;
Pallas kernels in interpret mode at a few hundred positions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check
from benchmark.reference import follow
from benchmark.reference import sdar_moe as ref
from distributed_resnet_tensorflow_tpu.data.tokens import (
    block_diffusion_iterator, block_diffusion_noise)
from distributed_resnet_tensorflow_tpu.models import moe, transformer
from distributed_resnet_tensorflow_tpu.models.moe import (
    DroplessMoe, softmax_topk_route)
from distributed_resnet_tensorflow_tpu.models.transformer import (
    GroupedAttention, rotary)
from distributed_resnet_tensorflow_tpu.ops.attention import (
    attention, block_diffusion_mask)
from distributed_resnet_tensorflow_tpu.ops.pallas.flash_attention import (
    _plan, _steps, _tiles, _walk, flash_attention, tile_census)
from distributed_resnet_tensorflow_tpu.parallel.mesh import create_mesh
from distributed_resnet_tensorflow_tpu.telemetry.tracer import SCOPE_CATALOG
from distributed_resnet_tensorflow_tpu.train.loop import Trainer
from distributed_resnet_tensorflow_tpu.utils.config import get_preset

MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "layer_types": ["full_attention"] * 3,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-6,
    "num_dense_layers": 0, "moe_intermediate_size": 32,
    "experts_published": 16, "experts_held": [4, 8], "num_experts_per_tok": 4,
    "num_shared_experts": 0, "mup_enabled": False, "vocab_held": 50,
    "mask_token_held": 49, "block_length": 4, "noise_eps": 1e-3,
    "compute_dtype": "float32", "seq_len": 64}
OPTIMIZER = {"name": "adamw", "learning_rate": 3e-4, "weight_decay": 0.1,
             "b1": 0.9, "b2": 0.999, "eps": 1e-8, "schedule": "constant"}
CONFIG = {"family": "sdar_moe", "model": MODEL, "optimizer": OPTIMIZER,
          "start_step": 0}
ROWS = 4
#: the reference's names where the program's config has the source's
PROGRAM_KEY = {"seq_len": "data.seq_len", "experts_published": "model.num_experts"}


@pytest.fixture(scope="module", autouse=True)
def several_chunks():
    """Chunks small enough that 512 positions make several of each."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "TOKEN_CHUNK", 64)
        mp.setattr(transformer, "LOSS_CHUNK", 32)
        yield


def tiny_trainer(impl: str = "dense", **overrides) -> Trainer:
    cfg = get_preset("sdar_30b_a3b_share8")
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


def batches(n: int, seed: int = 0, masked=None):
    rng = np.random.default_rng(seed)
    ids = MODEL["mask_token_held"]
    p = np.arange(1, ids + 1, dtype=np.float64) ** -0.7
    out = []
    for _ in range(n):
        tokens = rng.choice(ids, (ROWS, MODEL["seq_len"]), p=p / p.sum()).astype(np.int32)
        m, t = block_diffusion_noise(tokens, rng, MODEL["block_length"],
                                     MODEL["noise_eps"])
        if masked is not None:
            m = np.full_like(m, masked)
        out.append({"tokens": tokens, "masked": m, "t": t})
    return out


def on_device(batch):
    return {k: jnp.asarray(v) for k, v in batch.items()}


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(follow.init_key(11), MODEL)


def test_the_programs_leaves_are_the_references(weights):
    trainer = tiny_trainer()
    mine = jax.tree_util.tree_map(lambda a: a.shape, trainer.state.params)
    assert mine == jax.tree_util.tree_map(lambda a: a.shape, to_tree(weights))
    names = set(ref.program_paths(MODEL))
    assert not any("router_bias" in n or "gate_proj" in n or "post_" in n
                   or "shared" in n for n in names)


def losses(trainer, batch):
    objective = trainer.model.objective()

    def mine(params):
        ce, metrics, _, _, aux = objective.forward(
            trainer.model.apply, {"params": params, "batch_stats": {}},
            on_device(batch))
        assert aux is None and objective.after_update is None
        return ce, metrics

    def theirs(flat):
        total, _ = ref.loss_sum(flat, ref.examples(batch, 0, 0),
                                jnp.ones((ROWS,)), MODEL)
        return total / ROWS
    return mine, theirs


@pytest.mark.parametrize("impl", ["dense", "flash_interpret"])
def test_loss_and_gradients_match_the_reference(weights, impl):
    trainer = tiny_trainer(impl)
    batch = batches(1)[0]
    mine, theirs = losses(trainer, batch)
    with jax.default_matmul_precision("highest"):
        (a, metrics), grads_a = jax.value_and_grad(mine, has_aux=True)(to_tree(weights))
        b, grads_b = jax.value_and_grad(theirs)(weights)
    assert abs(float(a) - float(b)) < 1e-5 * abs(float(b))
    grads_a = to_flat(grads_a)
    scale = np.median([float(jnp.linalg.norm(g)) for g in grads_b.values()])
    for name, g in grads_b.items():
        gap = float(jnp.linalg.norm(grads_a[name] - g)) / max(float(jnp.linalg.norm(g)), scale)
        assert gap < 2e-4, (name, gap)
    assert float(metrics["masked_share"]) == pytest.approx(batch["masked"].mean())
    weight = batch["masked"] / np.repeat(batch["t"], MODEL["block_length"], axis=1)
    assert float(metrics["loss_weight_mean"]) == pytest.approx(weight.mean(), rel=1e-5)
    # 3 routing layers x 512 positions x 4 choices x 4 of 16 experts
    assert 0 < float(metrics["moe_assignments_held"]) < ROWS * 128 * 4
    assert 0.0 <= float(metrics["precision"]) <= 1.0


def test_three_steps_match_the_references_walk(weights):
    """Through ``Trainer.train``: AdamW on the matrices, no decay on norms
    and embedding, no rule after the update."""
    trainer = tiny_trainer("flash_interpret")
    # a copy: the step is given its state's buffers, and the fixture lives on
    trainer.state = trainer.state.replace(
        params=jax.tree_util.tree_map(jnp.copy, to_tree(weights)))
    fed = batches(3)
    got = {"loss": {}}

    def record(step, state, metrics):
        got["loss"][step] = float(metrics["loss"])
        if step == 1:
            mu = state.opt_state[0].mu
            got["moment"] = jax.tree_util.tree_map(
                float, follow.norms_and_probes(to_flat(mu), 11))
        if step == 3:
            p0 = ref.init_params(follow.init_key(11), MODEL)
            got["change"] = jax.tree_util.tree_map(float, follow.norms_and_probes(
                {n: v - p0[n] for n, v in to_flat(state.params).items()}, 11))
    with jax.default_matmul_precision("highest"):
        trainer.train(iter(fed), num_steps=3, hooks=(record,))
    theirs = follow.follow(CONFIG, 11, fed, [1, 2, 3])
    numbers, where = check.compare(got, theirs)
    assert max(numbers.values()) < 2e-3, (numbers, where)
    assert "ruled" not in theirs


@pytest.mark.parametrize("masked", [0, 1])
def test_a_sequence_with_every_token_masked_and_one_with_none(weights, masked):
    """None masked: every weight is 0, the loss is 0 and so is every
    gradient; all masked: the loss is every position's, weighted 1/t."""
    trainer = tiny_trainer()
    batch = batches(1, masked=masked)[0]
    mine, theirs = losses(trainer, batch)
    (a, metrics), grads = jax.value_and_grad(mine, has_aux=True)(to_tree(weights))
    assert float(metrics["masked_share"]) == masked
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree_util.tree_leaves(grads))
    if masked:
        assert float(a) == pytest.approx(float(theirs(weights)), rel=1e-4)
        assert float(a) > 1.0
    else:
        assert float(a) == 0.0 and float(metrics["precision"]) == 0.0
        assert all(float(jnp.max(jnp.abs(g))) == 0.0
                   for g in jax.tree_util.tree_leaves(grads))


# -- the mask: four rules, three readers --------------------------------------

def rules(length: int, block: int) -> np.ndarray:
    """[2L, 2L] bool over [noisy; clean], the four rules as a loop."""
    out = np.zeros((2 * length, 2 * length), bool)
    for p in range(2 * length):
        for r in range(2 * length):
            q_clean, k_clean = p >= length, r >= length
            qb, kb = (p % length) // block, (r % length) // block
            if q_clean and k_clean:
                out[p, r] = kb <= qb
            elif not q_clean and k_clean:
                out[p, r] = kb < qb
            elif not q_clean and not k_clean:
                out[p, r] = kb == qb
    return out


@pytest.mark.parametrize("length,block", [(32, 4), (48, 16), (40, 8), (12, 1)])
def test_the_twins_mask_and_the_references_are_the_four_rules(length, block):
    rows = jnp.arange(2 * length)
    want = rules(length, block)
    mask = block_diffusion_mask(length, block)
    np.testing.assert_array_equal(mask.counts(rows[:, None], rows[None, :]), want)
    np.testing.assert_array_equal(ref.seen(rows, rows, length, block), want)
    assert want.sum() == length * length + length * block
    assert not want[length:, :length].any()  # no clean query sees a noisy key


def test_a_mask_that_does_not_fit_the_call_is_refused():
    q = jnp.zeros((1, 64, 2, 16))
    with pytest.raises(ValueError, match="whole number"):
        block_diffusion_mask(30, 4)
    for fn in (lambda: attention(q, q, q, block_diffusion_mask(16, 4)),
               lambda: flash_attention(q, q, q, block_diffusion_mask(16, 4), True)):
        with pytest.raises(ValueError, match="block_diffusion"):
            fn()
    with pytest.raises(ValueError, match="window"):
        attention(q, q, q, block_diffusion_mask(32, 4), 8)


# (L, B, block_q, block_k): L a multiple of the tiles and not, the tile over
# and under a diffusion block, block_q != block_k both ways, the cell's call
CENSUS_CASES = [(64, 4, 32, 32), (96, 4, 32, 64), (100, 4, 32, 32),
                (128, 32, 64, 32), (72, 4, 16, 48), (128, 4, 16, 64),
                (256, 64, 32, 32), (4096, 4, 512, 512), (4096, 4, 1024, 256)]


@pytest.mark.parametrize("length,block,bq,bk", CENSUS_CASES)
def test_tile_census_against_the_dense_mask(length, block, bq, bk):
    mask = block_diffusion_mask(length, block)
    plan = _plan(2 * length, 128, mask, bq, bk)
    tiles = _tiles(2 * length, mask, plan)
    bq, bk, padded = plan.block_q, plan.block_k, length + plan.tpad
    assert tiles.stride == padded and padded % bq == 0 and padded % bk == 0
    # the dense mask in the kernels' layout: each copy padded at its own end
    rows = np.concatenate([np.arange(length), np.arange(length) + padded])
    counts = np.zeros((2 * padded, 2 * padded), bool)
    counts[np.ix_(rows, rows)] = np.asarray(mask.counts(
        jnp.arange(2 * length)[:, None], jnp.arange(2 * length)[None, :]))
    per_tile = counts.reshape(tiles.nq, bq, tiles.nk, bk).transpose(0, 2, 1, 3)
    any_counts = per_tile.any(axis=(2, 3))

    by_k = np.zeros_like(any_counts)
    for qi in range(tiles.nq):
        runs = tiles.k_runs(qi)
        held = [int(_walk(runs, j)) for j in range(tiles.k_steps)]
        live = held[:_steps(runs)]
        assert len(set(live)) == len(live) <= tiles.k_steps  # no tile twice
        assert held[len(live):] == [live[-1]] * (tiles.k_steps - len(live))
        by_k[qi, live] = True
    by_q = np.zeros_like(any_counts)
    for kj in range(tiles.nk):
        runs = tiles.q_runs(kj)
        live = [int(_walk(runs, r)) for r in range(_steps(runs))]
        assert len(set(live)) == len(live) <= tiles.q_steps
        by_q[live, kj] = True
    np.testing.assert_array_equal(by_k, by_q)
    assert not (any_counts & ~by_k).any()
    # a computed tile that holds no pair: only where a copy's padded keys or
    # padded queries lie (the runs are laid over ids, padded or not)
    for qi, kj in zip(*np.nonzero(by_k & ~any_counts)):
        assert (kj % (tiles.nk // 2) + 1) * bk > length \
            or (qi % (tiles.nq // 2) + 1) * bq > length, (qi, kj)
    if not plan.tpad:
        np.testing.assert_array_equal(by_k, any_counts)
    census = tile_census(2 * length, 128, mask, None, bq, bk)
    assert census == {"grid_steps": tiles.nq * tiles.k_steps,
                      "grid_steps_dkv": tiles.nk * tiles.q_steps,
                      "live": int(by_k.sum()), "masks": True}
    # a tile's mask is the dense mask's tile, both ways round, on the rows
    # of real queries (a padded query's row is sliced off, whatever it saw)
    real = (np.arange(2 * padded) % padded < length).reshape(tiles.nq, bq)
    for qi, kj in zip(*np.nonzero(by_k)):
        if max(bq, bk) > 64 and (qi + kj) % 5:
            continue
        want = per_tile[qi, kj][real[qi]]
        got = np.broadcast_to(tiles.mask_of(qi, kj), (bq, bk))
        np.testing.assert_array_equal(got[real[qi]], want)
        got = np.broadcast_to(tiles.mask_of(qi, kj, keys_first=True), (bk, bq))
        np.testing.assert_array_equal(got[:, real[qi]], want.T)


def test_tile_census_of_the_cells_call():
    """4,096 ids in blocks of 4 at (512, 512): of the 16 x 16 tiles of the
    doubled sequence 80 are live (a noisy q-block its own diagonal tile and
    the clean tiles up to its own, a clean one the clean triangle's), the
    k-walk is 9 steps, the q-walk 16 (clean k-block 0 is seen by all)."""
    mask = block_diffusion_mask(4096, 4)
    assert tile_census(8192, 128, mask, None, 512, 512) == {
        "grid_steps": 144, "grid_steps_dkv": 256, "live": 80, "masks": True}


# L a multiple of the tile and not, B = 4 and 32, equal and grouped heads
TWIN_CASES = [(128, 4, 4, 4, 64, 64), (128, 4, 8, 2, 32, 64), (100, 4, 8, 2, 32, 32),
              (128, 32, 4, 1, 64, 32), (72, 4, 2, 2, 16, 48), (96, 32, 8, 1, 64, 64)]


@pytest.mark.parametrize("length,block,heads,kv,bq,bk", TWIN_CASES)
def test_flash_kernels_match_their_twin_under_the_mask(length, block, heads, kv, bq, bk):
    mask = block_diffusion_mask(length, block)
    key = jax.random.PRNGKey(length + heads)
    q = jax.random.normal(jax.random.fold_in(key, 1), (2, 2 * length, heads, 32))
    k = jax.random.normal(jax.random.fold_in(key, 2), (2, 2 * length, kv, 32))
    v = jax.random.normal(jax.random.fold_in(key, 3), (2, 2 * length, kv, 32))

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, True, bq, bk)

    def twin(q, k, v):
        return attention(q, k, v, mask)
    np.testing.assert_allclose(flash(q, k, v), twin(q, k, v), atol=2e-6)
    wrt = (0, 1, 2)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), wrt)(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(twin(*a))), wrt)(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_the_twin_matches_the_references_blocks_of_queries():
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 96, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 96, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, 96, 2, 16))
    want = ref._attention_core(q[0], k[0], v[0], 48, 4, lambda a: a)
    got = attention(q, k, v, block_diffusion_mask(48, 4))[0].reshape(96, -1)
    np.testing.assert_allclose(got, want, atol=2e-6)


# -- positions ----------------------------------------------------------------

def test_a_noisy_token_and_its_clean_twin_get_equal_rotary_angles():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 2, 16))
    both = jnp.concatenate([x, x], axis=1)
    got = rotary(both, 1e6, jnp.tile(jnp.arange(24), 2))
    np.testing.assert_array_equal(got[:, :24], got[:, 24:])
    np.testing.assert_array_equal(got[:, :24], rotary(x, 1e6))
    # the default is 0..T-1: the twin of a row's first token then sits at 24
    assert float(jnp.max(jnp.abs(rotary(both, 1e6)[:, 24:] - got[:, 24:]))) > 1e-3
    np.testing.assert_allclose(
        got[0], ref._rotary(both[0], 1e6, np.tile(np.arange(24), 2)), atol=1e-6)


def test_rotary_on_every_layer_at_the_positions_given():
    """A full layer of this family carries rotary positions (afmoe's does
    not): the output moves with the ids, and not with a shift that all
    queries and keys share less than with one they do not."""
    layer = GroupedAttention(4, 2, 16, "full_attention", 64, 1e6, 1e-6,
                             jnp.float32, "dense", None, False, True)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 64))
    params = layer.init(jax.random.PRNGKey(6), x)
    assert "gate_proj" not in params["params"]
    a = layer.apply(params, x)
    np.testing.assert_allclose(a, layer.apply(params, x, jnp.arange(32)), atol=1e-6)
    b = layer.apply(params, x, jnp.arange(32)[::-1])
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3


# -- the expert layer ---------------------------------------------------------

#: the registered scopes that lie inside a decoder block's attention and
#: expert layer, as the rows ``benchmark/tools/step_parts.py`` prints
PARTS = sorted(f"{s.under}/{name}" for name, s in SCOPE_CATALOG.items()
               if s.origin == "scope" and s.under.split("/")[0] in ("attention", "moe"))


@pytest.fixture(scope="module")
def gradient_paths():
    """The scope path of every operation in the lowered gradient of the tiny
    decoder's block-diffusion loss, taken under ``forward`` as the step
    takes it."""
    import re
    trainer = tiny_trainer()
    mine, _ = losses(trainer, batches(1)[0])
    text = jax.jit(jax.grad(jax.named_scope("forward")(mine), has_aux=True)).lower(
        trainer.state.params).as_text(debug_info=True)
    return {name.rsplit("/", 1)[0] for name in re.findall(r'loc\("(jit\([^"]+)"', text)
            if "/" in name}


@pytest.mark.parametrize("row", PARTS + ["blockdiff_input"])
def test_the_lowered_gradient_holds_every_registered_part(gradient_paths, row):
    """As ``tests/test_afmoe.py``'s, under this family's objective: rotary
    on every layer, no shared expert, ``blockdiff_input`` at the top of the
    forward pass alone (ids and weights: nothing to differentiate). The
    block has no post-norm, so nothing needs the sum of a walk made again:
    recomputation keeps the walk's ``plan`` (the backward walk reads it) and
    drops its windows (PERF.md section 7.9f), where Trinity's block pays
    for them."""
    from benchmark.tools import step_parts
    passes = {step_parts.which_pass(p) for p in gradient_paths
              if step_parts.row_of(p, SCOPE_CATALOG) == row}
    windows = {f"moe/experts/{part}": {"forward", "backward"}
               for part in ("operands", "gather", "products", "to_tokens")}
    want = {"moe/experts/carry": {"backward"}, "blockdiff_input": {"forward"},
            **windows}.get(row, {"forward", "recomputed", "backward"})
    assert passes == want, (row, passes)


def _moe_layer(held, experts=16):
    return DroplessMoe(experts, held, 4, 32, 0, 1.0, jnp.float32, "softmax")


def test_softmax_route_is_float32_top_k_renormalised():
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 64))
    router = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    sel, w, counts = softmax_topk_route(x.astype(jnp.bfloat16), router, 4)
    p = jax.nn.softmax(jnp.dot(x.astype(jnp.bfloat16).astype(jnp.float32), router,
                               precision="highest"), axis=-1)
    want_sel = np.argsort(-np.asarray(p), axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(sel, -1), np.sort(want_sel, -1))
    np.testing.assert_allclose(jnp.sum(w, -1), 1.0, atol=1e-6)
    np.testing.assert_allclose(w, jnp.take_along_axis(p, sel, -1)
                               / jnp.sum(jnp.take_along_axis(p, sel, -1), -1, keepdims=True),
                               atol=1e-6)
    assert w.dtype == jnp.float32 and float(jnp.sum(counts)) == 40 * 4
    theirs_sel, theirs_w = ref.route(x.astype(jnp.bfloat16).astype(jnp.float32), router,
                                     {"num_experts_per_tok": 4})
    np.testing.assert_array_equal(sel, theirs_sel)
    np.testing.assert_allclose(w, theirs_w, atol=1e-6)


def test_the_shares_add_up():
    """Eight layers holding experts 0-1 ... 14-15 sum to the uncut layer of
    all 16, the softmax weights renormalised over all the chosen experts
    whoever holds them; each share's counts are the uncut layer's; there is
    no shared expert and no router bias among the leaves."""
    whole_layer = _moe_layer((0, 16))
    params = whole_layer.init(jax.random.PRNGKey(0), jnp.zeros((8, 64)))["params"]
    assert set(params) == {"router", "experts"}
    x = jax.random.normal(jax.random.PRNGKey(2), (96, 64))
    whole, counts = whole_layer.apply({"params": params}, x)
    total = jnp.zeros_like(whole)
    for lo in range(0, 16, 2):
        share = dict(params, experts={n: v[lo:lo + 2]
                                      for n, v in params["experts"].items()})
        part, c = _moe_layer((lo, lo + 2)).apply({"params": share}, x)
        np.testing.assert_array_equal(c, counts)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert float(jnp.sum(counts)) == 96 * 4
    flat = {"moe.router": params["router"]["kernel"],
            **{f"moe.experts.{n}": v for n, v in params["experts"].items()}}
    want = ref._moe(x, flat, dict(MODEL, experts_held=[0, 16]), lambda a: a)
    np.testing.assert_allclose(whole, want, atol=2e-5)


@pytest.mark.parametrize("held,rows", [((0, 2), 128), ((4, 12), 384), ((0, 16), 384)])
def test_the_step_reports_the_windows_walked(held, rows):
    """``moe_windows`` among the step's metrics is the layers' mean of the
    windows their held experts walked a chunk: from a share of an eighth
    (rows 128 of a chunk's 384) to a half and the whole (the buffer, one
    window whatever the load)."""
    from distributed_resnet_tensorflow_tpu.models import moe
    from distributed_resnet_tensorflow_tpu.models.transformer import _held_load
    assert moe.window_rows(96, 4, held[1] - held[0], 16) == rows
    layer = _moe_layer(held)
    x = jax.random.normal(jax.random.PRNGKey(2), (96, 64))
    params = _moe_layer((0, 16)).init(jax.random.PRNGKey(0), jnp.zeros((8, 64)))["params"]
    share = dict(params, experts={n: v[held[0]:held[1]]
                                  for n, v in params["experts"].items()})
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "TOKEN_CHUNK", 96)
        _, counts, walked = layer.apply({"params": share}, x, method="walked")
    live = float(jnp.sum(counts[held[0]:held[1]]))
    assert float(walked) == -(-live // rows)

    class Cfg:
        experts_held = held
    got = _held_load(Cfg, {"counts": {"layer0": counts, "layer1": counts},
                           "windows": [walked, walked + 1.0]})
    assert float(got["moe_windows"]) == float(walked) + 0.5
    assert float(got["moe_assignments_held"]) == live
    assert _held_load(Cfg, {"counts": {}, "windows": []}) == {}


def test_the_start_gives_each_chips_range_one_choice_a_token(weights):
    """The reference's initialiser lays a router's columns in periods of
    experts / experts per token: whatever the token, its choices fall one
    into each range, so the held range's load is the expectation at the
    first step whatever the seed (the configuration's ``assumed``)."""
    model = dict(MODEL, experts_published=128, num_experts_per_tok=8,
                 experts_held=[0, 16])
    router = ref.init_params(follow.init_key(5), model)["layer0.moe.router"]
    assert router.shape == (64, 128)
    assert abs(float(jnp.std(router)) - 1 / 8) < 0.01  # fan-in normal still
    x = jax.random.normal(jax.random.PRNGKey(0), (512, 64))
    x = x.at[:128].set(x[0])  # a quarter of the positions one embedding
    sel, _, counts = softmax_topk_route(x, router, 8)
    per_range = np.stack([np.bincount(row // 16, minlength=8) for row in np.asarray(sel)])
    assert (per_range == 1).mean() > 0.97
    held = np.asarray(counts).reshape(8, 16).sum(axis=1)
    assert np.all(np.abs(held - 512) < 16)
    embed = ref.init_params(follow.init_key(5), MODEL)["embed"]
    assert abs(float(jnp.std(embed)) - 1.0) < 0.05


# -- the data layer -----------------------------------------------------------

def test_the_noising_is_the_linear_schedule():
    rng = np.random.default_rng(0)
    tokens = np.zeros((64, 4096), np.int32)
    masked, t = block_diffusion_noise(tokens, rng, 4, 1e-3)
    assert masked.dtype == np.uint8 and masked.shape == tokens.shape
    assert t.dtype == np.float32 and t.shape == (64, 1024)
    assert 1e-3 <= t.min() and t.max() <= 1.0
    assert abs(t.mean() - 0.5005) < 5e-3 and abs(masked.mean() - 0.5005) < 5e-3
    # a block at level t has about 4 t of its ids masked
    per_block = masked.reshape(64, 1024, 4).sum(-1)
    assert abs(np.corrcoef(per_block.ravel(), t.ravel())[0, 1]) > 0.7
    weight = masked / np.repeat(t, 4, axis=1)
    assert abs(weight.mean() - 1.0) < 0.05
    with pytest.raises(ValueError, match="whole number"):
        block_diffusion_noise(np.zeros((2, 30), np.int32), rng, 4, 1e-3)


def test_the_iterator_adds_the_two_leaves_and_keeps_the_mask_id_out():
    a = block_diffusion_iterator(3, 64, 49, 4, 1e-3, seed=5)
    b = block_diffusion_iterator(3, 64, 49, 4, 1e-3, seed=5)
    first, again = next(a), next(b)
    assert {k: (v.shape, v.dtype.name) for k, v in first.items()} == {
        "tokens": ((3, 64), "int32"), "masked": ((3, 64), "uint8"),
        "t": ((3, 16), "float32")}
    for key in first:
        np.testing.assert_array_equal(first[key], again[key])
    assert first["tokens"].max() < 49
    second = next(a)
    assert not np.array_equal(first["masked"], second["masked"])


def test_the_family_trains_through_main(tmp_path):
    """``main.py train`` with the preset, cut to a CPU's size by --set."""
    from distributed_resnet_tensorflow_tpu import main as cli
    args = ["--preset", "sdar_30b_a3b_share8", "--set", f"log_root={tmp_path}",
            "--set", "train.train_steps=3", "--set", "train.log_every_steps=1",
            "--set", "checkpoint.save_every_secs=0", "--set", "train.batch_size=8",
            "--set", "model.attention_impl=dense"]
    for key, value in MODEL.items():
        if isinstance(value, list):
            value = ",".join(str(v) for v in value)
        args += ["--set", PROGRAM_KEY.get(key, f"model.{key}") + f"={value}"]
    assert cli.main(args) in (0, None)
