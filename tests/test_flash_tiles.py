"""The flash kernels' schedule: which tiles a call visits (``tile_census``
against a brute-force enumeration of the mask), the three kernels against
their twin at shapes that hold dead tiles, masked ones and ones whose every
pair counts in one call (interpret mode on the CPU), every row of the block
table against the tuner's committed results, the tuner itself rehearsed in
interpret mode, and the real calls compiled for a described v5e, with the
blocks the compiler gives the expert layer's grouped products there and the
memory traffic of the Mamba-2 convolution's gradient (the described chip's
tests stay in this one file)."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_resnet_tensorflow_tpu.ops.attention import (
    attention, as_mask, block_diffusion_mask)
from distributed_resnet_tensorflow_tpu.ops.pallas.flash_attention import (
    _plan, _tiles, _walk, flash_attention, tile_census)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def counted_pairs(tp, t, causal, window):
    """(tp, tp) bool, brute force: query i (rows) attends key j."""
    i, j = np.arange(tp)[:, None], np.arange(tp)[None, :]
    counts = np.broadcast_to(j < t, (tp, tp)).copy()
    if causal:
        counts &= j <= i
    if window is not None:
        counts &= j > i - window
    return counts


# (t, causal, window, block_q, block_k): windows that are no multiple of a
# block, block_q != block_k both ways, a window under one block and over the
# sequence, padded sequences (200, 333), the cell's own two calls
CENSUS_CASES = [
    (256, True, None, 64, 64), (256, True, 96, 64, 32), (256, True, 96, 32, 64),
    (200, True, 50, 64, 32), (333, True, 100, 32, 128), (384, True, 100, 128, 64),
    (512, True, 7, 64, 64), (256, True, 1000, 64, 128), (256, False, None, 64, 32),
    (200, False, None, 64, 64), (197, False, None, 512, 512),
    (256, False, None, 64, 64),
    (1024, True, 300, 128, 256), (8192, True, 2048, 256, 512),
    (8192, True, None, 256, 512), (8192, True, 2048, 512, 128)]


@pytest.mark.parametrize("t,causal,window,bq,bk", CENSUS_CASES)
def test_tile_census_against_the_mask_enumerated(t, causal, window, bq, bk):
    mask = as_mask(causal, window)
    plan = _plan(t, 128, mask, bq, bk)
    tiles = _tiles(t, mask, plan)
    bq, bk = plan.block_q, plan.block_k
    tp = t + plan.tpad
    counts = counted_pairs(tp, t, causal, window)
    per_tile = counts.reshape(tiles.nq, bq, tiles.nk, bk).transpose(0, 2, 1, 3)
    any_counts = per_tile.any(axis=(2, 3))

    # the k-walk of every q-block and the q-walk of every k-block compute
    # the same tiles, and every counted pair lies in one of them
    by_k = np.zeros_like(any_counts)
    for qi in range(tiles.nq):
        (lo, hi), = tiles.k_runs(qi)
        assert hi - lo + 1 <= tiles.k_steps
        by_k[qi, lo:hi + 1] = True
        # the blocks held along the walk: the run, then its last block again
        held = [int(_walk(((lo, hi),), j)) for j in range(tiles.k_steps)]
        assert held == [min(lo + j, hi) for j in range(tiles.k_steps)]
    by_q = np.zeros_like(any_counts)
    for kj in range(tiles.nk):
        (lo, hi), = tiles.q_runs(kj)
        assert hi - lo + 1 <= tiles.q_steps
        by_q[lo:hi + 1, kj] = True
    np.testing.assert_array_equal(by_k, by_q)
    assert not (any_counts & ~by_k).any()
    # a computed tile that holds none: only where the band's keys in it are
    # all padding (the band is laid over positions, padded or not)
    for qi, kj in zip(*np.nonzero(by_k & ~any_counts)):
        assert (kj + 1) * bk > t, (qi, kj)

    # a call that leaves a pair out masks every tile it computes; one that
    # leaves none out (no diagonal, no padding) builds no mask
    assert tiles.masks == (not counts.all())

    census = tile_census(t, 128, causal, window, bq, bk)
    assert census["live"] == by_k.sum()
    assert census["masks"] == tiles.masks
    assert census["grid_steps"] == tiles.nq * tiles.k_steps >= census["live"]
    assert census["grid_steps_dkv"] == tiles.nk * tiles.q_steps >= census["live"]


def test_tile_census_of_the_cells_two_calls():
    """8,192 tokens at the old table's (256, 512): the full layer walks all
    512 tiles of a head's grid (272 live), a window layer the band alone
    (160, where it walked 512 before PR 34); at the table's blocks the band
    is 80 steps and the triangle 64."""
    assert tile_census(8192, 128, True, None, 256, 512) == {
        "grid_steps": 512, "grid_steps_dkv": 512, "live": 272, "masks": True}
    assert tile_census(8192, 128, True, 2048, 256, 512) == {
        "grid_steps": 160, "grid_steps_dkv": 160, "live": 140, "masks": True}
    assert tile_census(8192, 128, True, 2048, 0, 0) == {
        "grid_steps": 80, "grid_steps_dkv": 80, "live": 70, "masks": True}
    assert tile_census(8192, 128, True, None, 0, 0) == {
        "grid_steps": 64, "grid_steps_dkv": 64, "live": 36, "masks": True}


def test_a_mask_is_the_counted_pairs_of_its_tile():
    t, window, bq, bk = 200, 50, 64, 32
    mask = as_mask(True, window)
    plan = _plan(t, 128, mask, bq, bk)
    tiles = _tiles(t, mask, plan)
    counts = counted_pairs(t + plan.tpad, t, True, window)
    for qi, kj in ((0, 0), (1, 1), (2, 3), (3, 6), (3, 4)):
        want = counts[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
        got = np.broadcast_to(tiles.mask_of(qi, kj), (bq, bk))
        np.testing.assert_array_equal(got, want)
        got_t = np.broadcast_to(tiles.mask_of(qi, kj, keys_first=True),
                                (bk, bq))
        np.testing.assert_array_equal(got_t, want.T)


# dead tiles, tiles on the diagonal and on the band's edge, and tiles whose
# every pair counts, in one call; block_q != block_k both ways; 8 query heads
# on 1 and on 2 key heads; windows no multiple of a block
TWIN_CASES = [
    (512, 8, 1, 200, 64, 128), (512, 8, 2, 200, 128, 64),
    (500, 8, 2, 150, 64, 32), (512, 8, 1, None, 64, 128),
    (448, 8, 2, 100, 32, 64), (333, 8, 1, 77, 32, 32)]


@pytest.mark.parametrize("t,heads,kv,window,bq,bk", TWIN_CASES)
def test_kernels_match_their_twin_over_dead_edge_and_interior_tiles(
        t, heads, kv, window, bq, bk):
    census = tile_census(t, 32, True, window, bq, bk)
    tp = -(-t // max(bq, bk)) * max(bq, bk)
    per_tile = counted_pairs(tp, t, True, window).reshape(
        tp // bq, bq, tp // bk, bk).transpose(0, 2, 1, 3)
    some, every = per_tile.any(axis=(2, 3)), per_tile.all(axis=(2, 3))
    assert (~some).any() and (some & ~every).any() and every.any()
    assert census["live"] < (tp // bq) * (tp // bk)
    key = jax.random.PRNGKey(t + kv)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, t, heads, 32))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, t, kv, 32))
    v = jax.random.normal(jax.random.fold_in(key, 3), (1, t, kv, 32))

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


def test_a_padded_noncausal_call_walks_every_tile():
    """The encoder's call (197 tokens padded to 256 here): every tile live,
    each masked for the padded keys of the last block; unpadded, no mask."""
    assert tile_census(197, 64, False, None, 64, 64) == {
        "grid_steps": 16, "grid_steps_dkv": 16, "live": 16, "masks": True}
    assert tile_census(256, 64, False, None, 64, 64) == {
        "grid_steps": 16, "grid_steps_dkv": 16, "live": 16, "masks": False}
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 197, 2, 64))
               for i in range(3))
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(
        flash_attention(*a, False, True, 64, 64))), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(attention(*a))),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


# the sweep of {128, 256, 512, 1024}² that set the table, and a second run
# of its four best pairs at every call (the winners' margins, 0.29% at the
# least, against 0.02% between the two runs: PERF.md §6, PR 34)
TUNED = ("docs/flash_tune_v5e_gqa_window.json",
         "docs/flash_tune_v5e_gqa_window_repeat.json")
# the block-diffusion mask's calls (PR 35): T positions are two copies of
# T/2 ids in diffusion blocks of 4
TUNED_BLOCK_DIFFUSION = ("docs/flash_tune_v5e_blockdiff.json",)
# the latent-attention cell's call (PR 41): 8,192 tokens, 20 heads of head
# size 256, each with its own keys and values, causal
TUNED_LATENT = ("docs/flash_tune_v5e_mla256.json",)


def tuned_results(files=TUNED):
    results = []
    for name in files:
        with open(os.path.join(ROOT, name)) as f:
            results += [dict(r, file=name) for r in json.load(f)["results"]]
    return results


def call_of(r) -> str:
    return "%s-T%d-d%d-%don%d-w%s%s" % (
        "repeat" if "repeat" in r["file"] else "sweep", r["t"], r["d"],
        r["heads"], r["kv_heads"], r["window"],
        "-B%d" % r["diffusion_block"] if "diffusion_block" in r else "")


def mask_of(r):
    if "diffusion_block" in r:
        return block_diffusion_mask(r["t"] // 2, r["diffusion_block"])
    return as_mask(True, r["window"])


@pytest.mark.parametrize(
    "result", tuned_results() + tuned_results(TUNED_BLOCK_DIFFUSION)
    + tuned_results(TUNED_LATENT), ids=call_of)
def test_every_tuned_call_takes_its_winner(result):
    """``_BLOCK_TABLES`` gives every call the tuner's committed files hold
    (the cell's two among them: 8,192 tokens, head size 128, 32 query heads
    on 4, window 2,048 and none) the winner of that result: the pair with
    the least summed device time of the three kernels, measured on a TPU,
    in the sweep and again in its repeat. A row edited by hand fails here
    until tuner runs that it wins are committed."""
    t, d, window = result["t"], result["d"], result["window"]
    assert result["device"] == "TPU v5 lite"
    plan = _plan(t, d, mask_of(result))
    winner = result["best"]["kernels"]
    assert "%dx%d" % (plan.block_q, plan.block_k) == winner
    point = result["points"][winner]
    assert point["census"] == tile_census(t, d, mask_of(result), None, 0, 0)
    assert sum(point["kernel_ms"].values()) == min(
        sum(p["kernel_ms"].values()) for p in result["points"].values()
        if "kernel_ms" in p)


def test_the_cells_two_calls_are_tuned():
    for name in TUNED:
        calls = {(r["t"], r["d"], r["heads"], r["kv_heads"], r["window"])
                 for r in tuned_results([name])}
        assert {(8192, 128, 32, 4, 2048), (8192, 128, 32, 4, None)} <= calls


def test_the_latent_cells_call_is_tuned_over_every_pair():
    """The head-256 row rests on a sweep of all of {256,512,1024}² at the
    cell's call, the pair VMEM cannot hold recorded as such."""
    (result,) = tuned_results(TUNED_LATENT)
    assert (result["t"], result["d"], result["heads"], result["kv_heads"],
            result["window"]) == (8192, 256, 20, 20, None)
    pairs = {f"{q}x{k}" for q in (256, 512, 1024) for k in (256, 512, 1024)}
    assert set(result["points"]) == pairs
    assert "RESOURCE_EXHAUSTED" in result["points"]["1024x1024"]["error"]


def test_the_block_diffusion_cells_call_is_tuned_in_both_orders():
    """4,096 ids a sequence (8,192 positions), blocks of 4, 32 heads on 4:
    tuned as [noisy; clean] (what ships) and, once, with the copies
    interleaved block by block under the causal walk, which lost by 1.43x
    (PERF.md section 6, PR 35; the kernels cannot run that order any more)."""
    calls = {(r["t"], r["d"], r["heads"], r["kv_heads"], r["diffusion_block"])
             for r in tuned_results(TUNED_BLOCK_DIFFUSION)}
    assert {(t, 128, 32, 4, 4) for t in (4096, 8192, 16384)} <= calls
    (other,) = tuned_results(["docs/flash_tune_v5e_blockdiff_interleaved.json"])
    shipped = next(r for r in tuned_results(TUNED_BLOCK_DIFFUSION) if r["t"] == 8192)
    assert other["order"] == "interleaved" and other["t"] == 8192

    def best(r):
        return min(sum(p["kernel_ms"].values()) for p in r["points"].values())
    assert best(other) > 1.4 * best(shipped)


# -- the tuner: a TPU or nothing; rehearsed here through tune(interpret=True)

@pytest.fixture(scope="module")
def tuner():
    spec = importlib.util.spec_from_file_location(
        "tune_flash_attention",
        os.path.join(ROOT, "tools/tune_flash_attention.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_tuner_writes_nothing_without_a_tpu(tuner, tmp_path):
    out = tmp_path / "tune.json"
    with pytest.raises(SystemExit, match="no TPU"):
        tuner.main(["--out", str(out), "--seqs", "64", "--blocks", "32"])
    assert not out.exists()


def test_the_tuner_rehearsed_in_interpret_mode(tuner, tmp_path):
    """Every result is stamped with the device it ran on and holds the
    census beside the times; a second run measures only what is new; a file
    of another device's results is refused and left as it was."""
    out = str(tmp_path / "tune.json")
    shape = dict(dims=[32], windows=[24, None], batch=1, heads=2, kv_heads=1,
                 pairs=[(16, 32), (32, 16), (0, 0)], reps=1, interpret=True)
    results = tuner.tune(out, seqs=[64], **shape)["results"]
    assert [(r["t"], r["window"]) for r in results] == [(64, 24), (64, None)]
    assert not any("diffusion_block" in r for r in results)
    for r in results:
        assert r["device"].endswith(" interpret") and "TPU" not in r["device"]
        assert set(r["points"]) == {"16x32", "32x16", "0x0"}
        assert set(r["best"]) == {"grad", "fwd"}  # no trace off the chip
        for name, point in r["points"].items():
            bq, bk = map(int, name.split("x"))
            assert point["census"] == tile_census(64, 32, True, r["window"],
                                                  bq, bk)
            assert point["fwd_ms"] > 0 and point["grad_ms"] > 0
    again = tuner.tune(out, seqs=[64, 48], **shape)["results"]
    assert again[:2] == results
    assert [(r["t"], r["window"]) for r in again[2:]] == [(48, 24), (48, None)]

    # the block-diffusion mask: a point of its own beside the causal ones
    mixed = tuner.tune(out, seqs=[64], diffusion_block=4, **shape)["results"]
    assert mixed[:4] == again and len(mixed) == 5
    assert mixed[4]["diffusion_block"] == 4 and mixed[4]["window"] is None
    for name, point in mixed[4]["points"].items():
        bq, bk = map(int, name.split("x"))
        assert point["census"] == tile_census(
            64, 32, block_diffusion_mask(32, 4), None, bq, bk)

    with open(out) as f:
        on_disk = json.load(f)
    for r in on_disk["results"]:
        r["device"] = "TPU v5 lite"
    with open(out, "w") as f:
        json.dump(on_disk, f)
    with pytest.raises(SystemExit, match="TPU v5 lite"):
        tuner.tune(out, seqs=[32], **shape)
    with open(out) as f:
        assert json.load(f) == on_disk


@pytest.mark.parametrize("error,recorded", [
    (jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem"), True),
    (jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm"), False),
    (jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile"), False),
    (ValueError("a shape the kernel refuses"), False)])
def test_the_tuner_records_only_a_vmem_refusal(tuner, monkeypatch, error,
                                               recorded):
    def refuse(fn, args, reps):
        raise error
    monkeypatch.setattr(tuner, "call_ms", refuse)
    q = jnp.zeros((1, 64, 2, 32), jnp.bfloat16)
    if recorded:
        row = tuner.measure(q, q, q, as_mask(True), 32, 32, 1, True)
        assert row["error"].startswith("JaxRuntimeError: RESOURCE_EXHAUSTED")
        assert tuner.best_of({"32x32": row}) == {}
    else:
        with pytest.raises(type(error)):
            tuner.measure(q, q, q, as_mask(True), 32, 32, 1, True)


# -- the real calls, compiled for chips that are described and not attached --

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, *args) -> str:
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


@pytest.mark.parametrize("t,heads,kv,d,causal,window", [
    (8192, 32, 4, 128, True, 2048), (8192, 32, 4, 128, True, None),
    (196, 2, 2, 64, False, None),
    (8192, 32, 4, 128, block_diffusion_mask(4096, 4), None),
    (2000, 8, 2, 128, block_diffusion_mask(1000, 8), None),
    (8192, 20, 20, 256, True, None)])
def test_the_kernels_compile_for_a_v5e(one_chip, t, heads, kv, d, causal,
                                       window):
    """Mosaic takes the three kernels at the cells' shapes (the token
    cell's two calls, the block-diffusion cell's and a padded one of its
    kind, the latent-attention cell's at head size 256, whose row has to
    fit the dK/dV kernel's scoped VMEM) and at the encoder's unaligned one
    (interpret mode checks no layout)."""
    q = jax.ShapeDtypeStruct((2, t, heads, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((2, t, kv, d), jnp.bfloat16, sharding=one_chip)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal, False, 0, 0, window).astype(jnp.float32)), (0, 1, 2))
    text = compiled_text(grad, q, k, k)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text


@pytest.mark.parametrize("chunk,heads,d,causal", [
    (4096, 8, 64, True), (4096, 8, 64, False), (4096, 4, 128, True),
    (2100, 4, 128, False)])
def test_the_ring_compiles_for_four_v5e(topo, chunk, heads, d, causal):
    """``ring_flash_attention`` over four chips at a real chunk: the
    kernels off the diagonal (no ``causal``: every tile live, no mask) and
    on it, at the table's (1024, 1024) for a chunk over 2,048, with the
    logsumexp read, merged and padded as (B·H, 1, T) rows; a chunk that is
    padded (2,100 runs as 3,072) included."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from distributed_resnet_tensorflow_tpu.ops.attention import (
        ring_attention_sharded)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "seq"))
    q = jax.ShapeDtypeStruct(
        (1, 4 * chunk, heads, d), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, "seq", None, None)))
    grad = jax.grad(lambda q, k, v: jnp.sum(ring_attention_sharded(
        q, k, v, mesh, causal=causal, kernel="flash").astype(jnp.float32)),
        (0, 1, 2))
    text = compiled_text(grad, q, q, q)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "collective-permute"):
        assert name in text


def ragged_dot_blocks(text):
    """The blocks of every ``ragged-dot-none`` kernel (the compiler's own
    lowering of ``jax.lax.ragged_dot``) in a compiled program: the
    ``window_bounds`` of its operands and result, read from its Mosaic
    body."""
    import base64
    import re
    blocks = []
    for line in text.splitlines():
        if not re.match(r"\s*(ROOT )?%ragged-dot-none(\.\d+)? = ", line):
            continue
        body = base64.b64decode(
            re.search(r'"body":"([^"]+)"', line).group(1)).decode()
        blocks.append([tuple(int(v) for v in bounds.split(", ")) for bounds in
                       re.findall(r"window_bounds = array<i64: ([\d, ]+)>", body)])
    return blocks


@pytest.mark.parametrize("rows,groups,d,m,gated,calls,least", [
    (12288, 8, 2688, 1856, False, 6, 512),   # nemotron3_nano: relu², padded
    (32768, 16, 2048, 1024, True, 9, 512)])  # trinity_mini: SwiGLU, as published
def test_the_grouped_products_take_wide_blocks_on_a_v5e(
        one_chip, rows, groups, d, m, gated, calls, least):
    """One window's grouped products at a cell's sizes, forward and
    gradient, as the walk runs them (``_operands``, ``_window_sum``): every
    block of every kernel is ``least`` wide beside its 512 rows. Nemotron's
    2,688 x 1,856 ran in blocks of 128 x 128 before the walk padded it to
    3,072 x 2,048; Trinity's widths are left alone and keep their
    512 x 512."""
    from distributed_resnet_tensorflow_tpu.models import moe

    def loss(x, w, kernels, sizes):
        xb, flat_w, cast = moe._operands(jnp.bfloat16, x, w, kernels)
        return jnp.sum(moe._window_sum(jnp.bfloat16, xb, flat_w, cast, sizes))

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    kernels = [shaped((groups, d, m))] * (2 if gated else 1) + [shaped((groups, m, d))]
    text = compiled_text(jax.grad(loss, (0, 1, 2)), shaped((rows, d)), shaped((rows,)),
                         kernels, shaped((groups,), jnp.int32))
    blocks = ragged_dot_blocks(text)
    assert len(blocks) == calls
    widths = {v for call in blocks for bounds in call for v in bounds if v != 1}
    assert widths == {least}, blocks


def entry_traffic(text, shape):
    """Arrays of ``shape`` (an HLO type, ``f32[2,8192,6144]``) that each
    operation of a compiled program's entry writes, and how many of them
    all of its operations read and write together; parameters, tuples and
    their elements and bitcasts move nothing."""
    import re
    entry = text[text.index("\nENTRY "):]
    entry = entry[entry.index("\n", 1) + 1:entry.index("\n}")]
    types, written, moved = {}, {}, 0
    for line in entry.splitlines():
        name, rest = re.match(r"\s*(?:ROOT )?(%\S+) = (.*)", line).groups()
        depth, end = 0, 0
        for end, ch in enumerate(rest):
            depth += ch in "([{"
            depth -= ch in ")]}"
            if ch == " " and depth == 0:
                break
        kind, rest = rest[:end], rest[end + 1:]
        op = rest[:rest.index("(")]
        types[name] = kind
        if op in ("parameter", "get-tuple-element", "bitcast", "tuple"):
            continue
        args = rest[len(op):].split("), ")[0]
        written[name] = kind.count(shape + "{")
        moved += written[name] + sum(types.get(a, "").startswith(shape + "{")
                                     for a in re.findall(r"%[\w.\-]+", args))
    return written, moved


@pytest.mark.parametrize("gradient", ["hand-written", "autodiff"])
def test_the_convolutions_gradient_makes_two_passes_on_a_v5e(one_chip, gradient):
    """The Mamba-2 convolution and its SiLU, differentiated at the
    Nemotron cell's shape (x (2, 8192, 6144) float32, 403 MB): by
    ``conv_silu``'s hand-written gradient no operation writes more than one
    such array and the entry moves five of them (x and g read, g' written
    and read, dx written: 2.01 GB, under 2.1); autodiff of the same sum
    writes the four taps' shifted cotangents out and reads them back, 13
    arrays (5.23 GB), and fails both."""
    from distributed_resnet_tensorflow_tpu.models.mamba import causal_conv, conv_silu
    fn = conv_silu if gradient == "hand-written" else (
        lambda x, w, b: jax.nn.silu(causal_conv(x, w, b)))

    def shaped(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    big = (2, 8192, 6144)
    text = compiled_text(lambda x, w, b, g: jax.vjp(fn, x, w, b)[1](g),
                         shaped(big), shaped((4, big[2])), shaped((big[2],)), shaped(big))
    written, moved = entry_traffic(text, "f32[2,8192,6144]")
    gb = moved * 4 * np.prod(big) / 1e9
    holds = max(written.values()) <= 1 and gb <= 2.1
    assert holds == (gradient == "hand-written"), (written, moved, gb)
    assert moved == (5 if gradient == "hand-written" else 13), (written, moved)
