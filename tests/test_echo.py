"""Data echoing + decoded-sample cache + fused on-device imagenet
augmentation (round 9: data/echo.py, ops/augment.imagenet_train_augment,
the CoalescedStager's fused unpack, data.echo_transfer reuse, and the
decode-pool auto-scaling resolution)."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_resnet_tensorflow_tpu.data.echo import echoing_iterator
from distributed_resnet_tensorflow_tpu.utils.metrics import EchoStats


def _src(n_batches=4, b=8, s=4, seed=0):
    rng = np.random.RandomState(seed)
    for i in range(n_batches):
        yield {"images": rng.randint(0, 256, (b, s, s, 3)).astype(np.uint8),
               "labels": np.arange(i * b, (i + 1) * b, dtype=np.int32)}


def test_echo_passthrough_at_factor_one():
    src = _src()
    assert echoing_iterator(src, 1) is src


def test_echo_determinism_same_seed_same_order():
    a = list(echoing_iterator(_src(), 3, cache_mb=64, seed=5,
                              stats=EchoStats()))
    b = list(echoing_iterator(_src(), 3, cache_mb=64, seed=5,
                              stats=EchoStats()))
    c = list(echoing_iterator(_src(), 3, cache_mb=64, seed=6,
                              stats=EchoStats()))
    assert len(a) == len(b) == 12
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["labels"], y["labels"])
        np.testing.assert_array_equal(x["images"], y["images"])
    assert any(not np.array_equal(x["labels"], y["labels"])
               for x, y in zip(a, c))


def test_echo_epoch_accounting_no_starvation():
    """Finite stream, echo_factor=e, adequate cache: every sample is
    served EXACTLY e times — echoing must not starve (or over-serve) any
    sample, or epoch statistics silently skew."""
    st = EchoStats()
    out = list(echoing_iterator(_src(4, b=8), 2, cache_mb=64, seed=1,
                                stats=st))
    assert len(out) == 8  # 4 batches × e=2
    counts = collections.Counter(
        np.concatenate([b["labels"] for b in out]).tolist())
    assert len(counts) == 32
    assert set(counts.values()) == {2}
    snap = st.snapshot()
    assert snap["decoded"] == 32
    assert snap["emitted"] == 64
    assert snap["hits"] == 32          # every second serving is a hit
    assert snap["hit_rate"] == 0.5
    assert snap["evictions"] == 0


def test_echo_batches_are_reshuffled_not_replayed():
    out = list(echoing_iterator(_src(4, b=8), 2, cache_mb=64, seed=2,
                                stats=EchoStats()))
    # some emitted batch must differ in composition from every source batch
    src_sets = [set(range(i * 8, (i + 1) * 8)) for i in range(4)]
    assert any(set(b["labels"].tolist()) not in src_sets for b in out)


def test_echo_cache_bound_respected_under_eviction():
    """A cache too small for the stream: evictions happen (counted, with
    lost uses) and the byte high-water mark stays within one sample of
    the configured bound."""
    st = EchoStats()
    sample = 4 * 4 * 3 + 8  # image + label bytes per entry (approx)
    cap_mb = (5 * sample) / 1e6
    out = list(echoing_iterator(_src(6, b=8), 3, cache_mb=cap_mb, seed=1,
                                stats=st))
    snap = st.snapshot()
    assert snap["evictions"] > 0
    assert snap["lost_uses"] >= snap["evictions"]
    assert snap["peak_cache_bytes"] <= snap["cache_cap_bytes"] + 2 * sample
    assert out and snap["emitted"] > 0
    # decoded samples all entered the cache even though some were evicted
    assert snap["decoded"] == 48


def test_echo_cache_too_small_raises_loudly():
    """A cache that can never accumulate one batch of servings must be a
    loud ValueError, not a train loop silently blocked in next()."""
    sample = 4 * 4 * 3 + 8
    it = echoing_iterator(_src(3, b=8), 2, cache_mb=(2 * sample) / 1e6,
                          seed=0, stats=EchoStats())
    with pytest.raises(ValueError, match="echo_cache_mb"):
        next(it)


def test_echo_stats_event_row(tmp_path):
    from distributed_resnet_tensorflow_tpu.train.hooks import InputEchoHook
    from distributed_resnet_tensorflow_tpu.utils.metrics import (
        MetricsWriter, echo_stats, read_metrics)

    echo_stats.reset()
    echo_stats.configure(2, 10 ** 6)
    w = MetricsWriter(str(tmp_path), enable_tensorboard=False)
    hook = InputEchoHook(w, every_steps=10)
    hook(10, None, {})  # nothing emitted yet: no row
    echo_stats.add(decoded=8, emitted=16, hits=8, cache_bytes=1000)
    hook(20, None, {})
    w.close()
    rows = [r for r in read_metrics(str(tmp_path))
            if r.get("event") == "input_echo"]
    assert len(rows) == 1
    assert rows[0]["step"] == 20
    assert rows[0]["hits"] == 8 and rows[0]["hit_rate"] == 0.5
    assert rows[0]["echo_factor"] == 2
    echo_stats.reset()


def test_resolve_decode_workers_auto_and_explicit(monkeypatch):
    import distributed_resnet_tensorflow_tpu.data as data_mod
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset

    cfg = get_preset("imagenet_resnet50")
    import os
    for cores, want_procs, want_threads in ((1, 0, 4), (2, 0, 4),
                                            (4, 4, 4), (16, 8, 8)):
        monkeypatch.setattr(os, "cpu_count", lambda c=cores: c)
        procs, threads = data_mod.resolve_decode_workers(cfg)
        assert (procs, threads) == (want_procs, want_threads), cores
    # explicit settings win over auto
    cfg.data.decode_processes = 2
    cfg.data.num_parallel_calls = 3
    monkeypatch.setattr(os, "cpu_count", lambda: 16)
    assert data_mod.resolve_decode_workers(cfg) == (2, 3)


# ---------------------------------------------------------------------------
# fused on-device imagenet augmentation
# ---------------------------------------------------------------------------

def test_imagenet_eval_standardize_exact_vs_host():
    """Eval-mode device prep is EXACTLY the host float path."""
    from distributed_resnet_tensorflow_tpu.data.preprocessing import RGB_MEANS
    from distributed_resnet_tensorflow_tpu.ops.augment import vgg_standardize

    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 256, (4, 8, 8, 3)).astype(np.uint8)
    host = imgs.astype(np.float32) / 255.0 - RGB_MEANS
    dev = np.asarray(vgg_standardize(jnp.asarray(imgs)))
    np.testing.assert_allclose(dev, host, atol=1e-6)


def test_imagenet_train_augment_parity_modulo_rng():
    """Train-mode device augmentation == the same ops on the host, given
    the device's own flip draws (parity modulo RNG: same operations,
    the random draws extracted from the identical key)."""
    from distributed_resnet_tensorflow_tpu.data.preprocessing import RGB_MEANS
    from distributed_resnet_tensorflow_tpu.ops.augment import (
        imagenet_train_augment)

    rng = np.random.RandomState(1)
    imgs = rng.randint(0, 256, (8, 8, 8, 3)).astype(np.uint8)
    key = jax.random.PRNGKey(9)
    dev = np.asarray(imagenet_train_augment(jnp.asarray(imgs), key, pad=0))
    flips = np.asarray(jax.random.bernoulli(key, 0.5, (8,)))
    host = np.where(flips[:, None, None, None],
                    imgs[:, :, ::-1, :], imgs).astype(np.float32)
    host = host / 255.0 - RGB_MEANS
    np.testing.assert_allclose(dev, host, atol=1e-6)
    assert flips.any() and not flips.all()  # both branches exercised


def _plain_flip(imgs, key):
    """The formula the lane-dense path must reproduce bit for bit: the
    draws from the same key, a width-reversed select, then float32."""
    flips = np.asarray(jax.random.bernoulli(key, 0.5, (imgs.shape[0],)))
    assert flips.any() and not flips.all()  # both branches occur
    return np.where(flips[:, None, None, None], imgs[:, :, ::-1, :],
                    imgs).astype(np.float32)


def _all_pixel_values(b, h, w, c, seed):
    """uint8 NHWC batch in which every one of the 256 values occurs."""
    n = b * h * w * c
    assert n >= 256
    vals = np.concatenate([np.arange(256), np.random.RandomState(
        seed).randint(0, 256, n - 256)]).astype(np.uint8)
    np.random.RandomState(seed + 1).shuffle(vals)
    assert len(np.unique(vals)) == 256
    return vals.reshape(b, h, w, c)


@pytest.mark.parametrize("w", [7, 8])
@pytest.mark.parametrize("c", [1, 3])
def test_random_flip_lane_permutation_bit_identical(w, c):
    """The flip on the lane-dense [B, H, W*C] view — a one-hot lane
    permutation in bf16 — returns exactly the pixels of a width reverse:
    odd and even W, C = 3 and C = 1, all 256 values."""
    from distributed_resnet_tensorflow_tpu.ops.augment import random_flip

    b, h = 8, 5
    imgs = _all_pixel_values(b, h, w, c, seed=w * 10 + c)
    key = jax.random.PRNGKey(4)
    rows = random_flip(jnp.asarray(imgs.reshape(b, h, w * c)), key, c)
    assert rows.dtype == jnp.float32 and rows.shape == (b, h, w * c)
    np.testing.assert_array_equal(
        np.asarray(rows).reshape(b, h, w, c), _plain_flip(imgs, key))


@pytest.mark.parametrize("w", [7, 8])
@pytest.mark.parametrize("as_view", [False, True])
def test_imagenet_train_augment_pad0_bit_identical(w, as_view):
    """pad-0 train augmentation == where(flip, x[:, :, ::-1, :], x)
    .astype(f32) / 255 - RGB_MEANS, bit for bit (not allclose), given as
    NHWC and as the [B, H, W*C] view the fused unpack hands over."""
    from distributed_resnet_tensorflow_tpu.data.preprocessing import RGB_MEANS
    from distributed_resnet_tensorflow_tpu.ops.augment import (
        imagenet_train_augment)

    b, h, c = 8, 6, 3
    imgs = _all_pixel_values(b, h, w, c, seed=w)
    key = jax.random.PRNGKey(9)
    if as_view:
        dev = imagenet_train_augment(
            jnp.asarray(imgs.reshape(b, h, w * c)), key, pad=0, channels=c)
    else:
        dev = imagenet_train_augment(jnp.asarray(imgs), key, pad=0)
    assert dev.shape == (b, h, w, c) and dev.dtype == jnp.float32
    host = _plain_flip(imgs, key) / np.float32(255.0) - RGB_MEANS
    np.testing.assert_array_equal(np.asarray(dev), host)


def test_imagenet_train_augment_refuses_what_it_cannot_keep_exact():
    """bf16 holds uint8 pixels exactly and nothing wider; the standardize
    has three channel means."""
    from distributed_resnet_tensorflow_tpu.ops.augment import (
        imagenet_train_augment)

    key = jax.random.PRNGKey(0)
    with pytest.raises(TypeError, match="uint8"):
        imagenet_train_augment(jnp.zeros((2, 4, 4, 3), jnp.float32), key)
    with pytest.raises(ValueError, match="channel"):
        imagenet_train_augment(jnp.zeros((2, 4, 4, 1), jnp.uint8), key)


def test_imagenet_train_augment_pad_jitter_windows():
    """augment_pad > 0: every output is a valid window of the padded
    original (possibly flipped), standardized — the crop machinery is the
    proven cifar one-hot-matmul path."""
    from distributed_resnet_tensorflow_tpu.data.preprocessing import RGB_MEANS
    from distributed_resnet_tensorflow_tpu.ops.augment import (
        imagenet_train_augment)

    s, pad = 8, 2
    base = (np.arange(s * s * 3) % 251).reshape(s, s, 3).astype(np.uint8)
    imgs = np.stack([base] * 4)
    out = np.asarray(imagenet_train_augment(
        jnp.asarray(imgs), jax.random.PRNGKey(3), pad=pad))
    padded = np.pad(base, ((pad, pad), (pad, pad), (0, 0))).astype(np.float32)
    windows = set()
    for y in range(2 * pad + 1):
        for x in range(2 * pad + 1):
            win = padded[y:y + s, x:x + s] / 255.0 - RGB_MEANS
            windows.add(np.round(win, 5).tobytes())
            windows.add(np.round(win[:, ::-1], 5).tobytes())
    for i in range(4):
        assert np.round(out[i], 5).tobytes() in windows, i


def test_fused_unpack_augment_fresh_per_put_and_deterministic():
    """The stager's fused unpack draws a fresh augmentation per put
    (counter embedded in the staged bytes) and is deterministic in
    (seed, counter) — two stagers replay identically."""
    from distributed_resnet_tensorflow_tpu.ops.augment import (
        device_augment_fn)
    from distributed_resnet_tensorflow_tpu.parallel.mesh import create_mesh
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        CoalescedStager)
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig

    mesh = create_mesh(MeshConfig())  # data=-1: all (virtual) devices
    rng = np.random.RandomState(0)
    batch = {"images": rng.randint(0, 256, (8, 8, 8, 3)).astype(np.uint8),
             "labels": rng.randint(0, 10, (8,)).astype(np.int32)}
    aug = ("images", "imagenet_train", 0)
    st = CoalescedStager(mesh, ring=3, augment=aug, augment_seed=7)
    out0 = np.asarray(st.put_now(dict(batch))["images"])
    out1 = np.asarray(st.put_now(dict(batch))["images"])
    assert out0.dtype == np.float32
    assert not np.allclose(out0, out1)  # fresh draws per put
    # exact expected value: fn(images, fold_in(PRNGKey(seed), counter))
    fn = device_augment_fn("imagenet_train", 0)
    for ctr, got in ((0, out0), (1, out1)):
        exp = np.asarray(fn(jnp.asarray(batch["images"]),
                            jax.random.fold_in(jax.random.PRNGKey(7),
                                               np.uint32(ctr))))
        np.testing.assert_allclose(got, exp, atol=1e-6)
    st2 = CoalescedStager(mesh, ring=3, augment=aug, augment_seed=7)
    np.testing.assert_allclose(
        np.asarray(st2.put_now(dict(batch))["images"]), out0, atol=1e-6)
    # labels ride through untouched
    np.testing.assert_array_equal(
        np.asarray(st2.put_now(dict(batch))["labels"]), batch["labels"])


def test_fused_unpack_augment_stacked_per_step_keys():
    """Stacked (K, B) groups: each scan step's microbatch augments under
    its own split key — parity with applying the resolved fn per k."""
    from distributed_resnet_tensorflow_tpu.ops.augment import (
        device_augment_fn)
    from distributed_resnet_tensorflow_tpu.parallel.mesh import create_mesh
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        CoalescedStager)
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig

    mesh = create_mesh(MeshConfig())  # data=-1: all (virtual) devices
    rng = np.random.RandomState(2)
    sb = {"images": rng.randint(0, 256, (3, 8, 8, 8, 3)).astype(np.uint8),
          "labels": rng.randint(0, 10, (3, 8)).astype(np.int32)}
    st = CoalescedStager(mesh, stacked=True, ring=3,
                         augment=("images", "imagenet_train", 2),
                         augment_seed=11)
    out = np.asarray(st.put_now(dict(sb))["images"])
    fn = device_augment_fn("imagenet_train", 2)
    keys = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(11), np.uint32(0)), 3)
    exp = np.stack([np.asarray(fn(jnp.asarray(sb["images"][k]), keys[k]))
                    for k in range(3)])
    np.testing.assert_allclose(out, exp, atol=1e-5)


@pytest.mark.parametrize("stacked", [False, True])
def test_fused_unpack_pad0_bit_identical_to_plain_formula(stacked):
    """The fused unpack of a CoalescedStager (single-step and stacked)
    returns the plain formula for the counter it embedded: draws
    bernoulli(fold_in(PRNGKey(seed), n-th put), 1/2) over the rows
    (per-step split keys when stacked), the width-reversed select on
    NHWC, x / 255 - RGB_MEANS in float32.

    Held in two parts, because a COMPILED program does not pin the last
    bit of `x / 255 - m` on the CPU (XLA multiplies by the reciprocal and
    LLVM contracts multiply-add into fma where its vectoriser pleases:
    the NHWC form this path replaced came out fma in two channels and
    not in the third): every pixel, read back out of the float32 result,
    is exactly the pixel the plain formula puts there (flip, draws,
    counter and layout, bit for bit), and the float32 value is within
    two roundings of a number under 1 (2**-23) of the formula's. The
    op-by-op tests above hold the arithmetic itself to the bit."""
    from distributed_resnet_tensorflow_tpu.data.preprocessing import RGB_MEANS
    from distributed_resnet_tensorflow_tpu.parallel.mesh import create_mesh
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        CoalescedStager)
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig

    mesh = create_mesh(MeshConfig())  # data=-1: all (virtual) devices
    k, b, h, w, c = 3, 8, 6, 7, 3
    imgs = _all_pixel_values(k * b, h, w, c, seed=5).reshape(k, b, h, w, c)
    labels = np.arange(k * b, dtype=np.int32).reshape(k, b)
    batch = {"images": imgs, "labels": labels} if stacked else \
        {"images": imgs[0], "labels": labels[0]}
    st = CoalescedStager(mesh, stacked=stacked, ring=3,
                         augment=("images", "imagenet_train", 0),
                         augment_seed=13)
    for ctr in range(2):
        out = st.put_now(dict(batch))
        got = np.asarray(out["images"])
        assert got.dtype == np.float32 and got.shape == batch["images"].shape
        key = jax.random.fold_in(jax.random.PRNGKey(13), np.uint32(ctr))
        if stacked:
            keys = jax.random.split(key, k)
            flipped = np.stack([_plain_flip(imgs[i], keys[i])
                                for i in range(k)])
        else:
            flipped = _plain_flip(imgs[0], key)
        pixels = np.rint((got.astype(np.float64) + RGB_MEANS) * 255.0)
        np.testing.assert_array_equal(pixels, flipped)
        np.testing.assert_allclose(
            got, flipped / np.float32(255.0) - RGB_MEANS, rtol=0,
            atol=2.0 ** -23)
        np.testing.assert_array_equal(np.asarray(out["labels"]),
                                      batch["labels"])


def _minor_dims(stablehlo_text):
    """Minor dimension of every ranked tensor type in a lowered module."""
    return [(int(m.group(1)), m.group(0)) for m in re.finditer(
        r"tensor<(?:\d+x)*(\d+)x[a-z]+\d+>", stablehlo_text)]


@pytest.mark.parametrize("stacked", [False, True])
def test_fused_unpack_lowers_without_reverse_or_minor3(stacked):
    """Structure of the lowered fused unpack for [8,16,16,3] uint8 crops:
    no `reverse` op, and no tensor whose minor dimension is 3 other than
    the float32 result. On a TPU a [.., W, 3] tensor keeps the 3 in the
    128 lanes (42x the bytes) and `rev` runs along its sublanes: together
    41% of rn50_staged's device time before PR 26. A later edit must not
    quietly bring either back."""
    from distributed_resnet_tensorflow_tpu.parallel.mesh import create_mesh
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        staged_unpack_program)
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig

    mesh = create_mesh(MeshConfig())  # data=-1: all (virtual) devices
    lead = (2, 8) if stacked else (8,)
    shapes = {"images": jax.ShapeDtypeStruct(lead + (16, 16, 3), np.uint8),
              "labels": jax.ShapeDtypeStruct(lead, np.int32)}
    unpack, flat = staged_unpack_program(
        mesh, shapes, stacked=stacked,
        augment=("images", "imagenet_train", 0), augment_seed=3)
    text = unpack.lower(flat).as_text()
    assert "stablehlo.dot_general" in text  # the lane permutation engaged
    assert "reverse" not in text
    result = "tensor<" + "x".join(map(str, lead + (16, 16, 3))) + "xf32>"
    assert result in text
    minor3 = {ty for d, ty in _minor_dims(text) if d == 3} - {result}
    if stacked:  # lax.map's carry and per-step slot of the same result
        minor3 -= {"tensor<8x16x16x3xf32>", "tensor<1x8x16x16x3xf32>"}
    assert not minor3, minor3


def test_abstract_staged_unpack_traces_augment():
    """The allocation-free gate entry (analysis/elaborate.py uses it per
    preset): output shapes/dtypes of the fused unpack+augment program."""
    from distributed_resnet_tensorflow_tpu.parallel.mesh import create_mesh
    from distributed_resnet_tensorflow_tpu.parallel.sharding import (
        abstract_staged_unpack)
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig

    mesh = create_mesh(MeshConfig())  # data=-1: all (virtual) devices
    shapes = {"images": jax.ShapeDtypeStruct((8, 8, 8, 3), np.uint8),
              "labels": jax.ShapeDtypeStruct((8,), np.int32)}
    out = abstract_staged_unpack(mesh, shapes,
                                 augment=("images", "imagenet_train", 2))
    assert out["images"].shape == (8, 8, 8, 3)
    assert out["images"].dtype == np.float32  # augmented
    assert out["labels"].dtype == np.int32
    # neutral trace keeps uint8
    out2 = abstract_staged_unpack(mesh, shapes)
    assert out2["images"].dtype == np.uint8


def test_host_flip_skipped_when_device_flips():
    """device_flip contract: the flip is still DRAWN (RNG stream order
    preserved) but not applied — same crop geometry, unflipped pixels."""
    from distributed_resnet_tensorflow_tpu.data.preprocessing import (
        encode_jpeg, train_crop_from_bytes)

    rng = np.random.RandomState(0)
    img = rng.randint(0, 256, (64, 80, 3)).astype(np.uint8)
    data = encode_jpeg(img)
    a = train_crop_from_bytes(data, np.random.RandomState(5), 16,
                              resize_side_min=32, resize_side_max=48)
    b = train_crop_from_bytes(data, np.random.RandomState(5), 16,
                              resize_side_min=32, resize_side_max=48,
                              apply_flip=False)
    # identical crop geometry; the ONLY permitted difference is the flip
    assert np.array_equal(a, b) or np.array_equal(a, b[:, ::-1])
    assert a.shape == b.shape == (16, 16, 3)


# ---------------------------------------------------------------------------
# trainer integration: fused augment + transfer echo
# ---------------------------------------------------------------------------

def _imagenet_cfg(k=1, echo_transfer=1):
    from distributed_resnet_tensorflow_tpu.utils.config import get_preset
    cfg = get_preset("imagenet_resnet50")
    cfg.model.resnet_size = 18
    cfg.model.num_classes = 8
    cfg.model.compute_dtype = "float32"
    cfg.data.image_size = 16
    cfg.train.batch_size = 8
    cfg.train.steps_per_loop = k
    cfg.data.device_augment = "on"
    cfg.data.coalesced_transfer = "on"
    cfg.data.echo_transfer = echo_transfer
    cfg.mesh.data = -1  # all virtual devices (conftest's 8-way CPU mesh)
    cfg.checkpoint.save_every_secs = 0.0
    return cfg


def _uint8_batches(n, b=8, s=16):
    rng = np.random.RandomState(0)
    for _ in range(n):
        yield {"images": rng.randint(0, 256, (b, s, s, 3)).astype(np.uint8),
               "labels": rng.randint(0, 8, (b,)).astype(np.int32)}


@pytest.mark.slow  # re-tiered out of the 870s tier-1 (ISSUE 13); input_smoke.sh covers the live fused-augment+sanitizer path
@pytest.mark.heavy
def test_fused_augment_train_step_sanitizer_green():
    """Fused unpack+augment end-to-end under the cross-thread dispatch
    sanitizer: the augmented unpack is a multi-device program and must
    keep being dispatched ONLY from the consumer thread."""
    from distributed_resnet_tensorflow_tpu.analysis import (
        dispatch_sanitizer as ds)
    from distributed_resnet_tensorflow_tpu.train import Trainer

    cfg = _imagenet_cfg(k=1)
    tr = Trainer(cfg)
    assert tr.train_put_augments  # imagenet + device_augment + stager
    tr.init_state()
    with ds.enabled():
        state, m = tr.train(_uint8_batches(5), num_steps=3)
    assert int(state.step) == 3
    assert np.isfinite(float(m["loss"]))


def test_attach_device_dataset_keeps_imagenet_augment():
    """attach_device_dataset on a fused-augment imagenet Trainer must move
    the IMAGENET augmentation back into the step (the idx path bypasses
    the stager) — not install the cifar default."""
    from distributed_resnet_tensorflow_tpu.ops import augment
    from distributed_resnet_tensorflow_tpu.train import Trainer

    cfg = _imagenet_cfg(k=1)
    tr = Trainer(cfg)
    assert tr.train_put_augments and tr._aug_fn is None
    imgs = np.zeros((16, 16, 16, 3), np.uint8)
    tr.attach_device_dataset(imgs, np.zeros((16,), np.int32))
    key = jax.random.PRNGKey(0)
    out = np.asarray(tr._aug_fn(jnp.asarray(imgs[:2]), key))
    exp = np.asarray(augment.imagenet_train_augment(
        jnp.asarray(imgs[:2]), key, pad=cfg.data.augment_pad))
    np.testing.assert_allclose(out, exp, atol=1e-6)
    tr.detach_device_dataset()
    assert tr._aug_fn is None  # config-resolved fused choice restored


@pytest.mark.slow  # re-tiered out of the 870s tier-1 (ISSUE 13); input_smoke.sh covers live echoing end-to-end
@pytest.mark.heavy
def test_echo_transfer_amortizes_transfers():
    """data.echo_transfer=2: a finite source of exactly 2 stacked groups
    sustains 8 optimizer steps (one H2D transfer feeds
    echo_transfer × steps_per_loop steps). Without reuse the same source
    could feed only 4."""
    from distributed_resnet_tensorflow_tpu.train import Trainer

    cfg = _imagenet_cfg(k=2, echo_transfer=2)
    tr = Trainer(cfg)
    assert not tr.train_put_augments  # reuse forces step-side augment
    tr.init_state()
    state, m = tr.train(_uint8_batches(4), num_steps=8)
    assert int(state.step) == 8
    assert np.isfinite(float(m["loss"]))
