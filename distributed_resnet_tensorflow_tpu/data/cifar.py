"""CIFAR-10/100 input pipeline — numpy-native, TPU-feeding.

Replaces BOTH reference CIFAR paths with one implementation:
  * the legacy queue-runner pipeline (reference cifar_input.py:21-115 —
    string_input_producer + FixedLengthRecordReader + RandomShuffleQueue), and
  * the tf.data pipeline duplicated in the mains (reference
    resnet_cifar_main.py:134-246).

Record format (CIFAR binary): [label bytes][3072 bytes R,G,B planes of 32x32].
CIFAR-10: 1 label byte, files data_batch_{1..5}.bin / test_batch.bin
(reference resnet_cifar_main.py:137-154). CIFAR-100: coarse+fine label bytes,
fine label used — the reference handled this only on the legacy path via
label_offset=1 (reference cifar_input.py:40-43) while its tf.data path
one-hotted to 10 classes and silently broke cifar100 (reference
resnet_cifar_main.py:171, SURVEY.md §2 bug list). Fixed here: one parser,
both datasets.

Augmentation (train): pad 32→36, random 32x32 crop, random horizontal flip,
per-image standardization (reference resnet_cifar_main.py:185-199 and
cifar_input.py:66-75). Eval: standardization only.
"""
from __future__ import annotations

import os
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..telemetry.tracer import span

IMAGE_SIZE = 32
DEPTH = 3
_REC_IMG = IMAGE_SIZE * IMAGE_SIZE * DEPTH  # 3072


def _record_layout(dataset: str) -> Tuple[int, int]:
    """(label_bytes, label_offset): cifar10 = (1, 0); cifar100 = (2, 1) —
    byte 0 coarse, byte 1 fine (reference cifar_input.py:40-43)."""
    if dataset == "cifar10":
        return 1, 0
    if dataset == "cifar100":
        return 2, 1
    raise ValueError(f"unknown cifar dataset {dataset!r}")


def dataset_filenames(dataset: str, data_dir: str, mode: str) -> List[str]:
    """Train/eval shard lists (reference resnet_cifar_main.py:140-154)."""
    if dataset == "cifar10":
        if mode == "train":
            names = [f"data_batch_{i}.bin" for i in range(1, 6)]
        else:
            names = ["test_batch.bin"]
    else:  # cifar100 binary release
        names = ["train.bin"] if mode == "train" else ["test.bin"]
    paths = [os.path.join(data_dir, n) for n in names]
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        raise FileNotFoundError(f"missing CIFAR files: {missing}")
    return paths


def load_cifar(dataset: str, data_dir: str, mode: str,
               use_native: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Parse raw records → (images uint8 NHWC, labels int32).

    Records store CHW planes; transpose to NHWC, the TPU-native layout
    (reference parse_record did the same transpose, resnet_cifar_main.py:157-182).
    ``use_native`` parses in C++ (native/dataloader.cc) — identical output,
    used for the high-rate path. The library is built from the checkout on
    first use; if that fails the call raises NativeUnavailable with the
    reason (the python parser is ``use_native=False``, not a fallback).
    """
    label_bytes, label_offset = _record_layout(dataset)
    rec_len = label_bytes + _REC_IMG
    paths = dataset_filenames(dataset, data_dir, mode)
    # corrupt/truncated files must fail loudly on BOTH parsers (the C++
    # fread loop would silently stop at a partial record)
    for path in paths:
        size = os.path.getsize(path)
        if size % rec_len != 0:
            raise ValueError(f"{path}: size {size} not a multiple of "
                             f"record length {rec_len}")
    if use_native:
        from .native_loader import load_cifar_native
        imgs, lbls = [], []
        for path in paths:
            im, lb = load_cifar_native(path, label_bytes, label_offset)
            imgs.append(im)
            lbls.append(lb)
        return np.concatenate(imgs), np.concatenate(lbls)
    images, labels = [], []
    for path in paths:
        raw = np.fromfile(path, dtype=np.uint8)
        if raw.size % rec_len != 0:
            raise ValueError(f"{path}: size {raw.size} not a multiple of "
                             f"record length {rec_len}")
        recs = raw.reshape(-1, rec_len)
        labels.append(recs[:, label_offset].astype(np.int32))
        imgs = recs[:, label_bytes:].reshape(-1, DEPTH, IMAGE_SIZE, IMAGE_SIZE)
        images.append(imgs.transpose(0, 2, 3, 1))  # CHW → HWC
    return np.concatenate(images), np.concatenate(labels)


# ---------------------------------------------------------------------------
# augmentation (vectorized over the batch)
# ---------------------------------------------------------------------------

def standardize(images: np.ndarray) -> np.ndarray:
    """Per-image standardization: (x-mean)/adjusted_std with
    adjusted_std = max(std, 1/sqrt(N)) — TF semantics the reference used
    (reference resnet_cifar_main.py:199, cifar_input.py:75)."""
    x = images.astype(np.float32)
    n = np.prod(x.shape[1:])
    mean = x.mean(axis=(1, 2, 3), keepdims=True)
    std = x.std(axis=(1, 2, 3), keepdims=True)
    adj = np.maximum(std, np.float32(1.0 / np.sqrt(float(n))))
    return ((x - mean) / adj).astype(np.float32)


def augment_train(images: np.ndarray, rng: np.random.RandomState) -> np.ndarray:
    """Pad to 36, random 32-crop, random flip (reference
    resnet_cifar_main.py:188-199). Vectorized gather-based crop."""
    b = images.shape[0]
    pad = (36 - IMAGE_SIZE) // 2
    padded = np.pad(images, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    ys = rng.randint(0, 2 * pad + 1, size=b)
    xs = rng.randint(0, 2 * pad + 1, size=b)
    # gather crops via advanced indexing
    yy = ys[:, None] + np.arange(IMAGE_SIZE)[None, :]           # (b, 32)
    xx = xs[:, None] + np.arange(IMAGE_SIZE)[None, :]           # (b, 32)
    bidx = np.arange(b)[:, None, None]
    out = padded[bidx, yy[:, :, None], xx[:, None, :], :]       # (b,32,32,3)
    flip = rng.rand(b) < 0.5
    out[flip] = out[flip, :, ::-1, :]
    return out


# ---------------------------------------------------------------------------
# iterators
# ---------------------------------------------------------------------------

def cifar_iterator(dataset: str, data_dir: str, batch_size: int, mode: str,
                   seed: int = 0, shard_index: int = 0, num_shards: int = 1,
                   prefetch: int = 2, use_native: bool = False,
                   device_augment: bool = False
                   ) -> Iterator[Dict[str, np.ndarray]]:
    """In-memory epoch iterator with full-dataset shuffle per epoch (the
    reference shuffled a 50k buffer = full epoch, resnet_cifar_main.py:221).

    ``shard_index/num_shards`` give each process a disjoint slice — fixing the
    reference Horovod path's unsharded input (SURVEY.md §3.2).

    ``device_augment`` (train mode only): yield raw uint8 batches and leave
    crop/flip/standardize to the jitted step (ops/augment.py) — the host
    then only gathers records, which is what lets one CPU core feed TPU-rate
    training.
    """
    images, labels = load_cifar(dataset, data_dir, mode, use_native=use_native)
    if num_shards > 1:
        images = images[shard_index::num_shards]
        labels = labels[shard_index::num_shards]
    rng = np.random.RandomState(seed)
    n = images.shape[0]
    is_train = mode == "train"

    def gen():
        while True:
            order = rng.permutation(n) if is_train else np.arange(n)
            for start in range(0, n, batch_size):
                idx = order[start:start + batch_size]
                if len(idx) < batch_size:
                    if is_train:
                        break  # drop partial train batch (standard; reshuffles next epoch)
                    # eval: pad to a fixed shape (no jit recompile) and mask the
                    # padding out of the metrics — unlike the reference, which
                    # silently skipped tail images (resnet_cifar_eval.py ran
                    # fixed 50x100 batches over a 10k test set)
                    pad = batch_size - len(idx)
                    idx = np.concatenate([idx, np.zeros(pad, idx.dtype)])
                    mask = np.concatenate([np.ones(batch_size - pad, np.float32),
                                           np.zeros(pad, np.float32)])
                else:
                    mask = None
                # host-side gather/augment/standardize busy time (the cifar
                # analog of the imagenet decode stage)
                with span("input.decode") as sp:
                    batch_imgs = images[idx]
                    if is_train and device_augment:
                        out = {"images": batch_imgs,  # raw uint8; device augments
                               "labels": labels[idx].copy()}
                    else:
                        if is_train:
                            batch_imgs = augment_train(batch_imgs, rng)
                        out = {"images": standardize(batch_imgs),
                               "labels": labels[idx].copy()}
                        if mask is not None:
                            out["mask"] = mask
                sp.charge("decode", items=batch_size)
                yield out

    if prefetch > 0 and is_train:
        return _threaded_prefetch(gen(), prefetch)
    return gen()


def _threaded_prefetch(it: Iterator, depth: int) -> Iterator:
    """Background-thread prefetch — host-side successor of the reference's
    16-thread RandomShuffleQueue (reference cifar_input.py:77-96) and
    tf.data prefetch (resnet_cifar_main.py:232). One shared implementation
    (device_prefetch.threaded_iterator) covers worker/stop/error handling."""
    from .device_prefetch import threaded_iterator
    return threaded_iterator(it, depth, name="drt-cifar-prefetch")
