from .synthetic import synthetic_iterator, learnable_synthetic_iterator  # noqa: F401
from .cifar import cifar_iterator, load_cifar, standardize, augment_train  # noqa: F401
from .device_dataset import (  # noqa: F401
    device_dataset_enabled, epoch_index_iterator)


def resolve_decode_workers(cfg, mode: str = "train"):
    """(decode_processes, decode_threads) the imagenet pipeline will
    actually run with — THE resolution point for the auto (-1) defaults of
    ``data.decode_processes`` / ``data.num_parallel_calls``; explicit
    (>= 0) settings always win. Auto scales to the host: processes =
    min(8, cores) when the host has more than 2 cores (below that a
    process pool only adds queue pickling — the GIL-releasing decoders
    already share the core), threads = min(8, cores) with a floor of 4
    (threads hide I/O even on small hosts)."""
    import os
    d = cfg.data
    cpu = os.cpu_count() or 1
    procs = d.decode_processes
    if procs < 0:
        procs = min(8, cpu) if cpu > 2 else 0
    threads = d.num_parallel_calls
    if threads < 0:
        threads = min(8, max(4, cpu))
    return procs, threads


def device_augment_enabled(cfg, mode: str = "train") -> bool:
    """Single source of truth for who augments/standardizes — the iterator
    (yields raw uint8) and the Trainer (applies ops/augment in the jitted
    step or fuses it into the CoalescedStager unpack) MUST agree, so both
    call this.

    cifar*: the device does crop/flip/standardize (ops/augment.py).
    imagenet: the device does the random flip (+ optional
    ``data.augment_pad`` crop jitter) and the VGG standardize
    (ops/augment.imagenet_train_augment); the host decode keeps the
    random resize/crop (tied to per-image source geometry), SKIPS its
    flip (the device takes it over — imagenet_iterator ``device_flip``),
    and ships raw uint8 crops — 4× smaller transfers, no host float
    pass, and echoed appearances of one decoded crop draw fresh
    augmentations (data/echo.py). Round 4: the imagenet EVAL path gets
    the standardize on device too (deterministic, so the only question
    is where the float pass runs; make_eval_step applies it) — cifar
    eval stays host-side (its standardize is per-image moments, fused
    into the host parse)."""
    if cfg.data.dataset not in ("cifar10", "cifar100", "imagenet"):
        return False
    if mode != "train" and cfg.data.dataset != "imagenet":
        return False
    setting = cfg.data.device_augment
    if setting == "on":
        return True
    if setting == "off":
        return False
    if setting != "auto":
        raise ValueError(f"unknown device_augment setting {setting!r}")
    import jax
    return jax.default_backend() == "tpu"


def create_input_iterator(cfg, mode: str = "train", shard_index: int = 0,
                          num_shards: int = 1, batch_size=None,
                          deterministic: bool = False):
    """Input factory — the one definition replacing the 4 near-identical
    ``input_fn`` copies in the reference mains (SURVEY.md §1 note).

    ``deterministic``: required when several processes feed the SAME
    replicated batch slice (non-batch mesh axis over processes) — the
    imagenet pipeline's parallel decode is otherwise completion-ordered
    (see imagenet_iterator). The synthetic and cifar paths are
    deterministic by construction (seeded single-generator streams)."""
    d = cfg.data
    bs = batch_size or (cfg.train.batch_size if mode == "train"
                        else d.eval_batch_size)
    if d.dataset == "synthetic":
        it = synthetic_iterator(bs, d.image_size, cfg.model.num_classes,
                                seed=cfg.train.seed)
    elif d.dataset == "tokens":
        from .tokens import token_stream_iterator
        it = token_stream_iterator(bs, d.seq_len, cfg.model.vocab_held,
                                   seed=cfg.train.seed)
    elif d.dataset == "blockdiff_tokens":
        from .tokens import block_diffusion_iterator
        it = block_diffusion_iterator(
            bs, d.seq_len, cfg.model.mask_token_held, cfg.model.block_length,
            cfg.model.noise_eps, seed=cfg.train.seed)
    elif d.dataset in ("cifar10", "cifar100"):
        it = cifar_iterator(d.dataset, d.data_dir, bs, mode,
                            seed=cfg.train.seed, shard_index=shard_index,
                            num_shards=num_shards,
                            prefetch=d.prefetch_batches,
                            use_native=d.use_native_loader,
                            device_augment=device_augment_enabled(cfg, mode))
    elif d.dataset == "imagenet":
        from .imagenet import imagenet_iterator
        procs, threads = resolve_decode_workers(cfg, mode)
        dev_aug = device_augment_enabled(cfg, mode)
        it = imagenet_iterator(d.data_dir, bs, mode, image_size=d.image_size,
                               seed=cfg.train.seed, shard_index=shard_index,
                               num_shards=num_shards,
                               num_decode_threads=threads,
                               prefetch_batches=d.prefetch_batches,
                               use_native=d.use_native_loader,
                               device_standardize=dev_aug,
                               # flip moved on-device with the rest of the
                               # train augmentation (see
                               # device_augment_enabled): the host draw
                               # still happens (RNG contract) but is not
                               # applied, or train batches would be
                               # double-flipped
                               device_flip=dev_aug and mode == "train",
                               decode_processes=procs,
                               deterministic=deterministic,
                               max_corrupt_records=d.max_corrupt_records,
                               verify_crc=d.verify_crc)
    else:
        raise ValueError(f"unknown dataset {d.dataset!r}")
    if mode == "train" and d.echo_factor > 1:
        # data echoing: one decode feeds echo_factor batches, reshuffled
        # per echo out of the bounded decoded-sample cache (data/echo.py)
        from .echo import echoing_iterator
        it = echoing_iterator(it, d.echo_factor, cache_mb=d.echo_cache_mb,
                              seed=cfg.train.seed)
    return it
