#!/usr/bin/env python3
"""Prove that the reference walk holds a family of a given size on one chip.

    python3 benchmark/tools/walk_size.py --params-m 705 [--steps 3 --blocks 2]
    python3 benchmark/tools/walk_size.py --cpu

Lays ``benchmark/tests/toy/`` (a token family that is no one's model) over a
scratch copy of the benchmark, as ``tests/test_spec.py`` does, widens its
configuration to at least ``--params-m`` million parameters at the widths of
a language model's share of a chip (``vocab_held`` 25,024, ``d_model`` 2,048,
``d_ff`` as wide as the count asks), and walks it with ``follow.follow``
alone: no program, no cell, no entry in ``BENCHMARK.json``. Every step is
``--blocks`` blocks of whole sequences, short enough that a block's
activations stay under 2 GB, and the family's rule moves its bias after every
update. Prints one JSON object: ``parameters``, ``peak_bytes_in_use`` and
``bytes_limit`` of the fullest device as the runtime states them (null where
it states none, as on the CPU), ``seconds_a_step`` of a second walk, which
compiles nothing, and what the walk says of itself. A walk that does not fit
ends with the runtime's message and exit code 1. ``--cpu`` walks the toy at
its own size: a rehearsal of the control flow, whose seconds are not a
measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: a language model's share of one chip (PERF.md §7): an eighth of a
#: 200k-token vocabulary, hidden size 2,048; the feed-forward takes the rest
WIDE = {"vocab_held": 25_024, "d_model": 2_048}
SEQ_LEN = 126  # 128 ids a sequence: 2 sequences a block are 0.4 GB of activations at d_ff 90,112


def widened(model: dict, params_m: float) -> dict:
    """The toy's model block at the wide widths, ``d_ff`` the least multiple
    of 1,024 that reaches ``params_m`` million parameters."""
    v, d = WIDE["vocab_held"], WIDE["d_model"]
    fixed = 3 * v * d + d + v  # embed, two heads, norm, count_bias
    d_ff = max(1024, -(-int(params_m * 1e6 - fixed) // (3 * d * 1024)) * 1024)
    return dict(model, **WIDE, d_ff=d_ff)


def add_the_toy_files(here: str) -> None:
    """``benchmark/tests/toy`` laid over the copy of ``benchmark/`` at
    ``here``, file by new file: none that is there is replaced."""
    toy = os.path.join(HERE, "tests", "toy")
    for base, _, files in os.walk(toy):
        for f in files:
            to = os.path.join(here, os.path.relpath(os.path.join(base, f), toy))
            if os.path.exists(to):
                raise FileExistsError(to)
            shutil.copy(os.path.join(base, f), to)


def lay_toy_over_a_copy(dst: str) -> str:
    """A copy of ``benchmark/`` under ``dst`` with the toy family's files
    added; returns ``dst``, the copy's root."""
    shutil.copytree(HERE, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    add_the_toy_files(os.path.join(dst, "benchmark"))
    return dst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--params-m", type=float, default=None,
                    help="millions of parameters (705 on a chip; with --cpu the toy's own size)")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--blocks", type=int, default=2)
    ap.add_argument("--seed", type=int, default=2_900_000_011)
    ap.add_argument("--cpu", action="store_true", help="the toy's own size, on the CPU")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    with tempfile.TemporaryDirectory() as tmp:
        sys.path.insert(0, lay_toy_over_a_copy(tmp))  # the copy's modules, not this tree's
        import jax
        from benchmark.reference import follow
        devices = jax.devices()
        if not args.cpu and devices[0].platform == "cpu":
            print("[walk_size] JAX found no accelerator; --cpu rehearses", file=sys.stderr)
            return 1
        with open(os.path.join(tmp, "benchmark", "configs", "toy_tokens.json")) as f:
            config = json.load(f)
        with open(os.path.join(tmp, "benchmark", "traffic", "toy_tokens_b16.json")) as f:
            traffic = json.load(f)
        fam = follow.family_module(config["family"])
        if args.params_m or not args.cpu:
            config["model"] = widened(config["model"], args.params_m or 705.0)
            traffic["seq_len"] = SEQ_LEN
        traffic.update(chips=len(devices),
                       per_chip_batch=args.blocks * fam.EXAMPLE_BLOCK)
        shapes = jax.eval_shape(lambda k: fam.init_params(k, config["model"]),
                                follow.init_key(args.seed))
        out = {"parameters": sum(s.size for s in shapes.values()),
               "model": config["model"], "steps": args.steps, "blocks_a_step": args.blocks,
               "rule": bool(getattr(fam, "after_update", None)),
               "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                          "count": len(devices)}}
        from benchmark.generators import token_batches
        stream = token_batches.make(traffic, config, args.seed)
        batches = [stream.batch(i) for i in range(args.steps)]
        bounds = list(range(1, args.steps + 1))
        t0, failure, got = time.perf_counter(), None, {}
        try:
            follow.follow(config, args.seed, batches, bounds)  # compiles
            t0 = time.perf_counter()
            got = follow.follow(config, args.seed, batches, bounds)
        except Exception as e:  # the boundary: the runtime's message is the finding
            where = [f"{fr.name}:{fr.lineno}" for fr in traceback.extract_tb(e.__traceback__)
                     if fr.filename.endswith("follow.py")]
            text = f"{type(e).__name__}: {e}"
            failure = f"in follow.py at {where}: {text[:3000]} ... {text[-1500:]}" \
                if len(text) > 4500 else f"in follow.py at {where}: {text}"
        seconds = time.perf_counter() - t0
        stats = [d.memory_stats() or {} for d in devices]
        out.update(bytes_in_use=max((s.get("bytes_in_use", 0) for s in stats), default=0),
                   peak_bytes_in_use=max((s.get("peak_bytes_in_use") for s in stats
                                          if "peak_bytes_in_use" in s), default=None),
                   bytes_limit=min((s.get("bytes_limit") for s in stats
                                    if "bytes_limit" in s), default=None),
                   seconds_a_step=seconds / args.steps, seconds=seconds,
                   walk=got.get("walk"), loss=got.get("loss"), walked=failure is None)
        if failure:
            print(f"[walk_size] the walk did not reach its end: {failure}",
                  file=sys.stderr, flush=True)
        print(json.dumps(out), flush=True)
        return 1 if failure else 0


if __name__ == "__main__":
    sys.exit(main())
