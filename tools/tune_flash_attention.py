"""Autotune the flash-attention Pallas tile sizes on a real TPU.

For every point (T, head size, window) of a call shape (``--batch``,
``--heads``, ``--kv_heads``; bf16, causal) and every (block_q, block_k) of
``--blocks``², measures (with ``--diffusion_block B`` the mask is the
block-diffusion one of ``ops.attention.Mask`` over a noisy and a clean copy
of T/2 ids each, in diffusion blocks of B, and ``--windows`` is not read)

* ``fwd_ms``: the forward call alone, and ``grad_ms``: forward and backward
  (``jax.grad`` of the summed output): each a compiled call, warmed up, the
  median of ``--reps`` runs fenced with ``block_until_ready``;
* ``kernel_ms``: the device time of each of the three kernels in one gradient
  call, read from a profiler trace by the names their ``pallas_call`` gave
  them (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``), so each kernel's
  own winner can be told from the sum's;
* ``census``: ``tile_census`` of the point: grid steps and live tiles a head
  and whether they are masked, the counts the times are explained by.

A block pair whose tiles VMEM cannot hold is recorded as the compiler's
RESOURCE_EXHAUSTED; any other failure, a trace without the kernels' events
included, ends the run. The result is the evidence for
``ops/pallas/flash_attention._BLOCK_TABLES``:

    python tools/tune_flash_attention.py --out docs/flash_tune_v5e_gqa_window.json \\
        --batch 2 --heads 32 --kv_heads 4 --dims 128 --seqs 8192 --windows 2048,none

It runs on a TPU only: without one it exits 1 and writes nothing (a time
from interpret mode is no evidence; ``tests/test_flash_tiles.py`` rehearses
``tune(..., interpret=True)`` into a temporary file). Every result carries
the ``device`` it was measured on. A run extends ``--out``: points already
there are kept and not measured again, and a file that holds another
device's results is refused.
"""
from __future__ import annotations

import argparse
import glob
import itertools
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_resnet_tensorflow_tpu.ops.attention import (  # noqa: E402
    Mask, block_diffusion_mask)
from distributed_resnet_tensorflow_tpu.ops.pallas.flash_attention import (  # noqa: E402
    flash_attention, tile_census)
from distributed_resnet_tensorflow_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache)

BLOCKS = (128, 256, 512, 1024)
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def call_ms(fn, args, reps: int):
    """(median wall ms of the compiled ``fn(*args)`` after one warm-up, the
    compiled call)."""
    compiled = jax.jit(fn).lower(*args).compile()
    jax.block_until_ready(compiled(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times), compiled


def kernel_ms(compiled, args, ms_a_call: float) -> dict:
    """Device ms a call of each flash kernel: the mean duration of its events
    in a profiler trace of about a second of calls (each call runs each
    kernel once; a trace's first events can be lost, so the mean is over
    the events found, not over the calls made)."""
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as logdir:
        jax.profiler.start_trace(logdir)
        for _ in range(max(4, int(1000 / max(ms_a_call, 1e-3)))):
            jax.block_until_ready(compiled(*args))
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True)
        data = ProfileData.from_file(path)
    found = {name: [] for name in KERNELS}
    seen = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                # an event is named by its instruction's text, the kernel's
                # name inside the instruction's, under its transforms:
                # "%transpose_jvp_flash_bwd_dq__.1 = bf16[...] custom-call(...)"
                instruction = event.name.split(" ")[0]
                seen.append(instruction)
                for name in KERNELS:
                    if name in instruction:
                        found[name].append(event.duration_ns / 1e6)
    if not all(found.values()):
        raise RuntimeError(
            f"no event of {[n for n, v in found.items() if not v]} among "
            f"{len(seen)} device events {sorted(set(seen))[:12]} of planes "
            f"{[p.name for p in data.planes]}")
    return {name: round(statistics.fmean(ms), 4) for name, ms in found.items()}


def mask_of(t: int, window, diffusion_block: int = 0) -> Mask:
    """The mask of a point: causal with its window, or block diffusion."""
    if diffusion_block:
        return block_diffusion_mask(t // 2, diffusion_block)
    return Mask("causal", window)


def measure(q, k, v, mask, bq, bk, reps, interpret=False) -> dict:
    """One (block_q, block_k) of one point. ``interpret`` (a rehearsal off
    the chip) leaves out the trace, which has no device to read."""
    def fwd(q, k, v):
        return flash_attention(q, k, v, mask, interpret, bq, bk)

    grad = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))
    row = {"census": tile_census(q.shape[1], q.shape[3], mask, None, bq, bk)}
    try:
        row["fwd_ms"] = round(call_ms(fwd, (q, k, v), reps)[0], 4)
        ms, compiled = call_ms(grad, (q, k, v), reps)
    except jax.errors.JaxRuntimeError as e:
        if not ("RESOURCE_EXHAUSTED" in str(e) and "vmem" in str(e)):
            raise
        # tiles VMEM cannot hold: the compiler says so, and the pair is out
        return {"census": row["census"],
                "error": " ".join(f"{type(e).__name__}: {e}".split())[:300]}
    row["grad_ms"] = round(ms, 4)
    if not interpret:
        row["kernel_ms"] = kernel_ms(compiled, (q, k, v), ms)
    return row


def best_of(points: dict) -> dict:
    """The winning block pair by gradient time, forward time, the three
    kernels' summed device time (what ``_BLOCK_TABLES`` takes) and each
    kernel's own."""
    ok = {name: p for name, p in points.items() if "error" not in p}
    if not ok:
        return {}
    best = {"grad": min(ok, key=lambda n: ok[n]["grad_ms"]),
            "fwd": min(ok, key=lambda n: ok[n]["fwd_ms"])}
    if all("kernel_ms" in p for p in ok.values()):
        best["kernels"] = min(
            ok, key=lambda n: sum(ok[n]["kernel_ms"].values()))
        for kernel in KERNELS:
            best[kernel] = min(ok, key=lambda n: ok[n]["kernel_ms"][kernel])
    return best


def tune(out_path, seqs, dims, windows, batch, heads, kv_heads, pairs, reps,
         interpret=False, diffusion_block: int = 0) -> dict:
    """Measure every point not yet in ``out_path`` and write the file after
    each. ``pairs``: (block_q, block_k), (0, 0) the module's own pick.
    ``diffusion_block`` > 0: the block-diffusion mask at every T of
    ``seqs`` (T positions: two copies of T/2 ids), ``windows`` not read."""
    device = jax.devices()[0].device_kind + (" interpret" if interpret else "")
    out = {"dtype": "bfloat16", "causal": True, "results": []}
    if os.path.exists(out_path):
        with open(out_path) as f:
            out["results"] = json.load(f)["results"]
    results = out["results"]
    other = sorted({r["device"] for r in results} - {device})
    if other:
        raise SystemExit(f"{out_path} holds results of {other}: this is "
                         f"{device!r}, give another --out")

    def key(r):
        return (r["t"], r["d"], r["batch"], r["heads"], r["kv_heads"],
                r["window"], r.get("diffusion_block", 0))

    done = {key(r) for r in results}
    if diffusion_block:
        windows = [None]
    for t, d, window in itertools.product(seqs, dims, windows):
        point = {"device": device, "jax": jax.__version__, "t": t, "d": d,
                 "batch": batch, "heads": heads, "kv_heads": kv_heads,
                 "window": window, "points": {}}
        if diffusion_block:
            point["diffusion_block"] = diffusion_block
        if key(point) in done or (window is not None and window >= t):
            continue
        mask = mask_of(t, window, diffusion_block)
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(
            rng.randn(batch, t, h, d).astype(np.float32) * 0.3)
            .astype(jnp.bfloat16) for h in (heads, kv_heads, kv_heads))
        for bq, bk in pairs:
            if (bq == 0) != (bk == 0) or max(bq, bk) > t:
                continue
            row = measure(q, k, v, mask, bq, bk, reps, interpret)
            point["points"][f"{bq}x{bk}"] = row
            print(f"T={t} d={d} window={window} block {bq}x{bk}: "
                  f"{json.dumps(row)}", flush=True)
        point["best"] = best_of(point["points"])
        print(f"T={t} d={d} window={window}: best {point['best']}",
              flush=True)
        results.append(point)
        if os.path.dirname(out_path):
            os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(out, f, indent=1)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="docs/flash_tune_v5e_gqa_window.json")
    ap.add_argument("--seqs", default="8192")
    ap.add_argument("--dims", default="128")
    ap.add_argument("--windows", default="2048,none",
                    help="comma list of window sizes; none = plain causal")
    ap.add_argument("--diffusion_block", type=int, default=0,
                    help="> 0: the block-diffusion mask in blocks of this "
                         "many ids; --seqs are then the call's positions, "
                         "two copies of half as many ids")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv_heads", type=int, default=4)
    ap.add_argument("--blocks", default=",".join(map(str, BLOCKS)),
                    help="block sizes: every (block_q, block_k) pair of them "
                         "is run, and every QxK given as such; 0 = the "
                         "module's own pick for the call")
    ap.add_argument("--reps", type=int, default=7)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit(f"no TPU (backend {jax.default_backend()!r}): the "
                         "tuner's times are evidence only from the chip")
    configure_compile_cache()
    sizes = [int(b) for b in args.blocks.split(",") if "x" not in b]
    pairs = list(itertools.product(sizes, sizes)) + [
        tuple(map(int, b.split("x"))) for b in args.blocks.split(",")
        if "x" in b]
    windows = [None if w == "none" else int(w)
               for w in args.windows.split(",")]
    tune(args.out, [int(t) for t in args.seqs.split(",")],
         [int(d) for d in args.dims.split(",")], windows, args.batch,
         args.heads, args.kv_heads, pairs, args.reps,
         diffusion_block=args.diffusion_block)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
