"""Per-op TPU profile of the ImageNet ResNet-50 train step.

Captures a jax.profiler trace of the fused train dispatch and parses the
xplane proto directly into an HLO-op time breakdown — the auditable
evidence behind docs/perf_imagenet_r3.md (the reference kept its perf story
in README tables; this is the TPU analog with per-op receipts).

    python tools/profile_trace.py [--bs 128] [--k 8] [--sub 1] [--top 25]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from distributed_resnet_tensorflow_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache)

configure_compile_cache()


def capture(bs: int, k: int, sub: int, logdir: str) -> int:
    """Trace the fused k-step dispatch; returns the number of optimizer
    steps inside the traced window."""
    from profile_imagenet_bn import build_step
    trainer, multi_fn, batch, _one = build_step(bs, k, stat_subsample=sub)
    state = trainer.state
    for _ in range(2):  # compile + warm
        state, _ = multi_fn(state, batch)
    jax.block_until_ready(state.params)
    dispatches = 2
    with jax.profiler.trace(logdir):
        for _ in range(dispatches):
            state, _ = multi_fn(state, batch)
        jax.block_until_ready(state.params)
    return dispatches * k


def op_table(logdir: str, top: int):
    """xplane → [{op family, category, device_us, occurrences}] sorted.

    Parses the XSpace proto directly (the tensorboard_plugin_profile
    converter is binary-incompatible with this image's protobuf/TF pairing):
    the TPU plane's "XLA Ops" line carries one event per HLO-op execution
    with device_duration_ps + an hlo_category stat. Ops are grouped into
    families by stripping the trailing ".N" instance suffix — the level the
    perf doc reasons at (fusion.*, multiply_reduce_fusion.*, ...)."""
    import re
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    xplanes = sorted(glob.glob(os.path.join(
        logdir, "plugins/profile/*/*.xplane.pb")))
    if not xplanes:
        raise FileNotFoundError(f"no xplane under {logdir}")
    space = xplane_pb2.XSpace()
    with open(xplanes[-1], "rb") as f:
        space.ParseFromString(f.read())
    tpu = next((p for p in space.planes
                if p.name.startswith("/device:TPU")), None)
    if tpu is None:
        raise RuntimeError(
            f"no TPU plane in {xplanes[-1]} "
            f"({[p.name for p in space.planes]})")
    line = next((l for l in tpu.lines if l.name == "XLA Ops"), None)
    if line is None:
        raise RuntimeError(f"no 'XLA Ops' line ({[l.name for l in tpu.lines]})")
    smeta, emeta = tpu.stat_metadata, tpu.event_metadata
    # control-flow container ops whose duration INCLUDES every child op
    # below them — counting any of them would double the totals
    container = {"while", "conditional", "call", "control-flow"}
    fams = {}
    insts = {}
    for ev in line.events:
        md = emeta[ev.metadata_id]
        name_full = md.display_name or md.name
        fam = re.sub(r"\.\d+$", "", name_full)
        cat = ""
        dur_ps = ev.duration_ps
        for st in list(ev.stats) + list(md.stats):
            name = smeta[st.metadata_id].name
            if name == "hlo_category":
                cat = st.str_value or (
                    smeta[st.ref_value].name if st.ref_value else "")
            elif name == "device_duration_ps" and st.int64_value:
                dur_ps = st.int64_value
        if cat in container:
            continue
        agg = fams.setdefault((cat, fam), [0, 0])
        agg[0] += dur_ps
        agg[1] += 1
        iagg = insts.setdefault((cat, name_full), [0, 0])
        iagg[0] += dur_ps
        iagg[1] += 1
    out = [{"category": c, "op": f, "self_us": ps / 1e6, "n": n}
           for (c, f), (ps, n) in fams.items()]
    out.sort(key=lambda d: -d["self_us"])
    iout = [{"category": c, "op": f, "self_us": ps / 1e6, "n": n}
            for (c, f), (ps, n) in insts.items()]
    iout.sort(key=lambda d: -d["self_us"])
    return out[:top], iout[:top]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bs", type=int, default=128)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--sub", type=int, default=1)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--logdir", default="/tmp/drt_trace")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    steps = capture(args.bs, args.k, args.sub, args.logdir)
    table, instances = op_table(args.logdir, args.top)
    print(f"top-{args.top} HLO ops by self time "
          f"(bs={args.bs}, k={args.k}, stat_subsample={args.sub}):")
    for d in table:
        print(f"{d['self_us']:>10.0f} us  {d['category']:<22} "
              f"{str(d['op'])[:70]}")
    total_ms = sum(d["self_us"] for d in table) / steps / 1e3
    print(f"sum of top-{args.top} ≈ {total_ms:.1f} ms/step "
          "(sanity vs measured step time)")
    print(f"\ntop-{args.top} individual op instances:")
    for d in instances:
        print(f"{d['self_us']:>10.0f} us  n={d['n']:<6} {d['category']:<20} "
              f"{str(d['op'])[:70]}")
    for d in table + instances:
        d["ms_per_step"] = round(d["self_us"] / steps / 1e3, 3)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"bs": args.bs, "k": args.k, "sub": args.sub,
                       "steps_traced": steps,
                       "note": "device self time per HLO-op family; "
                               "control-flow container ops (while/"
                               "conditional/call = sum of children) "
                               "are excluded",
                       "table": table, "instances": instances}, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
