#!/usr/bin/env python3
"""Run from the root of a copy of the benchmark that holds the toy family
(``benchmark/tests/toy``) as files and entries: resolve its cell, walk it
in blocks and in one block, put sound and broken walks in the program's
place, and print what the comparison says as one JSON object."""
import copy
import json
import os
import sys

sys.path.insert(0, os.getcwd())
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")


def main() -> int:
    import jax
    from benchmark.harness import check, spec
    from benchmark.reference import follow
    cell = spec.resolve("toy_cell")
    seed, bounds = 2 ** 31 + 99, [1, 2, 3]
    stream = spec.module("generators", cell.traffic["generator"]).make(
        cell.traffic, cell.config, seed)
    batches = [stream.batch(i) for i in range(bounds[-1])]
    fam = follow.family_module(cell.config["family"])

    calls = []
    fns = follow._functions(cell.config, None)
    sound_block_grad = fns["block_grad"]
    fns["block_grad"] = lambda *a: (calls.append(1), sound_block_grad(*a))[1]

    def walk(config=cell.config, **kw):
        return follow.follow(config, seed, batches, bounds, **kw)

    def changed(**model):
        config = copy.deepcopy(cell.config)
        config["model"].update(model)
        return config

    in_blocks = walk()
    blocks_a_step = len(calls) // bounds[-1]
    faulted = {"half_batch": walk(fault="half_batch"),
               "quarter_batch": walk(fault="quarter_batch"),
               "control": walk(precision=cell.config["control_precision"])}
    # the same walk with the moments made to wait on the host: a limit that
    # the walk's 20 bytes a parameter pass the half of, three groups of leaves
    stated_limit, follow.device_bytes_limit = follow.device_bytes_limit, lambda devices: 8 * 4 * 3120
    on_host = walk()
    follow.device_bytes_limit = stated_limit
    per_device, fam.EXAMPLE_BLOCK = fam.EXAMPLE_BLOCK, None
    ref = walk()
    fam.EXAMPLE_BLOCK = per_device

    def verdict(mine):
        ok, rows = check.verdict(check.compare(mine, ref)[0], cell.limits)
        return {"correct": ok, "over": [n for n, v, lim in rows if v > lim],
                "numbers": {n: v for n, v, _ in rows}}
    # a traced run as the harness would hand it to the cell's two readers:
    # the kernel is one family among many, found by name; the scope by component
    trace = {"steps": 4, "op_s": {"toy_gated_ffn": 2e-6, "fusion": 1.0},
             "op_events": {"toy_gated_ffn": 8, "fusion": 400},
             "scope_s": {"jit(step)/jvp(forward)/ffn": 3e-3, "jit(step)/head": 1e-3,
                         "jit(step)/transpose(jvp(forward))/ffn/gate": 5e-3, "": 2e-3}}
    run = {"trace": trace, "config": cell.config, "traffic": cell.traffic,
           "global_batch": 16, "steps_per_dispatch": 1, "chips": 1,
           "peaks": {"bf16_flops_per_s": 1e12}}
    nothing = dict(run, trace=dict(trace, op_events={}, scope_s={"": 1.0}))
    out = {
        "root": spec.ROOT, "devices": len(jax.devices()),
        "read": {n: read(run) for n, read in cell.readers().items()},
        "read_nothing": {n: read(nothing) for n, read in cell.readers().items()},
        "flops_per_step": spec.module("flops", cell.config["family"])
        .train_flops_per_step(cell.config, cell.traffic),
        "blocks_a_step": blocks_a_step,
        "ruled_moved": ref["change"]["norm"]["count_bias"],
        "sound": verdict(in_blocks),
        "half_batch": verdict(faulted["half_batch"]),
        "second_term_left_out": verdict(walk(changed(second_head_weight=0.0))),
        "rule_left_out": verdict(walk(changed(rule_rate=0.0))),
        "control_fp8": verdict(faulted["control"]),
        # every number of the walk in blocks, for benchmark/tests/pinned_walks.json
        "raw": dict(faulted, sound=in_blocks), "raw_on_host": on_host,
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
