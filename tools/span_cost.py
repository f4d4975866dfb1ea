#!/usr/bin/env python3
"""What one flight-recorder span costs: enter + exit, in nanoseconds.

    python tools/span_cost.py [--root <checkout>] [--n 200000]

Times ``with span("input.wait"): pass`` and the ``train.step`` form with
its ``step_num`` on the host of this machine, three ways: telemetry off
(the shared no-op), on with no profiler session, on with a session active
(every span is then also written to the profiler's host plane). ``--root``
points at another checkout (the parent commit) so before and after come
from one command. A host number: it needs no accelerator and says nothing
about one.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import tempfile
import time


def per_call_ns(make, n: int) -> float:
    best = float("inf")
    for _ in range(5):  # the quietest of five repeats
        t0 = time.perf_counter()
        for _ in range(n):
            with make():
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    return best * 1e9


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--n", type=int, default=200000)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    from distributed_resnet_tensorflow_tpu.telemetry.tracer import (
        FlightRecorder)

    rec = FlightRecorder()
    step = [0]

    def plain():
        return rec.span("input.wait")

    # a checkout whose spans carry no step number times the plain form
    takes_step = "step_num" in inspect.signature(rec.span).parameters

    def stepped():
        step[0] += 1
        if takes_step:
            return rec.span("train.step", step_num=step[0])
        return rec.span("train.step")

    out = {"root": os.path.abspath(args.root), "n": args.n}
    rec.configure(enabled=False)
    out["off_ns"] = per_call_ns(plain, args.n)
    rec.configure(enabled=True)
    out["no_session_ns"] = per_call_ns(plain, args.n)
    out["no_session_step_ns"] = per_call_ns(stepped, args.n)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the benchmark traces: no per-call
    options.host_tracer_level = 2    # Python hook, TraceMe events kept
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            # a session keeps every event: fewer calls, same per-call cost
            out["session_ns"] = per_call_ns(plain, args.n // 10)
            out["session_step_ns"] = per_call_ns(stepped, args.n // 10)
        finally:
            jax.profiler.stop_trace()
    print(json.dumps({k: round(v, 1) if isinstance(v, float) else v
                      for k, v in out.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
