#!/usr/bin/env python3
"""Drive the whole of a run on the CPU at tiny sizes (no chip, no gate):
``python3 benchmark/tools/rehearse.py [cell ...]``. A rehearsal of control
flow and of the comparison; its numbers are not measurements."""
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")


def main(argv) -> int:
    import jax
    from benchmark.harness import spec, window
    from benchmark.tests import tiny
    with tempfile.TemporaryDirectory() as tmp:
        root = tiny.make_root(tmp)
        for name in argv or [c[0] for c in tiny.CELLS]:
            cell = spec.resolve(name, root)
            for traced in (False,):  # a CPU trace has no device plane
                result = window.run_cell(cell, 2 ** 31 + 77, 3.0, traced,
                                         jax.devices()[:cell.chips], tiny.PEAKS,
                                         time.time())
                print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
