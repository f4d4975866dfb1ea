"""The bring-up contract, as far as a CPU can check it (ISSUE 21).

``chip_smoke.py`` only passes on the TPU; what is pinned here is everything
around it that must hold WITHOUT a chip: the smoke refuses the CPU in
seconds and says what it found, its parent stays off jax, the compile cache
can be placed from outside, ``main.py route`` starts its replicas before it
ever opens a backend, and a utilization is never computed against a peak
the table does not hold. Subprocesses throughout: each check is about what
a fresh interpreter does before (or without) initializing a backend.
"""
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, env_extra=None, env_drop=(), cwd=REPO, timeout=120):
    env = dict(os.environ)
    for key in env_drop:
        env.pop(key, None)
    env.update(env_extra or {})
    args = [sys.executable, "-c", code_or_args] \
        if isinstance(code_or_args, str) else [sys.executable, *code_or_args]
    return subprocess.run(args, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=timeout)


def test_chip_smoke_refuses_cpu_fast_and_parent_stays_off_jax():
    # writes its (git-ignored) chiprun_out/chip_smoke/ like any run of it
    code = (
        "import sys, chip_smoke\n"
        "rc = chip_smoke.main([])\n"
        "print('PARENT_HAS_JAX', any(m == 'jax' or m.startswith('jax.') "
        "or m.startswith('distributed_resnet') for m in sys.modules))\n"
        "sys.exit(rc)\n")
    t0 = time.monotonic()
    out = _run(code, env_extra={"JAX_PLATFORMS": "cpu"})
    took = time.monotonic() - t0
    assert out.returncode != 0, out.stdout
    assert "PARENT_HAS_JAX False" in out.stdout, out.stdout + out.stderr
    assert "no TPU" in out.stdout and "platform='cpu'" in out.stdout
    # no result line, and only the first leg was tried
    assert '"ok"' not in out.stdout
    assert "cifar" not in out.stdout
    assert took < 60, f"took {took:.0f}s to refuse the CPU"


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program beside it: non-zero, no result."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run(["chip_smoke.py"], cwd=str(tmp_path),
               env_drop=("PYTHONPATH",))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


_CACHE_PROBE = (
    "import jax\n"
    "from distributed_resnet_tensorflow_tpu.utils.compile_cache import "
    "configure_compile_cache\n"
    "print('RETURNED', configure_compile_cache())\n"
    "print('CONFIG', jax.config.jax_compilation_cache_dir)\n"
    "from jax._src import xla_bridge\n"
    "print('BACKEND', xla_bridge.backends_are_initialized())\n")


def test_compile_cache_is_placed_from_outside(tmp_path):
    placed = str(tmp_path / "elsewhere")
    out = _run(_CACHE_PROBE, env_extra={"JAX_COMPILATION_CACHE_DIR": placed},
               env_drop=("JAX_PLATFORMS",))
    assert out.returncode == 0, out.stderr
    # JAX read the variable itself; the helper set nothing and reports it
    assert f"RETURNED {placed}" in out.stdout
    assert f"CONFIG {placed}" in out.stdout
    assert "BACKEND False" in out.stdout


def test_compile_cache_defaults_inside_the_checkout():
    out = _run(_CACHE_PROBE,
               env_drop=("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS"))
    assert out.returncode == 0, out.stderr
    want = os.path.join(REPO, ".jax_cache")
    assert f"RETURNED {want}" in out.stdout
    assert f"CONFIG {want}" in out.stdout
    assert "BACKEND False" in out.stdout  # placing it opened no device
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_pinned_process_places_no_cache():
    out = _run(_CACHE_PROBE, env_extra={"JAX_PLATFORMS": "cpu"},
               env_drop=("JAX_COMPILATION_CACHE_DIR",))
    assert out.returncode == 0, out.stderr
    assert "RETURNED None" in out.stdout and "CONFIG None" in out.stdout


def test_no_cache_dir_literal_outside_the_helper():
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "__pycache__")]
        for name in files:
            path = os.path.join(root, name)
            if not name.endswith((".py", ".sh")) or \
                    os.path.samefile(path, __file__):
                continue
            with open(path, errors="replace") as f:
                if "jax_compilation_cache_dir" in f.read():
                    hits.append(os.path.relpath(path, REPO))
    assert hits == [os.path.join("distributed_resnet_tensorflow_tpu",
                                 "utils", "compile_cache.py")]


def test_route_parent_opens_no_backend_before_first_spawn(tmp_path):
    """A parent that has touched JAX holds the chip its replicas need:
    ``main.py route`` must reach its first spawn with no backend open."""
    code = (
        "import sys\n"
        "from jax._src import xla_bridge\n"
        "from distributed_resnet_tensorflow_tpu.serve import fleet\n"
        "def spawn(*a, **k):\n"
        "    print('BACKEND_AT_SPAWN', "
        "xla_bridge.backends_are_initialized(), flush=True)\n"
        "    raise SystemExit(7)\n"
        "fleet.subprocess.Popen = spawn\n"
        "from distributed_resnet_tensorflow_tpu.main import main\n"
        "main(['route', '--preset', 'smoke', '--set', "
        f"'log_root={tmp_path}', '--set', 'route.replicas=1'])\n")
    out = _run(code, env_extra={"JAX_PLATFORMS": "cpu"})
    assert out.returncode == 7, out.stdout + out.stderr
    assert "BACKEND_AT_SPAWN False" in out.stdout, out.stdout + out.stderr


def test_unknown_accelerator_kind_raises_where_a_peak_is_asked(monkeypatch):
    import jax
    from distributed_resnet_tensorflow_tpu.utils import profiling

    class FakeDevice:
        platform = "tpu"
        device_kind = "TPU v9 experimental"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeDevice()])
    with pytest.raises(ValueError, match="TPU v9 experimental"):
        profiling.detect_peak_tflops()
    with pytest.raises(ValueError, match="TPU_PEAK_TFLOPS"):
        profiling.mfu(10.0, 1e12, num_devices=1)
    FakeDevice.device_kind = "TPU v5 lite"
    assert profiling.detect_peak_tflops() == 197.0


def test_cpu_reports_no_mfu_and_says_why():
    from distributed_resnet_tensorflow_tpu.train.hooks import LoggingHook
    from distributed_resnet_tensorflow_tpu.utils import profiling
    assert profiling.detect_peak_tflops() is None  # the suite runs on cpu
    lines = []
    hook = LoggingHook(every_steps=1, batch_size=8, print_fn=lines.append,
                       step_flops=1e9)
    assert hook.step_flops is None
    assert len(lines) == 1 and "no mfu" in lines[0] and "cpu" in lines[0]
