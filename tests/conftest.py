"""Test harness: force an 8-device fake CPU mesh.

This is the successor of the reference's only integration test — the local
1ps+2wk CPU smoke cluster (reference scripts/submit_mac_dist.sh:9-39,
run_dist_tf_local.sh:14-22) — done the JAX way: 8 virtual host devices via
``xla_force_host_platform_device_count`` so every sharding/collective path
runs without TPU hardware (SURVEY.md §4 implication).

The platform is pinned to CPU through jax.config before any backend is
initialized, so the suite runs on the virtual mesh whatever JAX_PLATFORMS
the caller exported.
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# tests must not read or write the tracked tree: the bandwidth catalog
# (telemetry/bandwidth.py) defaults to results/bandwidth/ in the checkout.
# Environment, not a fixture: subprocess tests inherit it.
_BANDWIDTH_TMP = tempfile.TemporaryDirectory(prefix="drt-bandwidth-")
os.environ["DRT_BANDWIDTH_DIR"] = _BANDWIDTH_TMP.name

from distributed_resnet_tensorflow_tpu.utils.virtual_devices import (  # noqa: E402
    apply_virtual_cpu)

apply_virtual_cpu(8)  # XLA_FLAGS device count + jax.config platform flip

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8 and devs[0].platform == "cpu"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    """Pure data-parallel 8-device mesh (the reference's topology)."""
    from distributed_resnet_tensorflow_tpu.parallel import create_mesh
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig
    return create_mesh(MeshConfig(data=8))


@pytest.fixture(scope="session")
def mesh_dp_fsdp(devices):
    from distributed_resnet_tensorflow_tpu.parallel import create_mesh
    from distributed_resnet_tensorflow_tpu.utils.config import MeshConfig
    return create_mesh(MeshConfig(data=4, fsdp=2))


@pytest.fixture()
def rng():
    return np.random.RandomState(0)
