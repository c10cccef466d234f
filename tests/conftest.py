"""Test fixtures.

Multi-chip behavior is tested on a virtual 8-device CPU mesh
(xla_force_host_platform_device_count) — the TPU-native analogue of the
reference's strategy of spinning up real multi-process node groups on
localhost (reference tests/conftest.py:25-161). Real-socket node-group
fixtures live in tests/p2p fixtures below; sharding/mesh tests use the
virtual devices.
"""

import os

# Tests run on the CPU with 8 virtual devices. The env is overridden (not
# setdefault) so spawned subprocesses inherit it too; the chip, where there
# is one, is only ever driven by chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# no persistent compile cache under test: what one test process compiled
# must not change what another compiles, logs or counts
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.setdefault("TOKENIZERS_PARALLELISM", "false")
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    import jax

    devs = jax.devices("cpu")
    assert len(devs) >= 8, (
        "expected 8 virtual CPU devices; XLA_FLAGS was likely preset without "
        "--xla_force_host_platform_device_count=8"
    )
    return devs


@pytest.fixture()
def tmp_keys(tmp_path):
    return tmp_path / "keys"
