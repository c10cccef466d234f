"""core/devices.py: the compile-cache rule, the HBM-size rule, and which
processes may initialise a JAX backend."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_CACHE_CHILD = (
    "from tensorlink_tpu.core.devices import configure_compile_cache;"
    "import jax;"
    "print('RET=' + configure_compile_cache());"
    "print('CFG=' + str(jax.config.jax_compilation_cache_dir))"
)


@pytest.mark.parametrize("env_dir", ["/some/where/jaxcache", None],
                         ids=["env-set", "env-unset"])
def test_compile_cache_rule(env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache directory and the
    helper sets none in code; unset, it is the one fixed path inside the
    checkout."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    p = subprocess.run(
        [sys.executable, "-c", _CACHE_CHILD], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=120,
    )
    assert p.returncode == 0, p.stderr[-800:]
    want = env_dir or str(REPO / ".jax_cache")
    assert f"RET={want}" in p.stdout and f"CFG={want}" in p.stdout, p.stdout


_LOWER_CHILD = """
from tensorlink_tpu.core.devices import configure_compile_cache
import jax, jax.numpy as jnp
configure_compile_cache()
def f(x):
    with jax.named_scope("tlink.probe"):
        return jnp.sin(x) + 1
txt = jax.jit(f).lower(jnp.ones(4)).compile().as_text()
print("LIMIT=%d" % jax.config.jax_traceback_in_locations_limit)
print("FRAMES=%d" % txt.count("stack_frame_id"))
print("SCOPED=%d" % txt.count("tlink.probe"))
"""


@pytest.mark.parametrize("env_limit", [None, "10"],
                         ids=["env-unset", "env-set"])
def test_programs_lower_without_stack_frames(env_limit):
    """The same helper lowers programs without Python stack frames in
    their operations' metadata (the TPU profiler resolves them for every
    device event when a trace is stopped) and keeps the named-scope path;
    JAX's own JAX_TRACEBACK_IN_LOCATIONS_LIMIT, when set, wins."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    env.pop("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", None)
    if env_limit is not None:
        env["JAX_TRACEBACK_IN_LOCATIONS_LIMIT"] = env_limit
    p = subprocess.run(
        [sys.executable, "-c", _LOWER_CHILD], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=120,
    )
    assert p.returncode == 0, p.stderr[-800:]
    out = dict(line.split("=") for line in p.stdout.split())
    assert int(out["LIMIT"]) == int(env_limit or 0), p.stdout
    assert (int(out["FRAMES"]) > 0) == (env_limit is not None), p.stdout
    assert int(out["SCOPED"]) > 0, p.stdout


def test_no_other_compile_cache_directory_in_the_tree():
    """The rule lives in ONE function: no other file names the config knob
    (a second setter is how two retired scripts each grew their own /tmp
    path)."""
    here = Path(__file__).resolve()
    rule = REPO / "tensorlink_tpu" / "core" / "devices.py"
    sources = [
        f for root in ("tensorlink_tpu", "tests", "tools", "scripts")
        for f in (REPO / root).rglob("*.py")
    ] + list(REPO.glob("*.py"))
    hits = [
        str(f.relative_to(REPO)) for f in sources
        if f not in (here, rule)
        and "jax_compilation_cache_dir" in f.read_text(errors="ignore")
    ]
    assert not hits, hits


def test_gitignore_lists_the_in_checkout_cache():
    from tensorlink_tpu.core.devices import COMPILE_CACHE_DIR

    assert COMPILE_CACHE_DIR.parent == REPO
    assert f"{COMPILE_CACHE_DIR.name}/" in (REPO / ".gitignore").read_text()


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self._stats = platform, stats

    def memory_stats(self):
        return self._stats


def test_hbm_size_is_reported_or_an_error_never_a_guess():
    from tensorlink_tpu.core.devices import device_hbm_bytes

    assert device_hbm_bytes(_FakeDevice("tpu", {"bytes_limit": 16e9})) == 16e9
    # the CPU reports no memory stats: 0, and the caller sizes from config
    assert device_hbm_bytes(_FakeDevice("cpu", None)) == 0.0
    with pytest.raises(RuntimeError, match="bytes_limit"):
        device_hbm_bytes(_FakeDevice("tpu", {}))
    with pytest.raises(RuntimeError, match="bytes_limit"):
        device_hbm_bytes(_FakeDevice("tpu", None))


_USER_CHILD = """
import numpy as np
from tensorlink_tpu.ml.module import _ce_sum_and_grad
import tensorlink_tpu.nodes.runners  # what a UserNode process imports
logits = np.random.default_rng(0).normal(size=(2, 5, 11)).astype(np.float32)
nll, d, n = _ce_sum_and_grad(logits, np.ones((2, 5), np.int64), np.ones((2, 5), bool))
assert np.isfinite(nll) and d.shape == logits.shape and n == 8
import sys
if "jax" in sys.modules:
    from jax._src import xla_bridge
    assert not xla_bridge.backends_are_initialized(), "user side claimed a backend"
print("USER_OK")
"""


def test_user_side_math_never_initialises_a_backend():
    """One process per chip: a UserNode in its own process on the chip host
    must not take the chip from the worker — its loss/cotangent math stays
    on numpy."""
    p = subprocess.run(
        [sys.executable, "-c", _USER_CHILD], capture_output=True, text=True,
        cwd=REPO, timeout=120,
    )
    assert p.returncode == 0 and "USER_OK" in p.stdout, p.stderr[-800:]


def test_network_process_modules_do_not_import_jax():
    """The spawned network processes are JAX-free by design
    (nodes/roles.py, p2p/): importing what they import must not pull jax
    in, let alone initialise a backend."""
    code = (
        "import sys, tensorlink_tpu.nodes.roles, tensorlink_tpu.p2p;"
        "assert 'jax' not in sys.modules, 'network process imports jax';"
        "print('NET_OK')"
    )
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=120,
    )
    assert p.returncode == 0 and "NET_OK" in p.stdout, p.stderr[-800:]
