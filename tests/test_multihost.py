"""Multi-host (multi-PROCESS) runtime: the framework's compiled training
step runs SPMD across two real OS processes joined by jax.distributed —
XLA's cross-process collectives carrying the same declarative shardings the
single-process mesh path uses (parallel/multihost.py; the reference scales
across hosts with NCCL/MPI instead). CPU backend: each process contributes
2 virtual devices to a 4-device global mesh."""

import json
import os
import socket
import subprocess
import sys

import jax
import pytest

pytestmark = pytest.mark.e2e

# jax < 0.5 CPU cannot run cross-process collectives at all — every
# program touching a multi-process mesh dies with "Multiprocess
# computations aren't implemented on the CPU backend" inside XLA. Not
# shimmable (the backend genuinely lacks the feature); newer jaxlibs
# run these tests unmodified.
_CPU_MULTIPROC_UNSUPPORTED = tuple(
    int(p) for p in jax.__version__.split(".")[:2]
) < (0, 5) and (
    # version first: jax >= 0.5 short-circuits before default_backend()
    # would initialize the real accelerator at collection time
    os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
    or jax.default_backend() == "cpu"
)
if _CPU_MULTIPROC_UNSUPPORTED:
    pytestmark = [
        pytest.mark.e2e,
        pytest.mark.skip(
            reason="jax<0.5 CPU backend has no multiprocess collectives"
        ),
    ]

_CHILD = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, __REPO__)

import jax
jax.config.update("jax_platforms", "cpu")

from tensorlink_tpu.parallel.multihost import is_multihost, maybe_initialize

assert maybe_initialize(__COORD__, 2, int(sys.argv[1]))
assert is_multihost()

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from tensorlink_tpu.engine.training import (
    make_optimizer, make_train_step, optimizer_state_specs,
)
from tensorlink_tpu.models import ModelConfig, init_params, partition_specs
from tensorlink_tpu.parallel.mesh import build_mesh

devs = jax.devices()
assert len(devs) == 4 and len(jax.local_devices()) == 2

cfg = ModelConfig(
    family="qwen3", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, max_seq_len=64, qk_norm=True,
    tie_embeddings=True, dtype=jnp.float32,
)
mesh = build_mesh({"fsdp": 2, "tensor": 2}, devs)
pspecs = partition_specs(cfg, tensor_axis="tensor", fsdp_axis="fsdp")
params = init_params(cfg, jax.random.PRNGKey(0))
params = jax.tree.map(
    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, pspecs
)
opt = make_optimizer("adamw", lr=1e-3)
ts = make_train_step(cfg, opt, n_micro=2, remat=True, donate=False)
sspecs = optimizer_state_specs(opt, params, pspecs)
state = jax.tree.map(
    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
    opt.init(params), sspecs,
)
tokens = jax.device_put(
    jnp.asarray(np.ones((4, 32), np.int32)),
    NamedSharding(mesh, jax.sharding.PartitionSpec()),
)
with jax.set_mesh(mesh):
    params, state, metrics = ts.step_fn(params, state, {"tokens": tokens})
loss = float(metrics["loss"])
print(f"MHLOSS {loss:.6f}", flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_train_step_across_two_processes(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    coord = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "mh_child.py"
    script.write_text(
        _CHILD.replace("__REPO__", repr(repo)).replace("__COORD__", repr(coord))
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=420)
        outs.append(out)
        assert p.returncode == 0, out[-3000:]
    losses = []
    for out in outs:
        line = next(ln for ln in out.splitlines() if ln.startswith("MHLOSS"))
        losses.append(float(line.split()[1]))
    # both controllers observe the SAME loss: one SPMD program over the
    # 4-device global mesh, collectives crossing the process boundary
    assert losses[0] == pytest.approx(losses[1], abs=1e-6)
    # and it matches the single-process virtual-mesh result for the same
    # config/shapes/seed (the dryrun's mesh math, now across processes)
    single = subprocess.run(
        [sys.executable, "-c", _SINGLE.format(repo=repo)],
        env={**env, "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        capture_output=True, text=True, timeout=420,
    )
    assert single.returncode == 0, single.stdout + single.stderr
    ref = json.loads(single.stdout.strip().splitlines()[-1])["loss"]
    assert losses[0] == pytest.approx(ref, rel=1e-4)


_SINGLE = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import json
from jax.sharding import NamedSharding
from tensorlink_tpu.engine.training import (
    make_optimizer, make_train_step, optimizer_state_specs,
)
from tensorlink_tpu.models import ModelConfig, init_params, partition_specs
from tensorlink_tpu.parallel.mesh import build_mesh
cfg = ModelConfig(
    family="qwen3", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, max_seq_len=64, qk_norm=True,
    tie_embeddings=True, dtype=jnp.float32,
)
mesh = build_mesh({{"fsdp": 2, "tensor": 2}}, jax.devices()[:4])
pspecs = partition_specs(cfg, tensor_axis="tensor", fsdp_axis="fsdp")
params = init_params(cfg, jax.random.PRNGKey(0))
params = jax.tree.map(
    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, pspecs
)
opt = make_optimizer("adamw", lr=1e-3)
ts = make_train_step(cfg, opt, n_micro=2, remat=True, donate=False)
sspecs = optimizer_state_specs(opt, params, pspecs)
state = jax.tree.map(
    lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
    opt.init(params), sspecs,
)
tokens = jnp.asarray(np.ones((4, 32), np.int32))
with jax.set_mesh(mesh):
    params, state, metrics = ts.step_fn(params, state, {{"tokens": tokens}})
print(json.dumps({{"loss": float(metrics["loss"])}}))
"""
