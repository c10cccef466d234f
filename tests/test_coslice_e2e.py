"""Co-slice merged-mesh training, composed end-to-end (VERDICT r4 #7).

Two REAL worker OS processes advertise the same ``slice_id`` and join one
``jax.distributed`` runtime (2 processes x 2 virtual CPU devices). The
validator plans with ``co_slice_planning=True`` -> the planner merges them
into ONE stage whose mesh spans both processes
(parallel/planner.py::_merge_co_slice). A training job through
DistributedModel then runs on the merged mesh: every work item is mirrored
to the coworker (ml/module.py::_request_mirrored), so each compiled call is
one SPMD program launched by both processes with XLA's collectives crossing
the process boundary — the composition of the multihost glue
(tests/test_multihost.py) with the planner merge (tests/test_planner.py),
which each had tests but never together.
"""

import os
import socket
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from tensorlink_tpu.core.config import MLConfig, UserConfig, ValidatorConfig
from tensorlink_tpu.models import ModelConfig

pytestmark = pytest.mark.e2e

# same environment limit test_multihost.py guards: jax < 0.5 CPU has no
# cross-process collectives, and a merged co-slice mesh IS a
# multi-process mesh — the worker dies inside XLA, not in our code
if tuple(int(p) for p in jax.__version__.split(".")[:2]) < (0, 5) and (
    # version first: jax >= 0.5 short-circuits before default_backend()
    # would initialize the real accelerator at collection time
    os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
    or jax.default_backend() == "cpu"
):
    pytestmark = [
        pytest.mark.e2e,
        pytest.mark.skip(
            reason="jax<0.5 CPU backend has no multiprocess collectives"
        ),
    ]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER_CHILD = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, __REPO__)


def main():
    from tensorlink_tpu.core.config import MLConfig, WorkerConfig
    from tensorlink_tpu.nodes.runners import WorkerNode

    pid = int(sys.argv[1])
    vport = int(sys.argv[2])
    coord = sys.argv[3]
    tmp = sys.argv[4]

    WorkerNode(WorkerConfig(
        local_test=True,
        key_dir=f"{tmp}/keys{pid}",
        log_dir=f"{tmp}/logs{pid}",
        env_file=f"{tmp}/env{pid}",
        seed_validators=[["127.0.0.1", vport]],
        ml=MLConfig(
            slice_id="testpod:0",
            coordinator_address=coord,
            num_processes=2,
            process_id=pid,
            dtype="float32",
        ),
    )).start()
    print("WORKER_READY", flush=True)
    while True:
        time.sleep(1.0)


if __name__ == "__main__":  # WorkerNode spawns its net process via the
    main()  # "spawn" context, which re-imports this module
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_coslice_merged_mesh_training(tmp_path):
    from tensorlink_tpu.ml.module import DistributedModel
    from tensorlink_tpu.nodes.runners import UserNode, ValidatorNode

    common = dict(
        local_test=True,
        key_dir=str(tmp_path / "keys_v"),
        log_dir=str(tmp_path / "logs_v"),
        env_file=str(tmp_path / "env_v"),
    )
    validator = ValidatorNode(ValidatorConfig(
        endpoint=False, ml=MLConfig(co_slice_planning=True), **common
    )).start()

    coord = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "coslice_worker.py"
    script.write_text(_WORKER_CHILD.replace("__REPO__", repr(REPO)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), str(validator.port),
             coord, str(tmp_path)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in (0, 1)
    ]
    user = None
    model = None
    try:
        # both children must be up (jax.distributed blocks until both join)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            stats = validator.send_request("stats_workers", timeout=15.0)
            if len(stats) == 2 and all(
                s.get("slice_id") == "testpod:0" for s in stats
            ):
                break
            for p in procs:
                assert p.poll() is None, p.stdout.read()[-3000:]
            time.sleep(0.5)
        else:
            raise AssertionError(f"workers never advertised the slice: {stats}")

        user = UserNode(UserConfig(
            seed_validators=[["127.0.0.1", validator.port]],
            **{**common, "key_dir": str(tmp_path / "keys_u")},
        )).start()

        cfg = ModelConfig(
            family="qwen3", vocab_size=256, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, max_seq_len=64,
            qk_norm=True, tie_embeddings=True, dtype="float32",
        )
        model = DistributedModel(
            cfg, node=user, training=True, batch=4, seq_len=64, seed=7,
        )
        # the planner MERGED the two workers: one stage, a coworker, and a
        # mesh spanning all 4 pooled devices (2 procs x 2 devices)
        assert model.plan.n_stages == 1, model.plan
        stage = model.plan.stages[0]
        assert len(stage.coworkers) == 1, stage
        mesh_n = 1
        for v in stage.mesh_axes.values():
            mesh_n *= v
        assert mesh_n == 4, stage.mesh_axes

        # eval forward parity: the merged-mesh logits equal the local
        # single-process forward (same seed -> same init)
        from tensorlink_tpu.models.transformer import forward, init_params
        import jax

        toks = np.array([[4, 8, 15, 16, 23, 42]], np.int32)
        out = model(toks)
        ref, _ = forward(init_params(cfg, jax.random.PRNGKey(7)), toks, cfg)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-4
        )

        # training: three steps on the merged mesh; loss moves down
        rng = np.random.default_rng(0)
        batch = rng.integers(1, cfg.vocab_size, (4, 32)).astype(np.int32)
        model.init_optimizer("adamw", lr=5e-3)
        losses = [model.train_step(batch)["loss"] for _ in range(3)]
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses

        # checkpoint + parameter download on the MERGED mesh: the work
        # items are mirrored to every member, the per-leaf gathers run as
        # lockstep collectives, only the primary touches the file
        # (previously a RuntimeError refusal, VERDICT "What's missing" §3)
        logits_before = np.asarray(model(toks))
        ckpt = tmp_path / "coslice_ckpt"
        paths = model.save_checkpoint(str(ckpt))["paths"]
        assert paths and (tmp_path / "coslice_ckpt" / "manifest.json").exists()
        model.restore_checkpoint(str(ckpt))
        np.testing.assert_allclose(
            np.asarray(model(toks)), logits_before, rtol=1e-5, atol=1e-6
        )

        # HF export round-trips: merged params -> safetensors -> load_params
        from tensorlink_tpu.engine.loader import load_params

        out_dir = tmp_path / "hf_export"
        model.export_hf_checkpoint(str(out_dir))
        _, reloaded = load_params(str(out_dir), cfg)
        merged = model._merge_stage_params(model.parameters())
        ref_leaves = jax.tree.leaves(merged["layers"])
        new_leaves = jax.tree.leaves(reloaded["layers"])
        assert len(ref_leaves) == len(new_leaves) > 0
        for a, b in zip(new_leaves, ref_leaves):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-6, atol=1e-7,
            )

        # serving is refused loudly on merged meshes (host-driven loops
        # are single-controller), not deadlocked
        with pytest.raises(RuntimeError, match="co-slice"):
            model.generate([[1, 2, 3]], max_new_tokens=4)
    finally:
        try:
            if model is not None:
                model.shutdown()
        except Exception:
            pass
        if user is not None:
            user.stop()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        validator.stop()
