"""CLI runner + profiling utilities."""

import json
import signal
import subprocess
import sys
import time

import pytest


def test_cli_starts_worker_and_reports(tmp_path):
    cfg = {
        "role": "worker",
        "mode": "local",
        "key_dir": str(tmp_path / "keys"),
        "log_dir": str(tmp_path / "logs"),
        "env_file": str(tmp_path / ".env"),
    }
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps(cfg))
    proc = subprocess.Popen(
        [sys.executable, "-m", "tensorlink_tpu.cli", "-c", str(cfg_path),
         "--ui-interval", "0"],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    try:
        line = proc.stdout.readline()
        info = json.loads(line)
        assert info["role"] == "worker" and info["port"] > 0
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_cli_fails_fast_on_dead_accelerator_backend(tmp_path):
    """A worker whose accelerator backend does not come up must NOT carry
    on: the CLI exits non-zero promptly with the backend's error on stderr
    (core/devices.py — no probe subprocess, no switch to the CPU), and
    leaves no network process behind."""
    import os

    cfg = {
        "role": "worker",
        "mode": "local",
        "key_dir": str(tmp_path / "keys"),
        "log_dir": str(tmp_path / "logs"),
        "env_file": str(tmp_path / ".env"),
    }
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps(cfg))
    env = dict(os.environ)
    # a platform name with no registered factory: backend init raises
    env["JAX_PLATFORMS"] = "bogus_tpu_runtime"
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, "-m", "tensorlink_tpu.cli", "-c", str(cfg_path),
         "--ui-interval", "0"],
        capture_output=True, text=True, env=env, timeout=90,
    )
    assert time.monotonic() - t0 < 60, "CLI took too long to give up"
    assert p.returncode != 0, p.stdout
    assert not p.stdout.strip(), p.stdout  # no "node is up" line
    assert "failed to start" in p.stderr, p.stderr[-800:]
    assert "bogus_tpu_runtime" in p.stderr, p.stderr[-800:]


def test_acquire_devices_cpu_fast():
    from tensorlink_tpu.core.devices import acquire_devices

    probe = acquire_devices()
    assert probe.n_devices >= 1
    assert probe.platform == "cpu"
    assert len(probe.devices) == probe.n_devices


def test_status_report_format(tmp_path):
    from tensorlink_tpu.cli import status_report
    from tensorlink_tpu.core.config import WorkerConfig
    from tensorlink_tpu.nodes.runners import WorkerNode

    node = WorkerNode(
        WorkerConfig(local_test=True, key_dir=str(tmp_path / "k"),
                     log_dir=str(tmp_path / "l"), env_file=str(tmp_path / ".e"))
    ).start()
    try:
        out = status_report(node)
        assert "worker" in out and "peers (0)" in out
    finally:
        node.stop()
