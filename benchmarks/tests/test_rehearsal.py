"""The harness end to end at a tiny size on the CPU: the same functions the
command runs, with a fixture configuration that is not in ``workloads``.
No number from here is a device metric. The command itself still fails
without a TPU."""

import json
import subprocess
import sys

import pytest

from benchmarks.harness.spec import REPO_DIR
from benchmarks.tests.fixtures import tiny_cell


@pytest.fixture(scope="module")
def run():
    from benchmarks import run as run_mod

    return run_mod


def check_line(out, cell, trace):
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    # each number that decided ``correct`` beside its limit, as the last key
    assert list(out)[-1] == "compared"
    assert {"gap_sigmas_max", "streams_short", "page_conservation_faults"} \
        <= set(out["compared"])
    for c in out["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 4
    json.dumps(out)
    wanted = cell.per_layer if trace else cell.end_to_end
    names = {m["name"] for m in wanted}
    assert set(out["metrics"]) <= names
    for v in out["metrics"].values():
        assert set(v) == {"value", "unit"} and isinstance(v["value"], float)


def test_closed_loop_traced(run, monkeypatch):
    # few enough that the trace ends inside the window and the profiler
    # is stopped beside the running engine, as on the chip
    monkeypatch.setattr(run, "TRACE_CHUNKS", 5)
    monkeypatch.setattr(run, "TRACE_AT", 0.3)
    cell = tiny_cell("closed")
    out = run.run_cell(cell, 2**31 + 52, 4.0, True, platform="cpu")
    check_line(out, cell, True)
    m = out["metrics"]
    assert m["compiles_in_window"]["value"] == 0.0
    assert 0 < m["slot_occupancy"]["value"] <= 100
    assert 0 <= m["host_gap_share"]["value"] < 100
    assert 0 <= m["device_idle_share.closed"]["value"] < 100
    assert out["device"]["busy_s"] > 0
    assert out["traced_chunks"] == 5
    # the stages before the judged set-up clock, and the gap beside the stall
    assert m["backend_up_s"]["value"] >= 0 and m["imports_s"]["value"] > 0
    assert m["itl_max_p50_ms"]["value"] > 0
    assert 0 < out["device"]["window_s"] < 2.0
    assert {"device_ops", "idle_gaps"} == set(out["breakdown"])
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_open_loop_end_to_end(run):
    cell = tiny_cell("open")
    out = run.run_cell(cell, 7, 4.0, False, platform="cpu")
    check_line(out, cell, False)
    assert out["attempted"] == 8  # floor(2.0 requests/s x 4 s)
    assert {m["name"] for m in cell.end_to_end} == set(out["metrics"])
    assert {"ttft_p50_ms", "tpot_p50_ms", "stall8_p50_ms", "out_tok_s",
            "out_tok_s.sessions", "setup_s"} <= set(out["metrics"])
    m = out["metrics"]
    assert m["out_tok_s"]["value"] == m["out_tok_s.sessions"]["value"]


def test_sessions_hit_the_prefix_cache(run):
    cell = tiny_cell("sessions", "qwen2")
    out = run.run_cell(cell, 8, 4.0, True, platform="cpu")
    check_line(out, cell, True)
    assert out["metrics"]["prefix_hit_share.sessions"]["value"] > 30
    assert out["metrics"]["turn_ttft_p90_ms"]["value"] > 0
    # the open loop's tail, per layer under a name of its own, is the same
    # client statistic
    assert (out["metrics"]["open_ttft_p90_ms"]["value"]
            == out["metrics"]["turn_ttft_p90_ms"]["value"])


def test_tensor_parallel_path_on_four_virtual_devices(run):
    cell = tiny_cell("closed", "qwen2", tp=4)
    out = run.run_cell(cell, 9, 3.0, False, platform="cpu")
    check_line(out, cell, False)


def test_the_command_fails_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "qwen3-4b.decode-closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO_DIR, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_ENABLE_COMPILATION_CACHE": "false"},
    )
    assert p.returncode != 0
    assert not p.stdout.strip().endswith("}")
    assert "needs a tpu device" in p.stderr
