"""The CPU rehearsal prints the per-layer metrics PR 24 adds, read from the
program's own counters, records and spans. No number from here is a device
metric. The three ``trace_phase_ms`` metrics need the device's ``XLA
Modules`` line, which a CPU trace does not have: there the reader says so
on the run's output and the line leaves them out (their numbers are pinned
on the recorded chip trace in ``test_phase_readers.py``)."""

import pytest

from benchmarks.tests.fixtures import tiny_cell
from benchmarks.tests.test_rehearsal import check_line

COUNTERS = ("chunk_host_share", "drain_share", "deliver_share",
            "row_fill_share")
DEVICE_ONLY = {"ragged_pass_ms": "ragged_pass", "verify_emit_ms": "verify_emit",
               "cont_step_ms": "decode_cont"}  # metric -> the loop it reads


@pytest.fixture(scope="module")
def run():
    from benchmarks import run as run_mod

    return run_mod


def test_closed_loop_prints_every_new_metric(run, monkeypatch, capsys):
    monkeypatch.setattr(run, "TRACE_S", 0.5)
    monkeypatch.setattr(run, "TRACE_AT", 0.3)
    cell = tiny_cell("closed")
    out = run.run_cell(cell, 2**31 + 24, 4.0, True, platform="cpu")
    check_line(out, cell, True)
    m = out["metrics"]
    for base in COUNTERS + ("idle_in_host_phases_share",):
        for name in (base, base + ".sessions"):
            assert 0 <= m[name]["value"] <= 100, name
            assert m[name]["unit"] == "%"
    shares = {k: m[k]["value"] for k in COUNTERS}
    # drain and deliver are parts of the host's share of a chunk
    assert shares["drain_share"] + shares["deliver_share"] \
        <= shares["chunk_host_share"]
    # prompts of 80 tokens in blocks of 4 x 32, then one row a slot
    assert 0 < shares["row_fill_share"] < 60
    for name in ("path_overhead_p50_ms.open", "path_overhead_p50_ms.sessions"):
        assert m[name]["value"] > 0 and m[name]["unit"] == "ms"
    said = capsys.readouterr().out
    for name, phase in DEVICE_ONLY.items():
        assert name not in m and name + ".sessions" not in m
        assert f"trace_phase_ms({phase}): nothing to read" in said
    # what was there reads as before
    assert 0 <= m["host_gap_share"]["value"] < 100
    assert {"device_ops", "idle_gaps"} == set(out["breakdown"])
