"""The reduction from a profiler trace to numbers: on a hand-made trace
whose answer is known by construction, and on a small trace recorded on the
chip in this PR's own traced run of ``qwen3-4b.decode-closed``."""

import json
from pathlib import Path

import pytest

from benchmarks.harness import xplane
from benchmarks.harness.peaks import PEAKS, peaks_for

DATA = Path(__file__).parent / "data"


def ev(meta, off_us, dur_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(off_us * 1e6)} "
            f"duration_ps: {int(dur_us * 1e6)} }}")


def hand_made():
    """One device. Window = the two chunks: 0..1000 us and 1200..2000 us.
    Operations: a while 100..900 holding attn 100..400 and fusion 500..900;
    a copy 1300..1500; attn 1600..1900. Busy = 800 + 200 + 300 = 1300 us of
    2000. Gaps: 0..100 (admission 0..60, packing 60..80, rest 20), 900..1000
    (rest), 1000..1200 (no work), 1200..1300 (admission 50, rest 50),
    1500..1600 (rest), 1900..2000 (rest)."""
    names = {1: "%while.7", 2: "%paged_attention.6 = custom-call tpu_custom_call",
             3: "%fusion.533", 4: "%copy.406", 5: "bench:step_chunk",
             6: "bench:admission", 7: "bench:pack_ragged",
             8: "jit_paged_ragged_step(123)"}
    meta = lambda ids: "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{names[i]}" }} }}'
        for i in ids)
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
    {ev(1, 100, 800)} {ev(2, 100, 300)} {ev(3, 500, 400)}
    {ev(4, 1300, 200)} {ev(2, 1600, 300)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000
    {ev(8, 100, 800)} {ev(8, 1300, 600)} }}
  {meta([1, 2, 3, 4, 8])}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "engine-driver" timestamp_ns: 1000
    {ev(5, 0, 1000)} {ev(6, 0, 60)} {ev(7, 60, 20)}
    {ev(5, 1200, 800)} {ev(6, 1200, 50)} }}
  {meta([5, 6, 7])}
}}
"""
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


def test_hand_made_trace_reduces_to_its_known_numbers():
    tr = xplane.reduce_profile(hand_made())
    us = 1e-6
    assert tr.window_s == pytest.approx(2000 * us)
    assert tr.busy_s == pytest.approx(1300 * us)
    assert tr.idle_share() == pytest.approx(0.35)
    # self time: the while owns only what its children leave (800-300-400)
    assert tr.op_seconds(["paged_attention"]) == pytest.approx(600 * us)
    assert tr.op_seconds(["^%while"]) == pytest.approx(100 * us)
    assert tr.module_seconds(["ragged_step"]) == pytest.approx(1400 * us)
    top = dict(map(tuple, tr.top_ops()))
    assert max(top, key=top.get).startswith("paged_attention.6")
    assert sum(top.values()) == pytest.approx(1300 * us)
    gaps = dict(map(tuple, tr.idle_gaps()))
    assert gaps["admission"] == pytest.approx(110 * us)
    assert gaps["packing_the_ragged_block"] == pytest.approx(20 * us)
    assert gaps["engine_had_no_work"] == pytest.approx(200 * us)
    assert gaps["dispatch_+_sync_+_token_delivery"] == pytest.approx(370 * us)
    assert sum(gaps.values()) == pytest.approx(700 * us)


def test_exposed_time_is_what_no_other_operation_covers():
    from jax.profiler import ProfileData

    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {ev(1, 0, 100)} {ev(2, 50, 100)} {ev(1, 300, 100)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%all-gather.3" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.9" }} }}
}}"""
    tr = xplane.reduce_profile(ProfileData.from_text_proto(text))
    assert tr.exposed_seconds(["all-gather"]) == pytest.approx(150e-6)
    assert tr.window_s == pytest.approx(400e-6)


def test_interval_helpers():
    assert xplane.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert xplane.overlap([(0, 2.5), (3, 4)], 2, 3.5) == pytest.approx(1.0)
    assert xplane.complement([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]


def test_peaks_table_is_keyed_by_device_kind_and_refuses_strangers():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops"] == 197e12
    assert all("source" in p for p in PEAKS.values())
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("cpu")


RECORDED = DATA / "qwen3-4b.decode-closed.chunk.xplane.pb.gz"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace_gives_its_known_numbers():
    want = json.loads((DATA / "qwen3-4b.decode-closed.chunk.expected.json").read_text())
    tr = xplane.reduce_profile(xplane.load_profile(str(RECORDED)))
    assert len([d for d in tr.devices if d.ops]) == want["devices"]
    assert tr.window_s == pytest.approx(want["window_s"], rel=1e-6)
    assert tr.busy_s == pytest.approx(want["busy_s"], rel=1e-6)
    assert tr.idle_share() == pytest.approx(want["idle_share"], rel=1e-6)
    for pattern, secs in want["op_seconds"].items():
        assert tr.op_seconds([pattern]) == pytest.approx(secs, rel=1e-6), pattern
    assert tr.module_seconds(["ragged_step"]) == pytest.approx(
        want["module_seconds"], rel=1e-6)
    got = tr.top_ops(5)
    assert [n for n, _ in got] == [n for n, _ in want["top_ops"]]
    gaps = dict(map(tuple, tr.idle_gaps()))
    for name, secs in want["idle_gaps"]:
        assert gaps[name] == pytest.approx(secs, rel=1e-6)
    # the parts of the idle time add up to the idle time
    assert sum(gaps.values()) == pytest.approx(tr.window_s - tr.devices[0].busy_s, rel=1e-6)
