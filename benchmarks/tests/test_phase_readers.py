"""The readers PR 24 adds: the step program's phases from the recorded
chip trace (and nothing when a loop is missing), the device's idle time
laid against the program's host phases, and the difference of two spans,
on hand-made inputs whose answers are known by construction."""

from pathlib import Path

import pytest

from benchmarks.harness import xplane
from benchmarks.harness.e2e import Rec
from benchmarks.harness.obs import Obs
from benchmarks.harness.spec import load_layer_metric, reader
from benchmarks.tests.test_xplane import ev

DATA = Path(__file__).parent / "data"
RECORDED = DATA / "qwen3-4b.decode-closed.chunk.xplane.pb.gz"


def obs_of(**kw):
    base = dict(mode="closed", recs=[], t0=0.0, t1=10.0, grace=1.0,
                stats0={}, stats1={})
    return Obs(**{**base, **kw})


def read(metric, obs):
    spec = load_layer_metric(metric)
    return reader(spec["kind"]).read(obs, spec)


# -- trace_phase_ms ----------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.exists():
        pytest.skip("no recorded trace")
    return xplane.reduce_profile(xplane.load_profile(str(RECORDED)))


def test_recorded_chunks_split_into_the_three_phases(recorded):
    """Two chunks of decode-closed, 8 decode steps each (PR 23's trace):
    the ragged pass, the verify walk, and 7 continuation steps a chunk."""
    obs = obs_of(trace=recorded, chunks=[{"decode_steps": 8}] * 2)
    assert read("ragged_pass_ms", obs) == pytest.approx(231.7, abs=2)
    assert read("verify_emit_ms", obs) == pytest.approx(106.3, abs=1)
    assert read("cont_step_ms", obs) == pytest.approx(75.0, abs=0.5)
    assert read("cont_step_ms.sessions", obs) == read("cont_step_ms", obs)
    # the three loops and the copies around them are the whole execution
    whole = recorded.module_seconds(["ragged_step"]) / 2 * 1e3
    parts = (read("ragged_pass_ms", obs) + read("verify_emit_ms", obs)
             + 7 * read("cont_step_ms", obs))
    assert 0.97 * whole < parts < whole


def test_prefill_only_chunks_count_no_continuation_step(recorded):
    obs = obs_of(trace=recorded,
                 chunks=[{"decode_steps": 8}, {"decode_steps": 0}])
    assert read("cont_step_ms", obs) == pytest.approx(2 * 75.0, abs=1)
    obs = obs_of(trace=recorded, chunks=[{"decode_steps": 0}] * 2)
    assert read("cont_step_ms", obs) is None


def test_a_missing_loop_gives_nothing_and_says_so(recorded, capsys):
    dev = recorded.devices[0]
    loops = [o for o in dev.ops if o.name.startswith("%while.44 ")]
    assert len(loops) == 2
    cut = xplane.Trace(
        devices=[xplane.DeviceTrace(
            ordinal=0, ops=[o for o in dev.ops if o not in loops[:1]],
            modules=dev.modules, busy=dev.busy)],
        host=recorded.host, t0=recorded.t0, t1=recorded.t1)
    obs = obs_of(trace=cut, chunks=[{"decode_steps": 8}] * 2)
    for name in ("ragged_pass_ms", "verify_emit_ms", "cont_step_ms"):
        assert read(name, obs) is None
    assert "2 top-level loops, not 3" in capsys.readouterr().out
    assert read("ragged_pass_ms", obs_of(trace=None)) is None


def test_nested_loops_are_not_phases():
    from jax.profiler import ProfileData

    names = {1: "%while.3", 2: "%while.9", 3: "%fusion.1",
             4: "jit_paged_ragged_step(1)"}
    meta = "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items())
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {ev(1, 0, 300)} {ev(2, 50, 100)} {ev(3, 60, 20)}
    {ev(1, 300, 100)} {ev(1, 450, 500)} {ev(2, 500, 50)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {ev(4, 0, 1000)} }}
  {meta}
}}"""
    tr = xplane.reduce_profile(ProfileData.from_text_proto(text))
    obs = obs_of(trace=tr, chunks=[{"decode_steps": 6}])
    assert read("ragged_pass_ms", obs) == pytest.approx(0.3)
    assert read("verify_emit_ms", obs) == pytest.approx(0.1)
    assert read("cont_step_ms", obs) == pytest.approx(0.5 / 5)


# -- trace_idle_by_phase -----------------------------------------------------
def hand_made_idle():
    """One device, two chunks on the trace's axis at 0..1000 us and
    1100..2000 us. Busy 100..900 and 1250..1950, so idle: 0..100,
    900..1250, 1950..2000 = 500 us."""
    from jax.profiler import ProfileData

    names = {1: "%fusion.1", 2: "bench:step_chunk"}
    meta = lambda ids: "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{names[i]}" }} }}'
        for i in ids)
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {ev(1, 100, 800)} {ev(1, 1250, 700)} }}
  {meta([1])}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "engine-driver" timestamp_ns: 0
    {ev(2, 0, 1000)} {ev(2, 1100, 900)} }}
  {meta([2])}
}}"""
    return xplane.reduce_profile(ProfileData.from_text_proto(text))


def test_idle_time_is_laid_against_the_programs_phases():
    """The taps' monotonic clock reads 50.0 s at the trace's 0. Chunk 1
    (record t0 = tap t0 + 2 us): admit 30, pack 20, dispatch 48 (ends at
    100 on the axis), wait 800, drain 30, deliver 40, post 20 (ends 990).
    Chunk 2: between 112 (from 990 to 1102), admit 48, pack 50, dispatch
    50 (ends 1250), wait 700, drain 20, deliver 20, post 8 (ends 1998).
    Idle inside a phase other than wait: 0..2 is before the record
    (no phase), 2..100 = 98; 900..990 = 90, then between 990..1102 = 112
    and 1102..1250 = 148; 1950..1998 = 48; 1998..2000 no phase.
    (98 + 90 + 112 + 148 + 48) / 500 = 99.2%."""
    us = 1e-6
    taps = [{"t0": 50.0}, {"t0": 50.0 + 1100 * us}]
    recs = [
        dict(step=7, t0=50.0 + 2 * us, between_ms=0.0, admit_ms=0.030,
             pack_ms=0.020, dispatch_ms=0.048, wait_ms=0.800,
             drain_ms=0.030, deliver_ms=0.040, post_ms=0.020),
        dict(step=8, t0=50.0 + 1102 * us, between_ms=0.112, admit_ms=0.048,
             pack_ms=0.050, dispatch_ms=0.050, wait_ms=0.700,
             drain_ms=0.020, deliver_ms=0.020, post_ms=0.008),
        # a record from long before the trace matches no traced chunk
        dict(step=3, t0=41.0, between_ms=5.0, admit_ms=1.0, pack_ms=1.0,
             dispatch_ms=1.0, wait_ms=1.0, drain_ms=1.0, deliver_ms=1.0,
             post_ms=1.0),
    ]
    obs = obs_of(trace=hand_made_idle(), chunks=taps, recorder=recs)
    assert read("idle_in_host_phases_share", obs) == pytest.approx(99.2)
    assert read("idle_in_host_phases_share.sessions", obs) == pytest.approx(99.2)
    # idle time under "wait" is a bubble inside the running program
    recs[0]["wait_ms"], recs[0]["dispatch_ms"] = 0.838, 0.010
    obs = obs_of(trace=hand_made_idle(), chunks=taps, recorder=recs)
    assert read("idle_in_host_phases_share", obs) == pytest.approx(
        (60 + 90 + 112 + 148 + 48) / 500 * 100)


def test_records_without_phases_give_nothing():
    """The parent's records: ``step``, ``chunk_ms``, ``host_ms``."""
    old = [dict(step=1, chunk_ms=900.0, host_ms=0.3)]
    obs = obs_of(trace=hand_made_idle(), chunks=[{"t0": 50.0}], recorder=old)
    assert read("idle_in_host_phases_share", obs) is None
    assert read("idle_in_host_phases_share", obs_of(trace=None)) is None


# -- span_diff_quantile ------------------------------------------------------
def test_path_overhead_is_the_difference_of_the_two_spans():
    recs = [Rec(idx=i, due=1.0 + i, asked=4, rid=f"r{i}") for i in range(4)]
    recs.append(Rec(idx=9, due=50.0, asked=4, rid="late"))  # not in window
    span = lambda name, ms: {"name": name, "dur_ms": ms, "site": "x"}
    spans = {
        "r0": [span("http_first_byte", 110.0), span("first_token", 100.0),
               span("queue_wait", 7.0)],
        "r1": [span("http_first_byte", 230.0), span("first_token", 200.0)],
        "r2": [span("http_first_byte", 320.0), span("first_token", 300.0)],
        "r3": [span("first_token", 300.0)],  # no first byte: left out
        "late": [span("http_first_byte", 999.0), span("first_token", 1.0)],
    }
    obs = obs_of(recs=recs, spans=spans)
    assert read("path_overhead_p50_ms.open", obs) == pytest.approx(20.0)
    assert read("path_overhead_p50_ms.sessions", obs) == pytest.approx(20.0)
    # the parent records no http_first_byte: nothing, and no error
    for v in spans.values():
        v[:] = [s for s in v if s["name"] != "http_first_byte"]
    assert read("path_overhead_p50_ms.open", obs) is None


# -- the counter ratios on a parent without the counters ---------------------
@pytest.mark.parametrize("name", ["chunk_host_share", "drain_share",
                                  "deliver_share", "row_fill_share"])
def test_counter_shares(name):
    keys = ("between", "admit", "pack", "dispatch", "wait", "drain",
            "deliver", "post")
    s0 = {f"chunk_us_{k}": 1000 for k in keys}
    grow = dict(between=100, admit=10, pack=40, dispatch=1100, wait=93000,
                drain=150, deliver=4500, post=1100)
    s1 = {f"chunk_us_{k}": 1000 + v for k, v in grow.items()}
    s0 |= {"ragged_rows_valid": 10, "ragged_rows_computed": 1024}
    s1 |= {"ragged_rows_valid": 90, "ragged_rows_computed": 11264}
    want = {"chunk_host_share": 7.0, "drain_share": 0.15,
            "deliver_share": 4.5, "row_fill_share": 80 / 10240 * 100}
    obs = obs_of(stats0=s0, stats1=s1)
    assert read(name, obs) == pytest.approx(want[name])
    assert read(name + ".sessions", obs) == pytest.approx(want[name])
    old = {"decode_steps": 5}  # a program without the counters
    assert read(name, obs_of(stats0=old, stats1=old)) is None
