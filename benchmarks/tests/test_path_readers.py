"""The readers PR 38 adds (PR 37's, refused for a machine's set-up), on
hand-made inputs whose answers are known by construction: what of
``http_first_byte`` its seven inner spans leave unnamed, and the device's
idle time laid against the requests that were on their way to a slot;
the two gauges of the step programs' build through the reader that was
there. A program that records none of the spans and neither gauge (the
parent) gives nothing and raises nothing."""

import pytest

from benchmarks.harness.e2e import Rec
from benchmarks.harness.spec import load_benchmark
from benchmarks.tests.test_phase_readers import hand_made_idle, obs_of, read

WAY = ("api_in", "prepare", "hop_in", "work_wait", "submit", "first_token",
       "token_out")


def span(name, ms, t0=0.0, host="h"):
    return {"name": name, "dur_ms": ms, "t0": t0, "host": host, "site": "x",
            "parent": ""}


def way(first_byte, durs):
    return [span("http_first_byte", first_byte)] + [
        span(n, ms) for n, ms in zip(WAY, durs)]


# -- span_residual_quantile --------------------------------------------------
def test_the_residual_is_the_outer_span_less_the_sum_of_the_inner():
    recs = [Rec(idx=i, due=1.0 + i, asked=4, rid=f"r{i}") for i in range(5)]
    recs.append(Rec(idx=9, due=50.0, asked=4, rid="late"))  # not in window
    spans = {
        "r0": way(300.0, (1, 2, 3, 140, 1, 150, 2)),  # 1 unnamed
        "r1": way(410.0, (1, 2, 3, 100, 1, 290, 8)),  # 5
        # a recovered request crossed the hop twice: durations add up
        "r2": way(520.0, (1, 2, 3, 100, 1, 400, 4)) + [span("hop_in", 6.0)],
        "r3": [s for s in way(900.0, (1,) * 7) if s["name"] != "submit"],
        "r4": [span("first_token", 100.0)],  # no first byte: left out
        "late": way(999.0, (1,) * 7),
    }
    obs = obs_of(recs=recs, spans=spans)
    for name in ("first_byte_unaccounted_p50_ms.sessions",
                 "first_byte_unaccounted_p50_ms.open"):
        assert read(name, obs) == pytest.approx(3.0)  # of 1, 3, 5
    # the quantiles of the single spans, through the reader that was there
    assert read("work_wait_p50_ms.sessions", obs) == pytest.approx(100.0)
    assert read("work_wait_p50_ms.open", obs) == pytest.approx(100.0)
    assert read("hop_in_p50_ms.sessions", obs) == pytest.approx(3.0)
    assert read("token_out_p50_ms.sessions", obs) == pytest.approx(3.0)
    assert read("api_in_p50_ms.sessions", obs) == pytest.approx(1.0)
    assert read("prepare_p50_ms.sessions", obs) == pytest.approx(2.0)


def test_the_parents_spans_give_no_metric_of_the_way_and_no_error():
    """The parent records ``http_first_byte`` and the engine's spans, with
    no ``t0``, and neither gauge of the build: the nine metrics that read
    this PR's spans and the two that read its gauges are left out of its
    line. ``prefill`` and ``first_decode`` it records too, so those three
    quantiles read there as here."""
    recs = [Rec(idx=0, due=1.0, asked=4, rid="r0")]
    old = [{"name": n, "dur_ms": ms, "site": "x", "ts": 1.0}
           for n, ms in (("http_first_byte", 300.0), ("first_token", 150.0),
                         ("queue_wait", 8.0), ("prefill", 130.0),
                         ("first_decode", 12.0))]
    obs = obs_of(recs=recs, spans={"r0": old}, trace=hand_made_idle(),
                 chunks=[{"t0": 50.0}],
                 stats1={"weights_bytes_device_max": 8_000_000_000})
    names = [m["name"] for m in load_benchmark()["per_layer"]]
    first = names.index("api_in_p50_ms.sessions")
    new = names[first:first + 14]  # PR 38's entries, wherever later ones go
    assert new[-3:] == ["idle_request_on_path_share.sessions",
                        "step_build_s", "step_build_waited_s"]
    theirs_too = {"prefill_p50_ms.sessions": 130.0,
                  "prefill_p50_ms.open": 130.0,
                  "first_decode_p50_ms.sessions": 12.0}
    for name in new:
        assert read(name, obs) == theirs_too.get(name), name
    assert read("idle_request_on_path_share.sessions",
                obs_of(trace=None)) is None


def test_the_builds_gauges_read_in_seconds_and_move_set_up_in_every_cell():
    obs = obs_of(stats1={"step_build_ms": 7912.4,
                         "step_build_waited_ms": 5630.25})
    assert read("step_build_s", obs) == pytest.approx(7.9124)
    assert read("step_build_waited_s", obs) == pytest.approx(5.63025)
    bench = load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    by = {m["name"]: m for m in bench["per_layer"]}
    for m in (by["step_build_s"], by["step_build_waited_s"]):
        assert (m["moves"], m["unit"], m["layer"], m["source"]) == (
            "setup_s", "s", "step program", "program_counter")
        assert sorted(m["workloads"]) == sorted(cells)


def test_the_engines_two_unread_spans_have_a_quantile_now():
    recs = [Rec(idx=i, due=1.0 + i, asked=4, rid=f"r{i}") for i in range(3)]
    spans = {f"r{i}": [span("prefill", 180.0 * (i + 1)),
                       span("first_decode", 4.0 + i)] for i in range(3)}
    obs = obs_of(recs=recs, spans=spans)
    assert read("prefill_p50_ms.sessions", obs) == pytest.approx(360.0)
    assert read("prefill_p50_ms.open", obs) == pytest.approx(360.0)
    assert read("first_decode_p50_ms.sessions", obs) == pytest.approx(5.0)


# -- trace_idle_by_spans -----------------------------------------------------
def test_idle_time_is_laid_against_the_requests_on_their_way():
    """``hand_made_idle``: busy 100..900 and 1250..1950 us of 0..2000, so
    idle 0..100, 900..1250, 1950..2000 = 500 us; the taps' clock reads
    50.0 s at the trace's 0 and 50.0011 at its second chunk.

    Request a entered the API at 950 and was admitted at 1150: 200 us of
    the middle gap. Request b entered at 1100 and was admitted at 1300:
    1100..1250 idle, of which 1100..1150 is a's too. Request c entered at
    1960 and is admitted after the trace: 1960..2000 = 40. Request d has
    no queue_wait yet, e's two spans name different hosts, f's spans hold
    no ``t0`` (an old peer): left out. (200 + 100 + 40) / 500 = 68%."""
    us = 1e-6
    taps = [{"t0": 50.0}, {"t0": 50.0 + 1100 * us}]

    def on_way(enter_us, admit_us, queue_us=5.0, **kw):
        return [
            span("api_in", 0.3, t0=50.0 + enter_us * us),
            span("queue_wait", queue_us * 1e-3,
                 t0=50.0 + (admit_us - queue_us) * us, **kw),
            span("first_token", 100.0, t0=50.0 + (admit_us - queue_us) * us),
        ]

    spans = {
        "a": on_way(950, 1150),
        "b": on_way(1100, 1300),
        "c": on_way(1960, 2400),
        "d": [span("api_in", 0.3, t0=50.0 + 10 * us)],
        "e": on_way(0, 2000, host="elsewhere"),
        "f": [{"name": "api_in", "dur_ms": 0.3},
              {"name": "queue_wait", "dur_ms": 2000.0}],
    }
    obs = obs_of(trace=hand_made_idle(), chunks=taps, spans=spans)
    assert read("idle_request_on_path_share.sessions", obs) \
        == pytest.approx(68.0)
    # a re-admitted request: the way ends at its FIRST admission
    spans["a"].append(span("queue_wait", 0.005, t0=50.0 + 1990 * us))
    assert read("idle_request_on_path_share.sessions", obs) \
        == pytest.approx(68.0)
    # requests there were, none of them during an idle gap: 0, not nothing
    busy_only = {"a": on_way(200, 800)}
    obs = obs_of(trace=hand_made_idle(), chunks=taps, spans=busy_only)
    assert read("idle_request_on_path_share.sessions", obs) == 0.0
    # nothing ties the clocks together: nothing
    obs = obs_of(trace=hand_made_idle(), chunks=[], spans=spans)
    assert read("idle_request_on_path_share.sessions", obs) is None


def test_the_new_entries_name_their_cells():
    bench = load_benchmark()
    by = {m["name"]: m for m in bench["per_layer"]}
    sessions = ["qwen3-4b.prefix-sessions", "qwen3-4b.long-cache-decode"]
    for name in ("api_in_p50_ms", "prepare_p50_ms", "hop_in_p50_ms",
                 "token_out_p50_ms", "work_wait_p50_ms", "prefill_p50_ms",
                 "first_decode_p50_ms", "first_byte_unaccounted_p50_ms"):
        m = by[name + ".sessions"]
        assert (m["workloads"], m["moves"], m["source"]) == (
            sessions, "ttft_p50_ms", "program_span")
        assert m["layer"] == by["path_overhead_p50_ms.sessions"]["layer"]
    for name in ("work_wait_p50_ms", "prefill_p50_ms",
                 "first_byte_unaccounted_p50_ms"):
        m = by[name + ".open"]
        assert (m["workloads"], m["moves"]) == (
            ["qwen3-4b.chat-steady"], "stall8_p50_ms")
    idle = by["idle_request_on_path_share.sessions"]
    assert idle["workloads"] == by["idle_in_host_phases_share.sessions"][
        "workloads"] and len(idle["workloads"]) >= 4
    assert (idle["layer"], idle["source"], idle["moves"], idle["unit"]) == (
        "device", "device_trace", "tpot_p50_ms.sessions", "%")
