"""The arithmetic of each end-to-end metric on hand-made arrival stamps."""

import pytest

from benchmarks.harness.e2e import (Rec, completed, is_failed, percentile,
                                    summarize, tokens_in_window)

T0, T1, GRACE = 100.0, 110.0, 15.0


def rec(idx, due, stamps, asked=None, status="ok", **kw):
    return Rec(idx=idx, due=due, asked=asked or len(stamps), sent=due + 0.001,
               stamps=list(stamps), status=status, **kw)


def test_tokens_are_counted_where_they_land():
    inside = rec(0, 101.0, [102.0, 103.0, 104.0])
    over_start = rec(1, 95.0, [98.0, 99.5, 100.0, 100.5], measured=False)
    over_end = rec(2, 108.0, [109.0, 109.9, 110.0, 111.0])
    recs = [inside, over_start, over_end]
    # the stamp AT t0 is inside, the stamp AT t1 is outside
    assert tokens_in_window(recs, T0, T1) == 3 + 2 + 2
    out = summarize(recs, mode="closed", t0=T0, t1=T1, grace=GRACE)
    assert out["metrics"]["out_tok_s"] == pytest.approx(7 / 10.0)
    # a request that completes after the window still gave its tokens inside
    assert out["tokens_in_window"] == 7


def test_out_tok_s_ignores_which_request_and_whether_it_completed():
    cut = rec(0, 101.0, [105.0, 106.0], asked=50, status="cut")
    out = summarize([cut], mode="closed", t0=T0, t1=T1, grace=GRACE)
    assert out["metrics"]["out_tok_s"] == pytest.approx(0.2)
    assert out["failed"] == 0  # still streaming correctly: cut, not failed


def test_ttft_is_from_due_and_failed_counts_as_the_worst():
    ok = [rec(i, 100.0 + i, [100.5 + i, 101.0 + i]) for i in range(9)]
    never = rec(9, 105.0, [], asked=4, status="cut")  # no first token at all
    refused = rec(10, 106.0, [], asked=4, status="refused")
    before = rec(11, 99.0, [99.5, 99.6])  # due before the window: not attempted
    out = summarize(ok + [never, refused, before], mode="open", t0=T0, t1=T1,
                    grace=GRACE)
    assert out["attempted"] == 11 and out["failed"] == 2
    # nine at 500 ms; the two failures are given the time to the end of the
    # wait: (110 + 15 - 105) and (110 + 15 - 106) seconds
    ttfts = sorted([500.0] * 9 + [20000.0, 19000.0])
    assert out["metrics"]["ttft_p90_ms"] == pytest.approx(
        percentile(ttfts, 90))
    assert out["metrics"]["ttft_p90_ms"] == pytest.approx(19000.0)


def test_first_token_after_the_wait_is_a_failure():
    late = rec(0, 109.0, [126.0, 127.0], status="cut")
    assert is_failed(late, T1 + GRACE)
    in_time = rec(1, 109.0, [124.0, 124.5], asked=40, status="cut")
    assert not is_failed(in_time, T1 + GRACE)


def test_short_stream_is_a_failure():
    short = rec(0, 101.0, [102.0, 103.0], asked=5, status="ok")
    assert is_failed(short, T1 + GRACE)
    short_by_usage = rec(1, 101.0, [102.0, 103.0], completion_tokens=1)
    assert is_failed(short_by_usage, T1 + GRACE)


def test_tpot_and_longest_gap_over_completed_requests():
    a = rec(0, 101.0, [102.0, 102.0, 102.0, 102.9, 102.9, 102.9])  # 2 events
    b = rec(1, 101.0, [103.0, 104.0, 106.0])
    out = summarize([a, b], mode="closed", t0=T0, t1=T1, grace=GRACE)
    # a: (102.9-102.0)/5 = 180 ms, longest gap 900; b: 1500 ms, gap 2000
    assert out["metrics"]["tpot_p50_ms"] == pytest.approx((180 + 1500) / 2)
    assert out["metrics"]["itl_max_p50_ms"] == pytest.approx((900 + 2000) / 2)


def test_closed_loop_medians_take_requests_that_completed_inside():
    started_before = rec(0, 95.0, [96.0, 101.0, 102.0])  # ends inside
    ends_after = rec(1, 108.0, [109.0, 111.0, 112.0])  # ends outside
    out = summarize([started_before, ends_after], mode="closed", t0=T0, t1=T1,
                    grace=GRACE)
    assert out["completed"] == 1 and out["attempted"] == 1
    assert out["metrics"]["tpot_p50_ms"] == pytest.approx(3000.0)
    # the open loop waits: a request due inside that ends in the wait counts
    out = summarize([ends_after], mode="open", t0=T0, t1=T1, grace=GRACE)
    assert out["completed"] == 1
    assert completed(ends_after, T1 + GRACE) and not completed(ends_after, T1)


def test_percentile_is_linear_interpolation():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile([10, 20], 90) == pytest.approx(19.0)
    assert percentile([], 50) is None
