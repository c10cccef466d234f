"""The arithmetic of each end-to-end metric on hand-made arrival stamps."""

import pytest

from benchmarks.harness.e2e import (Rec, completed, is_failed, percentile,
                                    setup_clock, stall_ms, summarize,
                                    tokens_in_window)

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


def bursts(first, n_bursts, size, every, trickle):
    """Tokens that leave in bursts of ``size`` every ``every`` seconds and
    reach the client ``trickle`` seconds apart."""
    return [first + b * every + i * trickle
            for b in range(n_bursts) for i in range(size)]


@pytest.mark.parametrize("size, every, trickle, stall, gap", [
    # bursts of 8 (a chunk): a span of 8 tokens always crosses ONE edge and
    # reads the start-to-start; the longest gap reads it LESS the trickle
    (8, 0.120, 0.000, 120.0, 120.0),
    (8, 0.120, 0.005, 120.0, 120.0 - 7 * 5.0),
    (8, 0.120, 0.010, 120.0, 120.0 - 7 * 10.0),
    # bursts of 16 (a chunk twice as long): the span crosses one edge of a
    # period twice as long, less the eight tokens it does not need
    (16, 0.240, 0.000, 240.0, 240.0),
    (16, 0.240, 0.005, 240.0 - 8 * 5.0, 240.0 - 15 * 5.0),
])
def test_stall_over_a_chunks_worth_of_tokens(size, every, trickle, stall, gap):
    r = rec(0, 101.0, bursts(102.0, 48 // size, size, every, trickle))
    assert stall_ms(r) == pytest.approx(stall)
    out = summarize([r], mode="closed", t0=T0, t1=T1, grace=GRACE)
    assert out["metrics"]["stall8_p50_ms"] == pytest.approx(stall)
    # a slower path LOWERS the longest gap and leaves the stall where it was
    assert out["metrics"]["itl_max_p50_ms"] == pytest.approx(gap)


def test_stall_needs_nine_tokens_and_is_a_median_over_those_that_have_them():
    assert stall_ms(rec(0, 101.0, [102.0 + i for i in range(8)])) is None
    assert stall_ms(rec(0, 101.0, [102.0 + i for i in range(9)])) \
        == pytest.approx(8000.0)
    short = rec(0, 101.0, [102.0, 102.5, 103.0])
    out = summarize([short], mode="closed", t0=T0, t1=T1, grace=GRACE)
    assert "stall8_p50_ms" not in out["metrics"]  # no sample: absent
    assert out["metrics"]["itl_max_p50_ms"] == pytest.approx(500.0)
    # one slow stretch in a long request decides its stall
    long = rec(1, 101.0, [102.0 + 0.01 * i for i in range(20)]
               + [103.0 + 0.01 * i for i in range(20)])
    assert stall_ms(long) == pytest.approx((103.0 - 102.19 + 0.07) * 1e3)
    out = summarize([short, long], mode="closed", t0=T0, t1=T1, grace=GRACE)
    assert out["metrics"]["stall8_p50_ms"] == pytest.approx(stall_ms(long))


def test_set_up_starts_where_the_backend_came_up():
    # process start 10.0, imports done 12.8, jax.devices() back 21.9 (a slow
    # machine: 9.1 s), window open 52.4
    c = setup_clock(10.0, 12.8, 21.9, 52.4)
    assert c == {"imports_s": pytest.approx(2.8),
                 "backend_up_s": pytest.approx(9.1),
                 "setup_s": pytest.approx(30.5)}
    # a machine whose backend takes 20 s longer reads the same set-up
    assert setup_clock(10.0, 12.8, 41.9, 72.4)["setup_s"] == pytest.approx(30.5)
    assert sum(c.values()) == pytest.approx(52.4 - 10.0)


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
