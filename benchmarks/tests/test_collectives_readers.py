"""The readers PR 26 added for a four-chip cell: collectives matched by
their own name, exposure by bisection (checked against the harness's scan),
and the gathered bytes from shapes."""

import json
import random

from benchmarks.harness import cluster, gather_bytes, xplane
from benchmarks.harness.obs import Obs
from benchmarks.harness.spec import BENCH_DIR
from benchmarks.readers import stats_value, trace_exposed_own, trace_gather_rate

GATHER = ("%all-gather.54 = bf16[8,128,3584]{2,1,0} all-gather(bf16[8,128,896] "
          "%reshape.1), channel_id=1, dimensions={2}")
CONSUMER = ("%fusion.556 = bf16[8,128,896]{2,1,0} fusion(bf16[8,128,3584] "
            "%all-gather.54, bf16[28,3584,896] %gte.4), kind=kLoop")


def trace_of(ops):
    dev = xplane.DeviceTrace(ordinal=0, ops=ops)
    xplane.self_times(dev.ops)
    dev.busy = xplane.union([(o.start, o.end) for o in dev.ops])
    return xplane.Trace(devices=[dev], host=[], t0=0.0, t1=10.0)


def test_a_collective_is_matched_by_its_own_name_only():
    tr = trace_of([
        xplane.Op(GATHER, 1.0, 2.0),          # alone: exposed
        xplane.Op(CONSUMER, 2.0, 4.0),        # names the gather as operand
        xplane.Op(GATHER, 5.0, 6.0),          # half under another operation
        xplane.Op("%copy.3 = s8[4] copy(s8[4] %p)", 5.5, 7.0),
    ])
    assert trace_exposed_own.exposed_seconds(tr, ["^all-gather"]) == 1.5
    # the anchored pattern of collective_share.json, against the unanchored
    assert tr.op_seconds(["^%?all-gather"]) == 2.0
    assert tr.op_seconds(["all-gather"]) == 4.0


def test_bisection_agrees_with_the_scan():
    rng = random.Random(3)
    other = xplane.union([(a, a + rng.random()) for a in
                          (rng.uniform(0, 100) for _ in range(400))])
    starts, ends = [x for x, _ in other], [y for _, y in other]
    cum = [0.0]
    for x, y in other:
        cum.append(cum[-1] + y - x)
    for _ in range(500):
        a = rng.uniform(-1, 101)
        b = a + rng.uniform(0, 5)
        want = xplane.overlap(other, a, b)
        got = trace_exposed_own.covered(starts, ends, cum, a, b)
        assert abs(got - want) < 1e-9, (a, b)


def test_gathered_bytes_follow_the_shapes():
    hf = json.loads((BENCH_DIR / "configs" / "qwen2p5-7b-tp4.json").read_text())
    sizes = gather_bytes.sizes_from_config(
        hf, cluster.ml_config(hf["deployment"]))
    assert sizes["tp"] == 4 and sizes["verify_rows"] == 9
    row = 28 * (3584 * 3 + 18944) * 2 * 3 / 4   # a row through the layers
    head = 152064 * 2 * 3 / 4
    one = gather_bytes.tp_gather_bytes([{"decode_steps": 1}], sizes)
    assert one == row * 8 * 128 + head * 8 * 9
    eight = gather_bytes.tp_gather_bytes([{"decode_steps": 8}], sizes)
    assert eight - one == 7 * 8 * (row + head)
    # a prefill-only chunk (no decode step counted) still ran its pass
    assert gather_bytes.tp_gather_bytes([{"decode_steps": 0}], sizes) == one
    assert gather_bytes.tp_gather_bytes([{"decode_steps": 8}],
                                        {**sizes, "tp": 1}) == 0.0


def test_readers_give_nothing_where_the_program_reports_nothing():
    obs = Obs(mode="closed", recs=[], t0=0, t1=1, grace=0, stats0={},
              stats1={"decode_steps": 5})
    spec = {"key": "weights_bytes_device_max", "scale": 1e-9}
    assert stats_value.read(obs, spec) is None  # the parent has no gauge
    obs.stats1["weights_bytes_device_max"] = 4_625_000_000
    assert stats_value.read(obs, spec) == 4.625
    rate = {"patterns": ["^%?all-gather"], "bytes_fn": "tp_gather_bytes",
            "config": "qwen2p5-7b-tp4"}
    assert trace_gather_rate.read(obs, rate) is None  # no trace
    obs.trace = trace_of([xplane.Op(CONSUMER, 0.0, 1.0)])
    obs.chunks = [{"decode_steps": 8}]
    assert trace_gather_rate.read(obs, rate) is None  # one chip: no gather
    obs.trace = trace_of([xplane.Op(GATHER, 0.0, 0.5)])
    assert trace_gather_rate.read(obs, rate) > 0
