"""The unified metrics registry (core/metrics.py) + /stats derivation.

Contracts pinned here:

- typed counters/gauges/histograms register once, collect consistently,
  and the Prometheus text render PARSES as valid exposition (HELP/TYPE
  per family, well-formed sample lines, cumulative histogram buckets
  ending at +Inf with consistent _sum/_count);
- the slot engine's ``serving_snapshot()`` keeps the EXACT pre-registry
  key set (byte-compatible /stats) while the same cells render as
  /metrics series with matching values;
- remote serving snapshots (the dict riding GENERATE_RESP) flatten into
  gauges so a validator can expose engines living in other processes;
- the CI guard script rejects ad-hoc dict counters in the /stats-feeding
  modules.
"""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from tensorlink_tpu.core.metrics import (
    MetricsRegistry,
    render_prometheus,
    sanitize_metric_name,
    snapshot_gauges,
)

REPO = Path(__file__).resolve().parent.parent

# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert c == 5 and c >= 5 and c < 6 and int(c) == 5
    with pytest.raises(ValueError):
        c.inc(-1)  # counters only go up

    g = reg.gauge("t_depth", "queue depth")
    g.set(7)
    assert g.value == 7.0
    gf = reg.gauge("t_live", "live", fn=lambda: 3)
    assert gf.value == 3.0
    with pytest.raises(ValueError):
        gf.set(1)  # callback gauges are read-only

    h = reg.histogram("t_wait_seconds", "wait", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    assert h.count == 3
    assert h.sum == pytest.approx(5.55)


def test_registration_is_idempotent_and_type_stable():
    reg = MetricsRegistry()
    a = reg.counter("t_x_total", "x")
    b = reg.counter("t_x_total", "x")
    assert a is b  # same (name, labels) cell
    la = reg.counter("t_y_total", "y", cls="a")
    lb = reg.counter("t_y_total", "y", cls="b")
    assert la is not lb  # distinct label sets, one family
    with pytest.raises(ValueError):
        reg.gauge("t_x_total", "x")  # family type conflict
    with pytest.raises(ValueError):
        reg.counter("bad name", "x")
    assert sanitize_metric_name("sched_classes.batch p50") == \
        "sched_classes_batch_p50"


# ---------------------------------------------------------------------------
# Prometheus text exposition: a real mini-parser, not a substring check
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"          # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""  # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"  # more labels
    r" (-?[0-9.eE+-]+|NaN|\+Inf|-Inf)$"      # value
)


def parse_exposition(text: str) -> dict:
    """Validate Prometheus text exposition; returns family -> metadata +
    samples. Raises AssertionError on any malformed line or a sample
    whose family lacks HELP/TYPE."""
    families: dict[str, dict] = {}
    current = None
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name = rest.split(" ", 1)[0]
            families.setdefault(name, {"samples": []})["help"] = True
            current = name
        elif line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, typ = rest.split(" ", 1)
            assert typ.strip() in ("counter", "gauge", "histogram",
                                   "summary", "untyped"), line
            families.setdefault(name, {"samples": []})["type"] = typ.strip()
            current = name
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"malformed sample line: {line!r}"
            sample_name = m.group(1)
            base = re.sub(r"_(bucket|sum|count)$", "", sample_name)
            fam = sample_name if sample_name in families else base
            assert fam in families, f"sample {line!r} has no HELP/TYPE"
            assert current in (fam, sample_name), (
                f"sample {line!r} outside its family block"
            )
            families[fam]["samples"].append(line)
    for name, fam in families.items():
        assert fam.get("help") and fam.get("type"), (
            f"family {name} missing HELP or TYPE"
        )
    return families


def test_render_parses_and_histogram_is_cumulative():
    reg = MetricsRegistry()
    reg.counter("t_a_total", "a").inc(2)
    reg.gauge("t_b", "b").set(1.5)
    h = reg.histogram("t_c_seconds", "c", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(9.0)
    text = reg.render({"model": "tiny"})
    fams = parse_exposition(text)
    assert fams["t_a_total"]["type"] == "counter"
    assert any('model="tiny"' in s for s in fams["t_a_total"]["samples"])
    bucket_lines = [
        s for s in fams["t_c_seconds"]["samples"] if "_bucket" in s
    ]
    # cumulative counts, EXACT per bucket (the double-cumulation
    # regression pin): le=0.1 -> 1, le=1 -> 2, le=+Inf -> 3
    vals = [float(s.rsplit(" ", 1)[1]) for s in bucket_lines]
    assert vals == [1, 2, 3], vals
    assert any('le="+Inf"' in s for s in bucket_lines)
    count = [s for s in fams["t_c_seconds"]["samples"] if "_count" in s]
    assert float(count[0].rsplit(" ", 1)[1]) == 3


def test_render_merges_registries_one_family_header():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.counter("t_m_total", "m").inc(1)
    r2.counter("t_m_total", "m").inc(2)
    text = render_prometheus([({"model": "a"}, r1), ({"model": "b"}, r2)])
    assert text.count("# TYPE t_m_total counter") == 1
    fams = parse_exposition(text)
    assert len(fams["t_m_total"]["samples"]) == 2


def test_snapshot_gauges_flattens_remote_snapshot():
    reg = MetricsRegistry()
    snapshot_gauges(reg, {
        "admitted": 3,
        "kv_quant": "int8",          # strings skipped
        "drain_state": "serving",     # strings skipped
        "sched_classes": {"batch": {"queue_depth": 2}},
    }, prefix="tlink_engine_")
    text = reg.render({"model": "remote"})
    fams = parse_exposition(text)
    assert "tlink_engine_admitted" in fams
    assert "tlink_engine_sched_classes_batch_queue_depth" in fams
    assert not any("kv_quant" in f for f in fams)


# ---------------------------------------------------------------------------
# engine integration: /stats byte-compat + /metrics value agreement
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_engine():
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models import ModelConfig, init_params

    cfg = ModelConfig(
        family="llama", vocab_size=128, d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=64,
        dtype=jnp.float32, tie_embeddings=False,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    return GenerationEngine(
        cfg, params, seq_buckets=(8, 32), batch_buckets=(1,), max_seq_len=64
    )


# the pre-registry serving_snapshot() engine-counter key set, pinned:
# /stats consumers (operators, the bench, remote snapshot riders) see
# EXACTLY these keys whether counters live in a dict or the registry
LEGACY_ENGINE_KEYS = (
    "admitted", "evicted", "preemptions", "decode_steps",
    "slot_steps_live", "slot_steps_total", "prefill_chunks",
    "prefill_tokens", "prefill_tokens_skipped",
    "migrations_started", "migrations_completed", "migrations_failed",
    "migrations_fell_back", "migrations_adopted",
    # disaggregated prefill/decode: prefill-pool slots frozen at the
    # prefill boundary and shipped to decode-pool workers at admission
    "handoffs_started", "handoffs_completed", "handoffs_fell_back",
    # speculative decoding (spec_decode): the draft/verify families
    "spec_drafted", "spec_accepted", "spec_verify_passes", "spec_killed",
    # multi-tenant co-hosting: slots torn down for another tenant's
    # higher-ranked candidate on a shared page pool
    "preempted_cross_tenant",
    # serve-and-train (docs/TRAINING.md): live weight publishes +
    # background train steps between serving chunks
    "weights_published", "train_steps",
    # tiered prefix cache (engine/kvtier.py): host-RAM demotions,
    # host-tier promotions, and cross-replica prefix pulls
    "prefix_demotions", "host_tier_hits",
    "fleet_pulls", "fleet_pull_fallbacks",
    # flat packing's count (ROADMAP S5): rows of the packed block that
    # carried a token / rows the ragged pass computed position-wise
    "ragged_rows_valid", "ragged_rows_computed",
    # the width ladder (ROADMAP S5): packed blocks dispatched / those
    # packed at the narrow width / those that fitted it and ran wide while
    # its program was still being built
    "ragged_blocks", "ragged_blocks_narrow", "ragged_blocks_narrow_unbuilt",
    # the flat rung (ROADMAP S5): blocks whose ragged pass ran over the
    # flat row list / those that fitted it and ran the full program
    "ragged_blocks_flat", "ragged_blocks_flat_unbuilt",
    # the paged kernels' live-span walk (ROADMAP S7): pages walked /
    # page slots of the same passes
    "attn_pages_live", "attn_pages_capacity",
    # the walk's two heights: slots with a row in the ragged pass / those
    # with exactly one (they walk the short row block)
    "ragged_slots_live", "ragged_slots_single",
    # the tensor-parallel step's activation gathers (0 at tp = 1)
    "tp_gather_bytes", "tp_gather_calls",
    # a patterned model's step (engine/latent.py): routing and selection
    # as the program counted them, window pages from the packed contexts
    # (0 for every other model)
    "moe_rows_routed_local", "moe_rows_computed", "moe_rows_busiest_expert",
    "moe_experts_touched", "moe_experts_held",
    "moe_rows_in_group", "moe_rows_valid",
    "sparse_positions_kept", "sparse_positions_scored",
    "window_pages_walked", "window_pages_context",
    "latent_rows_read", "latent_rows_capacity",
    # block-sparse GQA and lightning layers (engine/sala.py): the step's
    # own counts, and what admission did about the slots' states
    "sparse_blocks_kept", "sparse_blocks_visible", "sparse_rows_dense",
    "lightning_rows",
    "state_admissions", "state_snapshots_taken", "state_snapshots_restored",
    "state_snapshots_skipped", "state_rows_replayed",
    # ... and about the rings of a model whose window layers hold one
    "window_admissions", "window_snapshots_taken",
    "window_snapshots_restored", "window_snapshots_skipped",
    "window_rows_replayed",
    # ... and about the tails of a model with short-convolution layers
    "conv_admissions", "conv_snapshots_taken", "conv_snapshots_restored",
    "conv_snapshots_skipped", "conv_rows_replayed",
    # the sampling epilogue (ROADMAP S1): calls, those that sorted, and
    # the verify walk's length against the rows the program holds
    "sampler_calls", "sampler_calls_sampled",
    "verify_rows_walked", "verify_rows_capacity",
    # the anatomy of a chunk: cumulative host microseconds per phase
    "chunk_us_between", "chunk_us_admit", "chunk_us_pack",
    "chunk_us_dispatch", "chunk_us_wait", "chunk_us_drain",
    "chunk_us_deliver", "chunk_us_post",
    # the stream stage: its microseconds (inside wait or deliver) and the
    # tokens that left under a dispatched step / with none in flight
    "chunk_us_stream", "stream_tokens_overlapped", "stream_tokens_flushed",
    # the host-device boundary: arrays a chunk placed and fetched (2)
    "chunk_host_arrays",
    # slot binds and clears that rode a chunk's control buffer / device
    # calls the admission and retirement path still made
    "slot_binds_packed", "admit_device_calls",
    # intake ahead: requests submitted / of those inside a chunk's wait,
    # admissions an ahead round prepared, the intake's host microseconds
    "submitted", "submitted_ahead", "admitted_ahead", "chunk_us_intake",
    # the inside of a chunk's wait: what of its stream stage and of its
    # intake lay behind the driver's first sight of the result ready
    "chunk_us_late_stream", "chunk_us_late_intake",
)
PHASES = ("between", "admit", "pack", "dispatch", "wait", "drain",
          "deliver", "post")


def test_engine_stats_keys_are_byte_compatible(tiny_engine):
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    ce = ContinuousEngine(
        tiny_engine, max_slots=2, page_size=8, chunk_steps=4
    )
    assert tuple(ce.stats.keys()) == LEGACY_ENGINE_KEYS
    r = ce.submit([1, 2, 3], max_new_tokens=4, seed=1)
    ce.run_until_idle()
    assert r.finished
    snap = ce.serving_snapshot()
    for k in LEGACY_ENGINE_KEYS:
        assert k in snap, k
    assert snap["admitted"] == 1 and snap["evicted"] == 1
    # scheduler side keys unchanged too
    assert snap["sched_policy"] == "slo"
    for cls in ("interactive", "batch", "best_effort"):
        sub = snap["sched_classes"][cls]
        for key in ("queue_depth", "admitted", "rejected", "preempted",
                    "queue_wait_ms_p50", "queue_wait_ms_p95",
                    "ttft_ms_p50", "ttft_ms_p95"):
            assert key in sub, (cls, key)
    ce.close()


def test_engine_metrics_render_matches_stats(tiny_engine):
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    ce = ContinuousEngine(
        tiny_engine, max_slots=2, page_size=8, chunk_steps=4
    )
    for seed in (1, 2):
        ce.submit([1, 2, seed], max_new_tokens=3, seed=seed)
    ce.run_until_idle()
    text = ce.metrics.render({"model": "tiny"})
    fams = parse_exposition(text)
    admitted = [
        s for s in fams["tlink_engine_admitted_total"]["samples"]
    ]
    assert float(admitted[0].rsplit(" ", 1)[1]) == ce.stats["admitted"] == 2
    # scheduler histograms ride the same registry
    assert fams["tlink_sched_ttft_seconds"]["type"] == "histogram"
    # callback gauges render live values
    free = [s for s in fams["tlink_engine_kv_pages_free"]["samples"]]
    assert float(free[0].rsplit(" ", 1)[1]) == ce.alloc.n_free
    ce.close()


def test_chunk_phase_counters_grow_by_the_records_values(tiny_engine):
    """Each chunk adds its record's ``<phase>_ms`` to ``chunk_us_<phase>``
    (integers, microseconds), so a window reads a phase as a difference
    of two /stats reads whatever the flight recorder's length; the same
    cells render at /metrics."""
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    ce = ContinuousEngine(
        tiny_engine, max_slots=2, page_size=8, chunk_steps=4
    )
    ce.submit([1, 2, 3], max_new_tokens=10, seed=1)
    ce.step_chunk()
    s0, n0 = ce.stats, len(ce.recorder)
    ce.run_until_idle()
    s1, recs = ce.stats, ce.recorder.records()[n0:]
    assert len(recs) >= 2
    for ph in PHASES + ("late_stream", "late_intake"):
        grew = s1[f"chunk_us_{ph}"] - s0[f"chunk_us_{ph}"]
        assert isinstance(grew, int)
        assert grew == round(sum(r[f"{ph}_ms"] for r in recs) * 1e3), ph
    assert s1["chunk_us_wait"] > s0["chunk_us_wait"]
    # the inside of wait: the fetch is marked like a phase, and with the
    # stream stage before it lies inside wait (the last chunk's record
    # also holds the stage that follows it with no step in flight)
    for r in recs:
        assert 0.0 < r["fetch_ms"] <= r["wait_ms"]
        assert 0.0 <= r["late_stream_ms"] <= r["stream_ms"] + 0.1
        late = r["late_stream_ms"] + r["late_intake_ms"]
        assert late <= r["late_max_ms"]  # the lower bound and the upper
        assert r["late_max_ms"] + r["fetch_ms"] <= r["wait_ms"] + 2e-3
    for r in recs[:-1]:
        assert r["fetch_ms"] + r["stream_ms"] <= r["wait_ms"] + 1e-3
    fams = parse_exposition(ce.metrics.render({"model": "tiny"}))
    for ph in ("wait", "late_stream", "late_intake"):
        sample = fams[f"tlink_engine_chunk_us_{ph}_total"]["samples"][0]
        assert float(sample.rsplit(" ", 1)[1]) == s1[f"chunk_us_{ph}"]
    assert "tlink_engine_ragged_rows_computed_total" in fams
    snap = ce.serving_snapshot()
    for ph in ("deliver", "late_stream", "late_intake"):
        assert snap[f"chunk_us_{ph}"] == s1[f"chunk_us_{ph}"]
    assert "host_gap_ms" not in snap
    ce.close()


def test_page_counters_follow_the_slot_contexts(tiny_engine):
    """The live-span walk's count (ROADMAP S7): every attention pass of a
    chunk adds slots x pages-per-slot to ``attn_pages_capacity``; to
    ``attn_pages_live`` the ragged pass adds ceil(context / page) of every
    slot with a row, and each continuation step that of the emitting
    slots: a slot still mid-prompt rides the ragged pass only."""
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    S, C, page = 3, 16, 8
    ce = ContinuousEngine(
        tiny_engine, max_slots=S, page_size=page, chunk_steps=3,
        prefill_chunk=C,
    )
    n_pp = ce.cache.pages_per_slot
    ce.submit(list(range(1, 21)), max_new_tokens=12, seed=1)  # 20 > C
    ce.submit([6, 7, 8], max_new_tokens=12, seed=2)
    ce.step_chunk()
    # slot A: 16 of its 20 prompt tokens, no emit: 2 pages, one pass.
    # slot B: its whole prompt, emits: 1 page in each of the 3 passes
    s = ce.stats
    assert s["decode_steps"] == 3
    assert s["attn_pages_capacity"] == 3 * S * n_pp
    assert s["attn_pages_live"] == 2 + 3 * 1
    ce.step_chunk()
    # slot A: its last 4 prompt tokens, context 20: 3 pages, emits.
    # slot B: decode row at context 3 + 3: 1 page
    d = {k: ce.stats[k] - s[k] for k in s}
    assert d["attn_pages_capacity"] == 3 * S * n_pp
    assert d["attn_pages_live"] == 3 * (3 + 1)
    ce.step_chunk(admit_only=True)  # dispatches nothing: counts nothing
    assert ce.stats["attn_pages_capacity"] == 6 * S * n_pp
    fams = parse_exposition(ce.metrics.render({"model": "tiny"}))
    assert "tlink_engine_attn_pages_live_total" in fams
    ce.run_until_idle()
    ce.close()


def test_slot_counters_tell_single_rows_from_grants(tiny_engine):
    """How often the walk's short row block engages: a chunk adds the
    slots with a row in its ragged pass to ``ragged_slots_live`` and
    those with exactly ONE to ``ragged_slots_single`` (from the
    ``n_valid`` the host packed): seven decoding slots beside one grant
    read 7 of 8."""
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    ce = ContinuousEngine(
        tiny_engine, max_slots=8, page_size=8, chunk_steps=2,
        prefill_chunk=16,
    )
    for i in range(7):
        ce.submit([3 + i, 4, 5], max_new_tokens=12, seed=i)
    ce.step_chunk()  # seven prompts of three rows each: none single
    s = dict(ce.stats)
    assert (s["ragged_slots_live"], s["ragged_slots_single"]) == (7, 0)
    ce.submit(list(range(1, 11)), max_new_tokens=4, seed=9)
    ce.step_chunk()  # seven decode rows and a grant of ten
    d = {k: ce.stats[k] - s[k] for k in s}
    assert (d["ragged_slots_live"], d["ragged_slots_single"]) == (8, 7)
    ce.step_chunk(admit_only=True)  # dispatches nothing: counts nothing
    assert ce.stats["ragged_slots_live"] == 15
    fams = parse_exposition(ce.metrics.render({"model": "tiny"}))
    assert "tlink_engine_ragged_slots_single_total" in fams
    ce.run_until_idle()
    ce.close()


def test_row_counters_count_valid_and_computed_rows(tiny_engine):
    """ROADMAP S5's count: a chunk adds slots x the width its block was
    packed at to ``ragged_rows_computed`` whatever is live (a page where
    no grant is longer, ``prefill_chunk`` otherwise), and only the rows
    that carried a token to ``ragged_rows_valid``: the prompt's tokens in
    the prefill chunk, one row per live slot in a decode-only chunk.
    ``ragged_blocks`` / ``ragged_blocks_narrow`` count the blocks."""
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    S, C = 3, 16
    ce = ContinuousEngine(
        tiny_engine, max_slots=S, page_size=8, chunk_steps=2,
        prefill_chunk=C,
    )
    N = ce.block_widths[0]
    assert ce.block_widths == (8, C)
    ce.submit(list(range(1, 10)), max_new_tokens=8, seed=1)
    ce.submit([6, 7, 8], max_new_tokens=8, seed=2)
    ce.step_chunk()  # both prompts prefill in one block: 9 rows > a page
    s = ce.stats
    assert s["ragged_rows_computed"] == S * C
    assert s["ragged_rows_valid"] == 9 + 3
    assert (s["ragged_blocks"], s["ragged_blocks_narrow"]) == (1, 0)
    ce.step_chunk()  # decode only: one row per live slot, a page wide
    assert ce.live_slots == 2
    d = {k: ce.stats[k] - s[k] for k in s}
    assert d["ragged_rows_computed"] == S * N
    assert d["ragged_rows_valid"] == 2
    assert (d["ragged_blocks"], d["ragged_blocks_narrow"]) == (1, 1)
    ce.step_chunk(admit_only=True)  # dispatches nothing: counts nothing
    assert ce.stats["ragged_rows_computed"] == S * (C + N)
    assert ce.stats["ragged_blocks"] == 2
    assert [r["block_rows"] for r in ce.recorder.records()] == [C, N]
    ce.run_until_idle()
    fams = parse_exposition(ce.metrics.render({"model": "tiny"}))
    assert ce.stats["ragged_blocks_narrow_unbuilt"] == 0  # nothing is built behind
    for name in ("ragged_blocks", "ragged_blocks_narrow",
                 "ragged_blocks_narrow_unbuilt"):
        sample = fams[f"tlink_engine_{name}_total"]["samples"][0]
        assert float(sample.rsplit(" ", 1)[1]) == ce.stats[name]
    ce.close()


def _counter_metric_files() -> list[Path]:
    import json

    files = sorted((REPO / "benchmarks" / "layer_metrics").glob("*.json"))
    return [f for f in files
            if json.loads(f.read_text())["kind"].startswith("stats_")]


@pytest.mark.parametrize(
    "path", _counter_metric_files(), ids=lambda p: p.name[:-len(".json")])
def test_a_counter_metric_reads_keys_the_engine_reports(path):
    """Each of the benchmark's counter metrics (``layer_metrics/*.json`` of
    a ``stats_*`` kind) names counters of ``_ENGINE_COUNTERS`` or gauges
    of ``serving_snapshot()``: a counter renamed here would leave its
    metric silent there, and the benchmark's files are not this repo's
    tests' to see. ``narrow_block_share(.sessions)`` reads the width
    ladder's ``ragged_blocks_narrow`` over ``ragged_blocks``."""
    import json

    from tensorlink_tpu.engine import continuous

    spec = json.loads(path.read_text())
    keys = [spec["key"]] if "key" in spec else spec["num"] + spec["den"]
    counters = {c[0] for c in continuous._ENGINE_COUNTERS}
    gauges = {"latent_pool_bytes", "weights_bytes_device_max",
              "step_build_ms", "step_build_waited_ms", "state_pool_bytes",
              "window_pool_bytes", "conv_pool_bytes",
              "prefix_evictions"}  # serving_snapshot()'s own
    assert keys and set(keys) <= counters | gauges, set(keys) - counters
    if path.name.startswith("narrow_block_share"):
        assert (spec["num"], spec["den"], spec["scale"]) == (
            ["ragged_blocks_narrow"], ["ragged_blocks"], 100)


def _benchmark() -> dict:
    import json

    return json.loads((REPO / "BENCHMARK.json").read_text())


def _reported_by(bench: dict) -> dict:
    """``{cell: end-to-end metrics it reports}`` (an entry without
    ``workloads`` is every cell's)."""
    cells = [w["name"] for w in bench["workloads"]]
    return {c: {m["name"] for m in bench["end_to_end"]
                if c in m.get("workloads", cells)} for c in cells}


def test_every_end_to_end_entry_names_cells_that_exist():
    """A judged metric's ``workloads`` are cells of ``BENCHMARK.json``, and
    every cell is judged by a latency or a rate besides ``setup_s``: a new
    cell left out of the judged lists would be measured by set-up alone."""
    bench = _benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    for cell, names in _reported_by(bench).items():
        assert "setup_s" in names and names - {"setup_s"}, cell


@pytest.mark.parametrize(
    "metric", _benchmark()["per_layer"], ids=lambda m: m["name"])
def test_a_per_layer_metric_moves_what_its_cells_report(metric):
    """``moves`` names an end-to-end metric, and each cell of the metric's
    ``workloads`` (every cell, without the key) reports it: what refuses
    a new cell wired into the wrong lists."""
    bench = _benchmark()
    reported = _reported_by(bench)
    assert metric["moves"] in {m["name"] for m in bench["end_to_end"]}
    for cell in metric.get("workloads", list(reported)):
        assert cell in reported, (metric["name"], cell)
        assert metric["moves"] in reported[cell], (metric["name"], cell)


def _span_metric_files() -> list[Path]:
    import json

    files = sorted((REPO / "benchmarks" / "layer_metrics").glob("*.json"))
    kinds = {f: json.loads(f.read_text())["kind"] for f in files}
    return [f for f, kind in kinds.items()
            if kind.startswith("span_") or kind.endswith("_by_spans")]


@pytest.mark.parametrize(
    "path", _span_metric_files(), ids=lambda p: p.name[:-len(".json")])
def test_a_span_metric_selects_spans_the_program_records(path):
    """Each of the benchmark's span metrics (``layer_metrics/*.json`` of a
    ``span_*`` kind, and the idle share read against spans) selects names
    of ``core/trace.py::PATH_SPANS``, the request path's spans as the
    recording sites name them (tests/test_trace.py holds that one
    streamed request yields every one): a span renamed here would leave
    its metric silent there."""
    import json

    from tensorlink_tpu.core import trace

    spec = json.loads(path.read_text())
    minus = spec.get("minus", [])
    names = [spec[k] for k in ("span", "from", "to") if k in spec]
    names += [minus] if isinstance(minus, str) else list(minus)
    assert names and set(names) <= set(trace.PATH_SPANS), (
        set(names) - set(trace.PATH_SPANS))
    if spec["kind"] == "span_residual_quantile":
        # the seven that lie end to end inside http_first_byte
        assert spec["span"] == trace.HTTP_FIRST_BYTE
        assert minus == [trace.API_IN, trace.PREPARE, trace.HOP_IN,
                         trace.WORK_WAIT, trace.SUBMIT, "first_token",
                         trace.TOKEN_OUT]


def test_the_recording_sites_use_the_path_spans_names():
    """The new spans are recorded under the constants of
    ``core/trace.py``, each in the module the table of docs/SERVING.md
    "Telemetry" names, and the engine's literals are names of the tuple."""
    import re

    from tensorlink_tpu.core import trace

    pkg = REPO / "tensorlink_tpu"
    for const, mod in (("API_IN", "api/server.py"),
                       ("HTTP_FIRST_BYTE", "api/server.py"),
                       ("TOKEN_OUT", "api/server.py"),
                       ("PREPARE", "ml/validator.py"),
                       ("HOP_IN", "ml/worker.py"),
                       ("WORK_WAIT", "ml/worker.py"),
                       ("SUBMIT", "ml/worker.py")):
        assert getattr(trace, const) in trace.PATH_SPANS
        assert re.search(rf"\b{const}\b", (pkg / mod).read_text()), (const, mod)
    engine = (pkg / "engine" / "continuous.py").read_text()
    recorded = set(re.findall(r'self\._trace\(\s*\w+, "(\w+)"', engine))
    assert set(trace.ENGINE_SPAN_PARENT) <= recorded
    assert set(trace.ENGINE_SPAN_PARENT) <= set(trace.PATH_SPANS)
    assert set(trace.SPANS_NAMED_AHEAD) <= set(trace.ENGINE_SPAN_PARENT.values())


def test_adhoc_counter_guard_is_tl106(tmp_path):
    """The CI guard against `self.stats` dict counters is tlint's TL106
    now (the old scripts/check_adhoc_counters.sh grep): the /stats-
    feeding modules it watched stay clean, and the rule really catches
    the pre-PR-10 idiom."""
    from tools import tlint

    rules = {"TL106": tlint.RULES["TL106"]}
    for mod in ("engine/continuous.py", "engine/scheduler.py",
                "ml/worker.py", "ml/batching.py"):
        src = (REPO / "tensorlink_tpu" / mod).read_text()
        got, _ = tlint.check_source(src, f"tensorlink_tpu/{mod}", rules)
        assert got == [], (mod, got)
    # negative: the rule really catches the old idiom
    probe = (
        "class B:\n"
        "    def __init__(self):\n"
        "        self.stats = {'admitted': 0}\n"
        "    def admit(self):\n"
        "        self.stats['admitted'] += 1\n"
    )
    got, _ = tlint.check_source(probe, "tensorlink_tpu/engine/x.py", rules)
    assert {v.line for v in got} == {3, 5}, got


def test_batcher_exposes_registry(tiny_engine):
    from tensorlink_tpu.ml.batching import ContinuousBatcher

    cb = ContinuousBatcher(
        engine=tiny_engine, eos_ids=[], max_slots=2, page_size=8,
        chunk_steps=4,
    )
    try:
        assert cb.metrics_registry() is cb._cont.metrics
    finally:
        cb.close()
