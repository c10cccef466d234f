"""Every bench leg executes end-to-end on CPU before any TPU window.

VERDICT r4 weak #2: the batch8 / flash / int8 legs were ``on_tpu``-gated
and had never run anywhere — their first-ever execution would have burned
part of a scarce TPU session on possible leg bugs.
``TLTPU_BENCH_FORCE_ALL_LEGS=1`` runs them on CPU at toy shapes; this
smoke drives the whole harness that way and asserts every leg produced a
number (not an ``*_error`` / ``*_skipped`` entry)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow  # the full forced-all-legs bench child runs ~8 min —
# over half the tier-1 wall budget, which truncated the suite's TAIL
# (~60 tests) on slow hosts. CI's unit job runs this file with no
# 'not slow' filter, so every leg still executes on every push.
def test_bench_all_legs_cpu():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["TLTPU_BENCH_FORCE_ALL_LEGS"] = "1"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=1700, env=env, cwd=REPO,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, p.stdout  # the contract: ONE JSON line
    out = json.loads(lines[0])
    assert out["value"] > 0
    extra = out["extra"]
    errors = {k: v for k, v in extra.items()
              if k.endswith("_error") or k.endswith("_skipped")}
    assert not errors, errors
    # every leg produced its number
    for key in ("batch8_toks_s", "batch8_speedup_vs_b1",
                "prefill2k_einsum_ms", "prefill2k_flash_ms",
                "lookahead_nonrep_vs_b1", "spec_trained_speedup",
                "spec_trained_tokens_per_verify_pass",
                # continuous speculative decoding (draft/verify as
                # ragged slots) + its TTFT decomposition + the
                # adversarial kill-switch leg
                "spec_decode_speedup", "spec_tokens_per_pass",
                "spec_plain_toks_s", "spec_decode_toks_s",
                "spec_streams_exact", "spec_adversarial_speedup",
                "spec_adversarial_killed",
                "spec_queue_ms", "spec_prefill_ms",
                "spec_first_decode_ms", "spec_ttft_trace_ms",
                "int8_toks_s", "int8_vs_bf16_roofline",
                "prefix_skipped_prefill_tokens", "prefix_hit_rate",
                "prefix_ttft_on_ms_p50", "prefix_ttft_off_ms_p50",
                # tiered prefix cache: Zipf session flood past HBM
                # capacity — destroy-on-evict vs host-tier vs
                # host-tier + fleet-pull, skipped tokens and TTFT per
                # rung plus the recovered-fraction acceptance bar
                "tier_sessions", "tier_revisit_tokens",
                "tier_skipped_destroy", "tier_skipped_host",
                "tier_skipped_fleet", "tier_fleet_pulls",
                "tier_ttft_p50_destroy_ms", "tier_ttft_p50_host_ms",
                "tier_ttft_p50_fleet_ms",
                "tier_recovered_frac_host", "tier_recovered_frac",
                "sched_interactive_ttft_ms_p50", "sched_batch_ttft_ms_p50",
                "sched_unloaded_ttft_ms_p50",
                "sched_fcfs_interactive_ttft_ms_p50",
                "sched_preemptions", "sched_rejected", "sched_starved",
                "ragged_itl_ratio", "ragged_steady_itl_ms",
                "ragged_during_prefill_itl_ms",
                "kv_slots_ratio", "kv_residency_ratio",
                "kv_int8_slots", "kv_int8_resident_pages",
                # packed int4 pages (byte-matched vs int8) + the
                # two-models-one-pool co-tenancy leg
                "kv_int4_slots", "kv_int4_slots_ratio",
                "kv_int4_residency_ratio",
                "cotenancy_served", "cotenancy_cross_preemptions",
                "cotenancy_conservation_ok",
                "migration_resume_ms", "migration_reprefill_resume_ms",
                "migration_resume_speedup",
                # disaggregated prefill/decode pools: interactive ITL
                # isolation under a long-prompt flood + the per-phase
                # TTFT decomposition with the handoff span
                "disagg_handoffs", "disagg_streams_exact",
                "disagg_steady_itl_ms", "disagg_single_pool_itl_ms",
                "disagg_decode_pool_itl_ms",
                "disagg_single_pool_itl_ratio", "disagg_itl_ratio",
                "disagg_queue_ms", "disagg_prefill_ms",
                "disagg_handoff_ms", "disagg_first_decode_ms",
                "disagg_ttft_trace_ms", "disagg_ttft_wall_ms",
                # fleet serving: 1 vs N replicas behind the router under
                # a Zipf-prefix mixed-class flood, with a churned leg
                # (replica joins, rolling deploy, replica kill)
                "fleet_replicas", "fleet_tokps_1", "fleet_tokps_n",
                "fleet_scaling", "fleet_dropped", "fleet_streams_exact",
                "fleet_ttft_p95_1_ms", "fleet_ttft_p95_n_ms",
                "fleet_churn_ttft_p95_ms", "fleet_deploys",
                "fleet_route_cache_tokens",
                # trace-derived TTFT decompositions (core/trace.py) on the
                # serving, sched, and migration legs + the tracing
                # overhead bound
                "serving_queue_ms", "serving_prefill_ms",
                "serving_first_decode_ms", "serving_ttft_trace_ms",
                "serving_cont_ttft_ms_mean", "serving_trace_overhead_pct",
                "sched_queue_ms", "sched_prefill_ms",
                "sched_first_decode_ms", "sched_ttft_trace_ms",
                "migration_queue_ms", "migration_prefill_ms",
                "migration_first_decode_ms", "migration_ttft_trace_ms",
                "train_mfu", "train_step_s",
                # ZeRO-1 sharded train step: unsharded vs zero1 at a
                # matched global batch (bitwise pin + 1/dp opt bytes)
                "zero1_dp", "zero1_bitwise_identical", "zero1_step_ms",
                "zero1_unsharded_step_ms", "zero1_opt_state_ratio",
                "zero1_opt_bytes_per_replica",
                # tensor-parallel serving: 1-way vs 2-way on the same
                # model (bitwise streams, per-chip KV bytes, gather bill)
                "tp_degree", "tp_streams_bitwise_identical",
                "tp_kv_bytes_per_chip", "tp_page_capacity_gain",
                "tp_itl_ms", "tp_collective_bytes_per_token",
                # host-gap budget on the decode critical path
                "serving_host_gap_ms",
                # serve-and-train: background train steps + live weight
                # publishes against a serving engine
                "serve_train_steps", "serve_train_publishes",
                "serve_train_weights_version", "serve_train_dropped",
                "serve_train_stream_exact_len", "serve_train_itl_ms",
                "serve_train_baseline_itl_ms", "serve_train_itl_ratio",
                "serve_train_bg_steps_during_itl",
                "serve_train_publish_new_programs"):
        assert key in extra, (key, extra)
    # the TTFT decomposition contract: the engine records queue_wait,
    # prefill, and first_decode CONTIGUOUSLY, so the parts sum to the
    # trace's TTFT (exactly, modulo per-part rounding), and the trace
    # TTFT agrees with the leg's externally measured mean TTFT up to
    # batcher-dispatch overhead (generous bound: wall-clock CI hosts)
    for leg in ("serving", "sched", "migration", "spec"):
        q = extra[f"{leg}_queue_ms"]
        p = extra[f"{leg}_prefill_ms"]
        f = extra[f"{leg}_first_decode_ms"]
        total = extra[f"{leg}_ttft_trace_ms"]
        assert total > 0, (leg, total)
        assert abs((q + p + f) - total) <= 0.05, (leg, q, p, f, total)
    mean = extra["serving_cont_ttft_ms_mean"]
    trace = extra["serving_ttft_trace_ms"]
    assert abs(trace - mean) <= max(0.6 * mean, 40.0), (trace, mean)
    # tracing must not slow the serving step: disabled-vs-enabled chunk
    # cost within 2% (min-of-3 interleaved; negative = host noise)
    assert extra["serving_trace_overhead_pct"] <= 2.0, (
        extra["serving_trace_overhead_pct"]
    )
    # the unified ragged step's seam removal: decode-slot inter-token
    # latency while a co-resident prefill is in flight must be ~flat vs
    # (occupancy-matched) decode-only steady state. Noise-tolerant bound
    # (wall-clock on a possibly-contended CPU host; the measured ratio
    # is ~1.0, and the DETERMINISTIC pins of the same behavior — zero
    # stalls, bit-exact streams, one compiled program — live in
    # tests/test_continuous.py)
    assert extra["ragged_itl_ratio"] <= 3.0, extra["ragged_itl_ratio"]
    # the quantized-KV capacity bar: at a fixed page-pool byte budget the
    # int8 engine must ADMIT >=1.8x the slots and HOLD >=1.8x the
    # prefix-cache resident pages of the fp engine. These are structural
    # counts (real admissions on real pools, conservation-checked inside
    # the leg), not wall-clock — deterministic on CPU, and the exact
    # claim the TPU capacity math stands on (bf16: 2*hd vs hd+4 bytes
    # per position-head = 1.94x at hd=128)
    assert extra["kv_slots_ratio"] >= 1.8, extra["kv_slots_ratio"]
    assert extra["kv_residency_ratio"] >= 1.8, extra["kv_residency_ratio"]
    # the int4 density step: at a byte-matched budget the PACKED pool
    # must admit >=1.8x the slots of the INT8 pool (page bytes hd/2+4 vs
    # hd+4 — 1.89x at the bench's hd=64, 1.94x at hd=128, and the ratio
    # is dtype-independent so it transfers to bf16 unchanged). Same
    # structural, conservation-checked protocol as the int8 leg.
    assert extra["kv_int4_slots_ratio"] >= 1.8, extra["kv_int4_slots_ratio"]
    assert extra["kv_int4_residency_ratio"] >= 1.8, (
        extra["kv_int4_residency_ratio"]
    )
    # co-tenancy (two models, ONE page pool, per-model quotas): every
    # request of both tenants served, per-tenant page conservation held
    # at every chunk boundary (checked in-leg — a cross-tenant leak
    # fails the bench run itself), quotas never exceeded
    assert extra["cotenancy_conservation_ok"] is True
    assert extra["cotenancy_served"] == 12, extra["cotenancy_served"]
    # the disaggregation bars (ROADMAP item 1): every interactive stream
    # bit-identical to its single-pool run with every handoff completed
    # (deterministic), and decode-pool ITL during the long-prompt flood
    # ~flat vs decode-only steady state (noise-tolerant absolute bound,
    # mirroring ragged_itl_ratio). The single-pool-degrades contrast is
    # asserted IN-LEG on TPU rounds only — the CPU reference step
    # computes the full fixed-shape packed block whether its rows carry
    # the flood or padding, so both ratios sit ~1.0 here by construction
    # (disagg_note documents this; the ragged leg's note is the same
    # property). The TTFT decomposition gains the handoff leg: queue +
    # prefill + handoff + first_decode sum to the trace TTFT exactly
    # (per-part rounding), and the trace TTFT agrees with the externally
    # measured wall TTFT (source submit → destination first token) up to
    # the in-loop resubmit gap.
    assert extra["disagg_streams_exact"] is True
    assert extra["disagg_handoffs"] >= 3, extra["disagg_handoffs"]
    assert extra["disagg_itl_ratio"] <= 3.0, extra["disagg_itl_ratio"]
    assert extra["disagg_single_pool_itl_ratio"] > 0
    dz_sum = (extra["disagg_queue_ms"] + extra["disagg_prefill_ms"]
              + extra["disagg_handoff_ms"] + extra["disagg_first_decode_ms"])
    assert extra["disagg_ttft_trace_ms"] > 0
    assert abs(dz_sum - extra["disagg_ttft_trace_ms"]) <= 0.05, (
        dz_sum, extra["disagg_ttft_trace_ms"]
    )
    wall = extra["disagg_ttft_wall_ms"]
    assert abs(extra["disagg_ttft_trace_ms"] - wall) <= max(
        0.25 * wall, 20.0
    ), (extra["disagg_ttft_trace_ms"], wall)
    # the fleet leg's bars (ROADMAP item 2): the DETERMINISTIC ones —
    # zero dropped streams across the clean AND churned floods (the
    # churned leg joins a replica, rolling-deploys one, and KILLS one
    # mid-flood), every stream bit-identical to its solo run, at least
    # one zero-drop rolling deploy landed, and the router really placed
    # by prefix-cache affinity (digest-matched prompt tokens routed).
    # The scaling/TTFT PAIR is wall-clock and CPU-meaningless (N
    # replicas share one core — fleet_note documents it; the >=0.6*N
    # scaling and flat-TTFT bars arm in-leg on TPU rounds only).
    assert extra["fleet_dropped"] == 0, extra["fleet_dropped"]
    assert extra["fleet_streams_exact"] is True
    assert extra["fleet_deploys"] >= 1, extra["fleet_deploys"]
    assert extra["fleet_route_cache_tokens"] > 0
    assert extra["fleet_scaling"] > 0
    # the migration leg's robustness bar: draining a worker mid-stream
    # drops ZERO streams (every resume bit-identical — deterministic on
    # CPU), and both resume latencies are real numbers. The latency
    # RATIO is wall-clock on a tiny model and deliberately un-barred
    # (the leg's migration_note explains the CPU magnitude caveat)
    assert extra["migration_dropped_streams"] == 0, extra
    assert extra["migration_resume_ms"] > 0
    assert extra["migration_reprefill_resume_ms"] > 0
    # ZeRO-1: the deterministic bars — the sharded step is BITWISE the
    # unsharded step at matched global batch, and each replica resides
    # ~1/dp of the optimizer-state bytes (scalars replicate, hence the
    # slack); step-time parity is expected on CPU (zero1_note)
    assert extra["zero1_bitwise_identical"] is True
    assert extra["zero1_opt_state_ratio"] <= 1.0 / extra["zero1_dp"] + 0.05
    # tensor parallelism: the deterministic bars — a tp=N engine's
    # streams are BITWISE the 1-way engine's, and each chip resides
    # ~1/tp of the KV page bytes (same page count); ITL improvement is
    # the armed-on-TPU bar (tp_note)
    assert extra["tp_streams_bitwise_identical"] is True
    assert extra["tp_page_capacity_gain"] >= 0.9 * extra["tp_degree"]
    # serve-and-train: a best_effort stream spanning >=1 live weight
    # publish drops ZERO tokens and the publish compiles NOTHING; the
    # trainer yields to interactive at chunk granularity so armed-vs-off
    # ITL stays within noise (generous wall-clock bound), while idle
    # gaps really do run train steps
    assert extra["serve_train_dropped"] == 0, extra
    assert extra["serve_train_stream_exact_len"] is True
    assert extra["serve_train_publishes"] >= 1
    assert extra["serve_train_weights_version"] >= 2
    assert extra["serve_train_publish_new_programs"] == 0, extra
    assert extra["serve_train_bg_steps_during_itl"] >= 1, extra
    assert extra["serve_train_itl_ratio"] <= 3.0, extra
    # the scheduling overload leg's deterministic pins: interactive
    # arrivals at 2x slot capacity really did preempt lower-class slots,
    # the best_effort overflow burst really was rejected fail-fast (the
    # 429 path), nothing starved under either policy, and the FCFS
    # baseline never preempts
    assert extra["sched_preemptions"] >= 1, extra["sched_preemptions"]
    assert extra["sched_rejected"] >= 1, extra["sched_rejected"]
    assert extra["sched_starved"] == 0, extra["sched_starved"]
    assert extra["sched_fcfs_preemptions"] == 0
    # the latency claim, noise-tolerant like the other wall-clock bars:
    # under identical mixed-class overload, SLO scheduling must hold
    # interactive TTFT p50 to HALF the FCFS baseline's or better (the
    # measured CPU margin is ~10x; the bit-exactness + starvation
    # deterministic pins live in tests/test_scheduler.py)
    assert extra["sched_interactive_ttft_ms_p50"] * 2 < extra[
        "sched_fcfs_interactive_ttft_ms_p50"
    ], (extra["sched_interactive_ttft_ms_p50"],
        extra["sched_fcfs_interactive_ttft_ms_p50"])
    # the prefix-cache leg's acceptance bar: the shared-system-prompt
    # followers skip >= 80% of prefill tokens and TTFT p50 improves
    # (real skipped compute — faithful even on CPU fallback)
    assert extra["prefix_hit_rate"] >= 0.8, extra["prefix_hit_rate"]
    assert extra["prefix_off_skipped_prefill_tokens"] == 0
    # TTFT must improve (the ISSUE's acceptance bar). Strict improvement
    # only — the values are wall-clock on a possibly-contended host; the
    # measured margin is ~4x (1 prefill chunk vs 4), and the DETERMINISTIC
    # pin of the same behavior is the hit-rate bar above
    assert extra["prefix_ttft_on_ms_p50"] < extra[
        "prefix_ttft_off_ms_p50"
    ], (extra["prefix_ttft_on_ms_p50"], extra["prefix_ttft_off_ms_p50"])
    # the tiered-cache leg's acceptance bar (deterministic on CPU: the
    # skipped-token counters are counted compute, not wall-clock): once
    # the Zipf working set exceeds the HBM pool, host-tier spill — and
    # the fleet rung, where pulls must actually have fired — recover
    # >= 80% of the skipped-prefill tokens destroy-on-evict loses. The
    # TTFT columns are structural on CPU (tier_note documents why) so
    # they carry no ordering bar here
    assert extra["tier_skipped_destroy"] < extra["tier_revisit_tokens"], (
        extra["tier_skipped_destroy"], extra["tier_revisit_tokens"],
    )  # the working set genuinely overflowed HBM — the regime is real
    assert extra["tier_recovered_frac_host"] >= 0.8, (
        extra["tier_recovered_frac_host"]
    )
    assert extra["tier_recovered_frac"] >= 0.8, extra["tier_recovered_frac"]
    assert extra["tier_fleet_pulls"] > 0, extra["tier_fleet_pulls"]
    assert extra["tier_skipped_host"] > extra["tier_skipped_destroy"]
    # the trained-model speculation demo must emit exactly the vanilla
    # sequence and not lose MATERIALLY — the ratio is wall-clock on a
    # possibly-contended CPU host, so exact parity is within noise; the
    # real never-a-loss guarantee is the acceptance-rate kill switch
    # (test_engine.py::test_lookahead_acceptance_rate_auto_disable), and
    # the full >1.3x margin is asserted only where it is real (TPU runs)
    assert extra["spec_demo_learned"] and extra["spec_demo_exact"]
    assert extra["spec_trained_speedup"] >= 0.9, extra["spec_trained_speedup"]
    assert extra["spec_trained_tokens_per_verify_pass"] >= 5.0
    # the CONTINUOUS spec leg's acceptance bars (ISSUE 11): real
    # multi-token amortization on the repetitive workload (deterministic
    # count: accepted drafts per verify pass, > 1.5), an aggregate
    # decode speedup over the occupancy-matched plain flood (wall-clock,
    # CPU magnitude note in spec_cont_note), bit-identical streams on
    # BOTH workloads, and the kill switch demonstrably capping the
    # adversarial (never-matching drafts) workload: it fires on every
    # slot and the residual loss stays within the probe window's cost
    # (noise-tolerant 0.6 bound; the deterministic post-kill-zero-drafts
    # pin lives in tests/test_continuous.py)
    assert extra["spec_tokens_per_pass"] > 1.5, extra["spec_tokens_per_pass"]
    assert extra["spec_decode_speedup"] > 1.0, extra["spec_decode_speedup"]
    assert extra["spec_streams_exact"] is True
    assert extra["spec_adversarial_killed"] >= 1, extra
    assert extra["spec_adversarial_speedup"] >= 0.6, (
        extra["spec_adversarial_speedup"]
    )
