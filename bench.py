"""Benchmark harness — prints ONE JSON line.

Headline metric: single-chip decode throughput (tokens/sec/chip) for the
largest Qwen3-family preset that fits the chip's HBM at bf16, via the
fully-compiled decode loop (engine/generate.py::_decode_loop — the whole
token loop on device). ``extra`` carries a fine-tune step-time + MFU
measurement (engine/training.py::make_train_step).

The reference publishes no numbers (BASELINE.md), so ``vs_baseline`` reports
the fraction of the HBM-bandwidth roofline achieved: a B=1 decode step must
stream all parameter + KV bytes per token, so
``roofline_tokens/s = HBM_BW / (param_bytes + kv_bytes_per_token·len)``.

One process owns the chip: the parent never imports jax and runs exactly
one ``--run`` child, on whatever backend the environment gives it (the
accelerator by default; a CPU run is asked for explicitly with
``JAX_PLATFORMS=cpu``). A child that cannot get its backend, or dies, fails
the bench — nothing is re-run on the CPU.
"""

import json
import os
import subprocess
import sys
import time

_SELF = os.path.abspath(__file__)

# Per-chip peaks for roofline/MFU denominators. device_kind substring → (HBM
# bytes/s, peak bf16 FLOP/s). Conservative public numbers. A device that is
# not in the table is an error, not a default.
# tlint: disable=TL006(read-only constant table — never mutated at runtime)
_CHIP_TABLE = {
    "v5e": (819e9, 197e12),
    "v5 lite": (819e9, 197e12),  # the v5e's device_kind is "TPU v5 lite"
    "v5p": (2765e9, 459e12),
    "v4": (1228e9, 275e12),
    "v6e": (1640e9, 918e12),
}
# an explicit JAX_PLATFORMS=cpu run (the harness smoke) has no roofline;
# these only keep its ratios finite
_CPU_NOMINAL = (50e9, 1e12)


def _emit_error(detail: str) -> None:
    print(
        json.dumps(
            {"metric": "bench-error", "value": 0, "unit": detail[:200],
             "vs_baseline": 0}
        )
    )


def main() -> None:
    try:
        rc = subprocess.run(
            [sys.executable, _SELF, "--run"], timeout=3300
        ).returncode
    except subprocess.TimeoutExpired:
        rc = 124
    if rc != 0:
        _emit_error(f"rc={rc}")
        sys.exit(1)


def _chip_peaks(dev) -> tuple[float, float]:
    if dev.platform == "cpu":
        return _CPU_NOMINAL
    kind = getattr(dev, "device_kind", "") or ""
    for key, peaks in _CHIP_TABLE.items():
        if key in kind.lower():
            return peaks
    raise RuntimeError(
        f"no published peaks for device_kind {kind!r} — add it to "
        "_CHIP_TABLE with its source instead of borrowing another chip's"
    )


# The driver gives the child ~55 min; optional measurements (B=8, int8,
# training) are skipped when the elapsed budget runs low so a slow compile
# never times out the whole child and loses the HEADLINE number.
_CHILD_BUDGET_S = 3100.0
_T_CHILD_START = time.monotonic()


def _budget_left() -> float:
    return _CHILD_BUDGET_S - (time.monotonic() - _T_CHILD_START)


def run_bench() -> None:
    import jax

    from tensorlink_tpu.core.devices import (
        configure_compile_cache,
        device_hbm_bytes,
    )

    # re-runs (driver retries, profiling sessions) pay the 4B-class
    # decode/train compiles once
    configure_compile_cache()
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    hbm_bw, peak_flops = _chip_peaks(dev)
    # TLTPU_BENCH_FORCE_ALL_LEGS=1: run EVERY optional leg (batch8, flash,
    # int8) on CPU at toy shapes too — a leg must never see its first-ever
    # execution inside a scarce TPU window (VERDICT r4 weak #2)
    force_all = os.environ.get("TLTPU_BENCH_FORCE_ALL_LEGS") == "1"

    from tensorlink_tpu.core.trace import get_tracer
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.engine.sampling import SamplingParams
    from tensorlink_tpu.engine.training import make_optimizer, make_train_step
    from tensorlink_tpu.models import init_params
    from tensorlink_tpu.models.registry import config_presets

    presets = config_presets()

    def trace_decomp(tids) -> dict | None:
        """Mean trace-derived TTFT decomposition over ``tids``
        (core/trace.py spans): queue_ms + prefill_ms + first_decode_ms
        == ttft_trace_ms by construction — the engine records the three
        parts contiguously (submit→admit, admit→prefill-done,
        prefill-done→first token). First occurrence of each span name
        wins, so a preempted request decomposes its FIRST token's path."""
        parts = []
        for tid in tids:
            first: dict = {}
            for s in get_tracer().collect(tid):  # ts-ordered
                if "dur_ms" in s and s["name"] not in first:
                    first[s["name"]] = float(s["dur_ms"])
            if "first_token" not in first:
                continue
            parts.append((
                first.get("queue_wait", 0.0),
                first.get("prefill", 0.0),
                first.get("first_decode", 0.0),
            ))
        if not parts:
            return None
        q, p, f = (
            float(np.mean([x[i] for x in parts])) for i in range(3)
        )
        return {
            "queue_ms": round(q, 3),
            "prefill_ms": round(p, 3),
            "first_decode_ms": round(f, 3),
            "ttft_trace_ms": round(q + p + f, 3),
        }

    # ---- decode benchmark -------------------------------------------------
    if on_tpu:
        hbm = device_hbm_bytes(dev)
        # largest Qwen3 preset whose bf16 params fit in ~60% of HBM (rest
        # goes to KV cache + workspace)
        decode_name = "qwen3-1p7b"
        for name in ("qwen3-8b", "qwen3-4b", "qwen3-1p7b", "qwen3-0p6b"):
            if presets[name].param_count() * 2 <= 0.6 * hbm:
                decode_name = name
                break
        cfg = presets[decode_name].with_(dtype=jnp.bfloat16)
        batch, prompt_len, gen_tokens = 1, 128, 512
    else:  # explicit JAX_PLATFORMS=cpu run (harness smoke): toy shapes
        decode_name = "qwen3-tiny-cpu"
        cfg = presets["qwen3-1p7b"].with_(
            dtype=jnp.float32, n_layers=2, d_model=256, d_ff=512,
            n_heads=4, n_kv_heads=2, head_dim=64, vocab_size=1024,
        )
        batch, prompt_len, gen_tokens = 1, 32, 64

    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = GenerationEngine(
        cfg,
        params,
        seq_buckets=(prompt_len, prompt_len + gen_tokens),
        batch_buckets=(batch,),
        max_seq_len=prompt_len + gen_tokens,
    )
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, cfg.vocab_size, prompt_len).tolist() for _ in range(batch)
    ]
    greedy = SamplingParams.make()

    def timed_decode(engine, ps):
        """Pure decode tokens/s: warm up with the SAME max_new_tokens
        (_decode_loop's n_steps is static — a different count compiles a
        different program), then measure end-to-end minus a warmed prefill.
        Shared by the B=1 headline, the B=8, and the int8 measurements so
        the timing protocol can't drift between them."""
        engine.generate_compiled(ps, max_new_tokens=gen_tokens, sampling=greedy)
        jax.block_until_ready(engine.prefill(ps)[:2])
        t0 = time.perf_counter()
        jax.block_until_ready(engine.prefill(ps)[:2])
        prefill_dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        r = engine.generate_compiled(
            ps, max_new_tokens=gen_tokens, sampling=greedy
        )
        dt = max(time.perf_counter() - t0 - prefill_dt, 1e-9)
        return sum(len(s) for s in r.sequences) / dt

    toks_per_s = timed_decode(eng, prompts)

    pbytes = cfg.param_count() * (2 if cfg.dtype == jnp.bfloat16 else 4)
    kv_per_tok = (
        2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
        * (2 if cfg.dtype == jnp.bfloat16 else 4)
    )
    avg_len = prompt_len + gen_tokens / 2
    roofline = hbm_bw / (pbytes + kv_per_tok * avg_len)

    # ---- batched decode (serving batcher's regime; reported in extra) -----
    # aggregate tokens/s at B=8: a batched step streams the same parameter
    # bytes as B=1, so this shows the near-free ~8x the dynamic batcher
    # (ml/batching.py) buys concurrent requests
    batch_extra = {}
    if on_tpu and _budget_left() < 900:
        batch_extra = {"batch8_skipped": "low time budget"}
    elif on_tpu or force_all:
        try:
            B8 = 8
            eng8 = GenerationEngine(
                cfg, params,
                seq_buckets=(prompt_len, prompt_len + gen_tokens),
                batch_buckets=(B8,),
                max_seq_len=prompt_len + gen_tokens,
            )
            prompts8 = [
                rng.integers(1, cfg.vocab_size, prompt_len).tolist()
                for _ in range(B8)
            ]
            tps8 = timed_decode(eng8, prompts8)
            batch_extra = {
                "batch8_toks_s": round(tps8, 2),
                "batch8_speedup_vs_b1": round(tps8 / toks_per_s, 2),
            }
            del eng8
        except Exception as e:
            batch_extra = {"batch8_error": str(e)[:300]}

    # ---- serving load: continuous batching vs the static window batcher ---
    # N concurrent requests with staggered (Poisson-ish) arrivals through
    # the API batcher layer: aggregate tokens/s, time-to-first-token, and
    # inter-token latency. The static leg reproduces the OLD GenBatcher
    # behavior (arrival window + run-to-completion, no bucket shrink); the
    # continuous leg is the new slot scheduler (ml/batching.py +
    # engine/continuous.py). This is the regime BENCH_r05 measured at
    # 0.56x per-row — arrivals misaligned with the window serialize into
    # under-filled run-to-completion batches.
    serving_extra = {}
    if on_tpu and _budget_left() < 600:
        serving_extra = {"serving_skipped": "low time budget"}
    else:
        try:
            from tensorlink_tpu.engine.sampling import SamplingParams as _SP
            from tensorlink_tpu.ml.batching import (
                ContinuousBatcher, GenBatcher,
            )

            N_REQ = 8
            sv_budget = 48 if not on_tpu else 128
            sv_prompt_len = 16
            sv_gap = 0.08  # arrival spacing >> the 10 ms window
            sv_rng = np.random.default_rng(5)
            sv_prompts = [
                sv_rng.integers(1, cfg.vocab_size, sv_prompt_len).tolist()
                for _ in range(N_REQ)
            ]

            class _LocalModel:
                """GenBatcher-shaped facade over a local engine, decoding
                like the old serving worker for streamed requests:
                ``chunk=0`` is the shipped default (per-token host loop,
                MLConfig.stream_chunk_steps=0); ``chunk>0`` is the tuned
                compiled-chunk variant — both run the batch to its drain
                with no shrink-on-eviction (the OLD behavior)."""

                plan = None

                def __init__(self, engine, chunk=0):
                    self.engine = engine
                    self.chunk = chunk

                def generate(self, prompts, *, max_new_tokens,
                             temperature=0.0, top_k=0, top_p=1.0,
                             presence_penalty=0.0, frequency_penalty=0.0,
                             eos_ids=(), seed=0, stream_cb=None,
                             budgets=None, lookahead=False):
                    n = len(prompts)

                    def rows(v):
                        return (
                            list(v) if isinstance(v, (list, tuple))
                            else [v] * n
                        )

                    sp = _SP.stack(
                        [
                            _SP.make(temperature=t, top_k=k, top_p=p)
                            for t, k, p in zip(
                                rows(temperature), rows(top_k), rows(top_p)
                            )
                        ],
                        pad_to=n,
                    )
                    kw = dict(
                        max_new_tokens=max_new_tokens, sampling=sp,
                        eos_ids=eos_ids, seed=seed, stream_cb=stream_cb,
                        budgets=budgets,
                    )
                    if self.chunk > 0:
                        r = self.engine.generate_chunked(
                            prompts, chunk_steps=self.chunk,
                            shrink_on_eviction=False, **kw,
                        )
                    else:
                        r = self.engine.generate(prompts, **kw)
                    return r.sequences

            def serving_leg(batcher, trace_prefix=None):
                import threading as _th

                recs: list[tuple[float, list[float], int]] = []
                errs: list[BaseException] = []

                def one(i):
                    sub = time.perf_counter()
                    times: list[float] = []

                    def cb(_ts):
                        times.append(time.perf_counter())
                        return None

                    kw = (
                        {"trace_id": f"{trace_prefix}{i}"}
                        if trace_prefix else {}
                    )
                    try:
                        out = batcher.generate(
                            sv_prompts[i], max_new_tokens=sv_budget,
                            stream_cb=cb, **kw,
                        )
                    except BaseException as e:  # a silent drop would
                        errs.append(e)  # corrupt the leg's metrics
                        return
                    recs.append((sub, times, len(out)))

                threads = [
                    _th.Thread(target=one, args=(i,)) for i in range(N_REQ)
                ]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                    time.sleep(sv_gap)
                for t in threads:
                    t.join(600)
                if errs or len(recs) != N_REQ:
                    raise RuntimeError(
                        f"serving leg dropped {N_REQ - len(recs)} of "
                        f"{N_REQ} requests: {errs[:2]!r}"
                    )
                wall = time.perf_counter() - t0
                total = sum(r[2] for r in recs)
                ttfts = [r[1][0] - r[0] for r in recs if r[1]]
                itls = [
                    b - a for r in recs for a, b in zip(r[1], r[1][1:])
                ]
                return {
                    "toks_s": total / max(wall, 1e-9),
                    "ttft_ms_p50": float(np.percentile(ttfts, 50)) * 1e3,
                    "ttft_ms_p95": float(np.percentile(ttfts, 95)) * 1e3,
                    "ttft_ms_mean": float(np.mean(ttfts)) * 1e3,
                    "itl_ms_p50": float(np.percentile(itls, 50)) * 1e3,
                    "itl_ms_p95": float(np.percentile(itls, 95)) * 1e3,
                }

            sv_eng = GenerationEngine(
                cfg, params,
                seq_buckets=(sv_prompt_len, sv_prompt_len + sv_budget),
                batch_buckets=(1, 2, 4, 8),
                max_seq_len=sv_prompt_len + sv_budget,
            )
            # warm EVERY program either leg can hit so no leg times a
            # compile: both static variants (per-token host loop and
            # compiled chunks) at every batch bucket, through the same
            # adapter shapes the real legs use
            for chunk in (0, 8):
                warm = _LocalModel(sv_eng, chunk=chunk)
                for b in (1, 2, 4, 8):
                    warm.generate(
                        [sv_prompts[0]] * b, max_new_tokens=4,
                        temperature=[0.0] * b, top_k=[0] * b,
                        top_p=[1.0] * b, budgets=[4] * b,
                    )
            # old default serving (MLConfig.stream_chunk_steps=0: streamed
            # requests decode on the per-token host loop) — the "old
            # static GenBatcher" baseline
            stat = GenBatcher(
                _LocalModel(sv_eng, chunk=0), eos_ids=[], max_batch=N_REQ
            )
            static_m = serving_leg(stat)
            stat.close()
            # tuned static (compiled 8-step chunks) for an honest upper
            # bound on what window batching could do
            statc = GenBatcher(
                _LocalModel(sv_eng, chunk=8), eos_ids=[], max_batch=N_REQ
            )
            staticc_m = serving_leg(statc)
            statc.close()
            cont = ContinuousBatcher(
                engine=sv_eng, eos_ids=[], max_slots=N_REQ, chunk_steps=8
            )
            cont.generate(sv_prompts[0], max_new_tokens=4)  # warm
            cont_m = serving_leg(cont, trace_prefix="bench-sv-")
            occ = (cont.stats() or {}).get("slot_occupancy")
            cont.close()
            # trace-derived TTFT decomposition of the continuous leg
            # (core/trace.py): where a request's time-to-first-token went
            sv_decomp = trace_decomp(
                [f"bench-sv-{i}" for i in range(N_REQ)]
            ) or {}
            # tracing overhead: disabled-vs-enabled serving-step cost.
            # Same engine, same compiled programs, interleaved min-of-3
            # measurements of a fixed chunk count with all slots live —
            # min-of-k is robust to additive host noise, and the bound
            # the observability layer must hold is <= 2%.
            from tensorlink_tpu.engine.continuous import (
                ContinuousEngine as _OCE,
            )

            OH_CHUNKS = 12

            def traced_chunk_times(traced: bool, rep: int) -> list[float]:
                # chunk_steps=2 keeps every slot live through warm + the
                # timed window (prompt 16 + 32 decode steps < the 64-token
                # budget), so both modes time identical full-slot chunks
                ce = _OCE(
                    sv_eng, max_slots=4, page_size=16, chunk_steps=2,
                )
                for i in range(4):
                    ce.submit(
                        sv_prompts[i], max_new_tokens=sv_eng.max_seq_len,
                        seed=i,
                        trace_id=(
                            f"bench-oh-{rep}-{i}" if traced else None
                        ),
                    )
                for _ in range(4):  # admit + warm: all programs compiled
                    ce.step_chunk()
                times: list[float] = []
                for _ in range(OH_CHUNKS):
                    t0 = time.perf_counter()
                    ce.step_chunk()
                    times.append(time.perf_counter() - t0)
                    if not traced:
                        # host work between chunk syncs (admission,
                        # packing, draft lookup) — the decode critical
                        # path's host budget, per docs/SHARDING.md
                        oh_host_gaps.append(float(ce._host_gap_ms))
                ce.close()
                return times

            # per-CHUNK minimum over interleaved reps, not min-of-window:
            # a single ~ms chunk is very likely clean of scheduler noise
            # in at least one of 3x12 samples per mode, so each mode's
            # min converges to its true floor even on a contended host
            oh_off_t: list[float] = []
            oh_on_t: list[float] = []
            oh_host_gaps: list[float] = []
            for r in range(3):
                oh_off_t.extend(traced_chunk_times(False, r))
                oh_on_t.extend(traced_chunk_times(True, r))
            trace_overhead_pct = round(
                (min(oh_on_t) - min(oh_off_t))
                / max(min(oh_off_t), 1e-9) * 100.0, 2
            )
            del sv_eng
            serving_extra = {
                "serving_n_concurrent": N_REQ,
                "serving_budget": sv_budget,
                "serving_static_toks_s": round(static_m["toks_s"], 2),
                "serving_static_chunked_toks_s": round(
                    staticc_m["toks_s"], 2
                ),
                "serving_cont_toks_s": round(cont_m["toks_s"], 2),
                "serving_cont_speedup": round(
                    cont_m["toks_s"] / max(static_m["toks_s"], 1e-9), 2
                ),
                "serving_cont_speedup_vs_chunked": round(
                    cont_m["toks_s"] / max(staticc_m["toks_s"], 1e-9), 2
                ),
                "serving_static_ttft_ms_p50": round(
                    static_m["ttft_ms_p50"], 1
                ),
                "serving_static_ttft_ms_p95": round(
                    static_m["ttft_ms_p95"], 1
                ),
                "serving_cont_ttft_ms_p50": round(cont_m["ttft_ms_p50"], 1),
                "serving_cont_ttft_ms_p95": round(cont_m["ttft_ms_p95"], 1),
                "serving_static_itl_ms_p50": round(
                    static_m["itl_ms_p50"], 1
                ),
                "serving_static_itl_ms_p95": round(
                    static_m["itl_ms_p95"], 1
                ),
                "serving_cont_itl_ms_p50": round(cont_m["itl_ms_p50"], 1),
                "serving_cont_itl_ms_p95": round(cont_m["itl_ms_p95"], 1),
                # trace-derived TTFT decomposition (core/trace.py): the
                # three parts are recorded contiguously by the engine, so
                # they sum to serving_ttft_trace_ms exactly; the external
                # mean differs only by batcher-dispatch overhead
                "serving_queue_ms": sv_decomp.get("queue_ms", 0.0),
                "serving_prefill_ms": sv_decomp.get("prefill_ms", 0.0),
                "serving_first_decode_ms": sv_decomp.get(
                    "first_decode_ms", 0.0
                ),
                "serving_ttft_trace_ms": sv_decomp.get("ttft_trace_ms", 0.0),
                "serving_cont_ttft_ms_mean": round(
                    cont_m["ttft_ms_mean"], 2
                ),
                # disabled-vs-enabled tracing cost on the serving step —
                # the observability layer's <= 2% bound (negative = noise)
                "serving_trace_overhead_pct": trace_overhead_pct,
                **(
                    {"serving_cont_slot_occupancy": occ}
                    if occ is not None else {}
                ),
                **(
                    {}
                    if on_tpu
                    else {
                        "serving_note": (
                            "CPU is compute-bound: a batched step costs "
                            "~B x a B=1 step, so aggregate tokens/s is "
                            "~parity by construction; the >=2x batching "
                            "lever (batched decode ~ free) is the TPU "
                            "bandwidth-bound regime. The continuous win "
                            "visible on CPU is admission latency (TTFT) "
                            "and immediate eviction."
                        )
                    }
                ),
            }
            # per-chunk host-gap floor (min over clean samples, like the
            # trace-overhead floor above): host work between chunk syncs
            serving_extra["serving_host_gap_ms"] = round(
                min(oh_host_gaps), 3
            )
        except Exception as e:
            serving_extra = {"serving_error": str(e)[:500]}

    # ---- prefix cache: shared-system-prompt serving --------------------
    # 8 staggered requests sharing a long system prompt, with the prefix
    # cache off vs on (both warmed: every program compiled AND, for the
    # on-leg, the shared prefix already resident — the steady state the
    # cache serves; no leg times a compile). The cache-on leg must skip
    # the shared region's prefill compute entirely, which shows up as
    # prefill_tokens_skipped and a lower TTFT p50.
    prefix_extra = {}
    if on_tpu and _budget_left() < 500:
        prefix_extra = {"prefix_cache_skipped": "low time budget"}
    else:
        try:
            from tensorlink_tpu.ml.batching import (
                ContinuousBatcher as _PCB,
            )

            N_PF = 8
            pf_sys_len = 192 if not on_tpu else 1024
            pf_tail = 8
            pf_budget = 8 if not on_tpu else 64
            pf_gap = 0.05
            pf_len = pf_sys_len + pf_tail
            pf_rng = np.random.default_rng(7)
            pf_sys = pf_rng.integers(1, cfg.vocab_size, pf_sys_len).tolist()
            pf_prompts = [
                pf_sys
                + pf_rng.integers(1, cfg.vocab_size, pf_tail).tolist()
                for _ in range(N_PF)
            ]

            # ONE engine for both legs: the paged cache lives in the
            # batcher's ContinuousEngine, so off/on share every compiled
            # program (no leg times a compile the other didn't pay)
            eng_pf = GenerationEngine(
                cfg, params,
                seq_buckets=(64, pf_len + pf_budget),
                batch_buckets=(1,),
                max_seq_len=pf_len + pf_budget,
            )

            def prefix_leg(cache_on: bool) -> dict:
                import threading as _th

                cb = _PCB(
                    engine=eng_pf, eos_ids=[], max_slots=N_PF,
                    page_size=16, chunk_steps=8, prefill_chunk=64,
                    prefix_cache=cache_on,
                )
                try:
                    # warm request: compiles the chunk programs and
                    # (on-leg) leaves the shared system prompt resident
                    cb.generate(pf_sys + [1], max_new_tokens=2)
                    cont = cb._cont
                    skipped0 = cont.stats["prefill_tokens_skipped"]
                    recs: list[tuple[float, float | None, int]] = []
                    errs: list[BaseException] = []

                    def one(i):
                        sub = time.perf_counter()
                        first: list[float] = []

                        def cbk(_ts):
                            if not first:
                                first.append(time.perf_counter())
                            return None

                        try:
                            out = cb.generate(
                                pf_prompts[i], max_new_tokens=pf_budget,
                                stream_cb=cbk,
                            )
                        except BaseException as e:
                            errs.append(e)
                            return
                        recs.append(
                            (sub, first[0] if first else None, len(out))
                        )

                    threads = [
                        # daemon: a wedged request must degrade to a
                        # prefix_error entry, never hang the bench's
                        # one-JSON-line contract at interpreter exit
                        _th.Thread(target=one, args=(i,), daemon=True)
                        for i in range(N_PF)
                    ]
                    for t in threads:
                        t.start()
                        time.sleep(pf_gap)
                    for t in threads:
                        t.join(600)
                    if errs or len(recs) != N_PF:
                        raise RuntimeError(
                            f"prefix leg dropped {N_PF - len(recs)} of "
                            f"{N_PF} requests: {errs[:2]!r}"
                        )
                    skipped = (
                        cont.stats["prefill_tokens_skipped"] - skipped0
                    )
                    snap = cont.serving_snapshot()
                finally:
                    cb.close(timeout=60.0)
                out = {
                    "ttft_ms_p50": float(np.percentile(
                        [(f - s) * 1e3 for s, f, _ in recs if f], 50
                    )),
                    "skipped": int(skipped),
                    "hits": int(snap.get("prefix_hits", 0)),
                }
                return out

            pf_off = prefix_leg(False)
            pf_on = prefix_leg(True)
            del eng_pf
            pf_prompt_tokens = sum(len(p) for p in pf_prompts)
            prefix_extra = {
                "prefix_n_concurrent": N_PF,
                "prefix_sys_len": pf_sys_len,
                "prefix_prompt_tokens": pf_prompt_tokens,
                "prefix_skipped_prefill_tokens": pf_on["skipped"],
                "prefix_hit_rate": round(
                    pf_on["skipped"] / max(pf_prompt_tokens, 1), 3
                ),
                "prefix_off_skipped_prefill_tokens": pf_off["skipped"],
                "prefix_ttft_off_ms_p50": round(pf_off["ttft_ms_p50"], 1),
                "prefix_ttft_on_ms_p50": round(pf_on["ttft_ms_p50"], 1),
                "prefix_ttft_speedup": round(
                    pf_off["ttft_ms_p50"] / max(pf_on["ttft_ms_p50"], 1e-9),
                    2,
                ),
                **(
                    {}
                    if on_tpu
                    else {
                        "prefix_note": (
                            "CPU fallback CAN show the cache's real "
                            "effect: prefill compute is genuinely "
                            "skipped for the hit region, so "
                            "prefill_tokens_skipped and the TTFT drop "
                            "are faithful. What CPU canNOT show is the "
                            "TPU-side magnitude (HBM-resident pages vs "
                            "recompute at accelerator speed) or any "
                            "aggregate tokens/s change — decode is "
                            "compute-bound here, so steady-state "
                            "throughput is ~parity by construction."
                        )
                    }
                ),
            }
        except Exception as e:
            prefix_extra = {"prefix_error": str(e)[:500]}

    # ---- tiered prefix cache: Zipf session flood past HBM capacity -----
    # the regime the tier subsystem exists for (docs/SERVING.md "Tiered
    # prefix cache"): more distinct shared-prefix sessions than the HBM
    # page pool holds, revisited on a Zipf-ish schedule. Three rungs over
    # the SAME deterministic schedule: destroy-on-evict (the seed
    # behavior — an evicted prefix is gone), host-tier (evictions demote
    # to host RAM, revisits promote), and host-tier + fleet-pull (two
    # replicas, alternating placement, misses pulled from the sibling
    # through fleet/prefixmap). Reported per rung: prefill tokens
    # actually skipped and TTFT p50; the acceptance bar is the recovered
    # fraction of what destroy-on-evict loses.
    tier_extra = {}
    if on_tpu and _budget_left() < 450:
        tier_extra = {"tier_skipped": "low time budget"}
    else:
        try:
            from tensorlink_tpu.fleet.prefixmap import make_fleet_fetcher
            from tensorlink_tpu.ml.batching import (
                ContinuousBatcher as _TCB,
            )

            tr_page = 16
            tr_prefix = 64 if not on_tpu else 512
            tr_tail, tr_budget = 8, 8
            tr_len = tr_prefix + tr_tail + tr_budget
            tr_rng = np.random.default_rng(13)
            tr_sessions = [
                tr_rng.integers(1, cfg.vocab_size, tr_prefix).tolist()
                for _ in range(6)
            ]
            # Zipf-ish revisit schedule: session 0 hot, the tail cold —
            # 16 requests over 6 sessions, 10 revisits
            tr_sched = [0, 1, 0, 2, 0, 1, 3, 0, 2, 4, 0, 1, 5, 0, 2, 1]
            tr_prompts = [
                tr_sessions[s]
                + tr_rng.integers(1, cfg.vocab_size, tr_tail).tolist()
                for s in tr_sched
            ]
            n_revisit = len(tr_sched) - len(set(tr_sched))
            tr_potential = n_revisit * tr_prefix

            # max_slots=2 bounds the page pool (1 + 2 pages-per-slot
            # worth) far below the 6-session working set, so the HBM
            # trie MUST evict — the whole point of the leg
            eng_tr = GenerationEngine(
                cfg, params, seq_buckets=(64, tr_len), batch_buckets=(1,),
                max_seq_len=tr_len,
            )

            def tier_rung(n_replicas: int, host_pages: int) -> dict:
                cbs = [
                    _TCB(
                        engine=eng_tr, eos_ids=[], max_slots=2,
                        page_size=tr_page, chunk_steps=8,
                        prefill_chunk=64, host_tier_pages=host_pages,
                    )
                    for _ in range(n_replicas)
                ]
                try:
                    if n_replicas > 1:
                        # the fleet rung: each replica pulls misses from
                        # its sibling via the prefix map over live
                        # router snapshots — the real subsystem, not a
                        # bench shortcut
                        def views():
                            return {
                                f"r{j}": cb.router_snapshot()
                                for j, cb in enumerate(cbs)
                            }

                        for j, cb in enumerate(cbs):
                            pulls = {
                                f"r{k}": cbs[k].pull_prefix
                                for k in range(n_replicas) if k != j
                            }
                            cb._cont.fetch_prefix = make_fleet_fetcher(
                                f"r{j}", tr_page, views, pulls,
                            )
                    for cb in cbs:  # compile warmup, cold w.r.t. sessions
                        cb.generate([1] * 9, max_new_tokens=2)
                    skipped0 = [
                        cb._cont.stats["prefill_tokens_skipped"]
                        for cb in cbs
                    ]
                    ttfts = []
                    for i, prompt in enumerate(tr_prompts):
                        cb = cbs[i % n_replicas]
                        sub = time.perf_counter()
                        first: list[float] = []

                        def cbk(_ts):
                            if not first:
                                first.append(time.perf_counter())
                            return None

                        out = cb.generate(
                            prompt, max_new_tokens=tr_budget,
                            stream_cb=cbk,
                        )
                        assert len(out) == tr_budget
                        if first:
                            ttfts.append((first[0] - sub) * 1e3)
                    skipped = sum(
                        cb._cont.stats["prefill_tokens_skipped"] - s0
                        for cb, s0 in zip(cbs, skipped0)
                    )
                    pulls_n = sum(
                        cb._cont.stats["fleet_pulls"] for cb in cbs
                    )
                    for cb in cbs:
                        cb._cont.check_page_conservation()
                finally:
                    for cb in cbs:
                        cb.close(timeout=60.0)
                return {
                    "skipped": int(skipped),
                    "ttft_p50": float(np.percentile(ttfts, 50)),
                    "pulls": int(pulls_n),
                }

            tr_destroy = tier_rung(1, 0)
            tr_host = tier_rung(1, 48)
            tr_fleet = tier_rung(2, 48)
            del eng_tr
            tr_lost = max(tr_potential - tr_destroy["skipped"], 1)
            tier_extra = {
                "tier_sessions": len(tr_sessions),
                "tier_revisit_tokens": tr_potential,
                "tier_skipped_destroy": tr_destroy["skipped"],
                "tier_skipped_host": tr_host["skipped"],
                "tier_skipped_fleet": tr_fleet["skipped"],
                "tier_fleet_pulls": tr_fleet["pulls"],
                "tier_ttft_p50_destroy_ms": round(tr_destroy["ttft_p50"], 1),
                "tier_ttft_p50_host_ms": round(tr_host["ttft_p50"], 1),
                "tier_ttft_p50_fleet_ms": round(tr_fleet["ttft_p50"], 1),
                # the acceptance bar: of the skipped-prefill tokens the
                # destroy-on-evict baseline LOSES, what fraction do the
                # tiers claw back (host rung: spill alone on one box;
                # fleet rung: spill + sibling pull under alternating
                # placement — the ISSUE's >= 0.8 bar)
                "tier_recovered_frac_host": round(
                    (tr_host["skipped"] - tr_destroy["skipped"]) / tr_lost,
                    3,
                ),
                "tier_recovered_frac": round(
                    (tr_fleet["skipped"] - tr_destroy["skipped"]) / tr_lost,
                    3,
                ),
                **(
                    {}
                    if on_tpu
                    else {
                        "tier_note": (
                            "CPU fallback shows the tier subsystem's "
                            "real effect: skipped-prefill recovery is "
                            "counted compute, faithful on any backend. "
                            "What CPU canNOT show is the TPU-side "
                            "latency shape — host<->HBM page transfer "
                            "bandwidth vs re-prefill at accelerator "
                            "speed — so the TTFT columns are structural "
                            "here, not a TPU forecast."
                        )
                    }
                ),
            }
        except Exception as e:
            tier_extra = {"tier_error": str(e)[:500]}

    # ---- SLO scheduling: mixed-class overload at 2x slot capacity --------
    # the scheduler subsystem's regime (engine/scheduler.py): 2x slot
    # capacity of mixed-class staggered requests — batch work fills every
    # slot, then interactive turns arrive. The SLO leg (priority classes +
    # cache-backed preemption) must keep interactive TTFT near its
    # unloaded value; the FCFS baseline leg (sched_policy="fcfs", the PR-2
    # behavior) makes the convoy cost explicit. Both legs warmed (every
    # program preemption's re-admission can touch, incl. the COW copy);
    # an overflow burst past the best_effort queue cap demonstrates the
    # 429-shaped backpressure (sched_rejected).
    sched_extra = {}
    if on_tpu and _budget_left() < 450:
        sched_extra = {"sched_skipped": "low time budget"}
    else:
        try:
            import threading as _th

            from tensorlink_tpu.engine.scheduler import (
                SchedulerOverloaded as _SOver,
            )
            from tensorlink_tpu.ml.batching import (
                ContinuousBatcher as _SCB,
            )

            SL_SLOTS = 4
            SL_N = 2 * SL_SLOTS  # 2x slot capacity
            SL_CAP = 4  # best_effort queue cap the overflow burst exceeds
            sl_prompt_len = 16
            # long-running bulk work vs short chat turns: the batch legs
            # must still be decoding when every interactive turn arrives
            sl_batch_budget = 96
            sl_inter_budget = 16
            sl_gap = 0.02
            sl_page = 8
            sl_rng = np.random.default_rng(11)
            sl_prompts = [
                sl_rng.integers(1, cfg.vocab_size, sl_prompt_len).tolist()
                for _ in range(SL_N)
            ]
            # classes: the first SL_SLOTS arrivals are batch (they take
            # every slot), the next SL_SLOTS are interactive
            sl_classes = ["batch"] * SL_SLOTS + ["interactive"] * SL_SLOTS
            sl_budgets = (
                [sl_batch_budget] * SL_SLOTS + [sl_inter_budget] * SL_SLOTS
            )

            eng_sl = GenerationEngine(
                cfg, params,
                seq_buckets=(
                    sl_prompt_len, sl_prompt_len + sl_batch_budget,
                ),
                batch_buckets=(1,),
                max_seq_len=sl_prompt_len + sl_batch_budget,
            )

            def sched_leg(policy: str) -> dict:
                cb = _SCB(
                    engine=eng_sl, eos_ids=[], max_slots=SL_SLOTS,
                    page_size=sl_page, chunk_steps=4, prefill_chunk=16,
                    sched_policy=policy, sched_queue_cap=SL_CAP,
                )
                try:
                    # warm every program the leg can touch: prefill +
                    # decode chunks via a full-page prompt, then a
                    # mid-page divergence so the COW copy compiles too
                    # (a preempted request's re-admission walks the
                    # prefix cache like any admission)
                    warm = sl_rng.integers(
                        1, cfg.vocab_size, 3 * sl_page
                    ).tolist()
                    cb.generate(warm, max_new_tokens=2)
                    cb.generate(
                        warm[: 2 * sl_page + 3] + [7, 7],
                        max_new_tokens=2,
                    )
                    # unloaded interactive TTFT: the reference the loaded
                    # ratios are judged against (3 solo runs, p50) —
                    # DISTINCT prompts, like the loaded requests', so the
                    # baseline pays the same full-prefill cost and the
                    # ratio isn't flattered by prefix-cache hits
                    unloaded: list[float] = []
                    for _ in range(3):
                        first: list[float] = []
                        solo_prompt = sl_rng.integers(
                            1, cfg.vocab_size, sl_prompt_len
                        ).tolist()
                        sub = time.perf_counter()
                        cb.generate(
                            solo_prompt, max_new_tokens=4,
                            priority="interactive",
                            stream_cb=lambda _t, f=first: (
                                f.append(time.perf_counter()), None
                            )[1],
                        )
                        unloaded.append(first[0] - sub)

                    subs: dict[int, float] = {}
                    firsts: dict[int, float] = {}
                    errs: list[BaseException] = []
                    done: list[int] = []

                    def one(i):
                        def cbk(_t):
                            firsts.setdefault(i, time.perf_counter())
                            return None

                        subs[i] = time.perf_counter()
                        try:
                            cb.generate(
                                sl_prompts[i],
                                max_new_tokens=sl_budgets[i],
                                priority=sl_classes[i], stream_cb=cbk,
                                # trace the SLO leg's interactive turns:
                                # the decomposition shows whether loaded
                                # TTFT is queue wait or prefill cost
                                trace_id=(
                                    f"bench-sl-{i}"
                                    if policy == "slo"
                                    and sl_classes[i] == "interactive"
                                    else None
                                ),
                            )
                        except BaseException as e:
                            errs.append(e)
                            return
                        done.append(i)

                    rejected_live = [0]

                    def overflow(i):
                        # past the class cap the submit fails FAST with
                        # the 429-shaped record — never queues forever
                        try:
                            cb.generate(
                                sl_prompts[i % SL_N], max_new_tokens=4,
                                priority="best_effort",
                            )
                            done.append(SL_N + i)
                        except _SOver:
                            rejected_live[0] += 1
                            done.append(SL_N + i)
                        except BaseException as e:
                            errs.append(e)

                    threads = [
                        _th.Thread(target=one, args=(i,), daemon=True)
                        for i in range(SL_N)
                    ]
                    n_over = SL_CAP + 2 if policy == "slo" else 0
                    over_threads = [
                        _th.Thread(target=overflow, args=(i,), daemon=True)
                        for i in range(n_over)
                    ]
                    for t in threads[:SL_SLOTS]:
                        t.start()
                        time.sleep(sl_gap)
                    # deterministic overload: wait until every batch
                    # request is DECODING (first token out, long budget
                    # left) so the interactive arrivals genuinely find
                    # all slots taken
                    t_wait = time.perf_counter()
                    while (
                        len(firsts) < SL_SLOTS
                        and time.perf_counter() - t_wait < 60
                    ):
                        time.sleep(0.005)
                    for t in threads[SL_SLOTS:]:
                        t.start()
                        time.sleep(sl_gap)
                    # overflow burst while the queue is at its deepest:
                    # with slots full and interactive queued ahead, no
                    # best_effort drains mid-burst, so past SL_CAP the
                    # remainder must reject
                    for t in over_threads:
                        t.start()
                    for t in threads + over_threads:
                        t.join(300)
                    if errs:
                        raise RuntimeError(
                            f"sched leg ({policy}) errored: {errs[:2]!r}"
                        )
                    starved = (SL_N + n_over) - len(done)
                    snap = cb._cont.serving_snapshot()
                finally:
                    cb.close(timeout=60.0)

                def p50(cls):
                    vals = [
                        (firsts[i] - subs[i]) * 1e3 for i in firsts
                        if sl_classes[i] == cls and i in subs
                    ]
                    return float(np.percentile(vals, 50)) if vals else 0.0

                return {
                    "unloaded_ttft_ms_p50": float(
                        np.percentile([u * 1e3 for u in unloaded], 50)
                    ),
                    "interactive_ttft_ms_p50": p50("interactive"),
                    "batch_ttft_ms_p50": p50("batch"),
                    "preemptions": int(snap["sched_preemptions"]),
                    "rejected": int(max(
                        snap["sched_rejected"], rejected_live[0]
                    )),
                    "starved": int(starved),
                }

            fcfs_m = sched_leg("fcfs")
            slo_m = sched_leg("slo")
            del eng_sl
            sl_decomp = trace_decomp(
                [
                    f"bench-sl-{i}" for i in range(SL_N)
                    if sl_classes[i] == "interactive"
                ]
            ) or {}
            base_ttft = max(slo_m["unloaded_ttft_ms_p50"], 1e-9)
            sched_extra = {
                "sched_slots": SL_SLOTS,
                "sched_n_concurrent": SL_N,
                "sched_batch_budget": sl_batch_budget,
                "sched_interactive_budget": sl_inter_budget,
                "sched_unloaded_ttft_ms_p50": round(
                    slo_m["unloaded_ttft_ms_p50"], 1
                ),
                "sched_interactive_ttft_ms_p50": round(
                    slo_m["interactive_ttft_ms_p50"], 1
                ),
                "sched_batch_ttft_ms_p50": round(
                    slo_m["batch_ttft_ms_p50"], 1
                ),
                "sched_interactive_ttft_vs_unloaded": round(
                    slo_m["interactive_ttft_ms_p50"] / base_ttft, 2
                ),
                "sched_fcfs_interactive_ttft_ms_p50": round(
                    fcfs_m["interactive_ttft_ms_p50"], 1
                ),
                "sched_fcfs_batch_ttft_ms_p50": round(
                    fcfs_m["batch_ttft_ms_p50"], 1
                ),
                "sched_fcfs_interactive_ttft_vs_unloaded": round(
                    fcfs_m["interactive_ttft_ms_p50"] / base_ttft, 2
                ),
                "sched_preemptions": slo_m["preemptions"],
                "sched_rejected": slo_m["rejected"],
                "sched_starved": slo_m["starved"] + fcfs_m["starved"],
                "sched_fcfs_preemptions": fcfs_m["preemptions"],
                # trace-derived decomposition of the SLO leg's loaded
                # interactive TTFT (queue + prefill + first decode sum to
                # sched_ttft_trace_ms by construction)
                "sched_queue_ms": sl_decomp.get("queue_ms", 0.0),
                "sched_prefill_ms": sl_decomp.get("prefill_ms", 0.0),
                "sched_first_decode_ms": sl_decomp.get(
                    "first_decode_ms", 0.0
                ),
                "sched_ttft_trace_ms": sl_decomp.get("ttft_trace_ms", 0.0),
                **(
                    {}
                    if on_tpu
                    else {
                        "sched_note": (
                            "CPU decode chunks are compute-bound (a "
                            "4-live-slot chunk costs ~4x a solo chunk), "
                            "so the loaded-vs-unloaded TTFT ratios are "
                            "inflated vs the TPU bandwidth-bound regime; "
                            "the faithful CPU signals are the SLO-vs-FCFS "
                            "ordering, preemption count, zero starvation, "
                            "and the fail-fast rejections."
                        )
                    }
                ),
            }
        except Exception as e:
            sched_extra = {"sched_error": str(e)[:500]}

    # ---- unified ragged step: the prefill-stall seam is gone --------------
    # PR-6 regime: N co-resident decodes at steady state vs the SAME
    # decodes while one long admission prefills. The unified ragged step
    # carries prefill tokens and decode tokens in ONE dispatch, so decode
    # ITL with a prefill in flight must stay ~flat vs decode-only steady
    # state. (The legacy two-program baseline sub-leg retired with the
    # path itself — its seam ratio is preserved in BENCH_r06's
    # ragged_legacy_* keys.) Warmed; medians.
    ragged_extra = {}
    if on_tpu and _budget_left() < 400:
        ragged_extra = {"ragged_skipped": "low time budget"}
    else:
        try:
            from tensorlink_tpu.engine.continuous import (
                ContinuousEngine as _RCE,
            )

            RG_SLOTS = 4
            rg_dec_len, rg_long_len = 8, 160
            rg_chunk_steps, rg_prefill_chunk, rg_page = 4, 16, 16
            rg_max = rg_long_len + 32
            rg_rng = np.random.default_rng(13)
            rg_dec_prompts = [
                rg_rng.integers(1, cfg.vocab_size, rg_dec_len).tolist()
                for _ in range(RG_SLOTS - 1)
            ]
            rg_long = rg_rng.integers(
                1, cfg.vocab_size, rg_long_len
            ).tolist()
            eng_rg = GenerationEngine(
                cfg, params, seq_buckets=(16, rg_max), batch_buckets=(1,),
                max_seq_len=rg_max,
            )

            def ragged_leg() -> dict:
                ce = _RCE(
                    eng_rg, max_slots=RG_SLOTS, page_size=rg_page,
                    chunk_steps=rg_chunk_steps,
                    prefill_chunk=rg_prefill_chunk,
                )
                try:
                    # warm every program this leg can hit: a multi-chunk
                    # admission compiles the step program(s), then drains
                    w = ce.submit(
                        rg_rng.integers(1, cfg.vocab_size, 40).tolist(),
                        max_new_tokens=4, seed=0,
                    )
                    ce.run_until_idle()
                    assert w.finished
                    decs = [
                        ce.submit(p, max_new_tokens=200, seed=i)
                        for i, p in enumerate(rg_dec_prompts)
                    ]
                    # occupancy-matched steady state: a 4th DECODING slot
                    # stands where the admission will later go, so both
                    # phases gather 4 slots' worth of real pages (at
                    # steady the empty slot would re-gather the cache-hot
                    # scratch page — flattering the baseline on CPU)
                    helper = ce.submit(
                        rg_rng.integers(
                            1, cfg.vocab_size, rg_dec_len
                        ).tolist(),
                        max_new_tokens=1 + 11 * rg_chunk_steps, seed=99,
                    )
                    ce.step_chunk()  # admit; first tokens out
                    steady: list[float] = []
                    for _ in range(8):
                        t0 = time.perf_counter()
                        ce.step_chunk()
                        steady.append(time.perf_counter() - t0)
                    while not helper.finished:  # free the 4th slot
                        ce.step_chunk()
                    long_req = ce.submit(rg_long, max_new_tokens=4, seed=9)
                    during: list[float] = []
                    while long_req.slot < 0 or (
                        not long_req.finished
                        and long_req.prefill_pos < rg_long_len
                    ):
                        t0 = time.perf_counter()
                        ce.step_chunk()
                        during.append(time.perf_counter() - t0)
                    emitted = [len(d.tokens) for d in decs]
                finally:
                    ce.close()
                return {
                    # per-token decode ITL: chunk wall time / steps
                    "steady_itl_ms": float(np.median(steady))
                    / rg_chunk_steps * 1e3,
                    "during_itl_ms": float(np.median(during))
                    / rg_chunk_steps * 1e3,
                    "prefill_steps": len(during),
                    "dec_tokens": emitted,
                }

            rg_uni = ragged_leg()
            del eng_rg
            ragged_extra = {
                "ragged_slots": RG_SLOTS,
                "ragged_long_prompt": rg_long_len,
                "ragged_steady_itl_ms": round(rg_uni["steady_itl_ms"], 2),
                "ragged_during_prefill_itl_ms": round(
                    rg_uni["during_itl_ms"], 2
                ),
                # THE seam metric: decode ITL while a co-resident prefill
                # is in flight, as a multiple of decode-only steady state
                "ragged_itl_ratio": round(
                    rg_uni["during_itl_ms"]
                    / max(rg_uni["steady_itl_ms"], 1e-9), 2
                ),
                **(
                    {}
                    if on_tpu
                    else {
                        "ragged_note": (
                            "CPU fallback: the unified step's fixed-shape "
                            "block makes its per-step cost ~constant by "
                            "construction here, so the flat ITL ratio is "
                            "faithful but the absolute win is understated "
                            "— on TPU the ragged kernel's cost follows "
                            "each slot's live tokens (pages past "
                            "start+n_valid skip compute), which is where "
                            "the MXU-occupancy gain on mixed batches "
                            "lives. Both phases run at equal slot "
                            "occupancy (a 4th decoder stands in at steady "
                            "state) so CPU page-gather locality can't "
                            "skew the ratio. The legacy baseline's seam "
                            "ratio lives in BENCH_r06 (path retired)."
                        )
                    }
                ),
            }
        except Exception as e:
            ragged_extra = {"ragged_error": str(e)[:500]}

    # ---- quantized paged KV: capacity at a fixed page budget --------------
    # The int8 page pool's lever is BYTES, not wall-clock: at a page
    # budget where fp KV admits N slots, int8 admits ~2N (bf16: 2*hd vs
    # hd+4 bytes per (position, head) incl. the f32 scales; on the f32
    # CPU-fallback cfg the ratio is larger still) and holds ~2x the
    # prefix-cache resident pages. CPU fallback can't show the HBM
    # bandwidth win, so the leg asserts the STRUCTURAL win: actually
    # admit the occupancy-matched load on both engines and count
    # admitted slots + resident pages, with page conservation as teeth.
    kv_extra = {}
    if on_tpu and _budget_left() < 400:
        kv_extra = {"kv_quant_skipped": "low time budget"}
    else:
        try:
            from tensorlink_tpu.engine.continuous import (
                ContinuousEngine as _QCE,
            )

            KV_SLOTS_F = 4
            kv_page, kv_chunk, kv_pc = 16, 2, 16
            kv_max = 96
            eng_kv = GenerationEngine(
                cfg, params, seq_buckets=(32, kv_max), batch_buckets=(1,),
                max_seq_len=kv_max,
            )

            def pool_bytes(ce):
                c = ce.cache
                b = c.k.nbytes + c.v.nbytes
                if c.quantized:
                    b += c.k_scale.nbytes + c.v_scale.nbytes
                return b

            def mk(slots, quant):
                return _QCE(
                    eng_kv, max_slots=slots, page_size=kv_page,
                    chunk_steps=kv_chunk, prefill_chunk=kv_pc,
                    kv_quant="int8" if quant else "none",
                )

            # closed-form pool sizing (pool bytes are a pure function of
            # the page geometry — no need to allocate probe pools): per
            # physical page, k+v cost 2·L·Hkv·page·itemsize·hd in the
            # model dtype and 2·L·Hkv·page·(hd + 4) in int8+f32-scales
            n_pp = -(-kv_max // kv_page)
            row = 2 * cfg.n_layers * cfg.n_kv_heads * kv_page
            fp_page = row * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
            q_page = row * (cfg.head_dim + 4)
            budget_bytes = (1 + KV_SLOTS_F * n_pp) * fp_page
            # the largest int8 engine whose pool fits the SAME byte
            # budget (scale overhead means strictly < the dtype ratio)
            slots_q = min(
                int((budget_bytes // q_page - 1) // n_pp), 8 * KV_SLOTS_F
            )
            ce_f = mk(KV_SLOTS_F, False)
            ce_q = mk(slots_q, True)
            assert pool_bytes(ce_f) == budget_bytes, "sizing math drifted"
            assert pool_bytes(ce_q) <= budget_bytes, "int8 pool over budget"
            kv_rng = np.random.default_rng(17)

            def capacity_leg(ce) -> dict:
                # occupancy: flood 2x the int8 slot count; peak live
                # slots == what this pool can admit concurrently
                flood = [
                    ce.submit(
                        kv_rng.integers(1, cfg.vocab_size, 8).tolist(),
                        max_new_tokens=2 * kv_chunk, seed=i,
                    )
                    for i in range(2 * slots_q)
                ]
                ce.step_chunk(admit_only=True)
                peak = ce.live_slots
                ce.run_until_idle()
                assert all(r.finished for r in flood)
                # residency: distinct 64-token prompts promote 4 full
                # pages each; the pool bounds how many stay resident
                for i in range(slots_q):
                    ce.submit(
                        kv_rng.integers(1, cfg.vocab_size, 64).tolist(),
                        max_new_tokens=2, seed=100 + i,
                    )
                    ce.run_until_idle()
                ce.check_page_conservation()
                snap = ce.serving_snapshot()
                return {
                    "peak_slots": int(peak),
                    "resident": int(snap["prefix_resident_pages"]),
                    "pages": int(snap["kv_pages_total"]),
                    "page_bytes": int(snap["kv_page_bytes"]),
                }

            try:
                m_f = capacity_leg(ce_f)
                m_q = capacity_leg(ce_q)
            finally:
                ce_f.close()
                ce_q.close()
            del eng_kv
            kv_extra = {
                "kv_quant_page_budget_mb": round(budget_bytes / 2**20, 2),
                "kv_fp_slots": m_f["peak_slots"],
                "kv_int8_slots": m_q["peak_slots"],
                "kv_slots_ratio": round(
                    m_q["peak_slots"] / max(m_f["peak_slots"], 1), 2
                ),
                "kv_fp_resident_pages": m_f["resident"],
                "kv_int8_resident_pages": m_q["resident"],
                "kv_residency_ratio": round(
                    m_q["resident"] / max(m_f["resident"], 1), 2
                ),
                "kv_fp_page_bytes": m_f["page_bytes"],
                "kv_int8_page_bytes": m_q["page_bytes"],
                **(
                    {}
                    if on_tpu
                    else {
                        "kv_note": (
                            "CPU fallback: the capacity ratios are "
                            "structural (real pools, real admissions, "
                            "conservation-checked) and faithful — what "
                            "CPU canNOT show is the decode-bandwidth win "
                            "of streaming half the KV bytes per step; "
                            "that needs the TPU window (tpu_escalation "
                            "note). The f32 CPU cfg overstates the "
                            "slots ratio vs bf16 (4x payload shrink vs "
                            "2x); the >=1.8x bar is the bf16 claim."
                        )
                    }
                ),
            }
        except Exception as e:
            kv_extra = {"kv_quant_error": str(e)[:500]}

    # ---- packed int4 KV: capacity vs int8 at a byte-matched budget --------
    # The second density step: int4 packs two values per byte at int8's
    # scale granularity, so at a page budget where int8 admits N slots,
    # int4 admits ~2N (page bytes: hd/2 + 4 vs hd + 4 per (position,
    # head)). Same structural protocol as the int8 leg: real pools, real
    # admissions, conservation-checked; the >=1.8x slots bar vs INT8 is
    # what test_bench_smoke pins.
    kv4_extra = {}
    if on_tpu and _budget_left() < 400:
        kv4_extra = {"kv_int4_skipped": "low time budget"}
    else:
        try:
            from tensorlink_tpu.engine.continuous import (
                ContinuousEngine as _QCE4,
            )

            KV4_SLOTS_8 = 8
            kv_page, kv_chunk, kv_pc = 16, 2, 16
            kv_max = 96
            eng_kv4 = GenerationEngine(
                cfg, params, seq_buckets=(32, kv_max), batch_buckets=(1,),
                max_seq_len=kv_max,
            )

            def pool_bytes4(ce):
                c = ce.cache
                b = c.k.nbytes + c.v.nbytes
                if c.quantized:
                    b += c.k_scale.nbytes + c.v_scale.nbytes
                return b

            n_pp = -(-kv_max // kv_page)
            row = 2 * cfg.n_layers * cfg.n_kv_heads * kv_page
            q8_page = row * (cfg.head_dim + 4)
            q4_page = row * (cfg.head_dim // 2 + 4)
            budget_bytes = (1 + KV4_SLOTS_8 * n_pp) * q8_page
            slots_4 = min(
                int((budget_bytes // q4_page - 1) // n_pp),
                4 * KV4_SLOTS_8,
            )
            ce_8 = _QCE4(
                eng_kv4, max_slots=KV4_SLOTS_8, page_size=kv_page,
                chunk_steps=kv_chunk, prefill_chunk=kv_pc, kv_quant="int8",
            )
            ce_4 = _QCE4(
                eng_kv4, max_slots=slots_4, page_size=kv_page,
                chunk_steps=kv_chunk, prefill_chunk=kv_pc, kv_quant="int4",
            )
            assert pool_bytes4(ce_8) == budget_bytes, "sizing math drifted"
            assert pool_bytes4(ce_4) <= budget_bytes, "int4 pool over budget"
            kv4_rng = np.random.default_rng(19)

            def capacity_leg4(ce, flood_n) -> dict:
                flood = [
                    ce.submit(
                        kv4_rng.integers(1, cfg.vocab_size, 8).tolist(),
                        max_new_tokens=2 * kv_chunk, seed=i,
                    )
                    for i in range(flood_n)
                ]
                ce.step_chunk(admit_only=True)
                peak = ce.live_slots
                ce.run_until_idle()
                assert all(r.finished for r in flood)
                # residency flood sized to SATURATE the larger (int4)
                # pool too — otherwise its resident count reflects the
                # offered load, not the capacity being measured
                for i in range(2 * slots_4):
                    ce.submit(
                        kv4_rng.integers(1, cfg.vocab_size, 64).tolist(),
                        max_new_tokens=2, seed=100 + i,
                    )
                    ce.run_until_idle()
                ce.check_page_conservation()
                snap = ce.serving_snapshot()
                return {
                    "peak_slots": int(peak),
                    "resident": int(snap["prefix_resident_pages"]),
                    "page_bytes": int(snap["kv_page_bytes"]),
                }

            try:
                m_8 = capacity_leg4(ce_8, 2 * slots_4)
                m_4 = capacity_leg4(ce_4, 2 * slots_4)
            finally:
                ce_8.close()
                ce_4.close()
            del eng_kv4
            kv4_extra = {
                "kv_int4_page_budget_mb": round(budget_bytes / 2**20, 2),
                "kv_int4_slots": m_4["peak_slots"],
                "kv_int4_vs_int8_slots": m_8["peak_slots"],
                # the headline ratio: int4 capacity over INT8 (not fp) at
                # the same byte budget — the density step this leg lands
                "kv_int4_slots_ratio": round(
                    m_4["peak_slots"] / max(m_8["peak_slots"], 1), 2
                ),
                "kv_int4_resident_pages": m_4["resident"],
                "kv_int4_residency_ratio": round(
                    m_4["resident"] / max(m_8["resident"], 1), 2
                ),
                "kv_int4_page_bytes": m_4["page_bytes"],
                **(
                    {}
                    if on_tpu
                    else {
                        "kv_int4_note": (
                            "CPU fallback: structural ratios (real pools, "
                            "real admissions, conservation-checked); the "
                            "int4-vs-int8 page-byte ratio (hd+4 over "
                            "hd/2+4) is dtype-independent, so the >=1.8x "
                            "bar transfers to bf16 — the decode-bandwidth "
                            "win of quarter-size fetches needs the TPU "
                            "window (tpu_escalation note)."
                        )
                    }
                ),
            }
        except Exception as e:
            kv4_extra = {"kv_int4_error": str(e)[:500]}

    # ---- multi-tenant co-hosting: two models, ONE page pool ---------------
    # The density dividend spent on tenancy: two tenant engines share one
    # int4 page pool under per-model quotas. The leg floods both tenants
    # at once, checks per-tenant page conservation at every chunk
    # boundary (the ZERO-cross-tenant-leaks claim), and reports quota
    # occupancy + cross-tenant preemptions. Deterministic and structural
    # — faithful on CPU.
    cot_extra = {}
    if on_tpu and _budget_left() < 300:
        cot_extra = {"cotenancy_skipped": "low time budget"}
    else:
        try:
            from tensorlink_tpu.engine.continuous import (
                ContinuousEngine as _TCE,
            )
            from tensorlink_tpu.engine.paged import SharedPagePool

            cot_page, cot_chunk, cot_pc = 16, 2, 16
            cot_max = 64
            eng_cot = GenerationEngine(
                cfg, params, seq_buckets=(32, cot_max), batch_buckets=(1,),
                max_seq_len=cot_max,
            )
            n_pp_cot = -(-cot_max // cot_page)
            pool_pages = 6 * n_pp_cot  # ~6 concurrent slots' worth, shared
            quota = 4 * n_pp_cot  # each tenant may hold at most 4 slots'
            pool = SharedPagePool(
                cfg, pool_pages, page_size=cot_page, kv_quant="int4",
            )
            tenants = {
                mid: _TCE(
                    eng_cot, max_slots=4, page_size=cot_page,
                    chunk_steps=cot_chunk, prefill_chunk=cot_pc,
                    kv_quant="int4", pool=pool, model_id=mid,
                    page_quota=quota,
                )
                for mid in ("tenant_a", "tenant_b")
            }
            cot_rng = np.random.default_rng(23)
            reqs = {mid: [] for mid in tenants}
            try:
                # staggered two-tenant flood: B's work is best_effort so
                # A's interactive admissions exercise the cross-model
                # preemption rung when the shared free list runs dry
                for i in range(6):
                    for mid, ce in tenants.items():
                        reqs[mid].append(ce.submit(
                            cot_rng.integers(
                                1, cfg.vocab_size, 8 + 4 * (i % 3)
                            ).tolist(),
                            max_new_tokens=2 * cot_chunk, seed=10 * i,
                            priority=(
                                "interactive" if mid == "tenant_a"
                                else "best_effort"
                            ),
                        ))
                peak_used = {mid: 0 for mid in tenants}
                leaks = 0
                # list comprehension, NOT a generator: any() would
                # short-circuit and starve the second tenant's step
                while any([ce.step_chunk() for ce in tenants.values()]):
                    # the leg's teeth: per-tenant conservation at every
                    # boundary — a cross-tenant leak fails the bench run
                    pool.check_page_conservation()
                    for mid, ce in tenants.items():
                        peak_used[mid] = max(peak_used[mid], ce.alloc.used)
                        assert ce.alloc.used <= ce.alloc.quota, mid
                served = {
                    mid: sum(1 for r in rs if r.finished)
                    for mid, rs in reqs.items()
                }
                assert all(
                    n == len(reqs[mid]) for mid, n in served.items()
                ), f"co-tenancy dropped requests: {served}"
                pool.check_page_conservation()
            finally:
                for ce in tenants.values():
                    ce.close()
            del eng_cot
            cot_extra = {
                "cotenancy_tenants": 2,
                "cotenancy_pool_pages": pool_pages,
                "cotenancy_quota": quota,
                "cotenancy_served": sum(served.values()),
                "cotenancy_peak_used_a": peak_used["tenant_a"],
                "cotenancy_peak_used_b": peak_used["tenant_b"],
                "cotenancy_cross_preemptions": pool.cross_preemptions,
                "cotenancy_cache_reclaims": pool.cache_reclaims,
                "cotenancy_conservation_ok": True,
            }
        except Exception as e:
            cot_extra = {"cotenancy_error": str(e)[:500]}

    # ---- live slot migration: drain a worker mid-stream -------------------
    # The robustness leg's claim is ZERO dropped streams (bit-identical
    # resumes — deterministic, faithful on CPU) plus the latency shape:
    # a page-shipped resume skips the re-prefill compute entirely, so its
    # time-to-next-token should beat the re-prefill rung's.
    mig_extra = {}
    if on_tpu and _budget_left() < 300:
        mig_extra = {"migration_skipped": "low time budget"}
    else:
        try:
            from tensorlink_tpu.engine.continuous import (
                ContinuousEngine as _MCE,
            )

            mg_page, mg_chunk, mg_pc = 16, 4, 32
            mg_max = 192
            eng_mg = GenerationEngine(
                cfg, params, seq_buckets=(32, mg_max), batch_buckets=(1,),
                max_seq_len=mg_max,
            )
            mg_rng = np.random.default_rng(23)
            N_MG = 3
            mg_prompts = [
                mg_rng.integers(1, cfg.vocab_size, 48).tolist()
                for _ in range(N_MG)
            ]
            mg_budget = 48

            def mk_mg():
                return _MCE(
                    eng_mg, max_slots=N_MG + 1, page_size=mg_page,
                    chunk_steps=mg_chunk, prefill_chunk=mg_pc,
                )

            def baseline(i):
                ce = mk_mg()
                try:
                    r = ce.submit(
                        mg_prompts[i], max_new_tokens=mg_budget, seed=i,
                    )
                    ce.run_until_idle()
                    return list(r.tokens)
                finally:
                    ce.close()

            bases = [baseline(i) for i in range(N_MG)]

            def resume_ms(dst, moved, adopt):
                t0 = time.perf_counter()
                r2 = dst.submit(
                    moved.prompt + moved.tokens,
                    max_new_tokens=moved.budget - len(moved.tokens),
                    seed=moved.seed,
                    start_step=moved.start_step + len(moved.tokens),
                    adopt=adopt,
                )
                while not r2.tokens and not r2.finished:
                    dst.step_chunk()
                return (time.perf_counter() - t0) * 1e3, r2

            def drain_leg(page_ship: bool):
                """N co-resident decode streams on a source engine; drain
                them all to a destination mid-stream. Returns (per-stream
                resume-to-next-token ms, dropped count)."""
                src, dst = mk_mg(), mk_mg()
                try:
                    # warm every program both engines will run (incl. the
                    # gather/scatter page movers via a throwaway handoff)
                    w = src.submit(
                        mg_rng.integers(1, cfg.vocab_size, 8).tolist(),
                        max_new_tokens=mg_chunk + 1, seed=99,
                    )
                    while len(w.tokens) < 1:
                        src.step_chunk()
                    src.freeze_slot(w.slot)
                    wb = src.export_slot(w.slot)
                    assert dst.stage_migration("warm", wb)
                    wm = src.commit_migration(w.slot)
                    _, wr = resume_ms(dst, wm, "warm")
                    while not wr.finished:
                        dst.step_chunk()
                    reqs = [
                        src.submit(
                            mg_prompts[i], max_new_tokens=mg_budget,
                            seed=i,
                            # trace the page-ship leg's source streams:
                            # their first-token path decomposes like any
                            # serving request, and the freeze/export/
                            # commit spans ride the same trace ids
                            trace_id=(
                                f"bench-mg-{i}" if page_ship else None
                            ),
                        )
                        for i in range(N_MG)
                    ]
                    while any(len(r.tokens) < 8 for r in reqs):
                        src.step_chunk()
                    lat, done = [], []
                    src.begin_drain()
                    for i, r in enumerate(reqs):
                        mid = f"mg{i}"
                        if page_ship:
                            src.freeze_slot(r.slot)
                            chain, limit = src.migration_chain(r.slot)
                            blob = src.export_slot(
                                r.slot,
                                n_skip=dst.resident_prefix_pages(
                                    chain, limit
                                ),
                            )
                            assert dst.stage_migration(mid, blob)
                            moved = src.commit_migration(r.slot)
                        else:
                            moved = src.shed_slot(r.slot)
                            mid = None
                        src.check_page_conservation()
                        dst.check_page_conservation()
                        ms, r2 = resume_ms(dst, moved, mid)
                        lat.append(ms)
                        done.append((moved, r2))
                    dst.run_until_idle()
                    dropped = 0
                    for i, (moved, r2) in enumerate(done):
                        full = moved.tokens + r2.tokens
                        if not r2.finished or full != bases[i]:
                            dropped += 1
                    return lat, dropped
                finally:
                    src.close()
                    dst.close()

            mig_lat, mig_drop = drain_leg(page_ship=True)
            rep_lat, rep_drop = drain_leg(page_ship=False)
            del eng_mg
            assert mig_drop == 0 and rep_drop == 0, (mig_drop, rep_drop)
            mig_ms = float(np.median(mig_lat))
            rep_ms = float(np.median(rep_lat))
            mg_decomp = trace_decomp(
                [f"bench-mg-{i}" for i in range(N_MG)]
            ) or {}
            mig_extra = {
                "migration_streams": N_MG,
                "migration_dropped_streams": int(mig_drop),
                "migration_resume_ms": round(mig_ms, 2),
                "migration_reprefill_resume_ms": round(rep_ms, 2),
                # trace-derived TTFT decomposition of the migrated
                # streams' source-side admission (parts sum to
                # migration_ttft_trace_ms by construction)
                "migration_queue_ms": mg_decomp.get("queue_ms", 0.0),
                "migration_prefill_ms": mg_decomp.get("prefill_ms", 0.0),
                "migration_first_decode_ms": mg_decomp.get(
                    "first_decode_ms", 0.0
                ),
                "migration_ttft_trace_ms": mg_decomp.get(
                    "ttft_trace_ms", 0.0
                ),
                # >1 means page shipping resumed faster than re-prefill
                "migration_resume_speedup": round(
                    rep_ms / max(mig_ms, 1e-9), 2
                ),
                **(
                    {}
                    if on_tpu
                    else {
                        "migration_note": (
                            "CPU fallback: zero-dropped + bit-identical "
                            "resumes are deterministic and faithful "
                            "here; the resume-latency ratio is "
                            "wall-clock on a tiny model where the "
                            "skipped re-prefill is cheap, so the "
                            "magnitude understates the TPU win (a real "
                            "prompt's re-prefill burns seconds of MXU "
                            "time; a page adoption is a handful of HBM "
                            "writes). tpu_escalation streak logic "
                            "applies as for every CPU round."
                        )
                    }
                ),
            }
        except Exception as e:
            mig_extra = {"migration_error": str(e)[:500]}

    # ---- disaggregated prefill/decode pools (ROADMAP item 1) --------------
    # The claim: on a 1-prefill + 1-decode pool, interactive decode ITL
    # stays ~flat through a long-prompt flood (the decode engine's steps
    # carry only 1-token rows + page adoptions), while the single-pool
    # baseline's steps carry the flood's prefill grants and degrade. The
    # streams themselves are bit-identical to single-pool (deterministic,
    # faithful on CPU); plus the per-phase TTFT decomposition with the
    # new `handoff` span (queue → prefill → handoff → first decode at the
    # destination, summing to the trace TTFT).
    disagg_extra = {}
    if on_tpu and _budget_left() < 300:
        disagg_extra = {"disagg_skipped": "low time budget"}
    else:
        try:
            from tensorlink_tpu.engine.continuous import (
                ContinuousEngine as _DCE,
            )

            dz_page, dz_chunk, dz_pc = 16, 4, 32
            dz_max = 256
            eng_dz = GenerationEngine(
                cfg, params, seq_buckets=(32, dz_max), batch_buckets=(1,),
                max_seq_len=dz_max,
            )
            dz_rng = np.random.default_rng(31)
            N_INT, N_FLOOD, FLOOD_TOTAL = 3, 4, 6
            int_prompts = [
                dz_rng.integers(1, cfg.vocab_size, 12).tolist()
                for _ in range(N_INT)
            ]
            flood_len, int_budget, flood_budget = 160, 120, 4
            flood_prompts = [
                dz_rng.integers(1, cfg.vocab_size, flood_len).tolist()
                for _ in range(FLOOD_TOTAL)
            ]

            def mk_dz(handoff=False):
                return _DCE(
                    eng_dz, max_slots=N_INT + N_FLOOD + 1,
                    page_size=dz_page, chunk_steps=dz_chunk,
                    prefill_chunk=dz_pc,
                    handoff_after_prefill=handoff,
                    worker_role="prefill" if handoff else "mixed",
                )

            def dz_solo(prompt, budget, seed):
                ce = mk_dz()
                r = ce.submit(prompt, max_new_tokens=budget, seed=seed)
                ce.run_until_idle()
                out = list(r.tokens)
                ce.close()
                return out

            int_solos = [
                dz_solo(p, int_budget, i) for i, p in enumerate(int_prompts)
            ]

            def ship(src, dst, slot, mig_id):
                chain, limit = src.migration_chain(slot)
                blob = src.export_slot(
                    slot, n_skip=dst.resident_prefix_pages(chain, limit)
                )
                assert dst.stage_migration(mig_id, blob)
                return src.commit_handoff(slot)

            # warm every program either pool will run, page movers incl.
            warm_src, warm_dst = mk_dz(True), mk_dz()
            w = warm_src.submit(
                dz_rng.integers(1, cfg.vocab_size, 40).tolist(),
                max_new_tokens=4, seed=99, handoff=True,
            )
            for _ in range(20):
                warm_src.step_chunk()
                man = warm_src.handoff_manifest()
                if man:
                    moved = ship(warm_src, warm_dst, man[0][0], "warm")
                    wr = warm_dst.submit(
                        moved.prompt, max_new_tokens=moved.budget,
                        seed=moved.seed, adopt="warm",
                    )
                    break
            warm_dst.run_until_idle()
            assert wr.finished and w.tokens == []
            warm_src.close()
            warm_dst.close()

            def flood_driver(submit_fn, live):
                """Keep N_FLOOD long prompts in flight until FLOOD_TOTAL
                have been submitted; returns (poke, window_open)."""
                state = {"next": 0, "reqs": []}

                def poke():
                    state["reqs"] = [r for r in state["reqs"] if live(r)]
                    while (
                        state["next"] < FLOOD_TOTAL
                        and len(state["reqs"]) < N_FLOOD
                    ):
                        state["reqs"].append(
                            submit_fn(flood_prompts[state["next"]],
                                      state["next"])
                        )
                        state["next"] += 1

                def window_open():
                    return state["next"] < FLOOD_TOTAL or any(
                        live(r) for r in state["reqs"]
                    )

                return poke, window_open

            # -- single pool: one engine serves interactive AND flood ----
            sp = mk_dz()
            sp_int = [
                sp.submit(p, max_new_tokens=int_budget, seed=i)
                for i, p in enumerate(int_prompts)
            ]
            sp.step_chunk()  # admit + first tokens
            sp_steady: list[float] = []
            for _ in range(8):
                t0 = time.perf_counter()
                sp.step_chunk()
                sp_steady.append(time.perf_counter() - t0)

            def sp_live(r):
                # a flood request loads the pool while it's mid-prefill
                return not r.finished and r.prefill_pos < flood_len

            sp_poke, sp_window = flood_driver(
                lambda p, i: sp.submit(
                    p, max_new_tokens=flood_budget, seed=100 + i
                ),
                sp_live,
            )
            sp_during: list[float] = []
            sp_poke()
            while sp_window():
                t0 = time.perf_counter()
                sp.step_chunk()
                sp_during.append(time.perf_counter() - t0)
                sp_poke()
            sp.run_until_idle()
            sp_streams = [list(r.tokens) for r in sp_int]
            sp.close()

            # -- disaggregated: prefill engine feeds a decode engine -----
            src, dst = mk_dz(True), mk_dz()
            t_sub = {}
            t_first = {}
            dz_done = {}
            n_ship = [0]

            def resolve_handoffs():
                for slot, req in src.handoff_manifest():
                    mid = f"dz{n_ship[0]}"
                    n_ship[0] += 1
                    moved = ship(src, dst, slot, mid)
                    tid = moved.trace_id or None

                    def cb(_t, key=id(moved)):
                        if key not in t_first:
                            t_first[key] = time.perf_counter()
                        return False

                    r2 = dst.submit(
                        moved.prompt, max_new_tokens=moved.budget,
                        seed=moved.seed, adopt=mid, trace_id=tid,
                        stream_cb=cb if tid else None,
                    )
                    dz_done[id(moved)] = (moved, r2)

            dz_int = []
            for i, p in enumerate(int_prompts):
                t_sub[f"bench-dz-{i}"] = time.perf_counter()
                dz_int.append(src.submit(
                    p, max_new_tokens=int_budget, seed=i, handoff=True,
                    trace_id=f"bench-dz-{i}",
                ))
            # hand the interactive streams to the decode pool, reach
            # steady decode there
            while len(dz_done) < N_INT:
                src.step_chunk()
                resolve_handoffs()
            dst.step_chunk()
            for _ in range(4):
                dst.step_chunk()

            def dz_live(r):
                key = id(r)
                if key in dz_done:  # handed off: load left the prefill pool
                    return False
                return not r.finished and r.prefill_pos < flood_len - 1

            dz_poke, dz_window = flood_driver(
                lambda p, i: src.submit(
                    p, max_new_tokens=flood_budget, seed=100 + i,
                    handoff=True,
                ),
                dz_live,
            )
            dz_during: list[float] = []
            dz_poke()
            while dz_window():
                # the prefill pool chews the flood (and ships completed
                # prefills); its step time is NOT the decode pool's ITL
                src.step_chunk()
                resolve_handoffs()
                dz_poke()
                t0 = time.perf_counter()
                dst.step_chunk()
                dz_during.append(time.perf_counter() - t0)
            while src.has_work():
                src.step_chunk()
                resolve_handoffs()
            dst.run_until_idle()
            dz_streams = [
                list(dz_done[id(r)][1].tokens) for r in dz_int
            ]
            handoffs_done = int(src.stats["handoffs_completed"])
            assert src.serving_snapshot()["pages_in_transit"] == 0
            src.close()
            dst.close()
            del eng_dz

            exact = all(
                s == solo for s, solo in zip(sp_streams, int_solos)
            ) and all(
                s == solo for s, solo in zip(dz_streams, int_solos)
            )
            steady_itl = float(np.median(sp_steady)) / dz_chunk * 1e3
            sp_itl = float(np.median(sp_during)) / dz_chunk * 1e3
            dz_itl = float(np.median(dz_during)) / dz_chunk * 1e3
            if on_tpu:
                # the isolation teeth, armed where the effect is real:
                # the ragged kernel's cost follows total live tokens, so
                # a single-pool step carrying the flood's prefill grants
                # must cost measurably more than decode-only steady state
                # while the decode pool (1-token rows + adoptions only)
                # stays ~flat. The CPU reference path computes the full
                # fixed-shape block either way (see disagg_note), so the
                # contrast is asserted on TPU rounds only.
                assert dz_itl / max(steady_itl, 1e-9) <= 2.0, (
                    dz_itl, steady_itl,
                )
                assert sp_itl > 1.2 * dz_itl, (sp_itl, dz_itl)

            # per-phase TTFT decomposition: queue_wait + prefill +
            # handoff on the SOURCE, then the destination's first_token
            # span (resubmit → first draw, which covers its queue +
            # adoption) — contiguous by construction, so the parts sum
            # to the trace TTFT; the externally-measured wall TTFT
            # (submit at the source → first token at the destination)
            # checks the sum from outside the tracer.
            parts = []
            walls = []
            for i in range(N_INT):
                tid = f"bench-dz-{i}"
                first: dict = {}
                for s in get_tracer().collect(tid):  # ts-ordered
                    if "dur_ms" in s and s["name"] not in first:
                        first[s["name"]] = float(s["dur_ms"])
                if "first_token" not in first:
                    continue
                parts.append((
                    first.get("queue_wait", 0.0),
                    first.get("prefill", 0.0),
                    first.get("handoff", 0.0),
                    first["first_token"],
                ))
                key = id(dz_done[id(dz_int[i])][0])
                walls.append((t_first[key] - t_sub[tid]) * 1e3)
            q, p_, h, f = (
                float(np.mean([x[i] for x in parts])) for i in range(4)
            )
            disagg_extra = {
                "disagg_interactive_streams": N_INT,
                "disagg_flood_prompts": FLOOD_TOTAL,
                "disagg_flood_prompt_len": flood_len,
                "disagg_handoffs": handoffs_done,
                "disagg_streams_exact": bool(exact),
                "disagg_steady_itl_ms": round(steady_itl, 3),
                "disagg_single_pool_itl_ms": round(sp_itl, 3),
                "disagg_decode_pool_itl_ms": round(dz_itl, 3),
                # THE isolation metrics: interactive ITL during the flood
                # as a multiple of decode-only steady state — single pool
                # degrades (its steps carry the flood's prefill grants),
                # the decode pool stays ~flat
                "disagg_single_pool_itl_ratio": round(
                    sp_itl / max(steady_itl, 1e-9), 2
                ),
                "disagg_itl_ratio": round(
                    dz_itl / max(steady_itl, 1e-9), 2
                ),
                "disagg_queue_ms": round(q, 3),
                "disagg_prefill_ms": round(p_, 3),
                "disagg_handoff_ms": round(h, 3),
                "disagg_first_decode_ms": round(f, 3),
                "disagg_ttft_trace_ms": round(q + p_ + h + f, 3),
                "disagg_ttft_wall_ms": round(float(np.mean(walls)), 3),
                **(
                    {}
                    if on_tpu
                    else {
                        "disagg_note": (
                            "CPU fallback: stream bit-identity, the "
                            "handoff count, and the TTFT decomposition "
                            "are deterministic and faithful here. The "
                            "ITL ratio PAIR is not: the CPU reference "
                            "step computes the full fixed-shape packed "
                            "block whether its rows are a flood's "
                            "prefill grants or padding (the ragged "
                            "leg's documented property), so BOTH ratios "
                            "sit ~1.0 and the single-pool degradation "
                            "the split removes is invisible. On TPU the "
                            "ragged kernel's cost follows total live "
                            "tokens — a single-pool step carrying the "
                            "flood costs every co-resident decode slot "
                            "real MXU time — and the in-leg assertion "
                            "(decode-pool ~flat, single-pool > 1.2x "
                            "above it) arms on exactly those rounds. "
                            "tpu_escalation streak logic applies as "
                            "for every CPU round."
                        )
                    }
                ),
            }
        except Exception as e:
            disagg_extra = {"disagg_error": str(e)[:500]}

    # ---- fleet serving (ROADMAP item 2, the "millions of users" step) -----
    # 1 vs N engine replicas behind the cache-/SLO-aware FleetRouter under
    # a many-session flood: Zipf-distributed shared prefixes, mixed
    # priority classes, and mid-flood churn on the N-replica leg — a
    # replica JOINS, one rolling-DEPLOYS (drain → rebuild → rejoin, via
    # the autopilot), and one is KILLED (dispatches fail over). The bars:
    # zero dropped streams, every stream bit-identical to its solo run
    # (greedy — placement is not part of the determinism contract),
    # interactive TTFT p95 no worse than the queue-bound single replica.
    # Aggregate-throughput linearity is a TPU-rounds claim (N replicas on
    # ONE CPU share the core; see fleet_note).
    fleet_extra = {}
    if on_tpu and _budget_left() < 300:
        fleet_extra = {"fleet_skipped": "low time budget"}
    else:
        try:
            import threading as _fth

            from tensorlink_tpu.engine.continuous import (
                ContinuousEngine as _FCE,
            )
            from tensorlink_tpu.fleet.autopilot import (
                EngineFleetActions,
                FleetAutopilot,
            )
            from tensorlink_tpu.fleet.router import FleetRouter
            from tensorlink_tpu.ml.batching import ContinuousBatcher as _FCB

            fl_page, fl_chunk, fl_pc, fl_slots = 16, 4, 32, 6
            fl_max = 128
            eng_fl = GenerationEngine(
                cfg, params, seq_buckets=(32, fl_max), batch_buckets=(1,),
                max_seq_len=fl_max,
            )
            flr = np.random.default_rng(47)
            N_REPL, N_SESS = 3, 30
            n_groups, prefix_len, tail_len, fl_budget = 6, 32, 8, 6
            shared = [
                flr.integers(1, cfg.vocab_size, prefix_len).tolist()
                for _ in range(n_groups)
            ]
            zipf = 1.0 / np.arange(1, n_groups + 1, dtype=np.float64)
            zipf /= zipf.sum()
            sess_group = flr.choice(n_groups, size=N_SESS, p=zipf)
            sess_cls = [
                ("interactive", "batch", "best_effort")[i % 3]
                for i in range(N_SESS)
            ]
            sess_prompts = [
                shared[g] + flr.integers(
                    1, cfg.vocab_size, tail_len
                ).tolist()
                for g in sess_group
            ]

            def fl_engine():
                return _FCE(
                    eng_fl, max_slots=fl_slots, page_size=fl_page,
                    chunk_steps=fl_chunk, prefill_chunk=fl_pc,
                )

            def fl_batcher():
                return _FCB(engine=fl_engine(), eos_ids=[])

            def fl_solo(p):
                ce = fl_engine()
                r = ce.submit(p, max_new_tokens=fl_budget, seed=0)
                ce.run_until_idle()
                out = list(r.tokens)
                ce.close()
                return out

            fl_solos = [fl_solo(p) for p in sess_prompts]

            def run_fleet(n_repl, *, churn=False):
                batchers = {f"f{i}": fl_batcher() for i in range(n_repl)}
                router = FleetRouter(refresh_s=0.05)
                for rid, b in batchers.items():
                    router.register(rid, b)
                actions = EngineFleetActions(
                    lambda rid: router.batcher(rid)._cont,
                    exec_on=lambda rid, fn: router.batcher(
                        rid
                    ).run_on_driver(fn),
                    rebuild=lambda rid: fl_batcher(),
                )
                ap = FleetAutopilot(
                    router, actions, action_cooldown_s=0.0,
                    max_moves_per_tick=4,
                )
                # warm every program either path runs (incl. the page
                # movers, via a live rebalance on a throwaway stream)
                router.dispatch(sess_prompts[0], max_new_tokens=2)
                if n_repl > 1:
                    wdone: dict = {}

                    def _warm():
                        wdone["t"] = batchers["f0"].generate(
                            sess_prompts[1], max_new_tokens=24,
                        )

                    wt = _fth.Thread(target=_warm)
                    wt.start()
                    wdl = time.monotonic() + 60
                    while time.monotonic() < wdl:
                        if actions.movable_streams("f0") >= 1:
                            actions.rebalance("f0", "f1", 1)
                            break
                        time.sleep(0.005)
                    wt.join(timeout=120)
                results: dict = {}
                t_sub: dict = {}
                t_first: dict = {}

                def one(i):
                    def cb(_t, _i=i):
                        if _i not in t_first:
                            t_first[_i] = time.perf_counter()
                        return False

                    t_sub[i] = time.perf_counter()
                    try:
                        results[i] = router.dispatch(
                            sess_prompts[i], max_new_tokens=fl_budget,
                            priority=sess_cls[i], stream_cb=cb,
                        )
                    except Exception as e:  # dropped — counted below
                        results[i] = e

                t0 = time.perf_counter()
                threads = [
                    _fth.Thread(target=one, args=(i,))
                    for i in range(N_SESS)
                ]
                for k, t in enumerate(threads):
                    t.start()
                    if churn and k == N_SESS // 3:
                        jb = fl_batcher()  # a replica JOINS mid-flood
                        batchers["join"] = jb
                        router.register("join", jb)
                    if churn and k == N_SESS // 2:
                        # rolling deploy mid-flood: drain f1 onto a
                        # sibling, rebuild it, rejoin — zero drops
                        ap.request_deploy(["f1"])
                    if churn and k == (2 * N_SESS) // 3:
                        # KILL f2 mid-flood: its next chunk raises, the
                        # router fails affected dispatches over
                        def _arm(e):
                            def boom(**kw):
                                raise RuntimeError("fleet chaos kill")

                            e.step_chunk = boom

                        try:
                            batchers["f2"].run_on_driver(_arm)
                        # tlint: disable=TL005(the kill may race the driver's own death — either way the replica is dead, which is the point)
                        except Exception:
                            pass
                    time.sleep(0.002)
                deadline = time.monotonic() + 300
                while any(t.is_alive() for t in threads) \
                        and time.monotonic() < deadline:
                    if churn:
                        ap.tick()
                    time.sleep(0.01)
                for t in threads:
                    t.join(timeout=60)
                wall = time.perf_counter() - t0
                deploys = sum(
                    1 for h in ap.status()["history"]
                    if h["kind"] == "deploy_done"
                )
                cache_routed = router.snapshot()["route_cache_tokens"]
                ap.stop()
                # a rolling deploy REPLACED a batcher inside the router
                # (rebuild hook) — close the router's current set too,
                # or the rebuilt replica's driver thread + engine would
                # outlive the leg and skew every later measurement
                to_close = {id(b): b for b in batchers.values()}
                for rid in router.replica_ids():
                    b = router.batcher(rid)
                    if b is not None:
                        to_close[id(b)] = b
                for b in to_close.values():
                    b.close(timeout=60.0)
                ok = {
                    i: v for i, v in results.items()
                    if isinstance(v, list)
                }
                dropped = N_SESS - len(ok)
                exact = all(
                    ok.get(i) == fl_solos[i] for i in range(N_SESS)
                )
                ttfts = sorted(
                    (t_first[i] - t_sub[i]) * 1e3
                    for i in range(N_SESS)
                    if sess_cls[i] == "interactive" and i in t_first
                )
                p95 = (
                    ttfts[min(int(round(0.95 * (len(ttfts) - 1))),
                              len(ttfts) - 1)]
                    if ttfts else 0.0
                )
                toks = sum(len(v) for v in ok.values())
                return {
                    "wall": wall, "tokps": toks / max(wall, 1e-9),
                    "dropped": dropped, "exact": exact,
                    "ttft_p95": p95, "deploys": deploys,
                    "cache_routed": cache_routed,
                }

            one_leg = run_fleet(1)
            n_leg = run_fleet(N_REPL)  # clean: the TTFT/scaling numbers
            churn_leg = run_fleet(N_REPL, churn=True)  # join/deploy/kill
            del eng_fl
            assert one_leg["dropped"] == 0 and n_leg["dropped"] == 0 \
                and churn_leg["dropped"] == 0, (
                    one_leg["dropped"], n_leg["dropped"],
                    churn_leg["dropped"],
                )
            assert one_leg["exact"] and n_leg["exact"] \
                and churn_leg["exact"]
            assert churn_leg["deploys"] >= 1, "mid-flood deploy never landed"
            scaling = n_leg["tokps"] / max(one_leg["tokps"], 1e-9)
            if on_tpu:
                # the linearity teeth, armed where replicas actually get
                # their own compute (N chips): aggregate tok/s must scale
                # to >= 60% of linear, and interactive TTFT p95 must stay
                # flat (each replica's queue is 1/N as deep)
                assert scaling >= 0.6 * N_REPL, (scaling, N_REPL)
                assert n_leg["ttft_p95"] <= 2.0 * one_leg["ttft_p95"], (
                    n_leg["ttft_p95"], one_leg["ttft_p95"],
                )
            fleet_extra = {
                "fleet_replicas": N_REPL,
                "fleet_sessions": N_SESS,
                "fleet_prefix_groups": n_groups,
                "fleet_tokps_1": round(one_leg["tokps"], 2),
                "fleet_tokps_n": round(n_leg["tokps"], 2),
                "fleet_scaling": round(scaling, 3),
                "fleet_dropped": int(
                    n_leg["dropped"] + churn_leg["dropped"]
                ),
                "fleet_streams_exact": bool(
                    one_leg["exact"] and n_leg["exact"]
                    and churn_leg["exact"]
                ),
                "fleet_ttft_p95_1_ms": round(one_leg["ttft_p95"], 2),
                "fleet_ttft_p95_n_ms": round(n_leg["ttft_p95"], 2),
                "fleet_churn_ttft_p95_ms": round(
                    churn_leg["ttft_p95"], 2
                ),
                "fleet_deploys": int(churn_leg["deploys"]),
                "fleet_route_cache_tokens": int(
                    n_leg["cache_routed"] + churn_leg["cache_routed"]
                ),
                **(
                    {}
                    if on_tpu
                    else {
                        "fleet_note": (
                            "CPU fallback: zero-dropped + bit-identical "
                            "streams, the mid-flood join/deploy/kill "
                            "churn, the landed rolling deploy, and the "
                            "cache-affine routed-token count are "
                            "deterministic and faithful here. The "
                            "PERFORMANCE pair is not: N replicas share "
                            "ONE CPU core, so aggregate tok/s cannot "
                            "scale (fleet_scaling <= ~1) and the extra "
                            "driver threads make every chunk slower — "
                            "TTFT p95 reads WORSE with N here purely "
                            "from core contention. Both in-leg bars "
                            "(scaling >= 0.6*N, TTFT p95 flat within "
                            "2x) arm on TPU rounds, where each replica "
                            "owns its chip and the single replica's "
                            "queue depth is the real bottleneck. "
                            "tpu_escalation streak logic applies as "
                            "for every CPU round."
                        )
                    }
                ),
            }
        except Exception as e:
            fleet_extra = {"fleet_error": str(e)[:500]}

    # ---- flash vs einsum prefill (the Pallas kernel's actual TPU win) -----
    flash_extra = {}
    if (on_tpu and _budget_left() > 1200) or force_all:
        try:
            # flash pays off on LONG prompts (attention is O(S^2) and the
            # einsum path materializes [B, h, S, S]); time a 2k-token
            # prefill both ways. CPU force-all uses a short prompt — the
            # kernel runs in pallas interpret mode there, and the point is
            # executing the leg, not the timing
            fl_len = 2048 if on_tpu else 256
            fl_prompt = [rng.integers(1, cfg.vocab_size, fl_len).tolist()]

            def prefill_ms(fcfg_):
                engine = GenerationEngine(
                    fcfg_, params, seq_buckets=(fl_len,),
                    batch_buckets=(1,), max_seq_len=fl_len,
                )
                jax.block_until_ready(engine.prefill(fl_prompt)[:2])  # compile
                t0 = time.perf_counter()
                for _ in range(5):
                    jax.block_until_ready(engine.prefill(fl_prompt)[:2])
                dt = (time.perf_counter() - t0) / 5 * 1e3
                del engine
                return dt

            einsum_ms = prefill_ms(cfg)
            # off-TPU the engine auto-falls back to einsum (the kernel
            # only interprets there — pure overhead, BENCH_r10); opt in
            # explicitly so the CPU force-all round still EXECUTES the
            # kernel path rather than timing einsum twice
            if not on_tpu:
                os.environ["TLTPU_FLASH_INTERPRET"] = "1"
            try:
                flash_ms = prefill_ms(cfg.with_(flash_attention=True))
            finally:
                if not on_tpu:
                    os.environ.pop("TLTPU_FLASH_INTERPRET", None)
            flash_extra = {
                "flash_prefill_len": fl_len,
                "prefill2k_einsum_ms": round(einsum_ms, 2),
                "prefill2k_flash_ms": round(flash_ms, 2),
                "flash_prefill_speedup": round(einsum_ms / max(flash_ms, 1e-9), 2),
            }
            if not on_tpu:
                flash_extra["flash_note"] = (
                    "CPU: kernel ran in interpret mode via "
                    "TLTPU_FLASH_INTERPRET=1 (the serving path gates "
                    "flash to the TPU backend and uses einsum here)"
                )
        except Exception as e:
            flash_extra = {"flash_error": str(e)[:300]}

    # ---- speculative decode (prompt-lookup) on repetitive text ------------
    # product path: /v1/generate {"lookahead": true}. One fixed-shape verify
    # program (drafts pad to n_draft); acceptance-rate + tok/s vs the
    # headline show what repetition buys
    spec_extra = {}
    if on_tpu and _budget_left() < 800:
        spec_extra = {"lookahead_skipped": "low time budget"}
    else:
        try:
            # (a) adaptive guard on the BENCH model: its weights are random,
            # so no draft can genuinely predict it — the off-switch
            # (engine/generate.py::generate_lookahead) must keep a
            # {"lookahead": true} request at ~vanilla speed, not the r4
            # 0.92x slowdown. Warm with the SAME budget: the compiled-tail
            # n_steps bucket is part of the program key.
            n_la = min(gen_tokens, 128)
            rnd = prompts[0]
            eng.generate_lookahead([rnd], max_new_tokens=n_la)  # warm
            t0 = time.perf_counter()
            r = eng.generate_lookahead([rnd], max_new_tokens=n_la)
            dt = max(time.perf_counter() - t0, 1e-9)
            st_rnd = getattr(eng, "last_lookahead_stats", {})
            spec_extra = {
                "lookahead_nonrep_vs_b1": round(
                    len(r.sequences[0]) / dt / max(toks_per_s, 1e-9), 2
                ),
                "lookahead_nonrep_spec_disabled": st_rnd.get("spec_disabled"),
                "lookahead_nonrep_compiled_tail": st_rnd.get("compiled_tail"),
            }
            # (b) genuine-acceptance demo: speculation only pays off on
            # PREDICTABLE continuations, which random weights cannot
            # produce — so overfit a tiny model on a periodic token stream
            # (~15 s) until greedy continuation is exact, then race
            # lookahead against the compiled loop on the SAME model.
            from tensorlink_tpu.engine.training import (
                make_optimizer as _mo, make_train_step as _mts,
            )
            from tensorlink_tpu.models import ModelConfig as _MC

            scfg = _MC(
                family="qwen3", vocab_size=256, d_model=128, n_layers=2,
                n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256,
                max_seq_len=256,
                dtype=jnp.bfloat16 if on_tpu else jnp.float32,
            )
            sparams = init_params(scfg, jax.random.PRNGKey(3))
            srng = np.random.default_rng(7)
            period = srng.integers(1, 256, 16)
            stream = np.tile(period, 40)
            sts = _mts(scfg, _mo("adamw", lr=3e-3), remat=False, donate=False)
            sstate = sts.init_state(sparams)
            for _ in range(60):
                offs = srng.integers(0, 16, 8)
                toks = np.stack([stream[o : o + 64] for o in offs])
                sparams, sstate, _m = sts.step_fn(
                    sparams, sstate, {"tokens": jnp.asarray(toks.astype(np.int32))}
                )
            seng = GenerationEngine(
                scfg, sparams, seq_buckets=(64,), batch_buckets=(1,),
                max_seq_len=256,
            )
            sprompt = stream[:64].tolist()
            ref = seng.generate_compiled([sprompt], max_new_tokens=128)
            learned = all(
                t == int(stream[64 + i]) for i, t in enumerate(ref.sequences[0])
            )
            t0 = time.perf_counter()
            seng.generate_compiled([sprompt], max_new_tokens=128)
            dt_v = max(time.perf_counter() - t0, 1e-9)
            seng.generate_lookahead([sprompt], max_new_tokens=128)  # warm
            t0 = time.perf_counter()
            r2 = seng.generate_lookahead([sprompt], max_new_tokens=128)
            dt_s = max(time.perf_counter() - t0, 1e-9)
            st = getattr(seng, "last_lookahead_stats", {})
            spec_extra.update({
                "spec_demo_learned": learned,
                "spec_demo_exact": r2.sequences == ref.sequences,
                "spec_trained_speedup": round(dt_v / dt_s, 2),
                "spec_trained_tokens_per_verify_pass": st.get(
                    "tokens_per_verify_pass"
                ),
            })
            # (c) CONTINUOUS speculative decoding (draft/verify as ragged
            # slots, engine/continuous.py + docs/SERVING.md): an
            # occupancy-matched decode FLOOD on the same trained model,
            # spec on vs off, both warmed, identical seeds/budgets — the
            # serving-shaped version of the demo above. Then the
            # ADVERSARIAL workload: a repetitive-but-unlearned prompt
            # whose drafts keep hitting and keep being rejected — the
            # acceptance-rate kill switch must fire and cap the loss at
            # the probe window.
            from tensorlink_tpu.engine.continuous import (
                ContinuousEngine as _SCE,
            )

            SP_SLOTS = 4
            sp_chunk, sp_budget = 2, 48
            sp_prompts = [
                stream[o : o + 64].tolist() for o in (0, 4, 8, 12)
            ]

            def spec_leg(spec_on, prompts_set, budget, trace_prefix=None,
                         engine=None):
                ce = _SCE(
                    engine or seng, max_slots=SP_SLOTS, page_size=16,
                    chunk_steps=sp_chunk, prefill_chunk=32,
                    prefix_cache=False,  # measure decode, not prefix hits
                    spec_decode=spec_on, spec_draft=8,
                )
                try:
                    w = ce.submit(prompts_set[0], max_new_tokens=4,
                                  seed=0, speculative=spec_on)
                    ce.run_until_idle()  # warm: the leg never times a compile
                    assert w.finished
                    reqs = [
                        ce.submit(
                            p, max_new_tokens=budget, seed=100 + i,
                            speculative=spec_on,
                            trace_id=(f"{trace_prefix}{i}"
                                      if trace_prefix else None),
                        )
                        for i, p in enumerate(prompts_set)
                    ]
                    t0 = time.perf_counter()
                    ce.run_until_idle()
                    dt = max(time.perf_counter() - t0, 1e-9)
                    assert all(r.finished for r in reqs)
                    ce.check_page_conservation()
                    snap = ce.serving_snapshot()
                finally:
                    ce.close()
                total = sum(len(r.tokens) for r in reqs)
                return total / dt, snap, [r.tokens for r in reqs]

            plain_tps, _s0, plain_toks = spec_leg(
                False, sp_prompts, sp_budget
            )
            spec_tps, spec_snap, spec_toks = spec_leg(
                True, sp_prompts, sp_budget, trace_prefix="bench-spec-"
            )
            sp_decomp = trace_decomp(
                [f"bench-spec-{i}" for i in range(SP_SLOTS)]
            ) or {}
            # adversarial: repetitive prompts on an UNTRAINED model of
            # the SAME config (same compiled programs — params are data):
            # prompt-lookup drafts confidently from the repetition, but
            # the model's continuation has nothing to do with it, so
            # every pass rejects and the acceptance-rate kill switch
            # must cap the loss after its probe window. (The trained
            # model is useless here: 60 steps on a periodic stream teach
            # it period-16 INDUCTION generally, so any repetitive prompt
            # genuinely accepts — measured 9.0 tokens/pass on held-out
            # patterns, which is a win, not an adversary.)
            ueng = GenerationEngine(
                scfg, init_params(scfg, jax.random.PRNGKey(99)),
                seq_buckets=(64,), batch_buckets=(1,), max_seq_len=256,
            )
            adv_rng = np.random.default_rng(23)
            adv_pat = adv_rng.integers(1, 256, 16)
            adv_prompts = [
                np.tile(np.roll(adv_pat, i), 4).tolist()
                for i in range(SP_SLOTS)
            ]
            adv_plain_tps, _s1, adv_plain = spec_leg(
                False, adv_prompts, sp_budget, engine=ueng
            )
            adv_spec_tps, adv_snap, adv_spec = spec_leg(
                True, adv_prompts, sp_budget, engine=ueng
            )
            del ueng
            spec_extra.update({
                "spec_plain_toks_s": round(plain_tps, 1),
                "spec_decode_toks_s": round(spec_tps, 1),
                "spec_decode_speedup": round(
                    spec_tps / max(plain_tps, 1e-9), 2
                ),
                "spec_tokens_per_pass": spec_snap["spec_tokens_per_pass"],
                "spec_drafted": int(spec_snap["spec_drafted"]),
                "spec_accepted": int(spec_snap["spec_accepted"]),
                # the bit-identity contract, asserted where it's cheap:
                # speculation never moves a token, repetitive or not
                "spec_streams_exact": spec_toks == plain_toks
                and adv_spec == adv_plain,
                "spec_adversarial_speedup": round(
                    adv_spec_tps / max(adv_plain_tps, 1e-9), 2
                ),
                "spec_adversarial_killed": int(adv_snap["spec_killed"]),
                "spec_adversarial_tokens_per_pass": adv_snap[
                    "spec_tokens_per_pass"
                ],
                **{f"spec_{k}": v for k, v in sp_decomp.items()},
                **(
                    {}
                    if on_tpu
                    else {
                        "spec_cont_note": (
                            "CPU fallback: the speedup is real but its "
                            "mechanism here is pass amortization (fewer "
                            "compiled dispatches + host trips per token "
                            "at toy shapes); on TPU the same "
                            "tokens-per-verify-pass multiplies the "
                            "bandwidth-bound decode regime where a "
                            "k-row verify streams the weights once — "
                            "the claim BENCH_r05 measured at 1.57x with "
                            "a trained drafter. The deterministic pins "
                            "(bit-identical streams, kill-switch "
                            "cap, one compiled program) live in "
                            "tests/test_continuous.py."
                        )
                    }
                ),
            })
            del seng, sparams, sstate
        except Exception as e:
            spec_extra["lookahead_error"] = str(e)[:300]

    # ---- int8 weight-only decode (same prompts; reported in extra) --------
    # halves the parameter stream that bounds B=1 decode — can beat the
    # bf16 roofline the headline is normalized against
    int8_extra = {}
    if on_tpu and _budget_left() < 700:
        int8_extra = {"int8_skipped": "low time budget"}
        del eng
    elif on_tpu or force_all:
        try:
            del eng  # free the bf16 engine's cache first
            # run the int8 engine THROUGH the mesh path (1-device Mesh):
            # exercises quant+mesh serving (r3 gap: it raised) on real
            # hardware at no sharding cost
            from jax.sharding import Mesh

            from tensorlink_tpu.models.transformer import cache_specs as _cs

            qeng = GenerationEngine(
                cfg, params, quant="int8",
                mesh=Mesh(np.array(jax.devices()[:1]), ("data",)),
                cache_specs=_cs(cfg, data_axis=None, tensor_axis=None),
                seq_buckets=(prompt_len, prompt_len + gen_tokens),
                batch_buckets=(batch,),
                max_seq_len=prompt_len + gen_tokens,
            )
            tps_q = timed_decode(qeng, prompts)
            from tensorlink_tpu.models.quant import quantized_bytes

            qbytes = quantized_bytes(qeng.params)
            q_roofline = hbm_bw / (qbytes + kv_per_tok * avg_len)
            int8_extra = {
                "int8_toks_s": round(tps_q, 2),
                "int8_param_bytes": qbytes,
                "int8_vs_bf16_roofline": round(tps_q / roofline, 4),
                "int8_vs_int8_roofline": round(tps_q / q_roofline, 4),
            }
            del qeng
        except Exception as e:
            int8_extra = {"int8_error": str(e)[:500]}
    else:
        del eng

    del params  # free HBM before the training benchmark

    # ---- fine-tune step benchmark (step time + MFU) -----------------------
    extra: dict = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "decode_roofline_toks_s": round(roofline, 2),
        **batch_extra,
        **serving_extra,
        **prefix_extra,
        **tier_extra,
        **sched_extra,
        **ragged_extra,
        **kv_extra,
        **kv4_extra,
        **cot_extra,
        **mig_extra,
        **disagg_extra,
        **fleet_extra,
        **flash_extra,
        **spec_extra,
        **int8_extra,
    }
    if on_tpu and _budget_left() < 500:
        # emit the headline rather than dying in a slow train compile;
        # the decode number is the metric the driver records
        extra["train_skipped"] = "low time budget"
        _emit_result(decode_name, on_tpu, batch, prompt_len, toks_per_s,
                     roofline, extra)
        return
    try:
        if on_tpu:
            train_name = "qwen3-0p6b"
            tcfg = presets[train_name].with_(dtype=jnp.bfloat16, max_seq_len=1024)
            tbatch, tseq, n_micro = 8, 1024, 2
        else:
            train_name = "qwen3-tiny-cpu"
            tcfg = cfg.with_(max_seq_len=256)
            tbatch, tseq, n_micro = 4, 128, 2
        opt = make_optimizer("adamw", lr=1e-4)
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(
                1, tcfg.vocab_size, (tbatch, tseq), dtype=np.int64
            ).astype(np.int32)
        )

        def run_train(remat: bool):
            tparams = init_params(tcfg, jax.random.PRNGKey(1))
            ts = make_train_step(
                tcfg, opt, n_micro=n_micro, remat=remat, donate=True
            )
            state = opt.init(tparams)
            # warmup/compile
            tparams_, state_, m = ts.step_fn(tparams, state, {"tokens": tokens})
            jax.block_until_ready(m["loss"])
            n_steps = 5 if on_tpu else 2
            t0 = time.perf_counter()
            for _ in range(n_steps):
                tparams_, state_, m = ts.step_fn(
                    tparams_, state_, {"tokens": tokens}
                )
            jax.block_until_ready(m["loss"])
            return (time.perf_counter() - t0) / n_steps

        # remat ON, always: the sharding planner sizes training stages
        # assuming rematerialized activations (parallel/planner.py), so a
        # no-remat number describes a configuration the system never
        # schedules — BENCH_r05's train_remat:false measured exactly that
        # phantom. The ~25-33% extra forward FLOPs are the price of the
        # configuration that actually runs.
        step_dt = run_train(remat=True)
        remat_used = True
        # standard 6·N·D convention (remat's extra forward eats into MFU)
        train_flops = 6.0 * tcfg.param_count() * tbatch * tseq
        mfu = train_flops / step_dt / peak_flops
        train_config_str = (
            f"{train_name} "
            f"{'bf16' if tcfg.dtype == jnp.bfloat16 else 'fp32'} "
            f"B={tbatch} T={tseq}"
        )
        extra.update(
            {
                "train_config": train_config_str,
                "train_step_s": round(step_dt, 4),
                "train_tokens_s": round(tbatch * tseq / step_dt, 2),
                "train_mfu": round(mfu, 4),
                "train_remat": remat_used,
            }
        )
    except Exception as e:  # keep the decode metric even if training OOMs
        # full text: a truncated dtype-mismatch message cost round 2 the
        # self-contained diagnosis (ADVICE r2)
        extra["train_error"] = str(e)[:2000]

    # ---- ZeRO-1 sharded train step (docs/TRAINING.md) ---------------------
    # unsharded vs zero1 at MATCHED global batch: step time, the bitwise
    # pin, and per-replica optimizer-state bytes ~1/dp
    try:
        extra.update(_zero1_leg(on_tpu))
    except Exception as e:
        extra["zero1_error"] = str(e)[:2000]

    # ---- tensor-parallel serving (docs/SHARDING.md) -----------------------
    # 1-way vs N-way sharded engines on the SAME model: bitwise stream
    # parity, per-chip KV page bytes (the HBM-capacity win), ITL, and the
    # analytic collective bytes/token the per-chunk gathers cost
    try:
        extra.update(_tp_leg(on_tpu))
    except Exception as e:
        extra["tp_error"] = str(e)[:2000]

    # ---- serve-and-train (docs/TRAINING.md "Serve-and-train") -------------
    # background train steps as a best_effort-class tenant of a serving
    # engine + live weight publishes at chunk boundaries: interactive ITL
    # stays flat, streams spanning a publish drop zero tokens
    try:
        extra.update(_serve_train_leg(on_tpu))
    except Exception as e:
        extra["serve_train_error"] = str(e)[:2000]

    _emit_result(decode_name, on_tpu, batch, prompt_len, toks_per_s,
                 roofline, extra)


def _zero1_leg(on_tpu: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorlink_tpu.engine.training import make_optimizer, make_train_step
    from tensorlink_tpu.models import ModelConfig, init_params
    from tensorlink_tpu.parallel.mesh import build_mesh

    devs = jax.devices()
    if len(devs) < 2:
        # a 1-chip session has no dp axis to shard over; the structural
        # pins live in tests/test_zero1.py either way
        return {"zero1_skipped": "needs >= 2 devices"}
    dp = 2
    zcfg = ModelConfig(
        family="qwen3", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, max_seq_len=128,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    params = init_params(zcfg, jax.random.PRNGKey(0))
    opt = make_optimizer("adamw", lr=1e-3, grad_clip=1.0)
    mesh = build_mesh({"data": dp}, devs[:dp])
    base = make_train_step(zcfg, opt, n_micro=dp, donate=False)
    z1 = make_train_step(
        zcfg, opt, n_micro=dp, donate=False, zero1=True, mesh=mesh,
    )
    rng = np.random.default_rng(0)
    batches = [
        {"tokens": jnp.asarray(
            rng.integers(1, zcfg.vocab_size, (4, 64)).astype(np.int32)
        )}
        for _ in range(3)
    ]

    def run(ts, n_timed=3):
        p, s = params, ts.init_state(params)
        for b in batches:  # warm + make the bitwise trajectory
            p, s, m = ts.step_fn(p, s, b)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(n_timed):
            p2, s, m = ts.step_fn(p, s, batches[0])
        jax.block_until_ready(m["loss"])
        return p, (time.perf_counter() - t0) / n_timed, s

    p_base, dt_base, _s = run(base)
    p_z1, dt_z1, state_z1 = run(z1)
    bitwise = all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), p_base, p_z1
    )))
    opt_full = sum(leaf.nbytes for leaf in jax.tree.leaves(state_z1))
    dev0 = devs[0]
    opt_rep = sum(
        sh.data.nbytes
        for leaf in jax.tree.leaves(state_z1)
        for sh in leaf.addressable_shards if sh.device == dev0
    )
    out = {
        "zero1_dp": dp,
        "zero1_bitwise_identical": bool(bitwise),
        "zero1_step_ms": round(dt_z1 * 1e3, 2),
        "zero1_unsharded_step_ms": round(dt_base * 1e3, 2),
        "zero1_opt_bytes_full": int(opt_full),
        "zero1_opt_bytes_per_replica": int(opt_rep),
        "zero1_opt_state_ratio": round(opt_rep / max(opt_full, 1), 4),
    }
    if not on_tpu:
        out["zero1_note"] = (
            "CPU fallback: the deterministic pins are the payload — "
            "bitwise identity to the unsharded step and 1/dp resident "
            "optimizer bytes; step-time parity is expected here (the dp "
            "'replicas' share one CPU's cores, so sharding the batch "
            "halves per-replica FLOPs but not wall time). On TPU the "
            "same leg gives dp-way grad compute AND 1/dp weight-update "
            "FLOPs/bytes per chip."
        )
    return out


def _tp_leg(on_tpu: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorlink_tpu.engine.continuous import ContinuousEngine
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models import ModelConfig, init_params

    devs = jax.devices()
    if len(devs) < 2:
        # no tp axis to shard over; the structural pins live in
        # tests/test_tp.py either way
        return {"tp_skipped": "needs >= 2 devices"}
    tp = 2
    tcfg = ModelConfig(
        family="llama", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, max_seq_len=128,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        tie_embeddings=False,
    )
    params = init_params(tcfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, tcfg.vocab_size, 8).tolist() for _ in range(4)]

    def serve(degree):
        # fresh GenerationEngine per run: a tp engine re-places
        # engine.params onto its mesh
        ce = ContinuousEngine(
            GenerationEngine(tcfg, params, seq_buckets=(8, 32),
                             batch_buckets=(1,), max_seq_len=128),
            max_slots=4, page_size=16, chunk_steps=8,
            tensor_parallel=degree,
        )
        # warm the compile outside the timed window
        w = ce.submit(prompts[0], max_new_tokens=4, seed=99)
        ce.run_until_idle()
        assert w.finished
        reqs = [ce.submit(p, max_new_tokens=24, seed=i)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        ce.run_until_idle()
        dt = time.perf_counter() - t0
        n_tok = sum(len(r.tokens) for r in reqs)
        k = ce.cache.k
        dev0 = devs[0]
        kv_chip = sum(
            sh.data.nbytes
            for arr in (ce.cache.k, ce.cache.v)
            for sh in arr.addressable_shards if sh.device == dev0
        )
        return ([r.tokens for r in reqs], dt / max(n_tok, 1) * 1e3,
                kv_chip, int(k.shape[1]))

    ref, itl_1, kv_chip_1, n_pages = serve(1)
    tp_streams, itl_tp, kv_chip_tp, n_pages_tp = serve(tp)

    # the per-chunk gather bill, per device per token (exact fp path):
    # 4 gathers/layer (attn columns, attn out, mlp hidden, mlp out) +
    # the logits gather, each moving (tp-1)/tp of the full activation
    b = jnp.dtype(tcfg.dtype).itemsize
    per_layer = (tcfg.n_heads * tcfg.head_dim + 2 * tcfg.d_model
                 + tcfg.d_ff)
    coll_bytes_tok = (tp - 1) / tp * b * (
        tcfg.n_layers * per_layer + tcfg.vocab_size
    )

    out = {
        "tp_degree": tp,
        "tp_streams_bitwise_identical": bool(tp_streams == ref),
        "tp_itl_ms": round(itl_tp, 3),
        "tp1_itl_ms": round(itl_1, 3),
        "tp_kv_bytes_per_chip": int(kv_chip_tp),
        "tp1_kv_bytes_per_chip": int(kv_chip_1),
        # same page COUNT, 1/tp of the bytes per chip: a fixed per-chip
        # HBM budget therefore holds tp x more pages
        "tp_page_capacity_gain": round(kv_chip_1 / max(kv_chip_tp, 1), 2),
        "tp_pages": int(n_pages_tp),
        "tp_collective_bytes_per_token": int(coll_bytes_tok),
    }
    if not on_tpu:
        out["tp_note"] = (
            "CPU fallback: the deterministic pins are the payload — "
            "bitwise stream identity to the 1-way engine and 1/tp KV "
            "bytes per chip; ITL parity or regression is expected here "
            "(the tp 'chips' share one CPU's cores and the gathers are "
            "memcpys through host RAM). The ITL-improvement bar arms on "
            "TPU, where each shard owns a chip, per-chip weight reads "
            "drop 1/tp in the bandwidth-bound decode regime, and the "
            "gathers ride the ICI (collective_quant=True quarters their "
            "bytes at a bounded, deterministic error)."
        )
    return out


def _serve_train_leg(on_tpu: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.engine.serve_train import ServeTrainLoop
    from tensorlink_tpu.engine.training import make_optimizer, make_train_step
    from tensorlink_tpu.ml.batching import ContinuousBatcher
    from tensorlink_tpu.models import ModelConfig, init_params

    scfg = ModelConfig(
        family="qwen3", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, max_seq_len=128,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    params = init_params(scfg, jax.random.PRNGKey(0))
    bat = ContinuousBatcher(
        engine=GenerationEngine(
            scfg, params, seq_buckets=(64,), batch_buckets=(1,),
            max_seq_len=128,
        ),
        eos_ids=[], max_slots=4, page_size=16, chunk_steps=2,
        prefill_chunk=32, kv_quant="none",
    )
    try:
        # warm every serving program before anything is timed
        bat.generate([9, 8, 7], max_new_tokens=4, timeout=300)

        def itl_ms(prompt, budget=24, priority="interactive"):
            stamps: list[float] = []

            def cb(toks):
                stamps.append(time.perf_counter())
                return None

            out = bat.generate(
                prompt, max_new_tokens=budget, priority=priority,
                stream_cb=cb, timeout=300,
            )
            assert len(out) == budget
            gaps = np.diff(stamps) * 1e3
            return float(np.median(gaps))

        # baseline: interactive ITL with NO trainer attached
        base_itl = float(np.median([
            itl_ms([3 + i] * 8) for i in range(3)
        ]))

        # phase 1: trainer armed — interactive ITL must stay flat (the
        # tick yields at chunk granularity), train steps fill the gaps
        opt = make_optimizer("adamw", lr=1e-3)
        ts = make_train_step(scfg, opt, n_micro=1, donate=False)
        rng = np.random.default_rng(1)

        def data_fn(step):
            return {"tokens": jnp.asarray(
                rng.integers(1, scfg.vocab_size, (2, 32)).astype(np.int32)
            )}

        loop = ServeTrainLoop(
            bat, ts, params, data_fn=data_fn, publish_every=0, max_steps=0,
            cfg=scfg,
        ).attach()
        # let the trainer warm its compile OFF the timed path
        deadline = time.monotonic() + 120
        while loop.step < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        steps_before = loop.step
        armed_itl = float(np.median([
            itl_ms([30 + i] * 8) for i in range(3)
        ]))
        time.sleep(0.3)  # an idle gap: background steps must flow again
        bg_steps = loop.step - steps_before

        # phase 2: a best_effort stream SPANS live weight publishes
        loop.detach()
        loop2 = ServeTrainLoop(
            bat, ts, loop.params, opt_state=loop.opt_state,
            data_fn=data_fn, publish_every=2, max_steps=6, cfg=scfg,
        ).attach()
        v_before = bat._cont.weights_version
        sizes_before = bat._cont.jit_cache_sizes()
        span = bat.generate(
            [5, 6, 7], max_new_tokens=48, priority="best_effort",
            timeout=300,
        )
        deadline = time.monotonic() + 300
        while not loop2.done and time.monotonic() < deadline:
            time.sleep(0.02)
        snap = bat.stats()["engine"]
        dropped = 48 - len(span)
        out = {
            "serve_train_baseline_itl_ms": round(base_itl, 3),
            "serve_train_itl_ms": round(armed_itl, 3),
            "serve_train_itl_ratio": round(
                armed_itl / max(base_itl, 1e-9), 2
            ),
            "serve_train_bg_steps_during_itl": int(bg_steps),
            "serve_train_steps": int(snap["train_steps"]),
            "serve_train_publishes": int(loop2.publishes),
            "serve_train_weights_version": int(snap["weights_version"]),
            "serve_train_dropped": int(dropped),
            "serve_train_stream_exact_len": bool(dropped == 0),
            "serve_train_publish_new_programs": sum(
                bat._cont.jit_cache_sizes().values()
            ) - sum(sizes_before.values()),
            "serve_train_step_ms": float(snap["train_step_ms"]),
        }
        assert snap["weights_version"] > v_before
        if not on_tpu:
            out["serve_train_note"] = (
                "CPU fallback: the deterministic pins carry the claim — "
                "zero dropped tokens across a publish, zero new compiled "
                "programs, ITL flat because train ticks yield to any "
                "class above best_effort at chunk granularity (an "
                "interactive arrival waits at most ONE train step). On "
                "TPU the same loop gives real MFU in the serving gaps; "
                "train_mfu rides /stats//metrics either way."
            )
        return out
    finally:
        bat.close()


def _emit_result(decode_name, on_tpu, batch, prompt_len, toks_per_s,
                 roofline, extra) -> None:
    """The ONE JSON line the driver records — single emit site."""
    print(
        json.dumps(
            {
                "metric": f"decode tokens/sec/chip ({decode_name} "
                f"{'bf16' if on_tpu else 'fp32'}, B={batch}, "
                f"prompt {prompt_len}, {extra['platform']})",
                "value": round(toks_per_s, 2),
                "unit": "tokens/s",
                "vs_baseline": round(toks_per_s / roofline, 4),
                "extra": extra,
            }
        )
    )


if __name__ == "__main__":
    if "--run" in sys.argv:
        try:
            run_bench()
        except Exception as e:
            print(f"bench child failed: {e!r}", file=sys.stderr)
            sys.exit(1)
    else:
        try:
            main()
        except SystemExit:
            raise
        except Exception as e:  # contract: a JSON line is ALWAYS emitted
            _emit_error(f"parent: {e!r}")
            sys.exit(1)
