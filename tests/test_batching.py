"""Dynamic serving batcher (ml/batching.py) + per-row sampling.

The reference serializes generation per hosted model; here concurrent
requests coalesce into one batched decode with per-row sampling knobs and
budgets, streams demuxed per request."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.engine.sampling import SamplingParams, sample
from tensorlink_tpu.ml.batching import GenBatcher


# ---------------------------------------------------------------------------
# per-row sampling
# ---------------------------------------------------------------------------
def test_sample_per_row_params():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (4, 64), jnp.float32) * 3
    # rows 0,2 greedy; rows 1,3 sampled at high temperature
    p = SamplingParams.stack(
        [
            SamplingParams.make(),
            SamplingParams.make(temperature=1.0, top_k=5),
            SamplingParams.make(),
            SamplingParams.make(temperature=0.7, top_p=0.9),
        ],
        pad_to=4,
    )
    toks = np.asarray(sample(logits, key, p))
    ref = np.asarray(logits).argmax(-1)
    assert toks[0] == ref[0] and toks[2] == ref[2]  # greedy rows exact
    assert all(0 <= t < 64 for t in toks)
    # scalar greedy fast path still matches argmax for the whole batch
    g = np.asarray(sample(logits, key, SamplingParams.make()))
    np.testing.assert_array_equal(g, ref)
    # stack pads extra (bucket) rows as greedy
    p3 = SamplingParams.stack([SamplingParams.make(temperature=0.5)], pad_to=4)
    assert p3.temperature.shape == (4, 1)
    assert float(p3.temperature[1, 0]) == 0.0


# ---------------------------------------------------------------------------
# engine budgets
# ---------------------------------------------------------------------------
def test_engine_per_row_budgets():
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models import ModelConfig, init_params

    cfg = ModelConfig(
        family="llama", vocab_size=128, d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=64,
        dtype=jnp.float32, tie_embeddings=False,
    )
    eng = GenerationEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)),
        seq_buckets=(8, 32), batch_buckets=(2,), max_seq_len=64,
    )
    r = eng.generate(
        [[1, 2, 3], [4, 5]], max_new_tokens=16, budgets=[3, 9]
    )
    assert len(r.sequences[0]) == 3
    assert len(r.sequences[1]) == 9
    # the fully-compiled loop honors the same per-row budgets on device
    rc = eng.generate_compiled(
        [[1, 2, 3], [4, 5]], max_new_tokens=16, budgets=[3, 9]
    )
    assert len(rc.sequences[0]) == 3
    assert len(rc.sequences[1]) == 9


def test_zero_room_rows_report_finished_consistently():
    """A prompt filling the whole context reports finished=True with an
    empty completion on BOTH decode paths (they diverged once: streaming
    said done, compiled said not)."""
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models import ModelConfig, init_params

    cfg = ModelConfig(
        family="llama", vocab_size=128, d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=32,
        dtype=jnp.float32, tie_embeddings=False,
    )
    eng = GenerationEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)),
        seq_buckets=(32,), batch_buckets=(1,), max_seq_len=32,
    )
    full = list(range(1, 33))  # room 0
    for gen_fn in (eng.generate, eng.generate_compiled):
        r = gen_fn([full], max_new_tokens=8)
        assert r.sequences == [[]]
        assert r.finished == [True]


def test_per_row_room_no_cross_truncation():
    """A long-prompt request co-batched with a short one must not shrink
    the short one's completion: each row is clamped by its OWN cache room
    (pre-fix: steps were clamped by max(lens) for the whole batch)."""
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models import ModelConfig, init_params

    cfg = ModelConfig(
        family="llama", vocab_size=128, d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=64,
        dtype=jnp.float32, tie_embeddings=False,
    )
    eng = GenerationEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)),
        seq_buckets=(64,), batch_buckets=(2,), max_seq_len=64,
    )
    long_prompt = list(range(1, 61))  # room = 4
    short_prompt = [1, 2, 3]  # room = 61
    for gen_fn in (eng.generate, eng.generate_compiled):
        r = gen_fn([long_prompt, short_prompt], max_new_tokens=50,
                   budgets=[50, 20])
        assert len(r.sequences[0]) == 4  # clamped by ITS room
        assert len(r.sequences[1]) == 20  # full budget, not truncated


# ---------------------------------------------------------------------------
# batch bucket selection (the r5 co-batch throughput regression)
# ---------------------------------------------------------------------------
def test_batch_bucket_smallest_fit_for_1_to_8_pending():
    """The serving batch shape for n pending requests is the SMALLEST
    compiled bucket ≥ n — 2 live requests must never pad to B=8 (4× the
    decode FLOPs for dead rows)."""
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models import ModelConfig, init_params

    cfg = ModelConfig(
        family="llama", vocab_size=64, d_model=16, n_layers=1, n_heads=2,
        n_kv_heads=2, head_dim=8, d_ff=32, max_seq_len=32,
        dtype=jnp.float32, tie_embeddings=False,
    )
    eng = GenerationEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)),
        seq_buckets=(8,), batch_buckets=(1, 2, 4, 8), max_seq_len=32,
    )
    want = {1: 1, 2: 2, 3: 4, 4: 4, 5: 8, 6: 8, 7: 8, 8: 8}
    assert {n: eng.batch_bucket(n) for n in range(1, 9)} == want
    # prefill agrees with the public rule
    logits, cache, lens, B = eng.prefill([[1, 2], [3, 4]])
    assert B == 2
    del cache


@pytest.mark.slow  # compiles decode-loop programs at three batch buckets;
# CI runs it unfiltered — tier-1 keeps the (cheap) bucket-choice regression
def test_chunked_decode_shrinks_bucket_on_eviction():
    """When co-batched rows finish early, the next chunk re-buckets the
    survivors: a greedy batch of 4 whose short rows drain must end its
    decode at B=1, not dead-step B=4 to the long row's budget — with the
    emitted sequences identical to the one-shot compiled loop."""
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models import ModelConfig, init_params

    cfg = ModelConfig(
        family="llama", vocab_size=128, d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=64,
        dtype=jnp.float32, tie_embeddings=False,
    )
    eng = GenerationEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)),
        seq_buckets=(8,), batch_buckets=(1, 2, 4), max_seq_len=64,
    )
    prompts = [[1, 2], [3, 4], [5, 6], [7, 8]]
    budgets = [24, 3, 3, 3]
    ref = eng.generate_compiled(prompts, max_new_tokens=24, budgets=budgets)
    got = eng.generate_chunked(
        prompts, max_new_tokens=24, budgets=budgets, chunk_steps=4
    )
    assert got.sequences == ref.sequences
    batches = eng.last_chunk_batches
    assert batches[0] == 4  # started at the smallest bucket ≥ 4 live
    assert batches[-1] == 1  # ended with only the long row decoding
    # and the shrink is monotone — no bucket ever grows mid-decode
    assert all(b2 <= b1 for b1, b2 in zip(batches, batches[1:]))


# ---------------------------------------------------------------------------
# batcher over a fake model
# ---------------------------------------------------------------------------
class FakeModel:
    """Deterministic 'decode': row i emits base+i repeated; records calls."""

    plan = None  # single-stage semantics

    def __init__(self, step_delay=0.0):
        self.calls: list[dict] = []
        self.step_delay = step_delay

    def generate(self, prompts, *, max_new_tokens, temperature, top_k,
                 top_p, eos_ids, seed, stream_cb=None, budgets=None,
                 presence_penalty=0.0, frequency_penalty=0.0):
        self.calls.append({
            "n": len(prompts), "temperature": temperature,
            "budgets": budgets, "max": max_new_tokens,
        })
        budgets = budgets or [max_new_tokens] * len(prompts)
        seqs = [[] for _ in prompts]
        for step in range(max(budgets)):
            time.sleep(self.step_delay)
            emitted = []
            for i, p in enumerate(prompts):
                if step < budgets[i]:
                    t = int(p[0]) * 100 + step
                    seqs[i].append(t)
                    emitted.append(t)
                else:
                    emitted.append(None)
            if stream_cb:
                stream_cb(emitted)
        return seqs


def test_batcher_coalesces_concurrent_requests():
    fake = FakeModel(step_delay=0.002)
    b = GenBatcher(fake, eos_ids=[99], max_batch=4, window_s=0.15)
    results: dict[int, list[int]] = {}
    streams: dict[int, list[int]] = {1: [], 2: [], 3: []}

    def req(i, n_toks, temp):
        results[i] = b.generate(
            [i], max_new_tokens=n_toks, temperature=temp,
            stream_cb=lambda ts, i=i: streams[i].extend(ts),
        )

    threads = [
        threading.Thread(target=req, args=(1, 4, 0.0)),
        threading.Thread(target=req, args=(2, 2, 0.8)),
        threading.Thread(target=req, args=(3, 6, 0.0)),
    ]
    for t in threads:
        t.start()
        time.sleep(0.01)  # arrive within the window, in order
    for t in threads:
        t.join(10)
    b.close()

    # one batched dispatch served all three
    assert max(b.batch_sizes) == 3, b.batch_sizes
    call = fake.calls[0]
    assert call["n"] == 3
    assert call["budgets"] == [4, 2, 6]
    assert call["temperature"] == [0.0, 0.8, 0.0]
    # results demuxed per request, trimmed to each budget
    assert results[1] == [100, 101, 102, 103]
    assert results[2] == [200, 201]
    assert results[3] == [300, 301, 302, 303, 304, 305]
    # streams match results row-for-row
    assert streams == {1: results[1], 2: results[2], 3: results[3]}


def test_batcher_serial_when_idle_and_error_fanout():
    fake = FakeModel()
    b = GenBatcher(fake, eos_ids=[], max_batch=4, window_s=0.01)
    r1 = b.generate([7], max_new_tokens=2)
    r2 = b.generate([8], max_new_tokens=1)
    assert r1 == [700, 701] and r2 == [800]
    assert list(b.batch_sizes) == [1, 1]  # idle queue -> no artificial batching

    class Boom(FakeModel):
        def generate(self, *a, **k):
            raise RuntimeError("engine fell over")

    b2 = GenBatcher(Boom(), eos_ids=[], max_batch=2, window_s=0.05)
    errs = []

    def bad(i):
        try:
            b2.generate([i], max_new_tokens=2)
        except RuntimeError as e:
            errs.append(str(e))

    ts = [threading.Thread(target=bad, args=(i,)) for i in (1, 2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(5)
    assert errs == ["engine fell over"] * 2
    b.close()
    b2.close()


def test_batcher_pipelined_co_batches():
    """Multi-stage jobs co-batch too: the head-holding worker samples
    per-row on device (ml/worker.py::_sample_from_logits), so the batcher
    no longer degrades pipelined models to strict batch size 1."""

    class Plan:
        n_stages = 2

    fake = FakeModel(step_delay=0.02)
    fake.plan = Plan()
    b = GenBatcher(fake, eos_ids=[], max_batch=8, window_s=0.2)
    out = []
    ts = [
        threading.Thread(
            target=lambda i=i: out.append(b.generate([i], max_new_tokens=2))
        )
        for i in (1, 2, 3)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join(5)
    b.close()
    assert len(out) == 3
    assert sum(c["n"] for c in fake.calls) == 3
    assert any(c["n"] > 1 for c in fake.calls)  # requests coalesced
    # every request still gets its own rows back
    assert sorted(o[0] // 100 for o in out) == [1, 2, 3]
