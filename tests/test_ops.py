"""Pallas kernel parity (interpret mode — no TPU needed).

The flash-attention prefill kernel (ops/attention.py) is pinned against
the einsum reference (models/transformer.py::attention) across GQA/MHA
shapes and block configurations, then end-to-end through the generation
engine with cfg.flash_attention on. The paged decode kernel
(continuous batching) is pinned against its pure-jnp reference and the
reference against the dense einsum path."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.models.transformer import _mask_bias, attention
from tensorlink_tpu.ops.attention import (
    flash_attention,
    paged_attention,
    paged_attention_ref,
    paged_prefill_attention_ref,
    ragged_paged_attention,
    ragged_paged_attention_ref,
)


def _ref(q, k, v, scale):
    B, T = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    bias = _mask_bias(pos, T, jnp.ones((B, T), bool), None)
    return attention(q, k, v, bias, scale)


@pytest.mark.parametrize(
    "B,T,Hq,Hkv,hd,bq,bk",
    [
        (2, 256, 8, 2, 64, 64, 64),  # GQA, multi-block
        (1, 128, 4, 4, 32, 128, 128),  # MHA, single block
        (2, 128, 8, 1, 16, 32, 64),  # MQA, asymmetric blocks
        (1, 64, 2, 2, 128, 16, 16),  # many tiny blocks
    ],
)
def test_flash_matches_einsum(B, T, Hq, Hkv, hd, bq, bk):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, T, Hq, hd), jnp.float32)
    k = jax.random.normal(k2, (B, T, Hkv, hd), jnp.float32)
    v = jax.random.normal(k3, (B, T, Hkv, hd), jnp.float32)
    scale = hd**-0.5
    got = flash_attention(
        q, k, v, scale=scale, block_q=bq, block_k=bk, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_ref(q, k, v, scale)),
        rtol=2e-5, atol=2e-5,
    )


@pytest.mark.parametrize("window", [8, 64, 200])
def test_flash_sliding_window_matches_einsum(window):
    """Mistral-style sliding window: parity vs the einsum mask, including
    windows smaller than / equal to / larger than the block size."""
    B, T, Hq, Hkv, hd = 1, 128, 4, 2, 32
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(k1, (B, T, Hq, hd), jnp.float32)
    k = jax.random.normal(k2, (B, T, Hkv, hd), jnp.float32)
    v = jax.random.normal(k3, (B, T, Hkv, hd), jnp.float32)
    scale = hd**-0.5
    got = flash_attention(
        q, k, v, scale=scale, block_q=32, block_k=32, interpret=True,
        window=window,
    )
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    bias = _mask_bias(pos, T, jnp.ones((B, T), bool), window)
    ref = attention(q, k, v, bias, scale)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_flash_rejects_indivisible_seq():
    q = jnp.zeros((1, 100, 4, 32))
    k = v = jnp.zeros((1, 100, 2, 32))
    with pytest.raises(ValueError):
        flash_attention(q, k, v, scale=1.0, block_q=64, block_k=64,
                        interpret=True)


@pytest.fixture
def flash_off_tpu(monkeypatch):
    """Off the TPU the model takes the flash kernel (interpreted) only
    when asked (models/transformer.py)."""
    monkeypatch.setenv("TLTPU_FLASH_INTERPRET", "1")


@pytest.mark.slow  # engine-level compile-heavy; CI engine job runs these
# unfiltered — the tier-1 'not slow' pass keeps the kernel parity tests only
def test_engine_flash_windowed_prefill_matches_dense(flash_off_tpu):
    """A sliding-window (mistral-style) config takes the flash path too."""
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.engine.sampling import SamplingParams
    from tensorlink_tpu.models import ModelConfig, init_params

    cfg = ModelConfig(
        family="mistral", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, max_seq_len=128,
        dtype=jnp.float32, tie_embeddings=False, sliding_window=16,
    )
    params = init_params(cfg, jax.random.PRNGKey(2))
    kw = dict(seq_buckets=(32, 128), batch_buckets=(1,), max_seq_len=128)
    prompts = [list(range(1, 33))]  # one full bucket, window < prompt
    greedy = SamplingParams.make()
    dense = GenerationEngine(cfg, params, **kw)
    flash = GenerationEngine(cfg.with_(flash_attention=True), params, **kw)
    r_d = dense.generate_compiled(prompts, max_new_tokens=8, sampling=greedy)
    r_f = flash.generate_compiled(prompts, max_new_tokens=8, sampling=greedy)
    assert r_f.sequences == r_d.sequences


@pytest.mark.slow  # see above
def test_engine_flash_prefill_matches_dense(flash_off_tpu):
    """cfg.flash_attention routes the engine's fresh-cache prefill through
    the kernel; generated tokens must match the einsum engine exactly
    (same math, same greedy argmax), including right-padded batch rows."""
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.engine.sampling import SamplingParams
    from tensorlink_tpu.models import ModelConfig, init_params

    cfg = ModelConfig(
        family="llama", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, max_seq_len=128,
        dtype=jnp.float32, tie_embeddings=False,
    )
    params = init_params(cfg, jax.random.PRNGKey(1))
    kw = dict(seq_buckets=(32, 128), batch_buckets=(2,), max_seq_len=128)
    prompts = [[7, 3, 9, 11, 2], [5, 1, 8]]  # ragged -> right-padded bucket
    greedy = SamplingParams.make()

    dense = GenerationEngine(cfg, params, **kw)
    flash = GenerationEngine(
        cfg.with_(flash_attention=True), params, **kw
    )
    r_dense = dense.generate_compiled(prompts, max_new_tokens=10, sampling=greedy)
    r_flash = flash.generate_compiled(prompts, max_new_tokens=10, sampling=greedy)
    assert r_flash.sequences == r_dense.sequences

    # prefill logits agree numerically, not just post-argmax
    lg_d = dense.prefill(prompts)[0]
    lg_f = flash.prefill(prompts)[0]
    np.testing.assert_allclose(
        np.asarray(lg_f), np.asarray(lg_d), rtol=2e-4, atol=2e-4
    )


# ---------------------------------------------------------------------------
# paged decode attention (continuous batching)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "S,Hq,Hkv,hd,page,n_pp",
    [
        (4, 8, 2, 32, 8, 4),  # GQA, ragged lengths
        (2, 4, 4, 16, 16, 2),  # MHA
        (3, 8, 1, 64, 4, 8),  # MQA, many small pages
    ],
)
def test_paged_kernel_matches_ref(S, Hq, Hkv, hd, page, n_pp):
    """The Pallas paged kernel (scalar-prefetched block tables, online
    softmax per page) matches the pure-jnp reference across GQA shapes
    and ragged lengths — including a free slot (length 0, zero output)
    and a full slot."""
    rng = np.random.default_rng(0)
    P = 1 + S * n_pp
    q = jnp.asarray(rng.normal(size=(S, Hq, hd)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
    bt = jnp.asarray(rng.permutation(np.arange(1, P))[: S * n_pp]
                     .reshape(S, n_pp).astype(np.int32))
    lens = np.linspace(0, n_pp * page, S).astype(np.int32)  # 0 .. full
    lens = jnp.asarray(lens)
    scale = hd**-0.5
    ref = paged_attention_ref(q, kp, vp, bt, lens, scale=scale)
    got = paged_attention(q, kp, vp, bt, lens, scale=scale, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    assert np.abs(np.asarray(ref)[np.asarray(lens) == 0]).max() == 0


def test_paged_ref_matches_dense_attention():
    """A slot whose pages are filled contiguously computes EXACTLY what
    the dense einsum path computes over a contiguous cache with the same
    valid length — pages change layout, never math."""
    rng = np.random.default_rng(1)
    S, Hq, Hkv, hd, page, n_pp = 2, 4, 2, 16, 8, 3
    L = n_pp * page
    lens = [13, 24]
    k_dense = rng.normal(size=(S, L, Hkv, hd)).astype(np.float32)
    v_dense = rng.normal(size=(S, L, Hkv, hd)).astype(np.float32)
    q = rng.normal(size=(S, 1, Hq, hd)).astype(np.float32)
    # scatter the dense rows into pages (slot s gets pages 1+s*n_pp ...)
    P = 1 + S * n_pp
    kp = np.zeros((P, Hkv, page, hd), np.float32)
    vp = np.zeros((P, Hkv, page, hd), np.float32)
    bt = np.zeros((S, n_pp), np.int32)
    for s in range(S):
        pages = 1 + s * n_pp + np.arange(n_pp)
        bt[s] = pages
        kp[pages] = k_dense[s].reshape(n_pp, page, Hkv, hd).transpose(
            0, 2, 1, 3
        )
        vp[pages] = v_dense[s].reshape(n_pp, page, Hkv, hd).transpose(
            0, 2, 1, 3
        )
    got = paged_attention_ref(
        jnp.asarray(q[:, 0]), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(lens, jnp.int32), scale=hd**-0.5,
    )
    # dense einsum reference: query at position lens-1 over a [S, L] cache
    pos = jnp.asarray(np.asarray(lens, np.int64)[:, None] - 1)
    valid = jnp.arange(L)[None, :] < jnp.asarray(lens)[:, None]
    bias = _mask_bias(pos, L, valid, None)
    ref = attention(
        jnp.asarray(q), jnp.asarray(k_dense), jnp.asarray(v_dense),
        bias, hd**-0.5,
    )[:, 0]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# offset-carrying paged PREFILL attention (chunked prefill / prefix cache)
# ---------------------------------------------------------------------------
def test_paged_prefill_ref_matches_dense_causal():
    """A chunk at offset ``start`` over contiguously-paged KV computes
    exactly dense causal attention restricted to the chunk's rows: query
    start+j sees keys 0..start+j. Pages change layout, never math."""
    rng = np.random.default_rng(5)
    C, Hq, Hkv, hd, page, n_pp = 8, 4, 2, 16, 8, 4
    start = 11
    L = n_pp * page
    T = start + C  # keys live through the chunk's last position
    k_dense = rng.normal(size=(T, Hkv, hd)).astype(np.float32)
    v_dense = rng.normal(size=(T, Hkv, hd)).astype(np.float32)
    q = rng.normal(size=(C, Hq, hd)).astype(np.float32)
    kp = np.zeros((1 + n_pp, Hkv, page, hd), np.float32)
    vp = np.zeros((1 + n_pp, Hkv, page, hd), np.float32)
    bt = 1 + np.arange(n_pp, dtype=np.int32)
    pad = np.zeros((L - T, Hkv, hd), np.float32)
    kp[bt] = np.concatenate([k_dense, pad]).reshape(
        n_pp, page, Hkv, hd
    ).transpose(0, 2, 1, 3)
    vp[bt] = np.concatenate([v_dense, pad]).reshape(
        n_pp, page, Hkv, hd
    ).transpose(0, 2, 1, 3)
    got = paged_prefill_attention_ref(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.int32(start), scale=hd**-0.5,
    )
    # dense reference: a [1, T] causal attention, rows start..start+C-1
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (1, T))
    bias = _mask_bias(pos, T, jnp.ones((1, T), bool), None)
    full_q = np.zeros((1, T, Hq, hd), np.float32)
    full_q[0, start:] = q
    ref = attention(
        jnp.asarray(full_q), jnp.asarray(k_dense)[None],
        jnp.asarray(v_dense)[None], bias, hd**-0.5,
    )[0, start:]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


# ---------------------------------------------------------------------------
# ragged paged attention (unified prefill+decode step)
# ---------------------------------------------------------------------------
def _ragged_case(rng, S, C, Hq, Hkv, hd, page, n_pp, starts, nv):
    P = 1 + S * n_pp
    q = jnp.asarray(rng.normal(size=(S, C, Hq, hd)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P))[: S * n_pp]
        .reshape(S, n_pp).astype(np.int32)
    )
    return q, kp, vp, bt, jnp.asarray(starts, jnp.int32), \
        jnp.asarray(nv, jnp.int32)


@pytest.mark.parametrize(
    "S,C,Hq,Hkv,hd,page,n_pp,starts,nv",
    [
        # mixed: decode slot + fresh prefill + mid-prefill offset + padding
        (4, 8, 8, 2, 32, 8, 4, [13, 0, 11, 0], [1, 8, 5, 0]),
        # decode-only block (every slot 1 valid token, ragged lengths)
        (4, 8, 4, 4, 16, 8, 4, [0, 7, 15, 30], [1, 1, 1, 1]),
        # prefill-only block, MQA, mid-page offsets (COW landings)
        pytest.param(3, 16, 8, 1, 64, 4, 8, [0, 3, 17], [16, 16, 9],
                     marks=pytest.mark.slow),
        # all-padding block (idle engine shape: all-zero output, no NaN)
        pytest.param(2, 8, 4, 2, 16, 8, 2, [0, 0], [0, 0],
                     marks=pytest.mark.slow),
        # one slot, a whole chunk at an offset: the prefill path
        (1, 8, 8, 2, 32, 8, 4, [0], [8]),  # GQA, fresh admission
        (1, 8, 8, 2, 32, 8, 4, [13], [8]),  # GQA, mid-page (COW landing)
        pytest.param(1, 16, 4, 4, 16, 16, 3, [16], [16],
                     marks=pytest.mark.slow),
        pytest.param(1, 4, 8, 1, 64, 4, 8, [27], [4],
                     marks=pytest.mark.slow),
    ],
)
def test_ragged_kernel_matches_ref(S, C, Hq, Hkv, hd, page, n_pp, starts, nv):
    """The ragged Pallas kernel (decode grid + whole-chunk query blocks,
    per-slot (start, n_valid) via scalar prefetch) matches the pure-jnp
    reference across decode-only / prefill-only / mixed / all-padding
    slot configurations — the one-kernel claim of the unified step — and
    each slot's valid rows the one-slot offset-prefill reference."""
    rng = np.random.default_rng(8)
    q, kp, vp, bt, st, nvj = _ragged_case(
        rng, S, C, Hq, Hkv, hd, page, n_pp, starts, nv
    )
    scale = hd**-0.5
    ref = ragged_paged_attention_ref(q, kp, vp, bt, st, nvj, scale=scale)
    got = ragged_paged_attention(
        q, kp, vp, bt, st, nvj, scale=scale, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    # invalid rows (and whole padding slots) are exactly zero, not garbage
    for s in range(S):
        assert np.abs(np.asarray(ref)[s, nv[s]:]).max(initial=0) == 0
        assert np.abs(np.asarray(got)[s, nv[s]:]).max(initial=0) == 0
        pf = paged_prefill_attention_ref(
            q[s], kp, vp, bt[s], st[s], scale=scale
        )
        np.testing.assert_allclose(
            np.asarray(got)[s, : nv[s]], np.asarray(pf)[: nv[s]],
            rtol=2e-5, atol=2e-5,
        )


def test_ragged_ref_matches_decode_and_prefill_refs_bitwise():
    """THE composition pin the unified step's stream contract stands on:
    a 1-valid-token slot of the ragged reference is BITWISE
    ``paged_attention_ref`` at length ``start + 1``, and a prefilling
    slot's valid rows are BITWISE ``paged_prefill_attention_ref`` at the
    same offset — so swapping the two legacy programs for the one ragged
    program cannot move a single bit of attention output."""
    rng = np.random.default_rng(9)
    S, C, Hq, Hkv, hd, page, n_pp = 4, 8, 8, 2, 32, 8, 4
    starts = [13, 0, 11, 22]
    nv = [1, 8, 5, 1]
    q, kp, vp, bt, st, nvj = _ragged_case(
        rng, S, C, Hq, Hkv, hd, page, n_pp, starts, nv
    )
    scale = hd**-0.5
    ref = np.asarray(
        ragged_paged_attention_ref(q, kp, vp, bt, st, nvj, scale=scale)
    )
    for s in (0, 3):  # decode-shaped slots
        dec = paged_attention_ref(
            q[s : s + 1, 0], kp, vp, bt[s : s + 1],
            jnp.asarray([starts[s] + 1], jnp.int32), scale=scale,
        )
        assert np.array_equal(ref[s, 0], np.asarray(dec)[0]), s
    for s in (1, 2):  # prefill-shaped slots
        pf = paged_prefill_attention_ref(
            q[s], kp, vp, bt[s], jnp.int32(starts[s]), scale=scale
        )
        assert np.array_equal(ref[s, : nv[s]], np.asarray(pf)[: nv[s]]), s


def test_ragged_verify_rows_match_sequential_decode():
    """THE speculative-verification pin (docs/SERVING.md "Speculative
    decoding"): a verifying slot — k+1 valid query rows at its current
    start — produces, at every row j, the attention output of a
    sequential decode step at length ``start + j + 1`` with the same
    query, to a few ulps. The ragged reference's causal ``q_pos``
    masking already encodes verify mode; no new kernel logic exists to
    drift. The two references contract einsums of different shapes, and
    the CPU backend does not promise them the same summation order
    (``0.13055041`` against ``0.13055044``): tolerance is the contract
    (ROADMAP D9), not bit equality. (Row 0 is the existing
    decode-composition pin; rows 1..k are what speculation adds.) The
    Pallas kernel is held to the reference on the same verify-shaped
    block."""
    rng = np.random.default_rng(11)
    S, C, Hq, Hkv, hd, page, n_pp = 2, 8, 4, 2, 16, 8, 4
    start, k = 13, 4  # a decode slot at length 13 verifying 4 drafts
    q, kp, vp, bt, st, nvj = _ragged_case(
        rng, S, C, Hq, Hkv, hd, page, n_pp, [start, 0], [1 + k, 0]
    )
    scale = hd**-0.5
    ref = np.asarray(
        ragged_paged_attention_ref(q, kp, vp, bt, st, nvj, scale=scale)
    )
    # oracle: k+1 sequential decode _ref steps — step j sees exactly the
    # keys <= start + j (the block's KV is pre-scattered, like the step)
    for j in range(1 + k):
        dec = paged_attention_ref(
            q[0:1, j], kp, vp, bt[0:1],
            jnp.asarray([start + j + 1], jnp.int32), scale=scale,
        )
        np.testing.assert_allclose(
            ref[0, j], np.asarray(dec)[0], rtol=0, atol=1e-6, err_msg=str(j)
        )
    got = ragged_paged_attention(
        q, kp, vp, bt, st, nvj, scale=scale, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the live-span walk of the two paged kernels: block and row-block edges
# ---------------------------------------------------------------------------
def test_walk_trip_counts_follow_the_live_span():
    """The pure helpers behind the walk: block sizes from shapes (a score
    tile of at least 128 key positions, row blocks of at most 128 query
    rows that divide the chunk) and, from ``(start, n_valid)``, how many
    row blocks a slot walks and how many pages and KV blocks each row
    block reaches — its own causal limit, never the capacity."""
    from tensorlink_tpu.ops.attention import (
        _heads_per_block, _pages_per_block, _positions_per_row_block,
        _walk_trips,
    )

    assert _pages_per_block(16, 256) == 8  # the served shape: 8 x 16
    assert _pages_per_block(16, 4) == 4  # clamps to what a slot has
    assert _pages_per_block(8, 20) == 16
    assert _pages_per_block(256, 16) == 1
    assert _positions_per_row_block(128, 4) == 32  # 128 rows a block
    assert _positions_per_row_block(1, 4) == 1  # the decode kernel
    assert _positions_per_row_block(16, 4) == 16  # one block, 64 rows
    assert _positions_per_row_block(48, 4) == 24  # divides the chunk
    assert _positions_per_row_block(8, 16) == 8
    # group sizes that are not a power of two (28/4 heads, its tp=4
    # shard 7/1) and one (32/32): a row block at a dynamic offset is
    # whole 16-row tiles, one block of any height is the whole chunk
    assert _positions_per_row_block(128, 7) == 16  # 112 rows
    assert _positions_per_row_block(1, 7) == 1  # the decode kernel
    assert _positions_per_row_block(8, 7) == 8  # one block, 56 rows
    assert _positions_per_row_block(128, 1) == 128  # one block
    assert _positions_per_row_block(256, 1) == 128
    assert _positions_per_row_block(128, 9) == 16  # 144 rows: none fits
    # kv heads a grid step: all where VMEM allows (qwen3-4b's 8, its
    # decode kernel, a tp shard), a divisor where not (olmo2-7b 32/32,
    # gemma-7b 16/16 at head_dim 256)
    assert _heads_per_block(8, 512, 128, 128, 128, 2, 128) == 8
    assert _heads_per_block(8, 4, 4, 128, 128, 2, 128) == 8
    assert _heads_per_block(2, 512, 128, 128, 128, 2, 128) == 2
    assert _heads_per_block(32, 128, 128, 128, 128, 2, 128) == 16
    assert _heads_per_block(32, 1, 1, 128, 128, 2, 256) == 32
    assert _heads_per_block(16, 128, 128, 128, 256, 2, 256) == 8
    assert _heads_per_block(3, 2**20, 128, 128, 128, 2, 128) == 1

    def trips(start, nv, rb, cb=32, page=16, ppb=8):
        return tuple(
            int(x) for x in _walk_trips(start, nv, rb, cb=cb, page=page,
                                        ppb=ppb)
        )

    # (row blocks, key positions, live pages, KV blocks)
    assert trips(0, 0, 0) == (0, 0, 0, 0)  # a padding slot walks nothing
    assert trips(249, 1, 0) == (1, 250, 16, 2)  # a decode row at 250
    assert trips(1199, 1, 0) == (1, 1200, 75, 10)
    assert trips(0, 128, 0) == (4, 32, 2, 1)  # a fresh chunk: row block
    assert trips(0, 128, 3) == (4, 128, 8, 1)  # 0 sees 32 keys, 3 all 128
    assert trips(1072, 128, 1) == (4, 1136, 71, 9)
    assert trips(245, 5, 0) == (1, 250, 16, 2)  # verify rows
    assert trips(100, 33, 1) == (2, 133, 9, 2)  # one row over a row block
    # the decode kernel's case: start = max(length - 1, 0), n_valid =
    # min(length, 1)
    for length, want in ((0, (0, 0, 0)), (127, (127, 8, 1)),
                         (128, (128, 8, 1)), (129, (129, 9, 2))):
        got = trips(max(length - 1, 0), min(length, 1), 0, cb=1)
        assert got == (min(length, 1), *want), (length, got)


def _poison(kp, vp, bt, live_len):
    """Pools in which every position a slot must not read holds NaN: the
    pages its dead block-table entries name (all point at one NaN page),
    and the positions past ``live_len`` inside its last live page. ``kp``
    and ``vp`` are f32 pages, or the scale planes ``[P, Hkv, page]`` of
    quantized ones (an int8 value cannot be NaN; its scale can). Returns
    (poisoned k, v, block tables, finite k, v for the reference)."""
    kp, vp, bt = np.array(kp), np.array(vp), np.array(bt)
    page = kp.shape[2]
    nan_page = kp.shape[0]  # one more page, all NaN
    pad = np.full((1,) + kp.shape[1:], np.nan, np.float32)
    kp, vp = np.concatenate([kp, pad]), np.concatenate([vp, pad])
    for s, n in enumerate(live_len):
        n_live = -(-int(n) // page)
        bt[s, n_live:] = nan_page
        if n % page:
            kp[bt[s, n_live - 1], :, n % page:] = np.nan
            vp[bt[s, n_live - 1], :, n % page:] = np.nan
    return (jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
            jnp.asarray(np.nan_to_num(kp)), jnp.asarray(np.nan_to_num(vp)))


def _poisoned_int8(rng, P, Hkv, page, hd, bt, live_len):
    """int8 pools whose scale planes are poisoned like :func:`_poison`
    (the values behind a NaN scale are full-scale 127s): ``(k8, v8, block
    tables, poisoned {k_scale, v_scale}, finite ones for the
    reference)``."""
    _, _, k8, ks, v8, vs = _quantized_pages(rng, P, Hkv, page, hd)
    ks_p, vs_p, bt, ks_r, vs_r = _poison(ks, vs, bt, live_len)
    full = jnp.full((1, Hkv, page, hd), 127, jnp.int8)
    k8, v8 = jnp.concatenate([k8, full]), jnp.concatenate([v8, full])
    return (k8, v8, bt, {"k_scale": ks_p, "v_scale": vs_p},
            {"k_scale": ks_r, "v_scale": vs_r})


@pytest.mark.parametrize(
    "S,Hq,Hkv,hd,page,n_pp,lens,poison",
    [
        # on, under and over a block edge (8 pages of 16 = 128 keys), a
        # free slot between live ones; 20 pages a slot: the last block
        # is partial (n_pp not a multiple of the block's 8 pages)
        (4, 8, 2, 32, 16, 20, [128, 0, 127, 129], False),
        # into and to the end of that partial last block
        (4, 8, 2, 32, 16, 20, [320, 257, 1, 300], False),
        # one kv head: a tensor-parallel shard's view
        (3, 4, 1, 32, 16, 20, [130, 16, 255], False),
        # small pages: a block is 16 pages of 8
        (3, 4, 2, 16, 8, 20, [129, 0, 160], False),
        # poison: dead block-table entries name a NaN page, positions
        # past the length in the last live page are NaN: output finite
        (4, 8, 2, 32, 16, 20, [128, 0, 121, 139], True),
        # one query row a kv head (32/32-style heads) and seven (28/4,
        # and its tensor-parallel shard 7/1): group sizes under and off
        # the 8-row tile
        (3, 4, 4, 32, 16, 20, [129, 0, 250], False),
        (3, 14, 2, 32, 16, 20, [128, 17, 301], False),
        (3, 7, 1, 32, 16, 20, [127, 0, 320], True),
        # int8 pages: NaN scales behind dead entries and past the length
        (4, 8, 2, 32, 16, 20, [128, 0, 121, 139], "int8"),
        (3, 7, 1, 32, 16, 20, [127, 0, 320], "int8"),
    ],
    ids=["block-edges", "partial-last-block", "hkv1", "page8", "poison",
         "g1", "g7", "g7-hkv1-poison", "poison-int8", "poison-int8-g7"],
)
def test_paged_kernel_walks_the_live_span(
    S, Hq, Hkv, hd, page, n_pp, lens, poison
):
    """The decode kernel's loop over the KV blocks a slot's length
    reaches: exact at the block edges, on a block table that does not
    divide into blocks, with a free slot between live ones, and reading
    nothing — not as an address, not as a value — past the live span."""
    rng = np.random.default_rng(31)
    P = 1 + S * n_pp
    q = jnp.asarray(rng.normal(size=(S, Hq, hd)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
    bt = jnp.asarray(rng.permutation(np.arange(1, P))
                     .reshape(S, n_pp).astype(np.int32))
    kr, vr, kw, kw_ref = kp, vp, {}, {}
    if poison == "int8":
        kp, vp, bt, kw, kw_ref = _poisoned_int8(
            rng, P, Hkv, page, hd, bt, lens)
        kr, vr = kp, vp
    elif poison:
        kp, vp, bt, kr, vr = _poison(kp, vp, bt, lens)
    lens = jnp.asarray(lens, jnp.int32)
    scale = hd**-0.5
    ref = np.asarray(
        paged_attention_ref(q, kr, vr, bt, lens, scale=scale, **kw_ref))
    got = np.asarray(paged_attention(
        q, kp, vp, bt, lens, scale=scale, interpret=True, **kw))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    assert np.abs(got[np.asarray(lens) == 0]).max(initial=0) == 0


@pytest.mark.parametrize(
    "S,C,Hq,Hkv,hd,page,n_pp,starts,nv,poison",
    [
        # start + n_valid on, under and over a KV block edge, a padding
        # slot between live ones, a partial last block (20 pages a slot)
        (4, 8, 8, 2, 32, 16, 20, [120, 0, 119, 121], [8, 0, 8, 8], False),
        # two row blocks (64 positions x 4 rows = 256 rows): a decode
        # row beside a full prefill slot and a padding slot
        (3, 64, 8, 2, 32, 16, 20, [200, 70, 0], [1, 64, 0], False),
        # n_valid on, under and over a row-block edge (32 positions)
        (3, 64, 8, 2, 32, 16, 20, [0, 100, 37], [32, 31, 33], False),
        # one kv head (a tensor-parallel shard), verify-shaped rows
        (3, 8, 4, 1, 32, 16, 20, [250, 0, 127], [5, 8, 1], False),
        # poison: NaN behind every dead block-table entry and past the
        # live span inside the last live page
        (4, 8, 8, 2, 32, 16, 20, [120, 0, 113, 131], [8, 0, 8, 8], True),
        (3, 64, 8, 2, 32, 16, 20, [200, 70, 0], [1, 64, 0], True),
        # one query row a kv head: one row block up to 128 positions,
        # two at 256
        (3, 64, 4, 4, 32, 16, 20, [200, 70, 0], [1, 64, 0], False),
        (2, 256, 2, 2, 16, 16, 20, [10, 0], [256, 130], False),
        # seven rows a kv head (28/4 and its shard 7/1): four row blocks
        # of 16 positions = 112 rows, and one block of 56 rows
        (3, 64, 14, 2, 32, 16, 20, [200, 70, 0], [1, 64, 0], False),
        (3, 64, 7, 1, 32, 16, 20, [0, 100, 37], [16, 15, 17], True),
        (3, 8, 7, 1, 32, 16, 20, [250, 0, 127], [5, 8, 1], False),
        # int8 pages: NaN scales behind dead entries and past the span
        (4, 8, 8, 2, 32, 16, 20, [120, 0, 113, 131], [8, 0, 8, 8], "int8"),
        (3, 64, 8, 2, 32, 16, 20, [200, 70, 0], [1, 64, 0], "int8"),
    ],
    ids=["block-edges", "decode-row-beside-prefill", "row-block-edges",
         "hkv1-verify", "poison", "poison-row-blocks", "g1", "g1-row-blocks",
         "g7-row-blocks", "g7-hkv1-poison", "g7-one-block", "poison-int8",
         "poison-int8-row-blocks"],
)
def test_ragged_kernel_walks_the_live_span(
    S, C, Hq, Hkv, hd, page, n_pp, starts, nv, poison
):
    """The ragged kernel's two loops: row blocks up to ``n_valid`` and,
    for each, KV blocks up to its own causal limit. Exact at both kinds
    of edge, zero rows past ``n_valid`` (whole padding slots and unwalked
    row blocks included), and nothing read past the live span."""
    rng = np.random.default_rng(32)
    q, kp, vp, bt, st, nvj = _ragged_case(
        rng, S, C, Hq, Hkv, hd, page, n_pp, starts, nv
    )
    kr, vr, kw, kw_ref = kp, vp, {}, {}
    live = [a + b if b else 0 for a, b in zip(starts, nv)]
    if poison == "int8":
        kp, vp, bt, kw, kw_ref = _poisoned_int8(
            rng, kp.shape[0], Hkv, page, hd, bt, live)
        kr, vr = kp, vp
    elif poison:
        kp, vp, bt, kr, vr = _poison(kp, vp, bt, live)
    scale = hd**-0.5
    ref = np.asarray(ragged_paged_attention_ref(
        q, kr, vr, bt, st, nvj, scale=scale, **kw_ref))
    got = np.asarray(ragged_paged_attention(
        q, kp, vp, bt, st, nvj, scale=scale, interpret=True, **kw))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    for s in range(S):
        assert np.abs(got[s, nv[s]:]).max(initial=0) == 0


@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_walk_over_blocks_of_kv_heads(monkeypatch, quantized):
    """Where every kv head of a slot does not fit one grid step's VMEM
    (olmo2-7b's 32 heads at chunk 128), the grid's second axis walks
    blocks of heads: each copies its own heads of a page and its own
    lanes of the scale planes. Forced here by a budget one head fills."""
    from tensorlink_tpu.ops import attention as A

    monkeypatch.setattr(A, "_VMEM_BUDGET", 1)
    assert A._heads_per_block(4, 32, 32, 128, 32, 4, 32) == 1
    rng = np.random.default_rng(33)
    S, C, Hq, Hkv, hd, page, n_pp = 3, 8, 8, 4, 32, 16, 20
    starts, nv = [120, 0, 250], [8, 0, 1]
    q, kp, vp, bt, st, nvj = _ragged_case(
        rng, S, C, Hq, Hkv, hd, page, n_pp, starts, nv
    )
    kw = {}
    if quantized:
        _, _, kp, ks, vp, vs = _quantized_pages(rng, kp.shape[0], Hkv, page, hd)
        kw = {"k_scale": ks, "v_scale": vs}
    scale = hd**-0.5
    lens = jnp.asarray([a + b for a, b in zip(starts, nv)], jnp.int32)
    # the un-jitted functions: a cached trace would keep its own budget
    for kern, ref, args in (
        (A.ragged_paged_attention, ragged_paged_attention_ref,
         (q, kp, vp, bt, st, nvj)),
        (A.paged_attention, paged_attention_ref, (q[:, 0], kp, vp, bt, lens)),
    ):
        got = jax.jit(functools.partial(
            kern.__wrapped__, scale=scale, interpret=True, **kw))(*args)
        want = ref(*args, scale=scale, **kw)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.slow  # compiles dedicated ragged shapes — CI engine job runs
# it unfiltered on every push (tier-1 wall-time)
def test_ragged_packing_framing_is_bitwise_invariant():
    """The chunk-framing contract extended to ragged packing: prefilling
    the same prompt through ``paged_ragged_step`` under DIFFERENT
    per-step token budgets — with a co-resident decode token riding
    every packed block — produces bitwise identical KV pages for both
    slots and the same first greedy draw. This is what lets the host
    packing function hand out any grant schedule (fair-share, budget-
    capped, full-chunk) without moving a bit of any stream."""
    from tensorlink_tpu.engine.paged import (
        PagedKVCache, pack_control, paged_ragged_step, unpack_results,
    )
    from tensorlink_tpu.models import ModelConfig, init_params

    cfg = ModelConfig(
        family="llama", vocab_size=128, d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=64,
        dtype=jnp.float32, tie_embeddings=False,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(6).integers(1, 128, 24).tolist()
    dec_toks = np.random.default_rng(7).integers(1, 128, 8).tolist()
    page, C, T, S = 8, 8, 24, 4
    bt0 = np.zeros(8, np.int32)
    bt0[:4] = range(1, 5)
    bt1 = np.zeros(8, np.int32)
    bt1[:4] = range(5, 9)

    def run(schedule):
        cache = PagedKVCache.init(cfg, S, page_size=page, max_len=64)
        # both slots are bound by the first block's control buffer, as
        # the engine binds an admitted slot (``Control.bind``)
        rows = np.zeros((S, 8), np.int32)
        rows[0], rows[1] = bt0, bt1
        zeros_i = np.zeros(S, np.int32)
        zeros_f = np.zeros(S, np.float32)
        counts = jnp.zeros((S, cfg.vocab_size), jnp.int32)
        eos = np.full((S, 2), -1, np.int32)
        pos = 0
        first_draw = None
        for step_i, g in enumerate(schedule):
            blk = np.zeros((S, C), np.int32)
            starts = np.zeros(S, np.int32)
            nv = np.zeros(S, np.int32)
            emit = np.zeros(S, bool)
            blk[0, :g] = prompt[pos : pos + g]
            starts[0], nv[0] = pos, g
            # slot 1 plays a co-resident decode: one pinned token per
            # step at its running length — its KV must come out bitwise
            # identical no matter how slot 0's prefill is framed
            blk[1, 0] = dec_toks[step_i]
            starts[1], nv[1] = step_i, 1
            done_prefill = pos + g >= T
            emit[0] = done_prefill  # final chunk: greedy first draw
            ctl = pack_control(
                blk, starts, nv, zeros_i, emit, zeros_i, zeros_i, zeros_f,
                zeros_i, np.ones(S, np.float32), zeros_f, zeros_f,
                np.ones(S, np.int32), eos,
                np.arange(S) < (2 if step_i == 0 else 0), zeros_i,
                np.zeros(S, bool), rows,
            )
            out, cache, counts = paged_ragged_step(
                params, ctl, cache, counts, cfg, 1, 1, False,
            )
            if done_prefill:
                first_draw = int(unpack_results(np.asarray(out), 1, 1)[0][0, 0])
            pos += g
        k = np.asarray(cache.k)
        real = np.stack(
            [k[:, bt0[p // page], :, p % page] for p in range(T)], 1
        )
        dec = np.stack(
            [k[:, bt1[p // page], :, p % page]
             for p in range(len(schedule))], 1
        )
        return real, dec, first_draw

    k_ref, d_ref, t_ref = run([8, 8, 8])
    for schedule in ([8, 8, 5, 3], [5, 8, 8, 3], [2, 8, 8, 6]):
        k_got, d_got, t_got = run(schedule)
        assert np.array_equal(k_got, k_ref), schedule
        assert np.array_equal(
            d_got[:, : min(len(schedule), 3)], d_ref[:, : min(len(schedule), 3)]
        ), schedule
        assert t_got == t_ref, schedule


# ---------------------------------------------------------------------------
# quantized paged KV (int8 pages + per-(page, position, head) scales)
# ---------------------------------------------------------------------------
def _quantized_pages(rng, P, Hkv, page, hd):
    from tensorlink_tpu.models.quant import quantize_kv

    kf = jnp.asarray(rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
    vf = jnp.asarray(rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
    k8, ks = quantize_kv(kf)
    v8, vs = quantize_kv(vf)
    return kf, vf, k8, ks, v8, vs


@pytest.mark.parametrize(
    "S,C,Hq,Hkv,hd,page,n_pp,starts,nv",
    [
        # mixed: decode slot + fresh prefill + mid-prefill offset + padding
        # (interpret-mode kernel compiles ride the CI engine job — tier-1
        # wall-time; the fast quantized pin is the divergence bound below)
        pytest.param(4, 8, 8, 2, 32, 8, 4, [13, 0, 11, 0], [1, 8, 5, 0],
                     marks=pytest.mark.slow),
        # decode-only block (every slot 1 valid token, ragged lengths)
        pytest.param(4, 8, 4, 4, 16, 8, 4, [0, 7, 15, 30], [1, 1, 1, 1],
                     marks=pytest.mark.slow),
        # all-padding block (idle engine shape: all-zero output, no NaN)
        pytest.param(2, 8, 4, 2, 16, 8, 2, [0, 0], [0, 0],
                     marks=pytest.mark.slow),
    ],
)
def test_quantized_ragged_kernel_matches_ref(
    S, C, Hq, Hkv, hd, page, n_pp, starts, nv
):
    """int8 pages + scales through the ragged Pallas kernel match the
    quantized pure-jnp reference across decode-only / mixed / all-padding
    slot configurations — the in-kernel dequant-at-fetch is the same math
    as the reference's dequant-at-gather."""
    rng = np.random.default_rng(21)
    P = 1 + S * n_pp
    q = jnp.asarray(rng.normal(size=(S, C, Hq, hd)).astype(np.float32))
    _, _, k8, ks, v8, vs = _quantized_pages(rng, P, Hkv, page, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P))[: S * n_pp]
        .reshape(S, n_pp).astype(np.int32)
    )
    st = jnp.asarray(starts, jnp.int32)
    nvj = jnp.asarray(nv, jnp.int32)
    scale = hd**-0.5
    ref = ragged_paged_attention_ref(
        q, k8, v8, bt, st, nvj, scale=scale, k_scale=ks, v_scale=vs
    )
    got = ragged_paged_attention(
        q, k8, v8, bt, st, nvj, scale=scale, interpret=True,
        k_scale=ks, v_scale=vs,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    for s in range(S):
        assert np.abs(np.asarray(got)[s, nv[s]:]).max(initial=0) == 0


@pytest.mark.slow  # see above — CI's engine job runs it on every push
def test_quantized_decode_and_prefill_kernels_match_refs():
    """The decode entry point and a one-slot offset chunk through the
    ragged one carry int8 pages too: kernel (interpret) vs quantized
    reference parity for both."""
    rng = np.random.default_rng(22)
    S, Hq, Hkv, hd, page, n_pp = 4, 8, 2, 32, 8, 4
    P = 1 + S * n_pp
    _, _, k8, ks, v8, vs = _quantized_pages(rng, P, Hkv, page, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P))[: S * n_pp]
        .reshape(S, n_pp).astype(np.int32)
    )
    scale = hd**-0.5
    qd = jnp.asarray(rng.normal(size=(S, Hq, hd)).astype(np.float32))
    lens = jnp.asarray([0, 9, 17, 32], jnp.int32)
    ref = paged_attention_ref(
        qd, k8, v8, bt, lens, scale=scale, k_scale=ks, v_scale=vs
    )
    got = paged_attention(
        qd, k8, v8, bt, lens, scale=scale, interpret=True,
        k_scale=ks, v_scale=vs,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    C = 8
    qp = jnp.asarray(rng.normal(size=(C, Hq, hd)).astype(np.float32))
    ref = paged_prefill_attention_ref(
        qp, k8, v8, bt[0], jnp.int32(13), scale=scale,
        k_scale=ks, v_scale=vs,
    )
    got = ragged_paged_attention(
        qp[None], k8, v8, bt[:1], jnp.asarray([13], jnp.int32),
        jnp.asarray([C], jnp.int32), scale=scale, interpret=True,
        k_scale=ks, v_scale=vs,
    )[0]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_quantized_kv_divergence_bounded():
    """THE fp16-vs-int8 KV accuracy bound: attention outputs over int8
    pages + per-(position, head) scales stay within a tight absolute
    bound of the full-precision pages' outputs. Symmetric int8 over
    head_dim bounds each KV element's error by scale/2 ≈ amax/254;
    attention outputs are convex combinations of V rows, so the output
    error is the same order — NOT accumulating with context length."""
    rng = np.random.default_rng(23)
    S, C, Hq, Hkv, hd, page, n_pp = 4, 8, 8, 2, 32, 8, 4
    P = 1 + S * n_pp
    q = jnp.asarray(rng.normal(size=(S, C, Hq, hd)).astype(np.float32))
    kf, vf, k8, ks, v8, vs = _quantized_pages(rng, P, Hkv, page, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P))[: S * n_pp]
        .reshape(S, n_pp).astype(np.int32)
    )
    st = jnp.asarray([13, 0, 11, 22], jnp.int32)
    nv = jnp.asarray([1, 8, 5, 1], jnp.int32)
    scale = hd**-0.5
    full = ragged_paged_attention_ref(q, kf, vf, bt, st, nv, scale=scale)
    quant = ragged_paged_attention_ref(
        q, k8, v8, bt, st, nv, scale=scale, k_scale=ks, v_scale=vs
    )
    err = float(np.abs(np.asarray(quant) - np.asarray(full)).max())
    # N(0,1) values: per-element KV error <= amax/254 (~0.02 here); the
    # measured output divergence is ~0.015 — 0.06 is the loud-failure bar
    assert err < 0.06, err
    # and the int8 payload really is what the engine stores: round-trip
    # through dequantize_kv reproduces the reference gather's view
    from tensorlink_tpu.models.quant import dequantize_kv

    np.testing.assert_allclose(
        np.asarray(dequantize_kv(k8, ks)), np.asarray(kf), atol=0.025
    )


@pytest.mark.slow  # see above
def test_engine_flash_sharded_mesh_matches_dense(flash_off_tpu, cpu_devices):
    """Flash prefill composes with a tensor/data mesh (r3 weak: it was
    silently ignored on sharded stages): the kernel runs inside shard_map
    over data/tensor, and the sharded flash engine's tokens match the
    unsharded einsum engine exactly."""
    from jax.sharding import NamedSharding
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.engine.sampling import SamplingParams
    from tensorlink_tpu.models import ModelConfig, init_params
    from tensorlink_tpu.models.transformer import cache_specs, partition_specs
    from tensorlink_tpu.parallel.mesh import build_mesh

    cfg = ModelConfig(
        family="llama", vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, max_seq_len=128,
        dtype=jnp.float32, tie_embeddings=False,
    )
    params = init_params(cfg, jax.random.PRNGKey(3))
    kw = dict(seq_buckets=(32, 128), batch_buckets=(2,), max_seq_len=128)
    prompts = [[7, 3, 9, 11, 2], [5, 1, 8]]
    greedy = SamplingParams.make()
    dense = GenerationEngine(cfg, params, **kw)

    mesh = build_mesh({"data": 2, "tensor": 2}, cpu_devices[:4])
    specs = partition_specs(cfg, tensor_axis="tensor")
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )
    flash = GenerationEngine(
        cfg.with_(flash_attention=True), sharded, mesh=mesh,
        cache_specs=cache_specs(cfg, data_axis="data", tensor_axis="tensor"),
        **kw,
    )
    assert flash._fmesh is mesh  # the kernel really takes the shard_map path
    r_d = dense.generate_compiled(prompts, max_new_tokens=10, sampling=greedy)
    r_f = flash.generate_compiled(prompts, max_new_tokens=10, sampling=greedy)
    assert r_f.sequences == r_d.sequences
    lg_d = dense.prefill(prompts)[0]
    lg_f = flash.prefill(prompts)[0]
    np.testing.assert_allclose(
        np.asarray(lg_f), np.asarray(lg_d), rtol=2e-4, atol=2e-4
    )


# ---------------------------------------------------------------------------
# packed int4 paged KV (two values per byte + per-(page, position, head)
# scales) — kernel parity and the divergence bound
# ---------------------------------------------------------------------------
def _int4_pages(rng, P, Hkv, page, hd):
    from tensorlink_tpu.models.quant import quantize_kv4

    kf = jnp.asarray(rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
    vf = jnp.asarray(rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
    k4, ks = quantize_kv4(kf)
    v4, vs = quantize_kv4(vf)
    assert k4.shape[-1] == hd // 2  # really packed: two values per byte
    return kf, vf, k4, ks, v4, vs


@pytest.mark.slow  # interpret-mode kernel compiles — CI engine job
def test_int4_kernels_match_refs():
    """Packed int4 pages through both paged entry points: the Pallas
    kernels' in-VMEM nibble unpack + dequant matches the pure-jnp
    references' gather-time dequant across mixed/decode/prefill shapes —
    the same parity bar the int8 pages hold."""
    rng = np.random.default_rng(31)
    S, C, Hq, Hkv, hd, page, n_pp = 4, 8, 8, 2, 32, 8, 4
    P = 1 + S * n_pp
    _, _, k4, ks, v4, vs = _int4_pages(rng, P, Hkv, page, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P))[: S * n_pp]
        .reshape(S, n_pp).astype(np.int32)
    )
    scale = hd**-0.5
    # ragged (mixed decode + prefill + padding slots)
    q = jnp.asarray(rng.normal(size=(S, C, Hq, hd)).astype(np.float32))
    st = jnp.asarray([13, 0, 11, 0], jnp.int32)
    nv = jnp.asarray([1, 8, 5, 0], jnp.int32)
    ref = ragged_paged_attention_ref(
        q, k4, v4, bt, st, nv, scale=scale, k_scale=ks, v_scale=vs
    )
    got = ragged_paged_attention(
        q, k4, v4, bt, st, nv, scale=scale, interpret=True,
        k_scale=ks, v_scale=vs,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    for s, n in enumerate([1, 8, 5, 0]):
        assert np.abs(np.asarray(got)[s, n:]).max(initial=0) == 0
    # decode entry point
    qd = jnp.asarray(rng.normal(size=(S, Hq, hd)).astype(np.float32))
    lens = jnp.asarray([0, 9, 17, 32], jnp.int32)
    ref = paged_attention_ref(
        qd, k4, v4, bt, lens, scale=scale, k_scale=ks, v_scale=vs
    )
    got = paged_attention(
        qd, k4, v4, bt, lens, scale=scale, interpret=True,
        k_scale=ks, v_scale=vs,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    # a one-slot offset chunk against the one-slot prefill reference
    qp = jnp.asarray(rng.normal(size=(C, Hq, hd)).astype(np.float32))
    ref = paged_prefill_attention_ref(
        qp, k4, v4, bt[0], jnp.int32(13), scale=scale,
        k_scale=ks, v_scale=vs,
    )
    got = ragged_paged_attention(
        qp[None], k4, v4, bt[:1], jnp.asarray([13], jnp.int32),
        jnp.asarray([C], jnp.int32), scale=scale, interpret=True,
        k_scale=ks, v_scale=vs,
    )[0]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_int4_kv_divergence_bounded():
    """THE fp-vs-int4 accuracy bound: attention outputs over packed int4
    pages + per-(position, head) scales stay within a loose-but-loud
    absolute bound of the full-precision outputs — 15 quantization levels
    instead of 255, so the bound is ~16x the int8 one — and, like int8,
    it does NOT grow with context length: per-element KV error is
    bounded by scale/2 ≈ amax/14 and attention outputs are convex
    combinations of V rows, so more context averages MORE rows, never
    compounds the error."""
    from tensorlink_tpu.models.quant import dequantize_kv4

    rng = np.random.default_rng(33)
    S, C, Hq, Hkv, hd, page = 4, 8, 8, 2, 32, 8
    scale = hd**-0.5

    def divergence(n_pp):
        P = 1 + S * n_pp
        q = jnp.asarray(
            rng.normal(size=(S, C, Hq, hd)).astype(np.float32)
        )
        kf, vf, k4, ks, v4, vs = _int4_pages(rng, P, Hkv, page, hd)
        bt = jnp.asarray(
            rng.permutation(np.arange(1, P))[: S * n_pp]
            .reshape(S, n_pp).astype(np.int32)
        )
        # every slot attends its FULL page span: long contexts really
        # average more rows
        K = n_pp * page
        st = jnp.asarray([K - 1, K - 8, K - 5, K - 1], jnp.int32)
        nv = jnp.asarray([1, 8, 5, 1], jnp.int32)
        full = ragged_paged_attention_ref(q, kf, vf, bt, st, nv,
                                          scale=scale)
        quant = ragged_paged_attention_ref(
            q, k4, v4, bt, st, nv, scale=scale, k_scale=ks, v_scale=vs
        )
        return float(np.abs(np.asarray(quant) - np.asarray(full)).max())

    short = divergence(2)   # 16-position contexts
    long = divergence(16)   # 128-position contexts
    # N(0,1) values: measured ~0.3; 0.5 is the loud-failure bar (int8's
    # is 0.06 — the 15-vs-255-level ratio, same order)
    assert short < 0.5, short
    assert long < 0.5, long
    # and the payload round-trips through the packed dequant within the
    # per-element bound scale/2 (scale = amax/7 ≈ 0.5 on N(0,1) tails)
    x = jnp.asarray(rng.normal(size=(8, 4, 32)).astype(np.float32))
    from tensorlink_tpu.models.quant import quantize_kv4

    q4, s4 = quantize_kv4(x)
    err = np.abs(np.asarray(dequantize_kv4(q4, s4)) - np.asarray(x))
    bound = np.asarray(s4)[..., None] / 2 + 1e-6
    assert (err <= bound).all()


def test_int4_pack_layout_is_split_half():
    """The packing layout contract the kernels' unpack depends on: byte
    j of a packed row holds element j (low nibble) and element
    j + hd/2 (high nibble) — pinned so a layout change cannot silently
    desync quantize_kv4 from the kernels' in-VMEM unpack."""
    from tensorlink_tpu.models.quant import pack_int4, unpack_int4

    v = jnp.asarray(np.arange(-4, 4, dtype=np.int32)[None])  # [-4..3]
    p = np.asarray(pack_int4(v))[0]
    # byte 0 = (-4 & 0xF) | ((0 & 0xF) << 4): low nibble is element 0,
    # high nibble is element hd/2 = 4
    assert p[0] == np.int8((-4 & 0xF) | ((0 & 0xF) << 4))
    assert np.array_equal(np.asarray(unpack_int4(jnp.asarray(p[None]))),
                          np.asarray(v))


# ---------------------------------------------------------------------------
# the walk over a layer-stacked pool: [L, P, Hkv, page, hd] + a layer index
# ---------------------------------------------------------------------------
def _stacked_pools(rng, fmt, L, P, Hkv, page, hd):
    """``L`` layers of pages in one storage format, stacked as the cache
    holds them: ``(k, v, {k_scale, v_scale})``."""
    if fmt == "bf16":
        k, v = (
            jnp.asarray(rng.normal(size=(L, P, Hkv, page, hd)), jnp.bfloat16)
            for _ in range(2)
        )
        return k, v, {}
    make = _int4_pages if fmt == "int4" else _quantized_pages
    layers = [make(rng, P, Hkv, page, hd)[2:] for _ in range(L)]
    k, ks, v, vs = (jnp.stack(x) for x in zip(*layers))
    return k, v, {"k_scale": ks, "v_scale": vs}


@pytest.mark.parametrize("G", [1, 4, 7])
@pytest.mark.parametrize("fmt", ["bf16", "int8", "int4"])
def test_walk_over_a_stacked_pool_equals_the_layers_slice(fmt, G):
    """Both entry points on the layer-stacked pools with a layer index
    equal, bitwise, the same call on that layer's ``[P, ...]`` slice: the
    kernel addresses ``(layer, page)`` where it addressed ``page``, and
    nothing else changes. hd 128: bf16 and int8 pages go to the kernel as
    the whole stack, packed int4 (64 bytes a row, under a lane row) has
    its layer cut out and padded first. The layer is traced, as under the
    step's scan."""
    rng = np.random.default_rng(29)
    L, S, C, Hkv, hd, page, n_pp = 3, 3, 8, 2, 128, 8, 4
    Hq, P = Hkv * G, 1 + S * n_pp
    k, v, scales = _stacked_pools(rng, fmt, L, P, Hkv, page, hd)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P))[: S * n_pp]
        .reshape(S, n_pp).astype(np.int32)
    )
    dt = jnp.bfloat16 if fmt == "bf16" else jnp.float32
    q = jnp.asarray(rng.normal(size=(S, C, Hq, hd)), dt)
    st = jnp.asarray([13, 0, 11], jnp.int32)
    nv = jnp.asarray([1, 8, 0], jnp.int32)
    lens = jnp.asarray([0, 9, 27], jnp.int32)
    kw = dict(scale=hd**-0.5, interpret=True)
    ragged = jax.jit(lambda layer: ragged_paged_attention(
        q, k, v, bt, st, nv, layer=layer, **scales, **kw))
    decode = jax.jit(lambda layer: paged_attention(
        q[:, 0], k, v, bt, lens, layer=layer, **scales, **kw))
    for layer in (0, L - 1):
        sl = {n: a[layer] for n, a in scales.items()}
        np.testing.assert_array_equal(
            np.asarray(ragged(jnp.int32(layer)), np.float32),
            np.asarray(ragged_paged_attention(
                q, k[layer], v[layer], bt, st, nv, **sl, **kw), np.float32),
        )
        np.testing.assert_array_equal(
            np.asarray(decode(jnp.int32(layer)), np.float32),
            np.asarray(paged_attention(
                q[:, 0], k[layer], v[layer], bt, lens, **sl, **kw),
                np.float32),
        )


# ---------------------------------------------------------------------------
# the walk with a start (a window) and one row as key and value (a latent
# cache): ops/attention.py::_walk_start, paged_attention(window=, v_pages=None)
# ---------------------------------------------------------------------------
def test_walk_starts_at_the_windows_first_block():
    from tensorlink_tpu.ops.attention import _walk_start, _walk_trips

    def start(s, rb, window, cb=1, page=16, ppb=8):
        return tuple(int(x) for x in _walk_start(
            s, rb, cb=cb, page=page, ppb=ppb, window=window))

    # (first key position, its KV block): a decode row at 12,799 with the
    # published window 513 (the token itself counts) walks from 12,287
    assert start(12799, 0, 513) == (12287, 95)
    assert start(100, 0, 513) == (0, 0)  # the window is not full yet
    assert start(512, 0, 513) == (0, 0)
    assert start(513, 0, 513) == (1, 0)
    assert start(1000, 2, 129, cb=32) == (936, 7)  # row block 2 of a chunk
    # and it never starts past the walk's end
    for s in (0, 1, 127, 128, 4095):
        lo, kb0 = start(s, 0, 17)
        n_kb = int(_walk_trips(s, 1, 0, cb=1, page=16, ppb=8)[3])
        assert lo == max(s - 16, 0) and kb0 < n_kb


@pytest.mark.parametrize("window", [None, 1, 17, 33, 200])
def test_windowed_latent_walk_matches_the_reference(window):
    """``paged_attention(window=, v_pages=None)`` (interpreted) against
    ``paged_attention_ref`` with the pool as keys and values both: a
    stacked pool of one "kv head" with a wide row, lengths from 0 (a free
    slot) to past several KV blocks; pages outside the window hold NaN
    where the window leaves whole blocks out, and are never read."""
    from tensorlink_tpu.ops.attention import paged_attention, paged_attention_ref

    rng = np.random.default_rng(11)
    L, P, page, W = 3, 60, 16, 256
    S, H, n_pp = 4, 8, 14
    pool = rng.normal(size=(L, P, 1, page, W)).astype(np.float32)
    bt = rng.permutation(np.arange(1, P))[:S * n_pp].reshape(S, n_pp).astype(np.int32)
    lengths = np.asarray([0, 5, 131, 220], np.int32)
    q = jnp.asarray(rng.normal(size=(S, H, W)), jnp.float32)
    ref = paged_attention_ref(
        q, jnp.asarray(pool[1]), jnp.asarray(pool[1]), jnp.asarray(bt),
        jnp.asarray(lengths), scale=0.1, window=window)
    poisoned = pool.copy()
    if window is not None:
        for s, n in enumerate(lengths):
            first_block = max(n - window, 0) // page // 8  # ppb 8
            for pg in bt[s, :first_block * 8]:
                poisoned[:, pg] = np.nan
    out = paged_attention(
        q, jnp.asarray(poisoned), None, jnp.asarray(bt), jnp.asarray(lengths),
        scale=0.1, interpret=True, layer=jnp.int32(1), window=window,
        name="latent_window_attention")
    assert not np.isnan(np.asarray(out)).any()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(out[0])).max() == 0.0  # the free slot


# one latent row for 128 heads (DeepSeek-V2's shape in small widths): the
# walk's ``latent`` form = (v_width, kv_tile, rows a row block, rows a grid
# step), with tiles small enough that every loop runs more than once
LATENT_SHAPE = (128, 64, 256, 512)


def _latent_case(rng, S, n_pp, page=16, W=256, L=2):
    P = 1 + S * n_pp
    pool = rng.normal(size=(L, P, 1, page, W)).astype(np.float32)
    bt = rng.permutation(np.arange(1, P)).reshape(S, n_pp).astype(np.int32)
    return pool, bt


def _poison_latent(pool, bt, live):
    """``pool`` with NaN in every page behind a dead block-table entry and
    past the live span inside the last live page (layer 1)."""
    pool, bt = pool.copy(), bt.copy()
    page = pool.shape[3]
    nan_page = pool.shape[1]
    pool = np.concatenate(
        [pool, np.full((pool.shape[0], 1) + pool.shape[2:], np.nan,
                       np.float32)], axis=1)
    for s, n in enumerate(live):
        n_live = -(-int(n) // page)
        bt[s, n_live:] = nan_page
        if n % page:
            pool[1, bt[s, n_live - 1], :, n % page:] = np.nan
    return pool, bt


def test_latent_walk_at_128_heads_decode_rows():
    """``paged_attention(..., v_pages=None, latent=...)``: one query
    position a slot, 128 heads on one cached row, the value the row's
    first ``v_width`` columns, operands to the MXU as stored and the
    scale on the scores: ``paged_attention_ref`` with the pool as keys and
    values both, at lengths from 0 to past several KV blocks, reading
    nothing past the live span."""
    from tensorlink_tpu.ops.attention import paged_attention, paged_attention_ref

    rng = np.random.default_rng(41)
    S, H, n_pp, W = 4, 128, 14, 256
    pool, bt = _latent_case(rng, S, n_pp, W=W)
    lengths = np.asarray([0, 5, 131, 220], np.int32)
    q = jnp.asarray(rng.normal(size=(S, H, W)), jnp.float32)
    ref = paged_attention_ref(
        q, jnp.asarray(pool[1]), jnp.asarray(pool[1]), jnp.asarray(bt),
        jnp.asarray(lengths), scale=0.07)[..., :LATENT_SHAPE[0]]
    poisoned, pbt = _poison_latent(pool, bt, lengths)
    out = paged_attention(
        q, jnp.asarray(poisoned), None, jnp.asarray(pbt),
        jnp.asarray(lengths), scale=0.07, interpret=True, layer=jnp.int32(1),
        name="latent_full_attention", latent=LATENT_SHAPE)
    assert out.shape == (S, H, LATENT_SHAPE[0])
    assert not np.isnan(np.asarray(out)).any()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(out[0])).max() == 0.0  # the free slot


@pytest.mark.parametrize(
    "C,starts,nv",
    [(16, [70, 0, 201], [16, 0, 9]),  # a mid-prefill block, a pad, a partial
     (8, [213, 130, 0], [5, 1, 8])],  # a verify block, a decode row, a fresh
    ids=["mid-prefill", "verify"],
)
def test_latent_walk_at_128_heads_blocks_of_rows(C, starts, nv):
    """``ragged_paged_attention(..., v_pages=None, latent=...)``: blocks of
    up to ``C`` positions x 128 heads with each row's causal limit, the
    query rows in groups over a third grid axis (2,048 or 1,024 rows in
    groups of 512, row blocks of 256 = two positions): exact against
    ``ragged_paged_attention_ref``, zero rows past ``n_valid``, nothing
    read past the live span."""
    from tensorlink_tpu.ops.attention import (
        ragged_paged_attention, ragged_paged_attention_ref)

    rng = np.random.default_rng(42)
    S, H, n_pp, W = 3, 128, 14, 256
    pool, bt = _latent_case(rng, S, n_pp, W=W)
    q = jnp.asarray(rng.normal(size=(S, C, H, W)), jnp.float32)
    st, nvj = jnp.asarray(starts, jnp.int32), jnp.asarray(nv, jnp.int32)
    rows = jnp.asarray(pool[1])
    ref = ragged_paged_attention_ref(
        q, rows, rows, jnp.asarray(bt), st, nvj, scale=0.07,
    )[..., :LATENT_SHAPE[0]]
    live = [a + b if b else 0 for a, b in zip(starts, nv)]
    poisoned, pbt = _poison_latent(pool, bt, live)
    out = ragged_paged_attention(
        q, jnp.asarray(poisoned), None, jnp.asarray(pbt), st, nvj, scale=0.07,
        interpret=True, layer=jnp.int32(1), name="latent_full_attention",
        latent=LATENT_SHAPE)
    assert out.shape == (S, C, H, LATENT_SHAPE[0])
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for s in range(S):
        assert np.abs(np.asarray(out[s, nv[s]:])).max(initial=0) == 0


def test_short_row_block_is_one_position_where_the_tall_one_is_more():
    """The walk's second height (:func:`_short_positions`) from the tall
    one's positions: one position (``G`` rows, a continuation step's
    block) wherever the tall block holds more, none where the query
    block is one position already (a ``C`` = 1 call keeps one body)."""
    from tensorlink_tpu.ops.attention import (
        _positions_per_row_block, _short_positions)

    def short_rows(C, G, rows=128):
        return _short_positions(_positions_per_row_block(C, G, rows)) * G

    # (C, G) of the cells' ragged passes: 128, 64, 128, 96, 112, 144 rows
    # of the tall block come down to G
    for C, G in ((128, 4), (16, 4), (128, 1), (128, 6), (128, 7), (128, 9)):
        assert short_rows(C, G) == G, (C, G)
    assert short_rows(16, 128, rows=512) == 128  # a latent walk: 512 -> 128
    # a continuation step, whatever its group: nothing is shorter
    for G in (1, 4, 6, 7, 9, 64, 128):
        assert short_rows(1, G) == 0 and short_rows(1, G, rows=512) == 0


@pytest.mark.parametrize(
    "G,C,kind",
    [(G, C, "plain") for G in (1, 4, 6, 7, 9) for C in (16, 128)]
    + [(4, 16, "int8"), (4, 128, "int8"), (4, 16, "window"),
       (9, 128, "window"), (128, 8, "latent"), (128, 8, "latent-groups")],
)
def test_walk_height_follows_a_slots_live_rows(monkeypatch, G, C, kind):
    """A slot with one live position walks a row block ``G`` rows tall, a
    slot with more the tall block, in one call: against the references
    at this file's tolerances, zero in every row past a slot's live rows,
    and against the one-height kernel (the same call with
    ``_short_positions`` answering 0): bit for bit in every slot that
    walks tall, and in the short ones as far as this backend can show
    (XLA's CPU dot orders a contraction by the operand's height, so a
    row of ``G`` differs from the same row of 128 in the last bit or two;
    the MXU does not, and there it IS bit for bit: PERF.md section 5,
    PR 61). Over the cells' group sizes, both widths of the ladder, int8
    pages, a window, and a latent cache's walk with and without row
    groups."""
    from tensorlink_tpu.ops import attention as A

    rng = np.random.default_rng(61)
    # slots of one packed block: padding, a decode row deep in its context,
    # a decode row at position 0, two verify rows, a full prefill block
    starts, nv = [0, 150, 0, 37, 9], [0, 1, 1, 2, C]
    S, page, n_pp, Hkv = len(nv), 64, 3, 1 if G > 4 else 2
    P = 1 + S * n_pp
    bt = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(S, n_pp)
                     .astype(np.int32))
    st, nvj = jnp.asarray(starts, jnp.int32), jnp.asarray(nv, jnp.int32)
    kw, ref_kw = {}, {}
    if kind.startswith("latent"):
        # v_width, KV tile, rows a row block (two positions), rows a grid
        # step: all of a slot's, or four positions a group
        hd, scale = 256, 0.07
        kw["latent"] = (128, 64, 256, C * G if kind == "latent" else 512)
        kp = jnp.asarray(rng.normal(size=(2, P, 1, page, hd)), jnp.float32)
        kw["layer"] = jnp.int32(1)  # a latent walk reads a stacked pool
        vp, ref_k, ref_v = None, kp[1], kp[1]
    elif kind == "int8":
        hd, scale = 32, 32**-0.5
        _, _, kp, ks, vp, vs = _quantized_pages(rng, P, Hkv, page, hd)
        kw.update(k_scale=ks, v_scale=vs)
        ref_kw, ref_k, ref_v = dict(kw), kp, vp
    else:
        hd, scale = 32, 32**-0.5
        kp = jnp.asarray(rng.normal(size=(P, Hkv, page, hd)), jnp.float32)
        ref_k = kp
        vp = ref_v = jnp.asarray(
            rng.normal(size=(P, Hkv, page, hd)), jnp.float32)
        if kind == "window":
            kw["window"] = ref_kw["window"] = 33
    q = jnp.asarray(rng.normal(size=(S, C, Hkv * G, hd)), jnp.float32)
    ref = A.ragged_paged_attention_ref(
        q, ref_k, ref_v, bt, st, nvj, scale=scale, **ref_kw)
    if "latent" in kw:
        ref = ref[..., :kw["latent"][0]]
    got = np.asarray(A.ragged_paged_attention(
        q, kp, vp, bt, st, nvj, scale=scale, interpret=True, **kw))
    np.testing.assert_allclose(got, np.asarray(ref), rtol=2e-5, atol=2e-5)
    for s in range(S):
        assert np.abs(got[s, nv[s]:]).max(initial=0) == 0
    # a new name is a new trace: the kernel with the tall height alone
    monkeypatch.setattr(A, "_short_positions", lambda cb: 0)
    tall = np.asarray(A.ragged_paged_attention(
        q, kp, vp, bt, st, nvj, scale=scale, interpret=True,
        name="one_height", **kw))
    for s in range(S):
        if nv[s] == 1:
            np.testing.assert_allclose(got[s], tall[s], rtol=1e-6, atol=1e-6)
        else:
            np.testing.assert_array_equal(got[s], tall[s])
