"""``minicpm_sala`` on the slot engine: block-sparse GQA layers and lightning
(linear-attention) layers whose state lives beside the pages
(models/sala.py, engine/sala.py, ops/lightning.py,
ops/attention.py::block_sparse_attention), against the plain reference
``benchmarks/reference/minicpm_sala.py``. A tiny float32 preset: 5 layers
(sparse, 2 x lightning, sparse, lightning: no period), 4 heads of 16, 2 kv
heads, pooled keys of 8 every 4 (a page), blocks of 16, window 24, top-4,
``dense_len`` 64."""

from __future__ import annotations

import functools
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import minicpm_sala as ref
from tensorlink_tpu.engine import paged, sala as es
from tensorlink_tpu.engine.continuous import (
    ContinuousEngine,
    PagedUnsupported,
    paged_unsupported,
    tp_serving_refusal,
)
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.latent import LatentPagedCache
from tensorlink_tpu.models import sala
from tensorlink_tpu.models.base import ModelConfig
from tensorlink_tpu.models.registry import config_from_hf
from tensorlink_tpu.models.transformer import init_params
from tensorlink_tpu.ops import attention, lightning

# tlint: disable=TL006(read-only table: every test copies it)
TINY = dict(
    model_type="minicpm_sala", hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
    num_hidden_layers=5,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
                 "lightning-attn"],
    vocab_size=97, max_position_embeddings=256, rms_norm_eps=1e-6,
    rope_theta=10000, scale_emb=12, scale_depth=1.4, dim_model_base=16,
    qk_norm=True, use_output_gate=True, use_output_norm=True,
    attn_use_output_gate=True, lightning_use_rope=True, attn_use_rope=False,
    tie_word_embeddings=False, published={"num_hidden_layers": 32},
    sparse_config=dict(kernel_size=8, kernel_stride=4, block_size=16,
                       init_blocks=1, window_size=24, topk=4, dense_len=64),
)
CONFIG = (Path(__file__).parent.parent / "benchmarks" / "configs"
          / "minicpm-sala-l16.json")


@pytest.fixture(scope="module")
def tiny():
    cfg = config_from_hf(TINY, dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def seqs():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 97, size=150) for _ in range(2)]


@pytest.fixture(scope="module")
def want(tiny, seqs):
    """The reference's logits of both sequences at every position."""
    arch = ref.arch_of(TINY)
    return [ref.forward_logits(tiny[1], s[None], arch, slice(0, 150))[0]
            for s in seqs]


def _engine(cfg, params, **kw):
    eng = GenerationEngine(cfg, params, seq_buckets=(8, 32),
                           batch_buckets=(1,), max_seq_len=256)
    kw = dict(max_slots=3, page_size=4, chunk_steps=4, prefill_chunk=8,
              state_snapshot_stride=32) | kw
    return ContinuousEngine(eng, **kw)


def _teacher_forced(params, cfg, seqs, lens, n_decode, *, C=8, S=3,
                    kernel=False):
    """Each sequence's logits at its last prompt position and at
    ``n_decode`` teacher-forced continuation steps through the cache:
    chunked prefill in blocks of ``C``, slots at their own lengths, one
    idle slot. Returns ``([len(seqs)][1 + n_decode, V], cache)``."""
    cache = LatentPagedCache.init(cfg, S, page_size=4, max_len=256)
    n_pp = cache.pages_per_slot
    bt = np.zeros((S, n_pp), np.int32)
    perm = np.random.default_rng(0).permutation(np.arange(1, cache.n_pages))
    for s in range(len(seqs)):
        bt[s] = perm[s * n_pp:(s + 1) * n_pp]
    cache = paged._with_kv(cache, paged._cache_kv(cache),
                           block_tables=jnp.asarray(bt))
    pos, got = [0] * len(seqs), [[] for _ in seqs]
    while any(p < n for p, n in zip(pos, lens)):
        blk = np.zeros((S, C), np.int32)
        starts, nv = np.zeros(S, np.int32), np.zeros(S, np.int32)
        for s, seq in enumerate(seqs):
            n = min(C, lens[s] - pos[s])
            if n > 0:
                blk[s, :n] = seq[pos[s]:pos[s] + n]
                starts[s], nv[s] = pos[s], n
        lv, _base, kv = paged._ragged_pass(
            params, jnp.asarray(blk), cache, jnp.asarray(starts),
            jnp.asarray(nv), jnp.zeros(S, jnp.int32), cfg, 1, kernel)
        cache = paged._with_kv(cache, kv, lengths=jnp.where(
            jnp.asarray(nv) > 0, jnp.asarray(starts + nv), cache.lengths))
        for s in range(len(seqs)):
            if nv[s] > 0:
                pos[s] += int(nv[s])
                if pos[s] == lens[s]:
                    got[s].append(np.asarray(lv[s, 0]))
    for i in range(n_decode):
        tok, active = np.zeros(S, np.int32), np.zeros(S, bool)
        for s, seq in enumerate(seqs):
            tok[s], active[s] = seq[lens[s] + i], True
        lg, cache = paged._decode_step_impl(
            params, jnp.asarray(tok), cache, jnp.asarray(active), cfg, kernel)
        for s in range(len(seqs)):
            got[s].append(np.asarray(lg[s]))
    return [np.stack(g) for g in got], cache


# -- the configuration -------------------------------------------------------


def test_catalog_config_gives_the_published_sizes():
    """``config_from_hf`` on the benchmark's file: 16 layers in the
    published order (4 sparse, 12 lightning, no period), 5,039.4 M
    parameters, the residual scale of the PUBLISHED depth; with the three
    reduced keys put back, 9.48 B and runs of 8, 6, 4, 6 lightning layers."""
    hf = json.loads(CONFIG.read_text())
    cut = config_from_hf(hf)
    assert cut.param_count() == 5_039_448_064
    assert cut.layer_kinds.count("sparse") == 4
    assert cut.layer_kinds.count("lightning") == 12
    assert cut.residual_mult == pytest.approx(1.4 / 32**0.5)
    assert (cut.embed_mult, cut.logit_div) == (12.0, 16.0)
    assert [(k, n) for k, _, n in sala.runs_of(cut.layer_kinds)] == [
        ("sparse", 1), ("lightning", 6), ("sparse", 2), ("lightning", 4),
        ("sparse", 1), ("lightning", 2)]
    sa, la = cut.latent_of("sparse"), cut.latent_of("lightning")
    assert (sa.n_heads, sa.n_kv_heads, sa.head_dim, sa.topk, sa.block,
            sa.window, sa.dense_len, sa.max_kept) == (
                32, 2, 128, 64, 64, 2048, 8192, 128)
    assert (la.n_heads, la.head_dim, la.state_bytes) == (32, 128, 2_097_152)
    assert la.slopes()[0] == pytest.approx(2 ** -0.25)
    assert la.slopes()[-1] == pytest.approx(2 ** -8)
    row = None
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        for line in catalog.read_text().splitlines():
            if json.loads(line)["name"] == "MiniCPM-SALA":
                row = json.loads(line)["config"]
    if row is None:
        pytest.skip("no catalog here")
    reduced = {"num_hidden_layers", "mixer_types", "max_position_embeddings"}
    for k, v in row.items():
        if k not in reduced:
            assert hf[k] == v, k
    assert hf["mixer_types"] == row["mixer_types"][9:25]
    whole = config_from_hf({**row, "sparse_config": hf["sparse_config"]})
    assert round(whole.param_count() / 1e9, 2) == 9.48
    assert [n for k, _, n in sala.runs_of(whole.layer_kinds)
            if k == "lightning"] == [8, 6, 4, 6]
    # a cut config that forgets the published depth scales by its own
    assert config_from_hf({k: v for k, v in hf.items() if k != "published"}
                          ).residual_mult == pytest.approx(1.4 / 4)


def test_config_round_trips_through_json(tiny):
    cfg = tiny[0]
    assert ModelConfig.from_json(json.loads(json.dumps(cfg.to_json()))) == cfg


# -- (a) the recurrence ------------------------------------------------------


def _qkv(rng, S, H, C, d):
    return tuple(jnp.asarray(rng.normal(size=(S, H, C, d)), jnp.float32)
                 for _ in range(3))


def _scan(q, k, v, state, slopes):
    """The plain scan over positions, every slot active."""
    outs = []
    for t in range(q.shape[2]):
        o, state = sala.lightning_step_ref(
            q[:, :, t], k[:, :, t], v[:, :, t], state, slopes,
            jnp.ones(q.shape[0], bool))
        outs.append(o)
    return jnp.stack(outs, 2), state


SLOPES = jnp.asarray([2.0 ** (-8 * (a + 1) / 4) for a in range(4)])


def test_lightning_chunk_form_equals_the_plain_scan():
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng, 2, 4, 24, 16)
    s0 = jnp.asarray(rng.normal(size=(2, 4, 16, 16)), jnp.float32)
    want_o, want_s = _scan(q, k, v, s0, SLOPES)
    o, s = sala.lightning_chunk_ref(q, k, v, s0, SLOPES, jnp.asarray([24, 24]))
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


def test_uneven_chunks_then_single_steps_equal_one_scan():
    """The state carried across chunks of 5, 8, 3 valid rows (padding rows
    leave it alone, an idle slot's state stands) and into single steps."""
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, 2, 4, 20, 16)
    zero = jnp.zeros((2, 4, 16, 16), jnp.float32)
    want_o, want_s = _scan(q, k, v, zero, SLOPES)
    state, outs, pos = zero, [], 0
    for n in (5, 8, 3):
        pad = lambda a: jnp.pad(  # noqa: E731
            a[:, :, pos:pos + n], ((0, 0), (0, 0), (0, 8 - n), (0, 0)))
        o, new = sala.lightning_chunk_ref(
            pad(q), pad(k), pad(v), state, SLOPES, jnp.asarray([n, 0]))
        # slot 1 granted nothing: its state stands
        np.testing.assert_array_equal(new[1], state[1])
        state = new.at[1].set(sala.lightning_chunk_ref(
            pad(q), pad(k), pad(v), state, SLOPES, jnp.asarray([0, n]))[1][1])
        assert n == 8 or float(jnp.abs(o[0, :, n:]).max()) == 0.0  # padding
        outs.append(o[0, :, :n])
        pos += n
    for t in range(pos, 20):
        o, state = sala.lightning_step_ref(
            q[:, :, t], k[:, :, t], v[:, :, t], state, SLOPES,
            jnp.asarray([True, True]))
        outs.append(o[0, :, None])
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want_o[0], atol=2e-5)
    np.testing.assert_allclose(state, want_s, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 1])
def test_lightning_kernels_interpreted_match_the_plain_forms(layer):
    """Both Pallas kernels on one layer of a stacked state array, the
    other layer untouched."""
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, 3, 4, 8, 16)
    state = jnp.asarray(rng.normal(size=(2, 3, 4, 16, 16)), jnp.float32)
    nv = jnp.asarray([8, 3, 0])
    li = jnp.int32(layer)
    want_o, want_s = sala.lightning_chunk_ref(q, k, v, state[layer], SLOPES, nv)
    o, st = lightning.lightning_attention_chunk(
        q, k, v, state, SLOPES, nv, li, interpret=True)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(st[layer], want_s, atol=2e-5)
    np.testing.assert_array_equal(st[1 - layer], state[1 - layer])
    act = jnp.asarray([True, False, True])
    want_o, want_s = sala.lightning_step_ref(
        q[:, :, 0], k[:, :, 0], v[:, :, 0], state[layer], SLOPES, act)
    o, st = lightning.lightning_attention_step(
        q[:, :, 0], k[:, :, 0], v[:, :, 0], state, SLOPES, act, li,
        interpret=True)
    np.testing.assert_allclose(o[jnp.asarray([0, 2])],
                               want_o[jnp.asarray([0, 2])], atol=2e-5)
    np.testing.assert_allclose(st[layer], want_s, atol=2e-5)


# -- (b) the selection -------------------------------------------------------


@pytest.mark.parametrize("t", [10, 63, 64, 65, 100, 149],
                         ids=lambda t: f"t{t}")
def test_selection_equals_the_references(t):
    """``select_blocks`` over page sums against the reference's per-row
    selection over plain means, below, at and above ``dense_len`` (64):
    everything visible below it; past it the first block and the window's
    blocks forced, ``topk`` kept in all."""
    cfg = config_from_hf(TINY, dtype=jnp.float32)
    sa = cfg.latent_of("sparse")
    rng = np.random.default_rng(t)
    T = 160
    keys = jnp.asarray(rng.normal(size=(T, 2, 16)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, 4, 16)) * 3, jnp.float32)
    pos = jnp.asarray([t])
    arch = ref.arch_of(TINY)
    a = dict(ref._static(arch)) | {"n_blocks": T // 16}
    want = np.asarray(ref.kept_blocks(
        q, ref._pooled(keys[:t + 1], k=ref._static(arch)), pos, a))
    sums = keys.reshape(T // 4, 4, 2, 16).sum(1)
    # pages past the query hold whatever was written there: never read
    got = np.asarray(sala.select_blocks(sala.block_scores(
        q, sala.pooled_keys(sums, sa), pos, sa), pos, sa))
    np.testing.assert_array_equal(got, want)
    visible = t // 16 + 1
    if t < 64:
        assert got.sum(-1).tolist() == [[visible, visible]]
    else:
        assert got.sum(-1).tolist() == [[4, 4]]
        window = set(range((t - 23) // 16, visible))
        for g in range(2):
            kept = set(np.flatnonzero(got[0, g]))
            assert {0} | window <= kept and max(kept) < visible


def test_selection_ties_go_to_the_lower_block():
    """Equal scores: the lower block first, in the program's
    ``top_k_few`` and in the reference's stable sort."""
    cfg = config_from_hf(TINY, dtype=jnp.float32)
    sa = cfg.latent_of("sparse")
    pos = jnp.asarray([159])
    flat = jnp.zeros((1, 2, 10), jnp.float32)  # every block scores alike
    got = np.asarray(sala.select_blocks(flat, pos, sa))[0, 0]
    # forced: block 0 and the window's 8, 9; one free pick: block 1
    assert np.flatnonzero(got).tolist() == [0, 1, 8, 9]
    table, count = sala.kept_table(jnp.asarray(got)[None], 6)
    assert table[0, :4].tolist() == [0, 1, 8, 9] and int(count[0]) == 4


def test_table_walk_interpreted_matches_its_plain_form():
    """``block_sparse_attention``: a block-table row and a length a (slot,
    kv head), one layer of a stacked pool, an idle slot reads zero."""
    rng = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    kp, vp = f(2, 40, 2, 4, 16), f(2, 40, 2, 4, 16)
    q = f(3, 8, 16)
    tables = jnp.asarray(rng.integers(1, 40, size=(3, 2, 12)), jnp.int32)
    lens = jnp.asarray([[48, 17], [5, 30], [0, 0]], jnp.int32)
    want = attention.block_sparse_attention_ref(
        q, kp[1], vp[1], tables, lens, scale=0.25)
    got = attention.block_sparse_attention(
        q, kp, vp, tables, lens, scale=0.25, layer=jnp.int32(1),
        interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(jnp.abs(got[2]).max()) == 0.0


# -- (c) the step's two passes against the reference -------------------------


def test_prefill_and_decode_through_the_cache_match_the_reference(
        tiny, seqs, want):
    """Logits of chunked prefill then continuation steps through the slot
    engine's cache against the reference's full forward: both kinds, two
    slots at different lengths (130 past ``dense_len``, 37 under it) in one
    block, padding rows, an idle slot."""
    cfg, params = tiny
    got, cache = _teacher_forced(params, cfg, seqs, [130, 37], 6)
    np.testing.assert_allclose(got[0], want[0][129:136], atol=2e-4)
    np.testing.assert_allclose(got[1], want[1][36:43], atol=2e-4)
    # the idle slot's state never moved
    assert float(jnp.abs(cache.state[:, 2]).max()) == 0.0


@pytest.mark.parametrize("kind", ["sparse", "lightning"])
def test_a_layer_through_the_interpreted_kernels_matches_the_xla_forms(
        tiny, kind, monkeypatch):
    """One layer of each kind through ``make_layer_probe`` (the step's two
    passes' placing) with the Pallas kernels interpreted against the same
    layer by the XLA forms: a prefill in chunks of 8 to position 100 (past
    ``dense_len``), then two continuation steps; what the layer added, its
    pages, key sums and state."""
    from dataclasses import replace

    cfg, params = tiny
    for name in ("block_sparse_attention", "lightning_attention_step",
                 "lightning_attention_chunk"):
        monkeypatch.setattr(es, name, functools.partial(
            getattr(es, name), interpret=True))
    lp = jax.tree.map(lambda a: a[1], params[kind])
    lp = {"ln1": lp["ln1"], "attn": lp["attn"]}
    x = jnp.asarray(np.random.default_rng(7).normal(size=(104, 64)),
                    jnp.float32)
    outs = {}
    for kernel in (False, True):
        ragged, decode = paged.make_layer_probe(cfg, kind, kernel=kernel)
        cache = LatentPagedCache.init(cfg, 2, page_size=4, max_len=128)
        cache = replace(cache, block_tables=jnp.arange(
            1, 65, dtype=jnp.int32).reshape(2, 32))
        got = []
        for pos in range(0, 100, 8):
            n = min(8, 100 - pos)
            blk = jnp.zeros((2, 8, 64)).at[1, :n].set(x[pos:pos + n])
            out, cache = ragged(lp, blk, cache, jnp.int32(1),
                                jnp.asarray([0, pos]), jnp.asarray([0, n]))
            got.append(out[1, :n])
        for t in (100, 101):
            out, cache = decode(lp, jnp.zeros((2, 1, 64)).at[1].set(x[t]),
                                cache, jnp.int32(1), jnp.asarray([False, True]))
            got.append(out[1])
        outs[kernel] = (jnp.concatenate(got), cache)
    np.testing.assert_allclose(outs[True][0], outs[False][0], atol=2e-5)
    for field in ("k", "v", "ksum", "state"):
        np.testing.assert_allclose(
            getattr(outs[True][1], field), getattr(outs[False][1], field),
            atol=2e-5, err_msg=field)


# -- (g) the step's counters -------------------------------------------------


def test_step_counters_equal_counts_made_by_hand(tiny, seqs):
    """One continuation step of two slots at contexts 130 and 37 (query
    positions 130 and 37): 2 sparse layers x 2 kv groups; slot 0 keeps 4 of
    9 visible blocks, slot 1 (under ``dense_len``) all 3; 3 lightning
    layers x 2 rows."""
    from dataclasses import replace

    cfg, params = tiny
    _, cache = _teacher_forced(params, cfg, seqs, [130, 37], 0)
    cache = replace(cache, stats=jnp.zeros_like(cache.stats))
    _, cache = paged._decode_step_impl(
        params, jnp.asarray([1, 2, 0]), cache,
        jnp.asarray([True, True, False]), cfg, False)
    names = sala.step_stats(cfg)
    stats = dict(zip(names, np.asarray(cache.stats).tolist()))
    assert len(names) == 13 and names[-4:] == sala.SALA_STATS
    assert stats["sparse_blocks_kept"] == 2 * 2 * (4 + 3)
    assert stats["sparse_blocks_visible"] == 2 * 2 * (9 + 3)
    assert stats["sparse_rows_dense"] == 2 * 1
    assert stats["lightning_rows"] == 3 * 2
    assert stats["moe_rows_valid"] == 0


# -- (d) (e) snapshots -------------------------------------------------------


def _run(ce, prompt, n=10):
    r = ce.submit([int(t) for t in prompt], max_new_tokens=n)
    ce.run_until_idle()
    assert r.error is None, r.error
    return list(r.tokens), r


def test_a_prefix_hit_restores_the_snapshot(tiny, seqs):
    """A session's second turn after a hit gives the stream of the same
    prompt served cold; so does a request whose match ends past the nearest
    snapshot (restore below it, replay to it), and one that diverges inside
    a prompt that was prefilled by stride."""
    cfg, params = tiny
    ce, cold = _engine(cfg, params), _engine(cfg, params, prefix_cache=False)
    doc = seqs[0][:100].tolist()
    t1, r1 = _run(ce, doc + [1, 2, 3])
    assert r1.state_restored_at == -1
    # stops at 32, 64, 96 (the stride) and 100 (the last page edge of 103)
    assert ce.stats["state_snapshots_taken"] == 4
    turn2 = doc + [1, 2, 3] + t1 + [5, 6, 7, 8, 9]
    t2, r2 = _run(ce, turn2)
    assert r2.state_restored_at == 100 and t2 == _run(cold, turn2)[0]
    assert ce.stats["state_rows_replayed"] == 0
    assert ce.stats["prefill_tokens_skipped"] == 100
    # diverges at 70 inside the document: the pages match to 68, the
    # nearest snapshot lies at 64
    fork = doc[:70] + [11, 12, 13]
    t3, r3 = _run(ce, fork)
    assert r3.state_restored_at == 64 and t3 == _run(cold, fork)[0]
    assert ce.stats["state_rows_replayed"] == 4
    # matches the whole first prompt's pages (100) and goes on
    longer = doc + [1, 2, 3, 40, 41, 42, 43, 44, 45]
    t4, r4 = _run(ce, longer)
    assert r4.state_restored_at == 100 and t4 == _run(cold, longer)[0]
    assert ce.stats["state_admissions"] == 4
    assert ce.stats["state_snapshots_restored"] == 3
    spans = [s for s in ce.recorder.records()]  # the chunks ran
    assert spans
    ce.check_page_conservation()
    ce.close()
    cold.close()


def test_a_prompt_without_a_stride_snapshot_gives_first_turns_no_hit(tiny, seqs):
    """The trap of a shared document: a set-up prompt that goes on past
    the document and turns that diverge at the document's end. A store
    that keeps one snapshot a finished prompt (a stride beyond every
    prompt) gives them nothing; the stride restores them at 96."""
    cfg, params = tiny
    doc = seqs[0][:100].tolist()
    for stride, at in ((32, 96), (4096, -1)):
        ce = _engine(cfg, params, state_snapshot_stride=stride)
        _run(ce, doc + [1, 2, 3, 4, 5, 6])
        _, r = _run(ce, doc + [9, 8, 7])
        assert r.state_restored_at == at
        ce.close()


def test_eviction_frees_the_snapshot_and_conservation_holds(tiny, seqs):
    cfg, params = tiny
    ce = _engine(cfg, params, state_snapshots=6)
    _run(ce, seqs[0][:103].tolist())  # stops at 32, 64, 96 and 100
    held = ce.serving_snapshot()["state_snapshots_resident"]
    assert held == 4 and len(ce._snap_free) == 2
    ce.check_page_conservation()
    # the pool is full: a second document's snapshots push the oldest out
    _run(ce, seqs[1][:103].tolist())
    assert ce.stats["state_snapshots_taken"] == 8
    assert ce.serving_snapshot()["state_snapshots_resident"] == 6
    ce.check_page_conservation()
    freed = ce.prefix.drop_all()
    assert freed and ce.serving_snapshot()["state_snapshots_resident"] == 0
    assert sorted(ce._snap_free) == list(range(6))
    ce.alloc.free(freed)
    ce.check_page_conservation()
    # a leak is seen
    lost = ce._snap_free.pop()
    with pytest.raises(AssertionError, match="snapshot conservation"):
        ce.check_page_conservation()
    ce._snap_free.append(lost)
    ce.close()


def test_snapshot_gauges_and_the_span_attribute(tiny, seqs):
    cfg, params = tiny
    ce = _engine(cfg, params)
    snap = ce.serving_snapshot()
    state = 3 * 3 * 4 * 16 * 16 * 4  # layers x slots x heads x d x d x f32
    assert snap["lightning_state_bytes"] == state
    n = 256 // 32 + 2 * 3
    assert snap["state_snapshot_bytes"] == n * state // 3
    assert snap["state_pool_bytes"] == state + n * state // 3
    from tensorlink_tpu.core.trace import get_tracer

    r = ce.submit(seqs[0][:40].tolist(), max_new_tokens=2, trace_id="sala-1")
    ce.run_until_idle()
    adm = [s for s in get_tracer().collect("sala-1") if s["name"] == "admission"]
    assert adm and adm[0]["state_restored_at"] == -1 and r.error is None
    ce.close()


# -- (f) refusals ------------------------------------------------------------


def test_each_refusal_gives_its_reason(tiny):
    cfg, params = tiny
    assert paged_unsupported(cfg) is None
    for kw, why in (
        (dict(kv_quant="int8"), "pages and recurrent states are stored in "
                                "the model dtype"),
        (dict(kv_quant="int4"), "stored in the model dtype"),
        (dict(host_tier_pages=8), "recurrent states in the host-RAM tier"),
        (dict(handoff_after_prefill=True), "do not hand off between workers"),
        (dict(tensor_parallel=2), "recurrent states have no partition specs"),
        (dict(page_size=8), "pooled keys of 8 positions every 4 at a page "
                            "of 8"),
    ):
        with pytest.raises(PagedUnsupported, match=why):
            _engine(cfg, params, **kw)
    assert "served whole on one chip" in tp_serving_refusal(cfg, 2)
    mixed = cfg.with_(layer_kinds=("sparse", "full"))
    assert "sizes for" in paged_unsupported(mixed)
    # drafting: the request is served, without drafts, and the engine says why
    ce = _engine(cfg, params, spec_decode=True)
    assert not ce.spec_decode and ce.spec_width == 1
    assert "rejected draft row" in ce.serving_snapshot()["spec_refusal"]
    r = ce.submit([1, 2, 3, 4, 5], max_new_tokens=4, speculative=True)
    ce.run_until_idle()
    assert not r.speculative and len(r.tokens) == 4
    # migration: the slot's stream falls back to re-prefill
    r = ce.submit([1, 2, 3, 4, 5, 6], max_new_tokens=20)
    ce.step_chunk()
    ce.freeze_slot(r.slot)
    with pytest.raises(PagedUnsupported, match="pages and recurrent states "
                                               "do not migrate"):
        ce.export_slot(r.slot)
    assert ce.stage_migration("m1", {}) is False
    assert ce.export_prefix_pages([1, 2, 3, 4], 4) is None
    ce.close()


def test_preemption_degrades_to_restore_and_replay(tiny, seqs):
    """A preempted slot's request re-admits through the trie: it restores
    the last snapshot its prefill left and replays, and its stream is the
    uninterrupted one."""
    cfg, params = tiny
    prompt = seqs[0][:90].tolist()
    whole = _run(_engine(cfg, params), prompt, n=12)[0]
    ce = _engine(cfg, params)
    r = ce.submit(prompt, max_new_tokens=12)
    while len(r.tokens) < 5:
        ce.step_chunk()
    ce._preempt(r.slot)
    ce.run_until_idle()
    assert r.error is None and list(r.tokens) == whole
    assert r.state_restored_at == 88 and ce.stats["preemptions"] == 1
    ce.check_page_conservation()
    ce.close()


# -- (h) scopes and kernel names ---------------------------------------------


def test_scopes_and_kernel_names_are_what_the_metrics_match(tiny):
    """The three new scopes in the lowered step, three phase loops in
    order, and the kernels' names as the benchmark's layer metrics match
    them (``benchmarks/layer_metrics/*``)."""
    import re

    cfg, params = tiny
    ce = _engine(cfg, params)
    text = ce.lower_step().as_text(debug_info=True)
    for scope in (sala.BLOCK_SELECT, sala.SPARSE_ATTN, sala.LIGHTNING,
                  *paged.STEP_PHASES):
        assert scope in text, scope
    from test_step_scopes import top_level_loops

    loops = top_level_loops(text)
    assert len(loops) == 3 and all(
        f"/{phase}/" in loop for phase, loop in zip(paged.STEP_PHASES, loops))
    ce.close()
    metrics = Path(__file__).parent.parent / "benchmarks" / "layer_metrics"
    for name, kernels in (
        ("block_sparse_attention_share", [es.SPARSE_KERNEL]),
        ("block_sparse_attention_roofline_share", [es.SPARSE_KERNEL]),
        ("lightning_attention_share",
         [lightning.STEP_KERNEL, lightning.CHUNK_KERNEL]),
        ("lightning_attention_roofline_share",
         [lightning.STEP_KERNEL, lightning.CHUNK_KERNEL]),
    ):
        spec = json.loads((metrics / f"{name}.json").read_text())
        for kernel in kernels:
            assert any(re.search(p, kernel) for p in spec["patterns"]), name
        # and nothing of another kernel's
        for other in ("paged_attention", "ragged_paged_attention",
                      "latent_full_attention", "latent_window_attention"):
            assert not any(re.search(p, other) for p in spec["patterns"])
    for name, keys in (
        ("block_keep_share", {"sparse_blocks_kept", "sparse_blocks_visible"}),
        ("state_restore_share",
         {"state_snapshots_restored", "state_admissions"}),
        ("state_replay_share", {"state_rows_replayed", "prefill_tokens",
                                "prefill_tokens_skipped"}),
    ):
        spec = json.loads((metrics / f"{name}.json").read_text())
        assert set(spec["num"]) | set(spec["den"]) == keys
        assert keys <= set(ce.stats)
    key = json.loads((metrics / "state_pool_gb.json").read_text())["key"]
    assert key in ce.serving_snapshot()


# -- (i) the other families' programs ----------------------------------------

# sha256 (first 16 hex) of ``ContinuousEngine.lower_step(width).as_text()``,
# tiny float32 presets with zero weights, on the CPU: PR 42 left the dense
# GQA step and both latent steps as they were at ba0bac1. A PR that
# changes one of these programs on purpose puts the new digest here: PR 43
# did (every step program binds and resets slots from its control buffer
# before the ragged pass).
# tlint: disable=TL006(read-only table)
_QWEN = dict(model_type="qwen3", vocab_size=258, hidden_size=64,
             num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=128,
             max_position_embeddings=64, rms_norm_eps=1e-6)
# tlint: disable=TL006(read-only table)
PARENT_PROGRAMS = {
    ("qwen3", 4): "b48e70bef57f47e8", ("qwen3", 8): "0ddc9d9103af0c85",
    ("dots3", 8): "fa34db53568c4f63", ("deepseek_v2", 8): "85c5626002e7ab0c",
}


@pytest.mark.parametrize("family", ["qwen3", "dots3", "deepseek_v2"])
def test_the_other_families_step_programs_are_the_parents(family):
    import test_latent as tl

    hf = {"qwen3": _QWEN, "dots3": tl.TINY, "deepseek_v2": tl.TINY_DS}[family]
    cfg = config_from_hf(hf, dtype=jnp.float32)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    ce = tl._engine(cfg, params)
    got = {(family, w): hashlib.sha256(
        ce.lower_step(w, flat=False).as_text().encode()).hexdigest()[:16]
        for w in ce.block_widths}
    ce.close()
    assert got == {k: v for k, v in PARENT_PROGRAMS.items() if k[0] == family}


# -- the planner -------------------------------------------------------------


def test_planner_counts_pages_of_paged_layers_states_and_snapshots():
    """A slot's memory is pages x the 4 sparse layers + a state x the 12
    lightning layers (+ the snapshot pool), not pages x 16 layers; a job
    that does not fit one worker is refused with the sizes."""
    from tensorlink_tpu.parallel.planner import (
        AssignmentError, MemoryEstimate, WorkerCapacity, plan_sharding)

    cfg = config_from_hf(json.loads(CONFIG.read_text()))
    parts = MemoryEstimate.state_parts(cfg, 8, 36864)
    # keys and values: 4 layers x 2 heads x 128 x 2 x 2 B = 4 KB a position,
    # and 1/16 of a float32 key row
    assert parts["pages"] == 8 * 36864 * 4 * (1024 + 64)
    assert parts["states"] == 8 * 12 * 2_097_152
    assert parts["snapshots"] == (9 + 16) * 12 * 2_097_152
    est = MemoryEstimate.build(cfg, batch=8, seq_len=36864, training=False)
    assert est.params == 5_039_448_064 * 2
    assert est.kv_cache == sum(parts.values())
    assert 12.5e9 < est.total < 15e9
    one = [WorkerCapacity(node_id="w0", hbm_bytes=15.75e9, n_devices=1)]
    plan = plan_sharding(cfg, one, batch=8, seq_len=36864)
    assert len(plan.stages) == 1
    small = [WorkerCapacity(node_id=f"w{i}", hbm_bytes=8e9, n_devices=1)
             for i in range(2)]
    with pytest.raises(AssignmentError) as e:
        plan_sharding(cfg, small, model_name="minicpm-sala-l16", batch=8,
                      seq_len=36864)
    msg = str(e.value)
    assert "weights 10.08 GB" in msg and "recurrent states 0.20 GB" in msg
    assert "state snapshots 0.63 GB" in msg and "8.00 GB" in msg
