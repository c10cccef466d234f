"""A patterned model (models/latent.py, engine/latent.py) on the slot
engine: a tiny ``dots3_note`` preset that keeps the structure (pattern full,
full, sliding x3; window and ``index_topk`` shorter than the context; 16
experts of which 4 held; a vocabulary slice) against the plain reference
``benchmarks/reference/dots3_note.py``."""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import deepseek_v2 as ref_ds
from benchmarks.reference import dots3_note as ref
from tensorlink_tpu.engine import paged
from tensorlink_tpu.engine.continuous import (
    ContinuousEngine,
    PagedUnsupported,
    paged_unsupported,
)
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.latent import LatentPagedCache
from tensorlink_tpu.models import latent as ml
from tensorlink_tpu.models.base import ModelConfig
from tensorlink_tpu.models.registry import config_from_hf
from tensorlink_tpu.models.transformer import init_params

# tlint: disable=TL006(read-only table: every test copies it)
TINY = dict(
    model_type="dots3_note", hidden_size=64, num_hidden_layers=5,
    layer_types=["full_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention"],
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, rope_theta=8e7,
    swa_num_attention_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=24,
    swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=8, swa_v_head_dim=8,
    swa_rope_theta=5e4, sliding_window_size=5, index_n_heads=4,
    index_head_dim=16, index_topk=8, apply_mla_qkv_lora_rescale=True,
    first_k_dense_replace=1, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
    norm_topk_prob=True, routed_scaling_factor=1, scoring_func="sigmoid",
    rms_norm_eps=1e-5, vocab_size=64, max_position_embeddings=64,
    tie_word_embeddings=False,
    published={"n_routed_experts": 16}, expert_group={"first_expert": 4},
)


# a tiny ``deepseek_v2``: one kind of layer (full, nothing selected), YaRN
# over an original length shorter than the contexts, 16 experts in 4
# routing groups of which this chip holds group 1, two shared experts
# tlint: disable=TL006(read-only table: every test copies it)
TINY_DS = dict(
    model_type="deepseek_v2", hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, rope_theta=1e4,
    rope_scaling=dict(
        type="yarn", factor=40, original_max_position_embeddings=8,
        beta_fast=32, beta_slow=1, mscale=0.707, mscale_all_dim=0.707),
    first_k_dense_replace=1, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=4, n_shared_experts=2, num_experts_per_tok=3,
    norm_topk_prob=False, routed_scaling_factor=16, scoring_func="softmax",
    topk_method="group_limited_greedy", n_group=4, topk_group=2,
    rms_norm_eps=1e-6, vocab_size=64, max_position_embeddings=64,
    tie_word_embeddings=False,
    published={"n_routed_experts": 16}, expert_group={"first_expert": 4},
)


def tiny_hf(**over) -> dict:
    return {**TINY, **over}


@pytest.fixture(scope="module")
def tiny():
    cfg = config_from_hf(TINY, dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    eng = GenerationEngine(
        cfg, params, seq_buckets=(8, 32), batch_buckets=(1,), max_seq_len=64
    )
    kw = dict(max_slots=3, page_size=4, chunk_steps=4, prefill_chunk=8) | kw
    return ContinuousEngine(eng, **kw)


def _teacher_forced(params, cfg, seqs, lens, n_decode, *, C=8, page=4, S=3,
                    kernel=False):
    """Each sequence's logits at its last prompt position and at
    ``n_decode`` teacher-forced decode steps, through the pages: chunked
    prefill in blocks of ``C`` (the step's ragged pass), then continuation
    steps. Returns ``([len(seqs)][1 + n_decode, V], cache)``."""
    cache = LatentPagedCache.init(cfg, S, page_size=page, max_len=64)
    n_pp = cache.pages_per_slot
    bt = np.zeros((S, n_pp), np.int32)
    perm = np.random.default_rng(0).permutation(np.arange(1, cache.n_pages))
    for s in range(len(seqs)):
        bt[s] = perm[s * n_pp:(s + 1) * n_pp]
    cache = paged._with_kv(
        cache, paged._cache_kv(cache), block_tables=jnp.asarray(bt)
    )
    pos = [0] * len(seqs)
    got = [[None] for _ in seqs]
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
            jnp.asarray(nv), jnp.zeros(S, jnp.int32), cfg, 1, kernel,
        )
        cache = paged._with_kv(cache, kv, lengths=jnp.where(
            jnp.asarray(nv) > 0, jnp.asarray(starts + nv), cache.lengths))
        for s in range(len(seqs)):
            if nv[s] > 0:
                pos[s] += int(nv[s])
                got[s][0] = np.asarray(lv[s, 0])
    for i in range(n_decode):
        tok, active = np.zeros(S, np.int32), np.zeros(S, bool)
        for s, seq in enumerate(seqs):
            tok[s], active[s] = seq[lens[s] + i], True
        lg, cache = paged._decode_step_impl(
            params, jnp.asarray(tok), cache, jnp.asarray(active), cfg, kernel
        )
        for s in range(len(seqs)):
            got[s].append(np.asarray(lg[s]))
    return [np.stack(g) for g in got], cache


def test_catalog_config_gives_the_published_sizes():
    """``config_from_hf`` on the catalog row's ``config`` verbatim: the
    published sizes, 46 layers as lead + 11 periods of four + one, and
    279.6 B parameters; the benchmark's cut holds 4.09 B of them."""
    # the catalog row's config: the benchmark file's keys with its five
    # reduced keys put back as published
    cut_file = json.loads((Path(__file__).parent.parent / "benchmarks"
                           / "configs" / "dots3-note-prev-ep8.json").read_text())
    period = ["full_attention"] + ["sliding_attention"] * 3
    hf = {**cut_file, "num_hidden_layers": 46, "n_routed_experts": 256,
          "vocab_size": 152064, "max_position_embeddings": 524288,
          "layer_types": ["full_attention"] + period * 11 + ["full_attention"]}
    for key in ("published", "expert_group", "deployment", "correct"):
        hf.pop(key)
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.open()
                   if '"dots3-note-prev"' in line)
        assert {k: hf[k] for k in row["config"]} == row["config"]
    cfg = config_from_hf(hf)
    full, slide = cfg.latent_of("full"), cfg.latent_of("sliding")
    assert (full.n_heads, full.q_rank, full.kv_rank, full.nope_dim,
            full.rope_dim, full.v_dim) == (128, 1024, 512, 128, 64, 128)
    assert (full.index_heads, full.index_dim, full.index_topk) == (64, 128, 2048)
    assert (slide.n_heads, slide.q_rank, slide.kv_rank, slide.nope_dim,
            slide.rope_dim, slide.v_dim, slide.window) == (
        64, 1024, 1024, 192, 64, 128, 513)
    assert (full.rope_theta, slide.rope_theta) == (8e7, 5e4)
    assert full.q_scale == pytest.approx(5 ** 0.5)
    assert full.kv_scale == pytest.approx(10 ** 0.5)
    assert (full.row_dim, full.pool_dim, slide.row_dim, slide.pool_dim) == (
        576, 640, 1088, 1152)
    assert (cfg.n_experts, cfg.n_held, cfg.n_experts_per_tok, cfg.moe_d_ff,
            cfg.d_ff, cfg.n_dense_layers) == (256, 256, 8, 1536, 13824, 1)
    pat = ml.pattern_of(cfg)
    assert (pat.lead, pat.period, pat.n_periods, pat.tail) == (
        ("full",), ("full", "sliding", "sliding", "sliding"), 11, ("full",))
    assert cfg.param_count() / 1e9 == pytest.approx(279.6, abs=0.1)
    cut = config_from_hf({
        **hf, "num_hidden_layers": 5, "layer_types": hf["layer_types"][:5],
        "n_routed_experts": 32, "vocab_size": 19008,
        "max_position_embeddings": 16384,
        "published": {"n_routed_experts": 256},
    })
    assert (cut.n_experts, cut.n_held) == (256, 32)
    assert cut.held_param_count() / 1e6 == pytest.approx(4087, abs=1)
    # a config is a static argument of the step and travels as JSON
    back = ModelConfig.from_json(json.loads(json.dumps(cut.to_json())))
    assert back == cut.with_(dtype=back.dtype) and hash(back) == hash(
        cut.with_(dtype=back.dtype))


def test_served_logits_match_the_reference(tiny):
    """Chunked prefill then decode through the pages, two slots at
    different offsets, against the reference's full forward: selection
    (8 of up to 26 positions), the window (5) and the expert share (4 of
    16) all cut, in float32 to rounding."""
    cfg, params = tiny
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 64, size=(2, 27)).astype(np.int32)
    lens = [21, 16]
    got, cache = _teacher_forced(params, cfg, toks, lens, 5)
    arch = ref.arch_of(TINY)
    for s, L in enumerate(lens):
        want = ref.forward_logits(
            params, toks[s:s + 1, :L + 5], arch, slice(L - 1, L + 5))[0]
        assert np.abs(got[s] - want).max() < 2e-4, s
    st = dict(zip(ml.STEP_STATS, np.asarray(cache.stats)))
    assert 0 < st["sparse_positions_kept"] < st["sparse_positions_scored"]
    assert 0 < st["moe_rows_routed_local"] <= st["moe_rows_computed"]
    assert st["moe_experts_touched"] <= st["moe_experts_held"]


def test_engine_streams_with_prefix_hit_and_copy_on_write(tiny):
    """Through ``ContinuousEngine``: a warm request makes its prompt
    resident, a cousin shares a prefix that ends mid-page (copy-on-write),
    and each greedy stream is the reference's own argmax chain; pages are
    conserved over the new pools and the step's counters move."""
    cfg, params = tiny
    ce = _engine(cfg, params)
    rng = np.random.default_rng(2)
    base = rng.integers(0, 64, size=22).tolist()
    cousin = base[:14] + rng.integers(0, 64, size=7).tolist()
    arch = ref.arch_of(TINY)

    def greedy_ref(prompt, n):
        seq = list(prompt)
        for _ in range(n):
            lg = ref.forward_logits(
                params, np.asarray([seq]), arch, slice(len(seq) - 1, len(seq)))
            seq.append(int(lg[0, 0].argmax()))
        return seq[len(prompt):]

    a = ce.submit(base, max_new_tokens=6, seed=0)
    ce.run_until_idle()
    b = ce.submit(cousin, max_new_tokens=6, seed=0)
    c = ce.submit(base, max_new_tokens=4, seed=0)
    ce.run_until_idle()
    assert a.tokens == greedy_ref(base, 6)
    assert b.tokens == greedy_ref(cousin, 6)
    assert c.tokens == a.tokens[:4]
    assert ce.stats["prefill_tokens_skipped"] > 0
    assert ce.jit_cache_sizes().get("copy_page", 0) >= 1
    ce.check_page_conservation()
    for k in ("moe_rows_routed_local", "moe_rows_computed",
              "moe_experts_touched", "moe_experts_held",
              "sparse_positions_kept", "sparse_positions_scored",
              "window_pages_walked", "window_pages_context"):
        assert ce.stats[k] > 0, k
    assert ce.stats["window_pages_walked"] < ce.stats["window_pages_context"]
    assert ce.stats["sparse_positions_kept"] < ce.stats["sparse_positions_scored"]
    snap = ce.serving_snapshot()
    assert snap["latent_pool_bytes"] == ce.cache.pool_bytes > 0
    ce.close()


def test_one_layer_through_the_pages_on_the_reference_s_input(tiny):
    """``paged.make_layer_probe``: a layer's attention placed as the step's
    two passes place it, on hidden states given from outside. On the
    reference's own input to each layer the rows it caches and what it adds
    to the residual stream are the reference's, whatever the layers before
    chose; int8 rows and a window off by one in the reference each read
    far over that (what ``correct`` holds on the chip, where bf16 tokens
    cannot tell them from noise)."""
    cfg, params = tiny
    hf = tiny_hf(deployment={"ml": {"prefill_chunk": 8, "cont_page_size": 4}})
    arch = ref.arch_of(hf)
    toks = np.random.default_rng(5).integers(0, 64, size=40)
    sound = ref.layer_gaps(params, toks, arch, 6)
    assert sorted(sound["by_layer"]["window"]) == [2, 3, 4]
    assert sorted(sound["by_layer"]["full"]) == [0, 1]
    assert max(sound["rows"], sound["window"], sound["full"]) < 1e-5
    off = ref.layer_gaps(
        params, toks,
        {**ref.arch_of(tiny_hf(sliding_window_size=4)), "config": hf}, 6)
    assert off["window"] > 0.05 and off["rows"] < 1e-5
    int8 = ref.layer_gaps(params, toks, {**arch, "int8_rows": True}, 6)
    assert int8["rows"] > 1e-3


def test_absorbed_equals_materialised(tiny):
    cfg, params = tiny
    la = cfg.latent_of("sliding")
    ap = params["periods"][1]["attn"]
    ap = jax.tree.map(lambda a: a[0], ap)
    rng = np.random.default_rng(3)
    B, R, K = 2, 3, 11
    q_n = jnp.asarray(rng.normal(size=(B, R, la.n_heads, la.nope_dim)), jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(B, R, la.n_heads, la.rope_dim)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(B, K, la.pool_dim)), jnp.float32)
    mask = jnp.asarray(rng.random((B, R, K)) < 0.7).at[:, :, 0].set(True)
    mat = ml.attend_materialised(q_n, q_r, rows, mask, ap, la)
    for b in range(B):
        ab = ml.attend_absorbed(
            q_n[b], q_r[b], jnp.broadcast_to(rows[b][None], (R, K, la.pool_dim)),
            mask[b], ap, la)
        np.testing.assert_allclose(np.asarray(ab), np.asarray(mat[b]),
                                   rtol=2e-4, atol=2e-5)


def test_short_context_is_latent_attention_without_the_indexer(tiny):
    """While no more positions are live than ``index_topk`` the selector
    drops nothing: a reference that never selects gives the same logits."""
    cfg, params = tiny
    rng = np.random.default_rng(4)
    toks = rng.integers(0, 64, size=(1, 8)).astype(np.int32)  # 8 = index_topk
    got, _ = _teacher_forced(params, cfg, toks, [6], 2)
    arch = {**ref.arch_of(TINY), "select": False}
    want = ref.forward_logits(params, toks[:, :8], arch, slice(5, 8))[0]
    assert np.abs(got[0] - want).max() < 2e-4
    # and a longer one does differ from the unselected reference
    toks = rng.integers(0, 64, size=(1, 30)).astype(np.int32)
    got, _ = _teacher_forced(params, cfg, toks, [26], 2)
    want = ref.forward_logits(params, toks[:, :28], arch, slice(25, 28))[0]
    assert np.abs(got[0] - want).max() > 1e-3


@pytest.mark.parametrize("family", ["dots3_note", "deepseek_v2"])
def test_expert_shares_add_up_to_the_uncut_layer(family):
    """The share test: the routed parts of all four shares of the expert
    group plus the shared expert(s) once are the uncut layer's output
    (dots3_note: sigmoid scores, normalised; deepseek_v2: softmax scores,
    the group limit with one routing group a share, unnormalised x 16)."""
    hf = {**(TINY if family == "dots3_note" else TINY_DS),
          "n_routed_experts": 16, "published": None, "expert_group": None}
    reference = ref if family == "dots3_note" else ref_ds
    whole = config_from_hf(hf, dtype=jnp.float32)
    wp = init_params(whole, jax.random.PRNGKey(5))
    mp = jax.tree.map(lambda a: a[0], wp["periods"][0]["moe"])
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(12, 64)), jnp.float32)
    valid = jnp.ones(12, bool)
    want, _ = ml.moe_mlp(h, mp, whole, valid)
    shared = ml.gated_mlp(h, mp["shared"])
    total = shared
    reach = 0
    for first in range(0, 16, 4):
        share_cfg = whole.with_(experts_first=first, experts_held=4)
        share_mp = {**mp, **{n: mp[n][first:first + 4]
                             for n in ("w_gate", "w_up", "w_down")}}
        y, st = ml.moe_mlp(h, share_mp, share_cfg, valid)
        total = total + (y - shared)
        st = dict(zip(ml.STEP_STATS, np.asarray(st)))
        assert st["moe_rows_valid"] == 12
        reach += st["moe_rows_in_group"]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # a row reaches topk_group of the n_group shares; all without a limit
    assert reach == 12 * (2 if family == "deepseek_v2" else 4)
    # and the uncut layer is the reference's
    arch = reference.arch_of(hf)
    lt = {"ln2": {"scale": jnp.ones(64)}, "moe": mp}
    normed = h  # ln2 with unit scale is applied by the reference itself
    out = reference.mlp_layer(normed, lt, arch) - normed
    a = reference._rmsnorm(normed, jnp.ones(64), arch["eps"])
    mine, _ = ml.moe_mlp(a, mp, whole, valid)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(out),
                               rtol=1e-4, atol=1e-5)


def test_dropless_when_every_row_goes_to_one_expert(tiny):
    """A router that sends every row to held expert 1 (and one absent
    expert): no row is dropped, the expert loop computes whole tiles of
    that one expert, and the output is that expert's, weighted."""
    cfg, params = tiny
    mp = jax.tree.map(lambda a: a[0], params["periods"][0]["moe"])
    bias = jnp.full((16,), -10.0).at[5].set(10.0).at[0].set(9.0)
    mp = {**mp, "bias": bias}  # experts 5 (held: 4 + 1) and 0 (absent)
    rng = np.random.default_rng(6)
    N = 300
    h = jnp.asarray(rng.normal(size=(N, 64)), jnp.float32)
    valid = jnp.ones(N, bool).at[7].set(False)
    y, st = ml.moe_mlp(h, mp, cfg, valid)
    st = dict(zip(ml.STEP_STATS, np.asarray(st)))
    assert st["moe_rows_routed_local"] == N - 1
    assert st["moe_rows_busiest_expert"] == N - 1
    assert st["moe_experts_touched"] == 1 and st["moe_experts_held"] == 4
    assert st["moe_rows_computed"] == 3 * ml.MOE_TILE  # ceil(299 / 128)
    topi, topw = ml.route(h, mp, cfg)
    assert set(np.asarray(topi).ravel()) == {0, 5}
    w5 = jnp.where(topi == 5, topw, 0).sum(-1)
    e = {n: mp[n][1] for n in ("w_gate", "w_up", "w_down")}
    want = ml.gated_mlp(h, e) * w5[:, None] + ml.gated_mlp(h, mp["shared"])
    want = want.at[7].set(ml.gated_mlp(h, mp["shared"])[7])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_refusals_are_stated(tiny):
    """What the cell does not use refuses this cache kind with a reason:
    the dense-cache forward, int8 latents, a shared pool, the host tier,
    tensor parallelism."""
    from tensorlink_tpu.models.transformer import forward, tp_shardable

    cfg, params = tiny
    assert paged_unsupported(cfg) is None
    with pytest.raises(NotImplementedError, match="slot engine"):
        forward(params, jnp.zeros((1, 4), jnp.int32), cfg)
    for kw, why in ((dict(kv_quant="int8"), "model dtype"),
                    (dict(host_tier_pages=8), "host-RAM tier")):
        with pytest.raises(PagedUnsupported, match=why):
            _engine(cfg, params, **kw)
    assert "MoE" in tp_shardable(cfg, 2)
    ce = _engine(cfg, params)
    assert ce.export_prefix_pages([1, 2, 3], 3) is None
    assert ce.stage_migration("m", {}) is False
    ce.close()


def test_the_step_keeps_its_three_phase_loops_and_names_its_scopes(tiny):
    """The patterned step is the same program shape a trace is read by:
    three top-level loops in phase order (the ragged pass's layers, with
    their own loops inside, run as ONE loop whose bound is data), and the
    new layers' scopes and the kernel's name are in it."""
    import re

    from tensorlink_tpu.engine.latent import WINDOW_KERNEL
    from tensorlink_tpu.engine.paged import STEP_PHASES

    from test_step_scopes import top_level_loops

    cfg, params = tiny
    ce = _engine(cfg, params, spec_decode=True, spec_draft=3)
    # a patterned model's block has one width (no second step program)
    assert ce.block_widths == (ce.prefill_chunk,) == (8,)
    text = ce.lower_step().as_text(debug_info=True)
    loops = top_level_loops(text)
    assert len(loops) == 3, loops
    for path, phase in zip(loops, STEP_PHASES):
        assert path.split("/")[1:] == [phase, "while"], (path, phase)
    compiled = ce.lower_step().compile().as_text()
    whiles = [ln for ln in compiled[compiled.index("ENTRY"):].splitlines()
              if " while(" in ln]
    assert [re.search(r'op_name="[^"]*/(tlink\.\w+)/while"', ln).group(1)
            for ln in whiles] == list(STEP_PHASES)
    paths = " ".join(set(re.findall(r'op_name="([^"]*)"', compiled)))
    for scope in (ml.LATENT_ATTN, ml.WINDOW_ATTN, ml.INDEX_SELECT, ml.MOE):
        assert f"tlink.ragged_pass/while/body/{scope}" in paths or \
            f"/{scope}/" in paths, scope
        assert f"tlink.decode_cont/while/body" in paths
    # on the chip the continuation step's sliding layers call the kernel
    # under this name (tests/test_chip_compile.py compiles it)
    assert WINDOW_KERNEL == "latent_window_attention"
    ce.close()
