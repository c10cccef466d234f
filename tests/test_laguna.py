"""``laguna`` on the slot engine: grouped-query layers of two kinds (full;
sliding with a window held as a ring a slot) beside routed experts
(models/base.py::GqaAttn, models/latent.py, engine/latent.py), against the
plain reference ``benchmarks/reference/laguna.py``. A tiny float32 preset
of the published shape: 9 layers (f s s s f s s s f), 4 query heads on a
full layer and 6 on a sliding one over 2 kv heads of 16 (groups of 2 and
3), window 24, YaRN over 32 positions on half of a full layer's head, 16
experts of 32 of which 4 are held (4-7), 3 a token."""

from __future__ import annotations

import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec
from benchmarks.reference import laguna as ref
from tensorlink_tpu.engine import latent as el, paged
from tensorlink_tpu.engine.continuous import (
    ContinuousEngine,
    PagedUnsupported,
    paged_unsupported,
    tp_serving_refusal,
)
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.latent import LatentPagedCache
from tensorlink_tpu.models import latent as ml
from tensorlink_tpu.models.base import GqaAttn, ModelConfig
from tensorlink_tpu.models.registry import config_from_hf
from tensorlink_tpu.models.transformer import init_params

# tlint: disable=TL006(read-only table: every test copies it)
TINY = dict(
    model_type="laguna", hidden_size=64, intermediate_size=128,
    num_hidden_layers=9, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, vocab_size=97, max_position_embeddings=256,
    rms_norm_eps=1e-6, num_experts=4, num_experts_per_tok=3,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    norm_topk_prob=True, gating="per-head", sliding_window=24,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 3,
    mlp_layer_types=["dense"] + ["sparse"] * 11,
    gating_types=["per_head"] * 12,
    num_attention_heads_per_layer=[4, 6, 6, 6] * 3,
    moe_routed_scaling_factor=2.5, moe_router_logit_softcapping=0,
    moe_apply_router_weight_on_input=False, tie_word_embeddings=False,
    published={"num_experts": 16}, expert_group={"first_expert": 4},
)
CONFIG = (Path(__file__).parent.parent / "benchmarks" / "configs"
          / "laguna-s-2.1-ep8.json")
T = 150  # six windows: a ring of 36 positions wraps four times


@pytest.fixture(scope="module")
def tiny():
    cfg = config_from_hf(TINY, dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def seqs():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 97, size=T) for _ in range(2)]


@pytest.fixture(scope="module")
def want(tiny, seqs):
    """The reference's logits of both sequences at every position."""
    arch = ref.arch_of(TINY)
    return [ref.forward_logits(tiny[1], s[None], arch, slice(0, T))[0]
            for s in seqs]


def _engine(cfg, params, **kw):
    eng = GenerationEngine(cfg, params, seq_buckets=(8, 32),
                           batch_buckets=(1,), max_seq_len=256)
    kw = dict(max_slots=3, page_size=4, chunk_steps=4, prefill_chunk=8,
              state_snapshot_stride=32) | kw
    return ContinuousEngine(eng, **kw)


def _teacher_forced(params, cfg, seqs, lens, n_decode, *, C=8, S=3):
    """Each sequence's logits at its last prompt position and at
    ``n_decode`` teacher-forced continuation steps through pages and ring:
    chunked prefill in blocks of ``C``, slots at their own lengths, one
    idle slot."""
    cache = LatentPagedCache.init(cfg, S, page_size=4, max_len=256,
                                  prefill_chunk=C)
    n_pp = cache.pages_per_slot
    bt = np.zeros((S, n_pp), np.int32)
    perm = np.random.default_rng(0).permutation(np.arange(1, cache.n_pages))
    for s in range(len(seqs)):
        bt[s] = perm[s * n_pp:(s + 1) * n_pp]
    cache = replace(cache, block_tables=jnp.asarray(bt))
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
            jnp.asarray(nv), jnp.zeros(S, jnp.int32), cfg, 1, False)
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
            params, jnp.asarray(tok), cache, jnp.asarray(active), cfg, False)
        for s in range(len(seqs)):
            got[s].append(np.asarray(lg[s]))
    return [np.stack(g) for g in got], cache


# -- the configuration -------------------------------------------------------


def test_catalog_config_gives_the_published_sizes():
    """``config_from_hf`` on the benchmark's file: layers 0-8 in the
    published order, 48 / 72 query heads over 8 kv heads of 128, both
    rotary settings, 3,199.5 M parameters held; with the reduced keys put
    back, the row's 117.56 B."""
    hf = json.loads(CONFIG.read_text())
    cut = config_from_hf(hf)
    assert cut.layer_kinds == ("gqa_full",) + ("gqa_window",) * 3 + (
        "gqa_full",) + ("gqa_window",) * 3 + ("gqa_full",)
    full, win = cut.latent_of("gqa_full"), cut.latent_of("gqa_window")
    assert isinstance(full, GqaAttn) and isinstance(win, GqaAttn)
    assert (full.n_heads, win.n_heads, full.n_kv_heads, win.head_dim) == (
        48, 72, 8, 128)
    assert (full.window, win.window, cut.ring_window) == (None, 512, 512)
    assert (full.rope_dim, full.rope_theta, win.rope_dim, win.rope_theta) == (
        64, 500000.0, 128, 10000.0)
    assert win.rope_scaling is None and full.rope_scaling[:4] == (
        128.0, 8192.0, 32.0, 1.0)
    from tensorlink_tpu.models.transformer import yarn_inv_freq

    _, amp = yarn_inv_freq(64, full.rope_theta, full.rope_scaling)
    assert abs(amp - 1.4852030263919618) < 1e-9
    assert (cut.n_experts, cut.n_held, cut.experts_first,
            cut.n_experts_per_tok, cut.moe_scale, cut.moe_router) == (
        256, 32, 0, 10, 2.5, "sigmoid")
    assert (cut.n_dense_layers, cut.d_ff, cut.moe_d_ff,
            cut.n_shared_experts) == (1, 12288, 1024, 1)
    assert cut.held_param_count() == 3_199_462_400
    p = ml.pattern_of(cut)
    assert (p.lead, p.period, p.n_periods, p.tail) == (
        ("gqa_full",), ("gqa_window",) * 3 + ("gqa_full",), 2, ())
    whole = config_from_hf({
        **hf, **{k: hf["published"][k] for k in (
            "num_hidden_layers", "num_experts", "vocab_size")},
        "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 12,
        "published": None})
    assert whole.param_count() == 117_561_965_312
    assert paged_unsupported(cut) is None
    # JSON and back (job specs carry the config over the wire)
    assert ModelConfig.from_json(
        json.loads(json.dumps(cut.to_json()))).latent == cut.latent


# tlint: disable=TL006(read-only table)
REFUSED = (
    (dict(gating="per-channel"), "gating"),
    (dict(mlp_layer_types=["sparse", "dense"] + ["sparse"] * 7),
     "mlp_layer_types"),
    (dict(moe_apply_router_weight_on_input=True), "router_weight_on_input"),
    (dict(moe_router_logit_softcapping=30.0), "softcapping"),
    (dict(num_attention_heads_per_layer=[4, 6, 4, 6] * 3), "query heads"),
    (dict(layer_types=["full_attention", "chunked_attention"] * 5),
     "layer_types"),
    (dict(attention_bias=True), "attention_bias"),
)


@pytest.mark.parametrize("change,why", REFUSED, ids=[w for _, w in REFUSED])
def test_the_registry_refuses_what_it_does_not_build(change, why):
    with pytest.raises(ValueError, match=f"laguna: .*{why}"):
        config_from_hf({**TINY, **change})


def test_yarn_tables_are_the_references(tiny):
    """The program's cos / sin of both kinds against the reference's
    float64 frequencies and amplitude."""
    cfg, _ = tiny
    pos = jnp.arange(200)[None]
    arch = ref.arch_of(TINY)
    for name, kind in ref.KINDS.items():
        cos, sin = ml.rope_by_kind(cfg, pos)[kind]
        freq, amp = ref.inv_freq(ref._kind(arch, 0 if kind == "gqa_full"
                                           else 1))
        ang = np.arange(200)[:, None] * np.concatenate([freq, freq])[None]
        np.testing.assert_allclose(np.asarray(cos[0]), np.cos(ang) * amp,
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(sin[0]), np.sin(ang) * amp,
                                   atol=2e-4)
    assert abs(amp - 1.0) < 1e-12  # the sliding kind's, the last read


# -- the cache ---------------------------------------------------------------


def test_a_window_layer_holds_a_ring_a_slot(tiny):
    """The sliding layers' pools hold ``ceil((window + chunk) / page) + 1``
    pages a slot and the scratch page, whatever the context; the full
    layers' the page table's."""
    cfg, params = tiny
    assert el.ring_len(24, 8, 4) == 9 and el.ring_len(512, 128, 16) == 41
    assert el.snapshot_pages(24, 4) == 6 and el.snapshot_pages(512, 16) == 32
    cache = LatentPagedCache.init(cfg, 3, page_size=4, max_len=256,
                                  prefill_chunk=8)
    assert cache.wk.shape == cache.wv.shape == (6, 1 + 3 * 9, 2, 4, 16)
    assert cache.k.shape == cache.v.shape == (3, 1 + 3 * 64, 2, 4, 16)
    assert cache.ring_pages == 9 and set(cache.pools()) == {"k", "v"}
    table = np.asarray(el.ring_table(3, 64, 9))
    assert table[0, :10].tolist() == [1, 2, 3, 4, 5, 6, 7, 8, 9, 1]
    assert table[2, 8:11].tolist() == [27, 19, 20]
    ce = _engine(cfg, params)
    assert ce.cache.wk.shape[1] == 1 + ce.max_slots * el.ring_len(24, 8, 4)
    assert ce._snaps.shape == (256 // 32 + 3 + 1, 2, 6, 6, 2, 4, 16)
    snap = ce.serving_snapshot()
    assert snap["window_pool_bytes"] == (
        ce.cache.ring_bytes + ce._snaps.size * 4)
    assert snap["state_pool_bytes"] == 0 and snap["latent_pool_bytes"] == (
        2 * ce.cache.k.size * 4)
    ce.close()


# -- logits against the reference ---------------------------------------------


def test_logits_through_pages_and_ring_are_the_references(tiny, seqs, want):
    """(a) Prefill in chunks of 8, then 10 continuation steps, two slots at
    their own lengths (130 and 111: past the window six times over, the
    ring wrapped three times) against the reference's full forward pass."""
    cfg, params = tiny
    lens = [130, 111]
    got, cache = _teacher_forced(params, cfg, seqs, lens, 10)
    for s in range(2):
        np.testing.assert_allclose(
            got[s], want[s][lens[s] - 1:lens[s] + 10], rtol=2e-3, atol=2e-4)
    # the idle slot's ring was never written
    assert not np.asarray(cache.wk[:, 1 + 2 * 9:]).any()


def test_the_engines_stream_is_the_references_greedy_chain(tiny, seqs):
    """(a) through ``ContinuousEngine``: the served greedy stream over a
    prompt of 120 tokens is the reference's argmax chain, each served
    token's reference logit the largest."""
    cfg, params = tiny
    ce = _engine(cfg, params)
    prompt = [int(t) for t in seqs[0][:120]]
    req = ce.submit(prompt, max_new_tokens=10)
    ce.run_until_idle()
    gaps = ref.token_gaps(params, [prompt], [list(req.tokens)],
                          ref.arch_of(TINY))
    assert gaps.max() < 1e-3
    ce.check_page_conservation()
    ce.close()


def _probe_run(cfg, params, x, kind, li, *, restore_at=None, C=8, n_dec=6):
    """What layer ``li`` of ``kind`` adds over ``x`` ``[T, d]`` through the
    layer probe: slot 0 all the way, or slot 0 to ``restore_at``, a
    snapshot there, and slot 1 from its restore on."""
    ragged, decode = paged.make_layer_probe(cfg, kind)
    cache = LatentPagedCache.init(cfg, 2, page_size=4, max_len=256,
                                  prefill_chunk=C)
    n_pp = cache.pages_per_slot
    cache = replace(cache, block_tables=(
        1 + jnp.arange(2 * n_pp, dtype=jnp.int32)).reshape(2, n_pp))
    p = ml.pattern_of(cfg)
    lp = jax.tree.map(lambda a: a[0], params["periods"][
        p.period.index(kind)])
    lp = {"ln1": lp["ln1"], "attn": lp["attn"]}
    n_pre = x.shape[0] - n_dec
    outs, slot, pos = [], 0, 0
    while pos < x.shape[0]:
        if pos == restore_at:
            snaps = el.window_snapshot_pool(cache, 2, cfg.ring_window)
            snaps = el.take_window(snaps, cache, jnp.int32(0), jnp.int32(1),
                                   jnp.int32(pos))
            cache = el.restore_window(cache, snaps, jnp.int32(1),
                                      jnp.int32(1), jnp.int32(pos))
            cache = replace(cache, lengths=cache.lengths.at[1].set(pos))
            slot = 1
        if pos < n_pre:
            n = min(C, n_pre - pos, (restore_at or 10**9) - pos
                    if pos < (restore_at or 0) else C)
            blk = jnp.zeros((2, C, x.shape[1]), x.dtype).at[slot, :n].set(
                x[pos:pos + n])
            out, cache = ragged(
                lp, blk, cache, jnp.int32(li),
                jnp.zeros(2, jnp.int32).at[slot].set(pos),
                jnp.zeros(2, jnp.int32).at[slot].set(n))
            outs.append(np.asarray(out[slot, :n]))
        else:
            n = 1
            out, cache = decode(
                lp, jnp.zeros((2, 1, x.shape[1]), x.dtype).at[slot].set(
                    x[pos:pos + 1]), cache, jnp.int32(li),
                jnp.zeros(2, bool).at[slot].set(True))
            outs.append(np.asarray(out[slot]))
        pos += n
    return np.concatenate(outs), cache


def test_a_restored_snapshot_goes_on_bit_for_bit(tiny):
    """(b) A sliding layer over 150 positions (the ring of 36 wraps four
    times): slot 0 to position 96, the window there as a snapshot, restored
    into slot 1, which goes on through prefill chunks and 6 continuation
    steps: bit for bit what a slot that never left computes."""
    cfg, params = tiny
    x = jnp.asarray(np.random.default_rng(3).normal(size=(T, 64)),
                    jnp.float32)
    straight, c0 = _probe_run(cfg, params, x, "gqa_window", 1)
    moved, c1 = _probe_run(cfg, params, x, "gqa_window", 1, restore_at=96)
    assert np.array_equal(straight, moved)
    # slot 1's ring pages are slot 0's of the run that never left
    assert np.array_equal(np.asarray(c0.wk[1, 1:10]),
                          np.asarray(c1.wk[1, 10:19]))


def test_a_prefix_hit_restores_a_window_snapshot_and_replays(tiny, seqs):
    """A document made resident, then two prompts that share it: each
    admission restores ONE snapshot (the nearest at or under its match),
    prefills the rest again, and streams what an engine without a prefix
    cache streams; the counters say so and conservation holds with
    snapshots in the trie."""
    cfg, params = tiny
    doc = [int(t) for t in seqs[0][:100]]
    tails = [[5, 6, 7], [5, 6, 9, 11]]
    plain = _engine(cfg, params, prefix_cache=False)
    assert plain._snaps is None
    want = []
    for tail in tails:
        r = plain.submit(doc + tail, max_new_tokens=8)
        plain.run_until_idle()
        want.append(list(r.tokens))
    plain.close()
    ce = _engine(cfg, params)
    ce.submit(doc, max_new_tokens=2)
    ce.run_until_idle()
    taken = ce.stats["window_snapshots_taken"]
    assert taken == 3  # at 32, 64 and 96, which is the last page edge too
    got, restored = [], []
    for tail in tails:
        before = dict(ce.stats)
        r = ce.submit(doc + tail, max_new_tokens=8, trace_id=f"t{len(got)}")
        ce.run_until_idle()
        got.append(list(r.tokens))
        assert ce.stats["window_snapshots_restored"] == (
            before["window_snapshots_restored"] + 1)
        assert ce.stats["window_admissions"] == before["window_admissions"] + 1
        restored.append(r.state_restored_at)
    assert got == want
    # the document's match ends at 100, a page past its snapshot; the first
    # prompt's own last page edge is 100, where the second restores
    assert restored == [96, 100]
    assert ce.stats["window_rows_replayed"] == 4
    assert ce.stats["state_admissions"] == 0
    ce.check_page_conservation()
    snap = ce.serving_snapshot()
    assert snap["window_snapshots_resident"] >= 4
    assert snap["state_snapshots_resident"] == 0
    from tensorlink_tpu.core.trace import get_tracer

    for i, at in enumerate(restored):
        adm = [s for s in get_tracer().collect(f"t{i}")
               if s["name"] == "admission"]
        assert adm and adm[0]["window_restored_at"] == at
        assert "state_restored_at" not in adm[0]
    ce.close()


def test_a_full_snapshot_pool_drops_the_node_matched_longest_ago(tiny, seqs):
    """Four places and prompts that take more: a snapshot point with no
    place free drops the snapshot of the node matched longest ago (the
    node stays), conservation counts every place, and a prompt whose
    snapshot went restores further down and replays."""
    cfg, params = tiny
    ce = _engine(cfg, params, state_snapshots=4)
    docs = [[int(t) for t in seqs[i][:70]] for i in range(2)]
    for d in docs:
        ce.submit(d, max_new_tokens=2)
        ce.run_until_idle()
        ce.check_page_conservation()
    # 32, 64, 68 each: six points for four places
    assert ce.stats["window_snapshots_taken"] == 6
    assert ce.stats["window_snapshots_skipped"] == 0
    assert len(ce._snap_nodes) == 4 and not ce._snap_free
    r = ce.submit(docs[0] + [3, 4], max_new_tokens=3)
    ce.run_until_idle()
    assert r.state_restored_at in (64, 68) and not r.error
    ce.check_page_conservation()
    ce.close()


def test_preemption_resumes_by_restore_and_replay(tiny, seqs):
    """A request preempted mid-decode resumes through the trie: its pages
    promoted, a snapshot restored, the rest replayed; the stream is the
    uninterrupted one."""
    cfg, params = tiny
    prompt = [int(t) for t in seqs[1][:70]]
    ce = _engine(cfg, params)
    r = ce.submit(prompt, max_new_tokens=16)
    ce.run_until_idle()
    want = list(r.tokens)
    ce.close()
    ce = _engine(cfg, params)
    r = ce.submit(prompt, max_new_tokens=16)
    while len(r.tokens) < 5:
        ce.step_chunk()
    ce._preempt(r.slot)
    ce.run_until_idle()
    assert list(r.tokens) == want and ce.stats["preemptions"] == 1
    assert ce.stats["window_snapshots_restored"] == 1
    ce.check_page_conservation()
    ce.close()


def test_an_admission_makes_one_restore_and_no_other_device_call(tiny, seqs):
    """PR 43's count holds: an admission with a prefix hit calls the
    device once (the restore), one without a hit not at all."""
    cfg, params = tiny
    doc = [int(t) for t in seqs[0][:100]]
    ce = _engine(cfg, params)
    ce.submit(doc, max_new_tokens=2)
    ce.run_until_idle()
    assert ce.stats["admit_device_calls"] == 0
    ce.submit(doc + [1, 2], max_new_tokens=2)
    ce.run_until_idle()
    assert ce.stats["admit_device_calls"] == 1
    ce.close()


def test_refusals_name_the_ring(tiny):
    """What moves or shares pages by name refuses a model whose window
    layers hold a ring, with the reason; drafting is served without
    drafts."""
    cfg, params = tiny
    for kw, why in [
        (dict(kv_quant="int8"), "pages and window rings are stored in "
                                "the model dtype"),
        (dict(host_tier_pages=8), "window rings in the host-RAM tier"),
        (dict(handoff_after_prefill=True), "do not hand off"),
        (dict(tensor_parallel=2), "window rings have no partition specs"),
    ]:
        with pytest.raises(PagedUnsupported, match=why):
            _engine(cfg, params, **kw)
    assert "MoE routing" in tp_serving_refusal(cfg, 2)
    ce = _engine(cfg, params, spec_decode=True)
    assert "does not draft" in ce.spec_refusal
    assert "ring" in ce.serving_snapshot()["spec_refusal"]
    r = ce.submit([1, 2, 3] * 10, max_new_tokens=4)
    ce.run_until_idle()
    with pytest.raises(PagedUnsupported, match="pages and window rings"):
        ce.export_slot(0)
    ce.close()
    only_windows = cfg.with_(layer_kinds=("gqa_window",) * 3)
    assert "without a full layer" in paged_unsupported(only_windows)


# -- the experts --------------------------------------------------------------


def test_the_shares_tie_to_the_uncut_layer():
    """(c) The four shares' routed parts, with the shared expert and the
    attention counted once, add up to the uncut reference's layer output."""
    hf = {**TINY, "num_experts": 16, "published": None, "expert_group": None}
    whole = config_from_hf(hf, dtype=jnp.float32)
    wp = init_params(whole, jax.random.PRNGKey(5))
    arch = ref.arch_of(hf)
    lt = ref.layer_tree(wp, 1)  # a sliding layer with experts
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    a = ref.attention_layer(x, lt, ref._kind(arch, 1))
    want = ref.mlp_layer(a, lt, arch)
    mp = {k: (v[lt["moe"]["stacked"]] if k in ml.EXPERT_STACKS else v)
          for k, v in lt["moe"].items() if k != "stacked"}
    h = ref._rmsnorm(a, lt["ln2"]["scale"], arch["eps"])
    valid = jnp.ones(40, bool)
    shared = ml.gated_mlp(h, mp["shared"])
    total = a + shared
    for first in range(0, 16, 4):
        share = whole.with_(experts_first=first, experts_held=4)
        share_mp = {**mp, **{n: mp[n][first:first + 4]
                             for n in ml.EXPERT_STACKS}}
        y, _ = ml.moe_mlp(h, share_mp, share, valid)
        total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


# -- the layer-matched numbers and the controls -------------------------------


@pytest.fixture(scope="module")
def sound(tiny, seqs):
    return ref.layer_gaps(tiny[1], seqs[0], ref.arch_of(TINY), 6)


def test_every_mechanism_has_a_layer_matched_number(sound):
    """Sound, in float32: each held number reads rounding, both sides pick
    the same experts on every row."""
    for name, _ in ref.HELD:
        assert sound[name] < 1e-4, (name, sound)
    assert sound["agree"] == 1.0
    by = sound["by_layer"]
    assert set(by["full"]) == set(by["rows"]) == {0, 4, 8}
    assert set(by["window"]) == {1, 2, 3, 5, 6, 7}
    assert set(by["experts"]) == set(range(1, 9))


CONTROLS = (
    ("window_delta", 1, "window"), ("window_delta", -1, "window"),
    ("sliding_heads", 4, "window"), ("no_yarn", True, "full"),
    ("full_rotary", True, "full"), ("gate", False, "full"),
    ("gate", False, "window"), ("router", "softmax", "route"),
    ("routed_scale", 1.0, "experts"), ("int8_rows", True, "rows"),
)


@pytest.mark.parametrize("key,value,held", CONTROLS,
                         ids=[f"{k}-{v}-{h}" for k, v, h in CONTROLS])
def test_each_planted_fault_fails_its_limit(tiny, seqs, key, value, held):
    """(d) The reference with one fault (the program sound) through
    ``layer_gaps``: the held number of that mechanism reads over its limit
    in ``reference/laguna.json``, the cell's own."""
    tol = spec.load_tolerance({"correct": {"tolerance": "laguna"}})
    limit = tol[dict(ref.HELD)[held]]
    bad = ref.layer_gaps(tiny[1], seqs[0],
                         {**ref.arch_of(TINY), key: value}, 6)
    assert bad[held] > limit, (key, bad)


# -- the other families and the planner ---------------------------------------


def test_the_stateful_familys_step_program_is_the_parents():
    """``minicpm_sala``'s step lowers to the parent's text (the three
    other families: tests/test_sala.py)."""
    import hashlib

    import test_sala as ts

    cfg = config_from_hf(ts.TINY, dtype=jnp.float32)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    ce = ts._engine(cfg, params)
    got = {w: hashlib.sha256(ce.lower_step(w, flat=False).as_text().encode()
                             ).hexdigest()[:16] for w in ce.block_widths}
    ce.close()
    assert got == {8: "d794a4fd1782fb09"}


def test_planner_counts_full_pages_rings_and_snapshots():
    """A slot's memory is pages x the 3 full layers + a ring x the 6
    sliding layers (+ the snapshot pool), not pages x 9 layers: held whole
    the sliding layers' pools alone would be 6.4 GB."""
    from tensorlink_tpu.parallel.planner import (
        AssignmentError, MemoryEstimate, WorkerCapacity, plan_sharding)

    cfg = config_from_hf(json.loads(CONFIG.read_text()))
    parts = MemoryEstimate.state_parts(cfg, 16, 16384)
    assert parts["pages"] == 3 * 16 * 16384 * 4096 == 3_221_225_472
    assert parts["states"] == 6 * 16 * 41 * 16 * 4096
    assert parts["snapshots"] == (8 + 24) * 6 * 512 * 4096
    est = MemoryEstimate.build(cfg, batch=16, seq_len=16384, training=False)
    assert est.params == 3_199_462_400 * 2
    assert est.kv_cache == sum(parts.values())
    assert 10.5e9 < est.total < 12.5e9
    one = [WorkerCapacity(node_id="w0", hbm_bytes=15.75e9, n_devices=1)]
    assert len(plan_sharding(cfg, one, batch=16, seq_len=16384).stages) == 1
    small = [WorkerCapacity(node_id=f"w{i}", hbm_bytes=8e9, n_devices=1)
             for i in range(2)]
    with pytest.raises(AssignmentError) as e:
        plan_sharding(cfg, small, model_name="laguna-s-2.1-ep8", batch=16,
                      seq_len=16384)
    msg = str(e.value)
    assert "weights 6.40 GB" in msg and "window rings 0.26 GB" in msg
    assert "window snapshots 0.40 GB" in msg
