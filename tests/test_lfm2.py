"""``lfm2_moe`` on the slot engine: gated short-convolution layers whose
tail lives a slot beside the pages, grouped-query layers with per-head q/k
norms and heads of 64 (keys beside values in one lane row of a page), and
routed experts held whole with no shared expert (models/base.py::ShortConv,
models/latent.py, engine/latent.py), against the plain reference
``benchmarks/reference/lfm2_moe.py``. A tiny float32 preset of the published
shape: 12 layers (c c f c, three times), 4 query heads over 2 kv heads of
64, 3 taps, two dense layers, then 8 experts of 32, 2 a token."""

from __future__ import annotations

import hashlib
import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec
from benchmarks.reference import lfm2_moe as ref
from tensorlink_tpu.engine import latent as el, paged
from tensorlink_tpu.engine.continuous import (
    PagedUnsupported,
    paged_unsupported,
    tp_serving_refusal,
)
from tensorlink_tpu.engine.latent import LatentPagedCache
from tensorlink_tpu.engine.sala import restore_snapshot, take_snapshot
from tensorlink_tpu.models import latent as ml
from tensorlink_tpu.models.base import GqaAttn, ModelConfig, ShortConv
from tensorlink_tpu.models.registry import config_from_hf
from tensorlink_tpu.models.transformer import init_params

# tlint: disable=TL006(read-only table: every test copies it)
TINY = dict(
    model_type="lfm2_moe", hidden_size=64, intermediate_size=128,
    num_hidden_layers=12, num_attention_heads=4, num_key_value_heads=2,
    head_dim=64, vocab_size=97, max_position_embeddings=256, norm_eps=1e-5,
    conv_L_cache=3, conv_bias=False, num_dense_layers=2, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32, norm_topk_prob=True,
    use_expert_bias=True, routed_scaling_factor=1, rope_theta=1000000,
    layer_types=["conv", "conv", "full_attention", "conv"] * 3,
)
CONFIG = (Path(__file__).parent.parent / "benchmarks" / "configs"
          / "lfm2-8b-a1b-l12.json")
T = 150


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


# an engine and the teacher-forced passes at the tiny shapes (slots at their
# own lengths, one idle slot): the grouped-query family's other model's
from test_laguna import _engine, _teacher_forced  # noqa: E402


# -- the configuration -------------------------------------------------------


def test_catalog_config_gives_the_published_sizes():
    """``config_from_hf`` on the benchmark's file: layers 0-11 in the
    published order, 32 query heads over 8 kv heads of 64 with q/k norms
    and no gate, 3 taps over 2,048, 32 experts all held, the head tied:
    3,928.7 M parameters; with the reduced keys put back, the published
    8.34 B (8.47 untied)."""
    hf = json.loads(CONFIG.read_text())
    cut = config_from_hf(hf)
    assert cut.layer_kinds == ("conv", "conv", "gqa_full", "conv") * 3
    conv, full = cut.latent_of("conv"), cut.latent_of("gqa_full")
    assert conv == ShortConv(kernel=3, width=2048) and conv.tail == 2
    assert isinstance(full, GqaAttn) and (
        full.n_heads, full.n_kv_heads, full.head_dim, full.rope_dim,
        full.rope_theta, full.window, full.rope_scaling, full.gate,
        full.qk_norm) == (32, 8, 64, 64, 1e6, None, None, False, True)
    assert el.kv_beside(full)
    assert (cut.n_experts, cut.n_held, cut.experts_first,
            cut.n_experts_per_tok, cut.moe_scale, cut.moe_router,
            cut.moe_norm_topk, cut.moe_norm_eps) == (
        32, 32, 0, 4, 1.0, "sigmoid", True, 1e-6)
    assert (cut.n_dense_layers, cut.d_ff, cut.moe_d_ff, cut.n_shared_experts,
            cut.tie_embeddings, cut.norm_eps, cut.vocab_size) == (
        2, 7168, 1792, 0, True, 1e-5, 65536)
    assert cut.held_param_count() == cut.param_count() == 3_928_728_256
    assert (cut.slot_state, cut.recurrent, cut.ring_window) == (
        "conv", True, None)
    p = ml.pattern_of(cut)
    assert (p.lead, p.period, p.n_periods, p.tail) == (
        ("conv", "conv"), ("gqa_full", "conv", "conv", "conv"), 2,
        ("gqa_full", "conv"))
    published = {**hf, "num_hidden_layers": 24,
                 "layer_types": hf["published"]["layer_types"],
                 "max_position_embeddings": 128000}
    whole = config_from_hf(published)
    assert whole.layer_kinds.count("conv") == 18
    assert [i for i, k in enumerate(whole.layer_kinds)
            if k == "gqa_full"] == [2, 6, 10, 14, 18, 21]
    assert whole.param_count() == 8_339_930_560
    assert config_from_hf({**published, "tie_word_embeddings": False}
                          ).param_count() == 8_474_148_288
    assert paged_unsupported(cut) is None
    # JSON and back (job specs carry the config over the wire)
    assert ModelConfig.from_json(
        json.loads(json.dumps(cut.to_json()))).latent == cut.latent


def test_one_property_says_what_a_slot_holds():
    """``slot_state`` answers for every family: the lightning layers'
    states, the window layers' rings, the conv layers' tails, or pages
    alone; ``recurrent`` and ``ring_window`` read it."""
    import test_laguna as tl
    import test_latent as tla
    import test_sala as ts

    got = {name: config_from_hf(hf).slot_state for name, hf in (
        ("lfm2", TINY), ("laguna", tl.TINY), ("sala", ts.TINY),
        ("dots3", tla.TINY), ("deepseek_v2", tla.TINY_DS))}
    assert got == {"lfm2": "conv", "laguna": "gqa_window",
                   "sala": "lightning", "dots3": None, "deepseek_v2": None}
    lag, sala = config_from_hf(tl.TINY), config_from_hf(ts.TINY)
    assert (lag.recurrent, lag.ring_window) == (False, 24)
    assert (sala.recurrent, sala.ring_window) == (True, None)
    assert ModelConfig().slot_state is None


# tlint: disable=TL006(read-only table)
REFUSED = (
    (dict(conv_bias=True), "conv_bias"),
    (dict(conv_L_cache=1), "conv_L_cache 1"),
    (dict(layer_types=["conv", "sliding_attention"] * 6), "layer_types"),
    (dict(layer_types=["conv"] * 5), "layer_types names 5 layers"),
    (dict(num_dense_layers=0), "num_dense_layers 0"),
    (dict(use_expert_bias=False), "use_expert_bias"),
    (dict(num_shared_experts=1), "shared expert"),
    (dict(num_key_value_heads=3), "query heads over 3 kv heads"),
)


@pytest.mark.parametrize("change,why", REFUSED, ids=[w for _, w in REFUSED])
def test_the_registry_refuses_what_it_does_not_build(change, why):
    with pytest.raises(ValueError, match=f"lfm2_moe: .*{why}"):
        config_from_hf({**TINY, **change})


# -- the cache ---------------------------------------------------------------


def test_a_slot_holds_a_tail_beside_pages_of_whole_lane_rows(tiny):
    """The conv layers' tails are ONE array ``[conv layers, slots, taps -
    1, width]``; a head of 64 lies beside its values in one pool 128 wide
    and there is no pool of values; the engine's snapshot pool holds a
    place a prefill chunk and two a slot."""
    cfg, params = tiny
    cache = LatentPagedCache.init(cfg, 3, page_size=4, max_len=256,
                                  prefill_chunk=8)
    assert cache.state.shape == (9, 3, 2, 64)
    assert cache.state.dtype == jnp.float32  # the activations'
    assert cache.k.shape == (3, 1 + 3 * 64, 2, 4, 128) and cache.v is None
    assert set(cache.pools()) == {"k"} and cache.wk is None
    ce = _engine(cfg, params, state_snapshot_stride=0)
    assert ce.snap_stride == 8  # wherever a chunk ends
    assert ce._snaps.shape == (256 // 8 + 2 * 3, 9, 2, 64)
    snap = ce.serving_snapshot()
    assert snap["conv_tail_bytes"] == 9 * 3 * 2 * 64 * 4
    assert snap["conv_pool_bytes"] == (
        snap["conv_tail_bytes"] + ce._snaps.size * 4)
    assert snap["state_pool_bytes"] == snap["window_pool_bytes"] == 0
    assert snap["lightning_state_bytes"] == 0
    assert snap["latent_pool_bytes"] == ce.cache.k.size * 4
    ce.check_page_conservation()
    ce.close()


# -- logits against the reference ---------------------------------------------


@pytest.mark.parametrize("lens", [(130, 111), (128, 116), (120, 96)],
                         ids=["inside-a-page", "on-a-page-edge",
                              "on-a-chunk-edge"])
def test_logits_through_pages_and_tail_are_the_references(tiny, seqs, want,
                                                          lens):
    """Prefill in chunks of 8, then 10 continuation steps, two slots at
    their own lengths (prompts that end inside a page, on a page edge, on
    a chunk edge) against the reference's full forward pass."""
    cfg, params = tiny
    got, cache = _teacher_forced(params, cfg, seqs, list(lens), 10)
    for s in range(2):
        np.testing.assert_allclose(
            got[s], want[s][lens[s] - 1:lens[s] + 10], rtol=2e-3, atol=2e-4)
    # the idle slot's tail was never written
    assert not np.asarray(cache.state[:, 2]).any()


def test_four_taps_are_the_references_too(seqs):
    """The operator is built for any kernel of two taps or more: four."""
    hf = {**TINY, "conv_L_cache": 4, "num_hidden_layers": 4}
    cfg = config_from_hf(hf, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(1))
    assert params["lead"][0]["attn"]["taps"].shape == (4, 64)
    want = ref.forward_logits(params, seqs[0][None], ref.arch_of(hf),
                              slice(0, T))[0]
    got, cache = _teacher_forced(params, cfg, seqs[:1], [61], 6)
    assert cache.state.shape == (3, 3, 3, 64)
    np.testing.assert_allclose(got[0], want[60:67], rtol=2e-3, atol=2e-4)


def test_the_engines_stream_is_the_references_greedy_chain(tiny, seqs):
    """Through ``ContinuousEngine``: the served greedy stream over a
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


def _probe_run(cfg, params, x, li, *, restore_at=None, C=8, n_dec=6):
    """What conv layer ``li`` adds over ``x`` ``[T, d]`` through the layer
    probe: slot 0 all the way, or slot 0 to ``restore_at``, a snapshot
    there, and slot 1 from its restore on."""
    ragged, decode = paged.make_layer_probe(cfg, "conv")
    cache = LatentPagedCache.init(cfg, 2, page_size=4, max_len=256,
                                  prefill_chunk=C)
    n_pp = cache.pages_per_slot
    cache = replace(cache, block_tables=(
        1 + jnp.arange(2 * n_pp, dtype=jnp.int32)).reshape(2, n_pp))
    lp = params["lead"][0]
    lp = {"ln1": lp["ln1"], "attn": lp["attn"]}
    n_pre = x.shape[0] - n_dec
    outs, slot, pos = [], 0, 0
    while pos < x.shape[0]:
        if pos == restore_at:
            st = cache.state
            snaps = jnp.zeros((2,) + st.shape[:1] + st.shape[2:], st.dtype)
            snaps = take_snapshot(snaps, st, jnp.int32(0), jnp.int32(1))
            cache = restore_snapshot(cache, snaps, jnp.int32(1), jnp.int32(1))
            cache = replace(cache, lengths=cache.lengths.at[1].set(pos))
            slot = 1
        if pos < n_pre:
            n = min(C, n_pre - pos)
            if restore_at is not None and pos < restore_at:
                n = min(n, restore_at - pos)
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
    """A conv layer over 150 positions: slot 0 to position 93 (inside a
    chunk), the tail there as a snapshot, restored into slot 1, which goes
    on through prefill chunks and 6 continuation steps: bit for bit what a
    slot that never left computes, and chunked at 8 what one chunk of 150
    computes."""
    cfg, params = tiny
    x = jnp.asarray(np.random.default_rng(3).normal(size=(T, 64)),
                    jnp.float32)
    straight, c0 = _probe_run(cfg, params, x, 1)
    moved, c1 = _probe_run(cfg, params, x, 1, restore_at=93)
    assert np.array_equal(straight, moved)
    assert np.array_equal(np.asarray(c0.state[1, 0]),
                          np.asarray(c1.state[1, 1]))
    whole, _ = _probe_run(cfg, params, x, 1, C=256, n_dec=0)
    np.testing.assert_allclose(straight, whole, rtol=1e-5, atol=1e-6)


def test_a_prefix_hit_restores_a_tail_snapshot_and_replays(tiny, seqs):
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
    assert ce.stats["conv_snapshots_taken"] == 3  # at 32, 64 and 96
    got, restored = [], []
    for tail in tails:
        before = dict(ce.stats)
        r = ce.submit(doc + tail, max_new_tokens=8, trace_id=f"c{len(got)}")
        ce.run_until_idle()
        got.append(list(r.tokens))
        assert ce.stats["conv_snapshots_restored"] == (
            before["conv_snapshots_restored"] + 1)
        assert ce.stats["conv_admissions"] == before["conv_admissions"] + 1
        restored.append(r.state_restored_at)
    assert got == want
    # the document's match ends at 100, a page past its snapshot; the first
    # prompt's own last page edge is 100, where the second restores
    assert restored == [96, 100]
    assert ce.stats["conv_rows_replayed"] == 4
    assert ce.stats["state_admissions"] == ce.stats["window_admissions"] == 0
    ce.check_page_conservation()
    snap = ce.serving_snapshot()
    assert snap["conv_snapshots_resident"] >= 4
    assert snap["state_snapshots_resident"] == 0
    from tensorlink_tpu.core.trace import get_tracer

    for i, at in enumerate(restored):
        adm = [s for s in get_tracer().collect(f"c{i}")
               if s["name"] == "admission"]
        assert adm and adm[0]["conv_restored_at"] == at
        assert "state_restored_at" not in adm[0]
    ce.close()


def test_the_default_stride_snapshots_wherever_a_chunk_ends(tiny, seqs):
    """A tail is small: without a stride of a test's own the engine keeps
    one wherever a prefill chunk ends, and a hit replays less than a
    chunk."""
    cfg, params = tiny
    ce = _engine(cfg, params, state_snapshot_stride=0)
    doc = [int(t) for t in seqs[1][:100]]
    ce.submit(doc, max_new_tokens=2)
    ce.run_until_idle()
    assert ce.stats["conv_snapshots_taken"] == 12  # 8, 16 .. 96
    r = ce.submit(doc[:70] + [1, 2, 3], max_new_tokens=2)
    ce.run_until_idle()
    assert r.state_restored_at == 64  # the match ends at 68
    assert ce.stats["conv_rows_replayed"] == 4
    ce.check_page_conservation()
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
    assert ce.stats["conv_snapshots_taken"] == 6
    assert ce.stats["conv_snapshots_skipped"] == 0
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
    assert ce.stats["conv_snapshots_restored"] == 1
    ce.check_page_conservation()
    ce.close()


def test_an_admission_makes_one_restore_and_no_other_device_call(tiny, seqs):
    """PR 43's count holds: an admission with a prefix hit calls the
    device once (the restore), one without a hit not at all (the ragged
    pass reads zeros before position 0)."""
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


def test_a_slot_reused_without_a_hit_starts_from_zeros(tiny, seqs):
    """No call zeroes a tail at an admission: a slot that held another
    stream serves a fresh prompt as a fresh engine does."""
    cfg, params = tiny
    a = [int(t) for t in seqs[0][:50]]
    b = [int(t) for t in seqs[1][:45]]
    fresh = _engine(cfg, params, max_slots=1, prefix_cache=False)
    r = fresh.submit(b, max_new_tokens=6)
    fresh.run_until_idle()
    want = list(r.tokens)
    fresh.close()
    ce = _engine(cfg, params, max_slots=1, prefix_cache=False)
    ce.submit(a, max_new_tokens=6)
    ce.run_until_idle()
    assert np.asarray(ce.cache.state[:, 0]).any()
    r = ce.submit(b, max_new_tokens=6)
    ce.run_until_idle()
    assert list(r.tokens) == want
    ce.close()


def test_refusals_name_the_tail(tiny):
    """What moves or shares pages by name refuses a model whose conv
    layers hold a tail, with the reason; drafting is served without
    drafts."""
    cfg, params = tiny
    for kw, why in [
        (dict(kv_quant="int8"), "pages and convolution tails are stored in "
                                "the model dtype"),
        (dict(host_tier_pages=8), "convolution tails in the host-RAM tier"),
        (dict(handoff_after_prefill=True), "do not hand off"),
        (dict(tensor_parallel=2),
         "convolution tails have no partition specs"),
    ]:
        with pytest.raises(PagedUnsupported, match=why):
            _engine(cfg, params, **kw)
    assert "MoE routing" in tp_serving_refusal(cfg, 2)
    ce = _engine(cfg, params, spec_decode=True)
    assert "does not draft" in ce.spec_refusal
    assert "tail" in ce.serving_snapshot()["spec_refusal"]
    ce.submit([1, 2, 3] * 10, max_new_tokens=4)
    ce.run_until_idle()
    with pytest.raises(PagedUnsupported, match="pages and convolution tails"):
        ce.export_slot(0)
    ce.close()
    assert "without a full layer" in paged_unsupported(
        cfg.with_(layer_kinds=("conv",) * 3))
    import test_laguna as tl

    lag = config_from_hf(tl.TINY)
    mixed = lag.with_(layer_kinds=lag.layer_kinds[:8] + ("conv",),
                      latent=lag.latent + (("conv", ShortConv(3, 64)),))
    assert "beside window layers" in paged_unsupported(mixed)
    assert "gqa_window, conv)" in paged_unsupported(
        cfg.with_(layer_kinds=("conv", "mamba")))


# -- the experts --------------------------------------------------------------


def test_an_expert_layer_without_a_shared_expert_has_no_empty_matmul(tiny):
    """``n_shared_experts`` 0: the parameter tree holds no shared expert
    and the lowered step holds no operand with a dimension of 0."""
    import re

    cfg, params = tiny
    assert "shared" not in params["periods"][0]["moe"]
    assert "lm_head" not in params  # the head is the embedding
    ce = _engine(cfg, params)
    text = ce.lower_step().as_text()
    ce.close()
    assert not re.search(r"tensor<(\d+x)*0x", text)


# -- the layer-matched numbers and the controls -------------------------------


@pytest.fixture(scope="module")
def sound(tiny, seqs):
    return ref.layer_gaps(tiny[1], seqs[0], ref.arch_of(TINY), 6)


def test_every_mechanism_has_a_layer_matched_number(sound):
    """Sound, in float32: each held number reads rounding, both sides pick
    the same experts on every row."""
    for name, _ in ref.HELD:
        assert sound[name] < 1e-4, (name, sound)
    by = sound["by_layer"]
    assert set(by["full"]) == set(by["rows"]) == {2, 6, 10}
    assert set(by["conv"]) == {0, 1, 3, 4, 5, 7, 8, 9, 11}
    assert set(by["picks"]) == set(by["experts"]) == set(range(2, 12))


CONTROLS = (
    ("edge_zeroed", 8, "conv"), ("taps_reversed", True, "conv"),
    ("snapshot_off", 1, "conv"), ("qk_norm", False, "full"),
    ("theta", 1e4, "full"), ("select_bias", False, "picks"),
    ("weights_biased", True, "route"), ("norm_topk", False, "route"),
    ("norm_topk", False, "experts"), ("int8_rows", True, "rows"),
)


@pytest.mark.parametrize("key,value,held", CONTROLS,
                         ids=[f"{k}-{v}-{h}" for k, v, h in CONTROLS])
def test_each_planted_fault_fails_its_limit(tiny, seqs, key, value, held):
    """The reference with one fault (the program sound) through
    ``layer_gaps``: the held number of that mechanism reads over its limit
    in ``reference/lfm2_moe.json``, the cell's own (the probes chunk at 8
    here, so that 150 positions cross chunk edges)."""
    tol = spec.load_tolerance({"correct": {"tolerance": "lfm2_moe"}})
    limit = tol[dict(ref.HELD)[held]]
    hf = {**TINY, "deployment": {"ml": {"prefill_chunk": 8,
                                        "cont_page_size": 4}}}
    bad = ref.layer_gaps(tiny[1], seqs[0], {**ref.arch_of(hf), key: value}, 6)
    assert bad[held] > limit, (key, bad)


# -- the other families and the planner ---------------------------------------


def test_lagunas_step_program_is_the_parents():
    """The grouped-query family's other model lowers to the parent's text:
    its pools, its walk and its q/k projections are untouched (the dense,
    latent and sparse families: tests/test_sala.py and
    tests/test_laguna.py hold theirs)."""
    import test_laguna as tl

    cfg = config_from_hf(tl.TINY, dtype=jnp.float32)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    ce = tl._engine(cfg, params)
    got = {w: hashlib.sha256(ce.lower_step(w, flat=False).as_text().encode()
                             ).hexdigest()[:16] for w in ce.block_widths}
    ce.close()
    assert got == {8: "0b009fec2d5ce770"}


def test_the_planner_counts_pages_tails_and_snapshots():
    """A slot's memory is pages x the 3 attention layers + a tail x the 9
    conv layers (+ the snapshot pool), not pages x 12 layers."""
    from tensorlink_tpu.parallel.planner import (
        AssignmentError, MemoryEstimate, WorkerCapacity, plan_sharding)

    cfg = config_from_hf(json.loads(CONFIG.read_text()))
    parts = MemoryEstimate.state_parts(cfg, 16, 16384)
    assert parts["pages"] == 3 * 16 * 16384 * 2048 == 1_610_612_736
    assert parts["states"] == 16 * 9 * 2 * 2048 * 2 == 16 * 73_728
    assert parts["snapshots"] == (128 + 32) * 73_728
    est = MemoryEstimate.build(cfg, batch=16, seq_len=16384, training=False)
    assert est.params == 3_928_728_256 * 2
    assert est.kv_cache == sum(parts.values())
    assert 10.0e9 < est.total < 11.5e9
    one = [WorkerCapacity(node_id="w0", hbm_bytes=15.75e9, n_devices=1)]
    assert len(plan_sharding(cfg, one, batch=16, seq_len=16384).stages) == 1
    small = [WorkerCapacity(node_id=f"w{i}", hbm_bytes=8e9, n_devices=1)
             for i in range(2)]
    with pytest.raises(AssignmentError) as e:
        plan_sharding(cfg, small, model_name="lfm2-8b-a1b-l12", batch=16,
                      seq_len=16384)
    msg = str(e.value)
    assert "weights 7.86 GB" in msg and "convolution tails 0.00 GB" in msg
    assert "tail snapshots 0.01 GB" in msg
