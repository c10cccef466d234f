"""``deepseek_v2`` on the slot engine: a pattern of ONE kind (full latent
attention, nothing selected) through the page walk in both passes, YaRN
positions with the ``mscale`` softmax scale, group-limited routing with one
routing group a chip, against the plain reference
``benchmarks/reference/deepseek_v2.py`` (tests/test_latent.py::TINY_DS: 3
layers, 4 heads, 16 experts in 4 groups of which group 1 is held)."""

from __future__ import annotations

import functools
import json
import math
import re
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import deepseek_v2 as ref
from tensorlink_tpu.engine import latent as el
from tensorlink_tpu.engine.continuous import paged_unsupported
from tensorlink_tpu.engine.latent import LatentPagedCache
from tensorlink_tpu.models import latent as ml
from tensorlink_tpu.models.registry import config_from_hf
from tensorlink_tpu.models.transformer import (
    init_params,
    rope_tables,
    yarn_inv_freq,
)
from tensorlink_tpu.ops import attention

from test_latent import TINY_DS, _engine, _teacher_forced

CONFIG = (Path(__file__).parent.parent / "benchmarks" / "configs"
          / "deepseek-v2-ep8.json")
# tlint: disable=TL006(read-only table: merged into a copy)
REDUCED = {"num_hidden_layers": 60, "n_routed_experts": 160,
           "vocab_size": 102400, "max_position_embeddings": 163840}


@pytest.fixture(scope="module")
def tiny():
    cfg = config_from_hf(TINY_DS, dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture
def interpreted(monkeypatch):
    """The walk's kernels interpreted, where the layers picked them up."""
    for name in ("paged_attention", "ragged_paged_attention"):
        monkeypatch.setattr(el, name, functools.partial(
            getattr(attention, name), interpret=True))


def test_catalog_config_gives_the_published_sizes():
    """``config_from_hf`` on the catalog row's ``config`` (the benchmark
    file's keys with its four reduced keys put back): 235.7 B parameters,
    one leading dense layer and 59 periods of one full layer; the
    benchmark's cut holds 3,814.6 M of them and its router stays 160 wide."""
    hf = json.loads(CONFIG.read_text())
    cut = config_from_hf(hf)
    assert cut.held_param_count() == 3_814_568_960
    assert (cut.n_experts, cut.n_held, cut.experts_first) == (160, 20, 0)
    assert (cut.moe_n_group, cut.moe_topk_group) == (8, 3)
    assert cut.layer_kinds == ("full",) * 6
    published = {k: v for k, v in hf.items()
                 if k not in ("published", "expert_group")} | REDUCED
    whole = config_from_hf(published)
    assert round(whole.param_count() / 1e9, 1) == 235.7
    pat = ml.pattern_of(whole)
    assert (pat.lead, pat.period, pat.n_periods, pat.tail) == (
        ("full",), ("full",), 59, ())
    la = whole.latent_of("full")
    assert (la.n_heads, la.q_rank, la.kv_rank, la.pool_dim) == (
        128, 1536, 512, 640)
    assert not la.gate and la.index_heads == 0 and la.window is None
    assert la.softmax_scale == pytest.approx(192**-0.5 * 1.2608**2, rel=1e-4)


@pytest.mark.parametrize("positions", [[0, 1, 7, 100, 4095],
                                       [4096, 12287, 12800, 16383]],
                         ids=["below-original", "above-original"])
def test_yarn_tables_match_the_equations_in_float64(positions):
    """cos / sin of the published ``rope_scaling`` against a float64 numpy
    transcription of the equations (ISSUE 34), at positions below and above
    ``original_max_position_embeddings``: low = 10, high = 23, the fast
    dimensions untouched, the slow ones divided by 40, amplitude 1."""
    hf = json.loads(CONFIG.read_text())
    la = config_from_hf(hf).latent_of("full")
    d, base, factor, orig = 64, 1e4, 40.0, 4096.0
    j = np.arange(d // 2, dtype=np.float64)
    f = base ** (-2 * j / d)

    def corr(r):
        return d * math.log(orig / (2 * math.pi * r)) / (2 * math.log(base))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((j - low) / (high - low), 0, 1)
    inv = f / factor * ramp + f * (1 - ramp)
    got_inv, amp = yarn_inv_freq(d, base, la.rope_scaling)
    np.testing.assert_allclose(got_inv, inv, rtol=1e-6)
    assert amp == 1.0 and (inv[:11] == f[:11]).all()
    np.testing.assert_allclose(inv[23:], f[23:] / 40, rtol=1e-12)
    pos = np.asarray(positions, np.float64)
    ang = pos[:, None] * inv[None, :]
    cos, sin = rope_tables(jnp.asarray([positions]), d, base, la.rope_scaling)
    # float32 angles of up to 16,383 radians: an ulp of the angle is 1e-3
    np.testing.assert_allclose(
        np.asarray(cos[0]), np.cos(np.concatenate([ang, ang], -1)), atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(sin[0]), np.sin(np.concatenate([ang, ang], -1)), atol=2e-3)
    # and it is not the plain table where the scaling acts
    plain = rope_tables(jnp.asarray([positions]), d, base)[0]
    assert np.abs(np.asarray(plain - cos)).max() > 0.5 or max(positions) < 8


def test_route_matches_the_reference_router(tiny):
    """Softmax over all 16 published experts in float32, the 2 best of 4
    groups, 3 picks inside them, their own scores x 16 as weights (ties
    absent with seeded weights)."""
    cfg, params = tiny
    mp = jax.tree.map(lambda a: a[0], params["periods"][0]["moe"])
    assert "bias" not in mp
    h = jnp.asarray(np.random.default_rng(3).normal(size=(40, 64)), jnp.float32)
    arch = ref.arch_of(TINY_DS)
    _, want_i, want_w = ref._route(h, jnp.ones(64), mp["router"],
                                   arch=ref._static(arch))
    a = ref._rmsnorm(h, jnp.ones(64), arch["eps"])
    topi, topw = ml.route(a, mp, cfg)
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(topw), np.asarray(want_w), rtol=1e-5)
    # unnormalised and scaled: the weights do not sum to 16
    assert np.abs(np.asarray(topw).sum(-1) - 16).min() > 1e-3
    # every pick lies inside the row's two kept groups
    groups = np.asarray(topi) // 4
    assert all(len(set(g)) <= 2 for g in groups)
    # without the limit some row picks from three groups
    free_i, _ = ml.route(a, mp, cfg.with_(moe_n_group=0, moe_topk_group=0))
    assert any(len(set(g)) == 3 for g in np.asarray(free_i) // 4)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_served_logits_match_the_reference(tiny, kernel, request):
    """Chunked prefill then decode through the pages, two slots at
    different offsets, against the reference's full forward, by the XLA
    fallback and by the walk's kernel (interpreted): positions past the
    original length (8), the group limit and the expert share all cut, in
    float32 to rounding. A fault in the reference reads far over the
    tolerance: the tokens' logits tell the m^2 scale, the YaRN frequencies,
    the group limit and the second shared expert."""
    if kernel:
        request.getfixturevalue("interpreted")
    cfg, params = tiny
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 64, size=(2, 27)).astype(np.int32)
    lens = [21, 16]
    got, cache = _teacher_forced(params, cfg, toks, lens, 5, kernel=kernel)
    arch = ref.arch_of(TINY_DS)
    for s, L in enumerate(lens):
        want = ref.forward_logits(
            params, toks[s:s + 1, :L + 5], arch, slice(L - 1, L + 5))[0]
        assert np.abs(got[s] - want).max() < 2e-4, s
    if not kernel:
        for fault in ({"mscale": False}, {"yarn": False},
                      {"group_limit": False}, {"shared_halved": True}):
            bad = ref.forward_logits(params, toks[:1, :lens[0] + 5],
                                     {**arch, **fault},
                                     slice(lens[0] - 1, lens[0] + 5))[0]
            assert np.abs(got[0] - bad).max() > 5e-3, fault
    st = dict(zip(ml.STEP_STATS, np.asarray(cache.stats)))
    assert st["sparse_positions_kept"] == st["sparse_positions_scored"] > 0
    assert 0 < st["moe_rows_routed_local"] <= st["moe_rows_computed"]
    assert 0 < st["moe_rows_in_group"] < st["moe_rows_valid"]
    assert cache.index is None and cache.slide is None


def test_absorbed_equals_materialised_with_the_scaled_softmax(tiny):
    """The two forms of latent attention are the same sums in another
    order, with YaRN's m^2 in the softmax scale of both."""
    cfg, params = tiny
    la = cfg.latent_of("full")
    assert la.temperature == pytest.approx((0.1 * 0.707 * math.log(40) + 1)**2)
    ap = jax.tree.map(lambda a: a[0], params["periods"][0]["attn"])
    rng = np.random.default_rng(4)
    R, K = 5, 12
    q_n = jnp.asarray(rng.normal(size=(R, la.n_heads, la.nope_dim)), jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(R, la.n_heads, la.rope_dim)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(K, la.pool_dim)), jnp.float32)
    mask = jnp.asarray(rng.random((R, K)) < 0.7).at[:, 0].set(True)
    a = ml.attend_absorbed(
        q_n, q_r, jnp.broadcast_to(rows, (R, K, la.pool_dim)), mask, ap, la)
    m = ml.attend_materialised(q_n[None], q_r[None], rows[None], mask[None],
                               ap, la)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(m), atol=2e-5)
    # the scale is in both: at m = 1 the same inputs read otherwise
    plain = ml.attend_absorbed(
        q_n, q_r, jnp.broadcast_to(rows, (R, K, la.pool_dim)), mask, ap,
        replace(la, rope_scaling=None))
    assert np.abs(np.asarray(a - plain)).max() > 1e-3


def test_one_layer_through_the_pages_on_the_reference_s_input(tiny,
                                                              interpreted):
    """The layer-matched numbers the benchmark holds, in float32 at a tiny
    size with the kernel interpreted: sound reads rounding; rows in int8,
    plain rotary frequencies and a softmax scale without m^2 each read
    orders of magnitude over it, by a number of its own."""
    cfg, params = tiny
    toks = np.random.default_rng(7).integers(0, 64, size=30)
    hf = {**TINY_DS, "deployment": {"ml": {"prefill_chunk": 8,
                                           "cont_page_size": 4}}}
    sound = ref.layer_gaps(params, toks, ref.arch_of(hf), n_dec=4)
    assert sound["rows"] < 1e-5 and sound["full"] < 1e-4, sound
    assert sound["route"] == 0.0 and sound["experts"] < 1e-5, sound
    int8 = ref.layer_gaps(params, toks, {**ref.arch_of(hf), "int8_rows": True},
                          n_dec=4)
    assert int8["rows"] > 2e-3
    for fault in ({"yarn": False}, {"mscale": False}):
        bad = ref.layer_gaps(params, toks, {**ref.arch_of(hf), **fault},
                             n_dec=4)
        assert bad["full"] > 100 * sound["full"], (fault, bad)
    # the expert layer: the share of rows picked otherwise, and what the
    # experts add where the picks agree
    loose = ref.layer_gaps(
        params, toks, {**ref.arch_of(hf), "group_limit": False}, n_dec=4)
    assert loose["route"] > 0.2 and loose["experts"] < 1e-4, loose
    halved = ref.layer_gaps(
        params, toks, {**ref.arch_of(hf), "shared_halved": True}, n_dec=4)
    assert halved["route"] == 0.0 and halved["experts"] > 0.1, halved


def test_engine_serves_it_and_counts_what_the_walk_reads(tiny):
    """Through ``ContinuousEngine``: each greedy stream is the reference's
    own argmax chain, no pool exists for a selector or a sliding kind, the
    gauge follows, pages are conserved, and the walk's counters follow the
    contexts, not the capacity."""
    cfg, params = tiny
    ce = _engine(cfg, params)
    assert ce.cache.index is None and ce.cache.slide is None
    L, P, _, page, W = ce.cache.full.shape
    assert (L, W) == (3, 128)
    assert ce.cache.pool_bytes == L * P * page * W * 4
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 64, size=n).tolist() for n in (22, 9)]
    arch = ref.arch_of(TINY_DS)
    reqs = [ce.submit(p, max_new_tokens=5) for p in prompts]
    ce.run_until_idle()
    for p, r in zip(prompts, reqs):
        seq = list(p)
        for _ in range(5):
            lg = ref.forward_logits(params, np.asarray([seq]), arch,
                                    slice(len(seq) - 1, len(seq)))
            seq.append(int(lg[0, 0].argmax()))
        assert r.tokens == seq[len(p):]
    st = ce.stats
    assert 0 < st["latent_rows_read"] < 0.5 * st["latent_rows_capacity"]
    assert st["window_pages_walked"] == 0 == st["window_pages_context"]
    assert 0 < st["moe_rows_in_group"] < st["moe_rows_valid"]
    assert ce.serving_snapshot()["latent_pool_bytes"] == ce.cache.pool_bytes
    ce.check_page_conservation()
    ce.close()


def test_no_pool_for_what_the_model_has_not():
    """``LatentPagedCache.init`` at the benchmark's sizes, as shapes: one
    pool of 640-wide rows, 2.01 GB; nothing one value wide that the chip
    would pad to whole lanes."""
    hf = json.loads(CONFIG.read_text())
    cfg = config_from_hf(hf)
    cache = jax.eval_shape(lambda: LatentPagedCache.init(
        cfg, 16, page_size=16, max_len=16384))
    assert cache.index is None and cache.slide is None
    assert cache.full.shape == (6, 16385, 1, 16, 640)
    assert cache.pool_bytes == 6 * 16385 * 16 * 640 * 2
    assert 2.0e9 < cache.pool_bytes < 2.02e9
    assert set(cache.pools()) == {"full"}


def test_refusals_state_their_reason():
    """What is not built says so: at the registry (another scoring
    function, another top-k method, a query without a latent, another rope
    scaling) and at the slot engine (groups that do not divide)."""
    for key, value, why in (
        ("scoring_func", "sigmoid", "scoring_func"),
        ("topk_method", "noaux_tc", "topk_method"),
        ("q_lora_rank", None, "q_lora_rank"),
        ("rope_scaling", {"type": "linear", "factor": 2}, "rope_scaling"),
        ("moe_layer_freq", 2, "moe_layer_freq"),
    ):
        with pytest.raises(ValueError, match=why):
            config_from_hf({**TINY_DS, key: value})
    cfg = config_from_hf(TINY_DS)
    assert paged_unsupported(cfg) is None
    assert "groups" in paged_unsupported(cfg.with_(moe_n_group=3))
    assert "window" in paged_unsupported(cfg.with_(latent=(
        ("full", replace(cfg.latent_of("full"), window=4)),)))
    greedy = config_from_hf({**TINY_DS, "topk_method": "greedy"})
    assert (greedy.moe_n_group, greedy.moe_topk_group) == (0, 0)


def test_the_step_keeps_its_three_phase_loops_and_names_its_walk(tiny):
    """A pattern of one kind is the same program shape a trace is read by:
    three top-level loops in phase order, the full layers' attention under
    ``tlink.latent_attn`` in both passes, the experts under ``tlink.moe``,
    no selector and no window scope; the kernel's name is what the
    benchmark's metric files match."""
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
    paths = " ".join(set(re.findall(r'op_name="([^"]*)"', compiled)))
    for phase in ("tlink.ragged_pass", "tlink.decode_cont"):
        assert re.search(rf"{phase}/while/body/[^ ]*{ml.LATENT_ATTN}", paths)
        assert re.search(rf"{phase}/while/body/[^ ]*{ml.MOE}", paths), phase
    assert ml.INDEX_SELECT not in paths and ml.WINDOW_ATTN not in paths
    assert el.FULL_KERNEL == "latent_full_attention"
    spec = json.loads((CONFIG.parent.parent / "layer_metrics"
                       / "latent_full_attention_share.json").read_text())
    assert all(re.search(p, el.FULL_KERNEL) for p in spec["patterns"])
    ce.close()


def test_the_planner_counts_a_block_of_the_walk_not_a_context_of_scores():
    """``MemoryEstimate`` for a full layer without a selector: the held
    experts, one 640-wide row a layer and position, and beside the residual
    stream one prefill block's absorbed queries and walk output (``[chunk,
    heads, 640 + 512]``), not ``[rows, heads, context]`` scores."""
    from tensorlink_tpu.parallel.planner import PREFILL_BLOCK, MemoryEstimate

    cfg = config_from_hf(json.loads(CONFIG.read_text()))
    est = MemoryEstimate.build(cfg, batch=16, seq_len=16384, training=False)
    assert est.params == 2 * cfg.held_param_count()
    assert est.kv_cache == 6 * 640 * 2 * 16 * 16384
    dense = 16 * 16384 * (8 * 5120 + 2 * 12288) * 2
    block = 16 * PREFILL_BLOCK * 128 * (640 + 512) * 2
    assert est.activations == dense + block
    one = MemoryEstimate.build(cfg, batch=1, seq_len=16384, training=False)
    assert one.total < 16e9  # what hosting plans with: one chip takes it
