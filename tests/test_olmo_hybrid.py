"""``olmo_hybrid`` on the slot engine: gated delta-rule layers whose float32
state AND conv tail live a slot beside the pages (two arrays in one
snapshot), full attention layers with a query head a kv head, an RMSNorm
over the whole query and key projections and no positions, and the norm
AFTER each branch (models/base.py::GatedDelta, models/latent.py,
engine/latent.py, ops/gated_delta.py), against the plain reference
``benchmarks/reference/olmo_hybrid.py``. A tiny float32 preset of the
published shape: 8 layers (l l l f, twice), 4 heads of 16, 4 gated-delta
heads of 8 x 16, 4 taps."""

from __future__ import annotations

import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import spec
from benchmarks.reference import olmo_hybrid as ref
from tensorlink_tpu.engine import paged
from tensorlink_tpu.engine.continuous import (
    PagedUnsupported,
    paged_unsupported,
)
from tensorlink_tpu.engine.latent import LatentPagedCache
from tensorlink_tpu.engine.sala import (
    held, restore_snapshot, snapshot_pool, take_snapshot,
)
from tensorlink_tpu.models import latent as ml
from tensorlink_tpu.models.base import GatedDelta, GqaAttn, ModelConfig
from tensorlink_tpu.models.registry import config_from_hf
from tensorlink_tpu.models.transformer import init_params
from tensorlink_tpu.ops import attention as A, gated_delta as G

# tlint: disable=TL006(read-only table: every test copies it)
TINY = dict(
    model_type="olmo_hybrid", vocab_size=97, hidden_size=64,
    intermediate_size=128, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=4, hidden_act="silu", max_position_embeddings=256,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None},
)
CONFIG = (Path(__file__).parent.parent / "benchmarks" / "configs"
          / "olmo-hybrid-7b-l16.json")
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
# own lengths, one idle slot): the grouped-query family's other models'
from test_laguna import _engine, _teacher_forced  # noqa: E402


# -- the configuration -------------------------------------------------------


def test_catalog_config_gives_the_published_sizes():
    """``config_from_hf`` on the benchmark's file: layers 0-15 in the
    published order, 30 gated-delta heads of 96 x 192 behind 4 taps, 30
    query heads over 30 kv heads of 128 with a full-width q/k norm, no
    rotation and no gate, the norm after each branch, the head untied:
    4,100.8 M parameters; with the reduced keys put back, the published
    7.43 B."""
    hf = json.loads(CONFIG.read_text())
    cut = config_from_hf(hf)
    assert cut.layer_kinds == (("gated_delta",) * 3 + ("gqa_full",)) * 4
    gd, full = cut.latent_of("gated_delta"), cut.latent_of("gqa_full")
    assert gd == GatedDelta(n_heads=30, key_dim=96, value_dim=192, kernel=4,
                            neg_eigval=True)
    assert (gd.tail, gd.conv_width, gd.state_bytes, gd.rope_dim) == (
        3, 11520, 2_211_840, 0)
    assert isinstance(full, GqaAttn) and (
        full.n_heads, full.n_kv_heads, full.head_dim, full.rope_dim,
        full.window, full.gate, full.qk_norm, full.qk_norm_full) == (
        30, 30, 128, 0, None, False, False, True)
    assert (cut.norm_position, cut.n_experts, cut.d_ff, cut.tie_embeddings,
            cut.norm_eps, cut.vocab_size, cut.max_seq_len) == (
        "post", 0, 11008, False, 1e-6, 100352, 8192)
    # a gated-delta layer 88.75 M, a full layer 58.99 M, an MLP 126.81 M
    assert gd.param_count(3840) == 88_750_332
    assert full.param_count(3840) == 58_990_080
    assert cut.held_param_count() == cut.param_count() == 4_100_788_944
    assert (cut.slot_state, cut.slot_arrays, cut.recurrent,
            cut.ring_window) == ("gated_delta", ("state", "tail"), True, None)
    p = ml.pattern_of(cut)
    assert (p.lead, p.period, p.n_periods, p.tail) == (
        (), ("gated_delta",) * 3 + ("gqa_full",), 4, ())
    whole = config_from_hf({
        **hf, "num_hidden_layers": 32, "max_position_embeddings": 65536,
        "layer_types": hf["published"]["layer_types"]})
    assert whole.layer_kinds.count("gated_delta") == 24
    assert whole.param_count() == 7_430_870_688  # the published "7B"
    assert round(whole.param_count() / 1e6) == 7431
    assert paged_unsupported(cut) is None
    # JSON and back (job specs carry the config over the wire)
    back = ModelConfig.from_json(json.loads(json.dumps(cut.to_json())))
    assert back.latent == cut.latent and back.norm_position == "post"


def test_slot_arrays_names_what_every_family_holds():
    """``slot_arrays`` answers for every family beside ``slot_state``: one
    array for the lightning and the conv families, two here, none for a
    ring (its snapshot is pages) or pages alone."""
    import test_laguna as tl
    import test_lfm2 as tf
    import test_sala as ts

    got = {name: config_from_hf(hf).slot_arrays for name, hf in (
        ("olmo", TINY), ("lfm2", tf.TINY), ("laguna", tl.TINY),
        ("sala", ts.TINY))}
    assert got == {"olmo": ("state", "tail"), "lfm2": ("state",),
                   "laguna": (), "sala": ("state",)}
    assert ModelConfig().slot_arrays == ()
    assert not config_from_hf(tl.TINY).recurrent


# tlint: disable=TL006(read-only table)
REFUSED = (
    (dict(layer_types=["linear_attention", "sliding_attention"] * 4),
     "layer_types"),
    (dict(layer_types=["linear_attention"] * 5), "layer_types names 5 layers"),
    (dict(linear_num_value_heads=8),
     "linear_num_key_heads 4 != linear_num_value_heads 8"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(linear_conv_kernel_dim=1), "linear_conv_kernel_dim 1"),
    (dict(num_key_value_heads=3), "query heads over 3 kv heads"),
    (dict(hidden_act="gelu"), "hidden_act 'gelu'"),
)


@pytest.mark.parametrize("change,why", REFUSED, ids=[w for _, w in REFUSED])
def test_the_registry_refuses_what_it_does_not_build(change, why):
    with pytest.raises(ValueError, match=f"olmo_hybrid: .*{why}"):
        config_from_hf({**TINY, **change})


def test_a_theta_in_the_keys_rotates_the_full_layers():
    """``rope_theta`` null is no rotation; a number is rotate-half over the
    whole head (what the control of the reference plants)."""
    cfg = config_from_hf({**TINY, "rope_parameters": {"rope_theta": 5e5}})
    full = cfg.latent_of("gqa_full")
    assert (full.rope_dim, full.rope_theta) == (16, 5e5)
    assert config_from_hf(TINY).latent_of("gqa_full").rope_dim == 0


# -- the kernels against the sequential recurrence ----------------------------


def _rows(S, C, H, dk, dv, seed, beta_lo=0.0, common=0.0):
    """``common``: how much of one direction every key and query of a slot
    and head shares (0: keys near orthogonal; 10: ``k_t . k_s`` ~ 0.99)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    shared = common * jax.random.normal(ks[6], (S, 1, H, dk))
    q = jax.random.normal(ks[0], (S, C, H, dk)) + shared
    k = jax.random.normal(ks[1], (S, C, H, dk)) + shared
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (S, C, H, dv))
    g = -jnp.exp(jax.random.normal(ks[3], (S, C, H)) - 2)
    beta = beta_lo + (2 - beta_lo) * jax.nn.sigmoid(
        jax.random.normal(ks[4], (S, C, H)))
    state = jax.random.normal(ks[5], (2, S, dk, H * dv))
    return (q, k, v, g, beta), state


@pytest.mark.parametrize("beta_lo", [0.0, 1.0], ids=["beta-any", "beta>1"])
def test_the_chunk_kernel_is_the_sequential_recurrence(beta_lo):
    """Interpret mode: slots with 0, 1, 63, 64, 65 and 128 live rows, two
    of them fresh, through layer 1 of a two-layer state array: outputs and
    states are the scan's, a slot without rows keeps its state, the other
    layer is untouched; with every step size above 1 too (an eigenvalue of
    the transition below 0)."""
    rows, state = _rows(6, 128, 4, 16, 24, 0, beta_lo)
    if beta_lo:
        assert float(rows[4].min()) > 1.0
    nv = jnp.array([0, 1, 63, 64, 65, 128], jnp.int32)
    fresh = jnp.array([0, 0, 1, 0, 0, 1], jnp.int32)
    o_ref, s_ref = G.gated_delta_chunk_ref(*rows, state[1], nv, fresh > 0)
    o, st = G.gated_delta_chunk(*rows, state, nv, fresh, 1, interpret=True)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(st[1], s_ref, atol=2e-5)
    assert np.array_equal(st[0], state[0])
    assert np.array_equal(st[1, 0], state[1, 0])  # no rows: its own state
    assert not np.asarray(o[1, 1:]).any()  # rows past the live length


def test_keys_that_lie_close_together_do_not_blow_the_inverse_up():
    """Keys of one direction (``k_t . k_s`` ~ 0.99) under step sizes near 2:
    ``A``'s entries are near 1.9 and its powers reach 1e26 before they
    cancel, which made garbage of the Neumann product on the chip in the
    deeper layers (a stream that is not normed before the branch); the
    inverse by halves stays at rounding."""
    rows, state = _rows(2, 128, 2, 32, 64, 4, beta_lo=1.9, common=10.0)
    assert float(jnp.einsum("schd,sthd->scth", rows[1], rows[1]).mean()) > 0.95
    nv, fresh = jnp.array([128, 100], jnp.int32), jnp.array([0, 1], jnp.int32)
    o_ref, s_ref = G.gated_delta_chunk_ref(*rows, state[1], nv, fresh > 0)
    o, st = G.gated_delta_chunk(*rows, state, nv, fresh, 1, interpret=True)
    np.testing.assert_allclose(o, o_ref, atol=1e-3 * float(jnp.abs(o_ref).max()))
    np.testing.assert_allclose(
        st[1], s_ref, atol=1e-3 * float(jnp.abs(s_ref).max()))


def test_a_narrow_block_is_padded_to_whole_sub_chunks():
    rows, state = _rows(2, 24, 2, 8, 16, 1)
    nv, fresh = jnp.array([24, 5], jnp.int32), jnp.array([1, 0], jnp.int32)
    o_ref, s_ref = G.gated_delta_chunk_ref(*rows, state[0], nv, fresh > 0)
    o, st = G.gated_delta_chunk(*rows, state, nv, fresh, 0, interpret=True)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(st[0], s_ref, atol=2e-5)


@pytest.mark.parametrize("heads", [2, 10, 12], ids=lambda h: f"{h}-heads")
def test_the_step_kernel_is_the_sequential_recurrence(heads):
    """Interpret mode, one position a slot: heads in blocks of up to five
    pairs (12 heads: three blocks of two pairs), an inactive slot keeps
    its state."""
    rows, state = _rows(4, 1, heads, 8, 16, 2)
    args = tuple(a[:, 0] for a in rows)
    act = jnp.array([1, 0, 1, 1]) > 0
    o_ref, s_ref = G.gated_delta_step_ref(*args, state[1], act)
    o, st = G.gated_delta_step(*args, state, act, 1, interpret=True)
    np.testing.assert_allclose(o, o_ref, atol=1e-5)
    np.testing.assert_allclose(st[1], s_ref, atol=1e-5)
    assert np.array_equal(st[1, 1], state[1, 1])
    assert np.array_equal(st[0], state[0])


def test_the_chunk_form_goes_on_where_the_step_left_off():
    """A block of 70 rows is 70 steps: the two forms are one recurrence."""
    rows, state = _rows(1, 70, 2, 8, 16, 3)
    s = state[0]
    outs = []
    for t in range(70):
        o, s = G.gated_delta_step_ref(*(a[:, t] for a in rows), s,
                                      jnp.array([True]))
        outs.append(o)
    o, st = G.gated_delta_chunk(*rows, state, jnp.array([70]),
                                jnp.array([0]), 0, interpret=True)
    np.testing.assert_allclose(o[0], jnp.concatenate(outs), atol=2e-5)
    np.testing.assert_allclose(st[0], s, atol=2e-5)


def test_the_walk_at_one_query_head_a_kv_head_is_plain_attention():
    """30 kv heads, G = 1 (ROADMAP R7: the row rule no test had run): both
    entry points of the page walk in interpret mode against a plain causal
    softmax over each slot's contiguous keys and values."""
    H, hd, page, n_pp, S, C = 30, 128, 16, 4, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    P = 1 + S * n_pp
    kp = jax.random.normal(ks[0], (P, H, page, hd), jnp.float32)
    vp = jax.random.normal(ks[1], (P, H, page, hd), jnp.float32)
    bt = (1 + np.random.default_rng(1).permutation(S * n_pp)).reshape(
        S, n_pp).astype(np.int32)
    starts = jnp.array([24, 0, 45], jnp.int32)
    nv = jnp.array([16, 0, 1], jnp.int32)
    q = jax.random.normal(ks[2], (S, C, H, hd), jnp.float32)

    def plain(s, pos):  # query at ``pos`` of slot ``s`` over keys 0 .. pos
        k = kp[bt[s]].transpose(0, 2, 1, 3).reshape(-1, H, hd)[:pos + 1]
        v = vp[bt[s]].transpose(0, 2, 1, 3).reshape(-1, H, hd)[:pos + 1]
        return k, v

    got = A.ragged_paged_attention(q, kp, vp, jnp.asarray(bt), starts, nv,
                                   scale=hd**-0.5, interpret=True)
    for s in (0, 2):
        for j in range(int(nv[s])):
            k, v = plain(s, int(starts[s]) + j)
            w = jax.nn.softmax(
                jnp.einsum("hd,shd->hs", q[s, j], k) * hd**-0.5, -1)
            np.testing.assert_allclose(
                got[s, j], jnp.einsum("hs,shd->hd", w, v), atol=2e-4)
    assert not np.asarray(got[1]).any()
    lengths = jnp.array([40, 0, 46], jnp.int32)
    got1 = A.paged_attention(q[:, 0], kp, vp, jnp.asarray(bt), lengths,
                             scale=hd**-0.5, interpret=True)
    for s in (0, 2):
        k, v = plain(s, int(lengths[s]) - 1)
        w = jax.nn.softmax(
            jnp.einsum("hd,shd->hs", q[s, 0], k) * hd**-0.5, -1)
        np.testing.assert_allclose(
            got1[s], jnp.einsum("hs,shd->hd", w, v), atol=2e-4)


# -- the cache ---------------------------------------------------------------


def test_a_slot_holds_a_state_and_a_tail_beside_its_pages(tiny):
    """TWO arrays a slot: the float32 states ``[layers, slots, dk, H dv]``
    (whole lane rows at the published sizes: 30 x 192 = 45 x 128) and the
    tails ``[layers, slots, taps - 1, conv width]`` in the activations'
    dtype; the snapshot pool holds both under every place."""
    cfg, params = tiny
    cache = LatentPagedCache.init(cfg, 3, page_size=4, max_len=256,
                                  prefill_chunk=8)
    assert cache.state.shape == (6, 3, 8, 64)
    assert cache.state.dtype == jnp.float32
    assert cache.tail.shape == (6, 3, 3, 128)
    assert cache.k.shape == cache.v.shape == (2, 1 + 3 * 64, 4, 4, 16)
    assert set(cache.pools()) == {"k", "v"} and cache.wk is None
    assert set(held(cache)) == {"state", "tail"}
    bf = LatentPagedCache.init(config_from_hf(TINY), 3, page_size=4,
                               max_len=256)
    assert (bf.state.dtype, bf.tail.dtype) == (jnp.float32, jnp.bfloat16)
    pub = config_from_hf(json.loads(CONFIG.read_text())).latent_of(
        "gated_delta")
    assert pub.n_heads * pub.value_dim % 128 == 0  # nothing padded
    ce = _engine(cfg, params)
    assert set(ce._snaps) == {"state", "tail"}
    n = 256 // 32 + 2 * 3
    assert ce._snaps["state"].shape == (n, 6, 8, 64)
    assert ce._snaps["tail"].shape == (n, 6, 3, 128)
    snap = ce.serving_snapshot()
    states, tails = 6 * 3 * 8 * 64 * 4, 6 * 3 * 3 * 128 * 4
    assert snap["lightning_state_bytes"] == states
    assert snap["state_snapshot_bytes"] == n * (states + tails) // 3
    assert snap["state_pool_bytes"] == (
        states + tails + snap["state_snapshot_bytes"])
    assert snap["conv_pool_bytes"] == snap["window_pool_bytes"] == 0
    ce.check_page_conservation()
    whole = ce._snaps["tail"]
    ce._snaps["tail"] = whole[:-1]  # a pool that lost a place of ONE array
    with pytest.raises(AssertionError, match="state conservation"):
        ce.check_page_conservation()
    ce._snaps["tail"] = whole
    ce.close()


# -- logits against the reference ---------------------------------------------


@pytest.mark.parametrize("lens", [(66, 47), (64, 52), (56, 32)],
                         ids=["inside-a-page", "on-a-page-edge",
                              "on-a-chunk-edge"])
def test_logits_through_pages_state_and_tail_are_the_references(
        tiny, seqs, want, lens):
    """Prefill in chunks of 8, then 6 continuation steps, two slots at
    their own lengths (prompts that end inside a page, on a page edge, on
    a chunk edge) against the reference's full forward pass."""
    cfg, params = tiny
    got, cache = _teacher_forced(params, cfg, seqs, list(lens), 6)
    for s in range(2):
        np.testing.assert_allclose(
            got[s], want[s][lens[s] - 1:lens[s] + 6], rtol=2e-3, atol=4e-4)
    # the idle slot's state and tail were never written
    assert not np.asarray(cache.state[:, 2]).any()
    assert not np.asarray(cache.tail[:, 2]).any()


def test_the_engines_stream_is_the_references_greedy_chain(tiny, seqs):
    """Through ``ContinuousEngine``: the served greedy stream over a
    prompt of 120 tokens is the reference's argmax chain."""
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
    """What gated-delta layer ``li`` adds over ``x`` ``[T, d]`` through the
    layer probe: slot 0 all the way, or slot 0 to ``restore_at``, a
    snapshot of state and tail there, and slot 1 from its restore on."""
    ragged, decode = paged.make_layer_probe(cfg, "gated_delta")
    cache = LatentPagedCache.init(cfg, 2, page_size=4, max_len=256,
                                  prefill_chunk=C)
    n_pp = cache.pages_per_slot
    cache = replace(cache, block_tables=(
        1 + jnp.arange(2 * n_pp, dtype=jnp.int32)).reshape(2, n_pp))
    lp = jax.tree.map(lambda a: a[0], params["periods"][0])
    lp = {"ln1": lp["ln1"], "attn": lp["attn"]}
    n_pre = x.shape[0] - n_dec
    outs, slot, pos = [], 0, 0
    while pos < x.shape[0]:
        if pos == restore_at:
            snaps = take_snapshot(snapshot_pool(cache, 2), held(cache),
                                  jnp.int32(0), jnp.int32(1))
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
    """A gated-delta layer over 150 positions: slot 0 to position 93
    (inside a chunk), state AND tail there as one snapshot, restored into
    slot 1, which goes on through prefill chunks and 6 continuation steps:
    bit for bit what a slot that never left computes, and chunked at 8
    what one chunk of 150 computes."""
    cfg, params = tiny
    x = jnp.asarray(np.random.default_rng(3).normal(size=(T, 64)),
                    jnp.float32)
    straight, c0 = _probe_run(cfg, params, x, 1)
    moved, c1 = _probe_run(cfg, params, x, 1, restore_at=93)
    assert np.array_equal(straight, moved)
    for name in ("state", "tail"):
        assert np.array_equal(np.asarray(getattr(c0, name)[1, 0]),
                              np.asarray(getattr(c1, name)[1, 1]))
    whole, _ = _probe_run(cfg, params, x, 1, C=256, n_dec=0)
    np.testing.assert_allclose(straight, whole, rtol=1e-4, atol=1e-5)


def test_a_prefix_hit_restores_both_arrays_and_replays(tiny, seqs):
    """A document made resident, then two prompts that share it: each
    admission restores ONE snapshot (state and tail together, the nearest
    at or under its match), prefills the rest again, and streams what an
    engine without a prefix cache streams; the counters are the lightning
    family's and conservation holds with snapshots in the trie."""
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
    assert ce.stats["state_snapshots_taken"] == 3  # at 32, 64 and 96
    got, restored = [], []
    for tail in tails:
        before = dict(ce.stats)
        r = ce.submit(doc + tail, max_new_tokens=8, trace_id=f"o{len(got)}")
        ce.run_until_idle()
        got.append(list(r.tokens))
        assert ce.stats["state_snapshots_restored"] == (
            before["state_snapshots_restored"] + 1)
        assert ce.stats["state_admissions"] == before["state_admissions"] + 1
        restored.append(r.state_restored_at)
    assert got == want
    assert restored == [96, 100]
    assert ce.stats["state_rows_replayed"] == 4
    assert ce.stats["conv_admissions"] == ce.stats["window_admissions"] == 0
    ce.check_page_conservation()
    snap = ce.serving_snapshot()
    assert snap["state_snapshots_resident"] >= 4
    assert snap["conv_snapshots_resident"] == 0
    from tensorlink_tpu.core.trace import get_tracer

    for i, at in enumerate(restored):
        adm = [s for s in get_tracer().collect(f"o{i}")
               if s["name"] == "admission"]
        assert adm and adm[0]["state_restored_at"] == at
    ce.close()


def test_a_full_snapshot_pool_drops_the_node_matched_longest_ago(tiny, seqs):
    """Four places and prompts that take more: a snapshot point with no
    place free drops the snapshot of the node matched longest ago (the
    node stays), conservation counts every place of both arrays."""
    cfg, params = tiny
    ce = _engine(cfg, params, state_snapshots=4)
    docs = [[int(t) for t in seqs[i][:70]] for i in range(2)]
    for d in docs:
        ce.submit(d, max_new_tokens=2)
        ce.run_until_idle()
        ce.check_page_conservation()
    assert ce.stats["state_snapshots_taken"] == 6  # 32, 64, 68 each
    assert ce.stats["state_snapshots_skipped"] == 0
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
    assert ce.stats["state_snapshots_restored"] == 1
    ce.check_page_conservation()
    ce.close()


def test_an_admission_makes_one_restore_and_no_other_device_call(tiny, seqs):
    """PR 43's count holds with two arrays: an admission with a prefix hit
    calls the device ONCE (one restore of both), one without a hit not at
    all (the ragged pass starts a sequence's first block from zeros)."""
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
    """No call zeroes a state or a tail at an admission: a slot that held
    another stream serves a fresh prompt as a fresh engine does."""
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
    assert np.asarray(ce.cache.tail[:, 0]).any()
    r = ce.submit(b, max_new_tokens=6)
    ce.run_until_idle()
    assert list(r.tokens) == want
    ce.close()


def test_refusals_name_the_state(tiny):
    """What moves or shares pages by name refuses a model whose layers
    hold a state and a tail, with the reason; drafting is served without
    drafts."""
    cfg, params = tiny
    for kw, why in [
        (dict(kv_quant="int8"), "pages, recurrent states and convolution "
                                "tails are stored in the model dtype"),
        (dict(host_tier_pages=8), "convolution tails in the host-RAM tier"),
        (dict(handoff_after_prefill=True), "do not hand off"),
        (dict(tensor_parallel=2),
         "recurrent states and convolution tails have no partition specs"),
    ]:
        with pytest.raises(PagedUnsupported, match=why):
            _engine(cfg, params, **kw)
    ce = _engine(cfg, params, spec_decode=True)
    assert "gated delta-rule layers does not draft" in ce.spec_refusal
    assert "state" in ce.serving_snapshot()["spec_refusal"]
    ce.submit([1, 2, 3] * 10, max_new_tokens=4)
    ce.run_until_idle()
    with pytest.raises(PagedUnsupported,
                       match="pages, recurrent states and convolution tails"):
        ce.export_slot(0)
    ce.close()
    gd = cfg.latent_of("gated_delta")
    assert "without a full layer" in paged_unsupported(
        cfg.with_(layer_kinds=("gated_delta",) * 3))
    import test_lfm2 as tf

    lfm = config_from_hf(tf.TINY)
    mixed = lfm.with_(layer_kinds=lfm.layer_kinds[:8] + ("gated_delta",),
                      latent=lfm.latent + (("gated_delta", gd),))
    assert "one of them" in paged_unsupported(mixed)
    odd = cfg.with_(latent=(("gated_delta", replace(gd, n_heads=3)),
                            cfg.latent[1]))
    assert "odd number of gated-delta heads" in paged_unsupported(odd)
    assert "lightning, gated_delta, gqa_full" in paged_unsupported(
        cfg.with_(layer_kinds=("gated_delta", "mamba")))


# -- the layer-matched numbers and the controls -------------------------------


@pytest.fixture(scope="module")
def sound(tiny, seqs):
    return ref.layer_gaps(tiny[1], seqs[0], ref.arch_of(TINY), 6)


def test_every_mechanism_has_a_layer_matched_number(sound):
    """Sound, in float32: each held number reads rounding."""
    for name, _ in ref.HELD:
        assert sound[name] < 1e-4, (name, sound)
    by = sound["by_layer"]
    assert set(by["full"]) == set(by["rows"]) == {3, 7}
    assert set(by["delta"]) == set(by["state"]) == {0, 1, 2, 4, 5, 6}
    assert set(by["state0"]) == {0}  # the first gated-delta layer alone


CONTROLS = (
    ("beta_scale", 1.0, ("delta",)), ("decay", False, ("delta", "state")),
    ("delta", False, ("delta",)), ("l2norm", False, ("delta",)),
    ("taps_reversed", True, ("delta",)), ("conv_silu", False, ("delta",)),
    ("edge_zeroed", 8, ("delta",)), ("snapshot_off", 1, ("delta",)),
    ("state_bf16", True, ("state0",)), ("out_gate", False, ("delta",)),
    ("norm_after", False, ("delta", "full")),
    ("qk_norm_full", False, ("full",)), ("theta", 5e5, ("full", "rows")),
    ("int8_rows", True, ("rows",)),
)


@pytest.mark.parametrize("key,value,held_by", CONTROLS,
                         ids=[f"{k}-{v}" for k, v, _ in CONTROLS])
def test_each_planted_fault_fails_its_limit(tiny, seqs, key, value, held_by):
    """The reference with one fault (the program sound) through
    ``layer_gaps``: the held numbers of that mechanism read over their
    limits in ``reference/olmo_hybrid.json``, the cell's own (the probes
    chunk at 8 here, so that 150 positions cross chunk edges)."""
    tol = spec.load_tolerance({"correct": {"tolerance": "olmo_hybrid"}})
    hf = {**TINY, "deployment": {"ml": {"prefill_chunk": 8,
                                        "cont_page_size": 4}}}
    bad = ref.layer_gaps(tiny[1], seqs[0], {**ref.arch_of(hf), key: value}, 6)
    for name in held_by:
        limit = tol[dict(ref.HELD)[name]]
        if key == "state_bf16":
            # the precision below: in float32 the served side adds no
            # rounding of its own, so this reads the bf16 state's ALONE,
            # where the chip's reading holds the served side's 0.00242
            # (reference/olmo_hybrid.json) beside it; two roundings add in
            # quadrature, so the part of the limit that is the control's
            limit = (limit**2 - 0.00242**2) ** 0.5
        assert bad[name] > limit, (key, name, bad)


# -- the other families and the planner ---------------------------------------


def test_lfm2s_step_programs_are_the_parents():
    """The family this one shares ``_conv_pass``, the ``state`` field and
    the snapshot entry points with lowers to the parent's text, the wide
    program and the flat rung (the dense, latent, sparse and Laguna
    families: tests/test_sala.py, tests/test_laguna.py and
    tests/test_lfm2.py hold theirs)."""
    import hashlib

    import test_lfm2 as tf

    cfg = config_from_hf(tf.TINY, dtype=jnp.float32)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    ce = _engine(cfg, params)
    got = {flat: hashlib.sha256(
        ce.lower_step(8, flat=flat).as_text().encode()).hexdigest()[:16]
        for flat in (False, True)}
    ce.close()
    assert got == {False: "7236d7e0f53e9a7a", True: "d69c17079a4ca431"}


def test_the_planner_counts_pages_states_tails_and_snapshots():
    """A slot's memory is pages x the 4 full layers + (a state + a tail) x
    the 12 gated-delta layers (+ the snapshot pool), not pages x 16
    layers."""
    from tensorlink_tpu.parallel.planner import (
        AssignmentError, MemoryEstimate, WorkerCapacity, plan_sharding)

    cfg = config_from_hf(json.loads(CONFIG.read_text()))
    parts = MemoryEstimate.state_parts(cfg, 8, 8192)
    assert parts["pages"] == 4 * 8 * 8192 * 15_360 == 4_026_531_840
    one = 12 * (2_211_840 + 69_120)  # a slot's, or a snapshot: 27.4 MB
    assert parts["states"] == 8 * one == 218_972_160
    assert parts["tails"] == 8 * 12 * 69_120
    assert parts["snapshots"] == (8192 // 1024 + 16) * one  # 24 places
    est = MemoryEstimate.build(cfg, batch=8, seq_len=8192, training=False)
    assert est.params == 4_100_788_944 * 2
    assert est.kv_cache == (
        parts["pages"] + parts["states"] + parts["snapshots"])
    assert 13.5e9 < est.total < 15.75e9
    one_chip = [WorkerCapacity(node_id="w0", hbm_bytes=15.75e9, n_devices=1)]
    assert len(plan_sharding(cfg, one_chip, batch=8, seq_len=8192).stages) == 1
    small = [WorkerCapacity(node_id=f"w{i}", hbm_bytes=8e9, n_devices=1)
             for i in range(2)]
    with pytest.raises(AssignmentError) as e:
        plan_sharding(cfg, small, model_name="olmo-hybrid-7b-l16", batch=8,
                      seq_len=8192)
    msg = str(e.value)
    assert "weights 8.20 GB" in msg
    assert "recurrent states and convolution tails 0.22 GB" in msg
    assert "state snapshots 0.66 GB" in msg
