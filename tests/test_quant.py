"""Weight-only int8 serving (models/quant.py) — a capability the reference
lacks entirely: halves decode's HBM parameter traffic (the B=1 roofline
bound, BASELINE.md) at bounded accuracy cost."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.sampling import SamplingParams
from tensorlink_tpu.models import ModelConfig, forward, init_params
from tensorlink_tpu.models.quant import (
    QTensor, dequantize, matmul, quantize_params, quantize_tensor,
    quantized_bytes,
)


def tiny_cfg(**kw):
    return ModelConfig(
        family="llama", vocab_size=512, d_model=64, n_layers=3, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, max_seq_len=128,
        dtype=jnp.float32, tie_embeddings=False, **kw,
    )


def test_quantize_roundtrip_error():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    qt = quantize_tensor(w)
    assert qt.q.dtype == jnp.int8
    assert qt.scale.shape == (1, 128)
    err = np.abs(np.asarray(dequantize(qt, jnp.float32)) - np.asarray(w))
    # symmetric int8: error bounded by scale/2 per channel
    assert float(err.max()) <= float(np.asarray(qt.scale).max()) * 0.51


def test_stacked_weights_keep_per_layer_scales():
    k = jax.random.PRNGKey(1)
    w = jax.random.normal(k, (3, 32, 64), jnp.float32)
    w = w * jnp.asarray([1.0, 10.0, 0.1])[:, None, None]  # layer magnitudes
    qt = quantize_tensor(w)
    assert qt.scale.shape == (3, 1, 64)
    for layer in range(3):
        got = np.asarray(dequantize(QTensor(qt.q[layer], qt.scale[layer]),
                                    jnp.float32))
        np.testing.assert_allclose(got, np.asarray(w[layer]), atol=0.08
                                   * float(np.abs(w[layer]).max()))


def test_matmul_matches_dequantized():
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(k1, (4, 64), jnp.float32)
    w = jax.random.normal(k2, (64, 96), jnp.float32)
    qt = quantize_tensor(w)
    np.testing.assert_allclose(
        np.asarray(matmul(x, qt)),
        np.asarray(x @ dequantize(qt, jnp.float32)),
        rtol=1e-5, atol=1e-5,
    )
    # plain arrays pass through untouched
    np.testing.assert_allclose(np.asarray(matmul(x, w)), np.asarray(x @ w))


def test_quantized_forward_close_and_halved():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(3))
    qparams = quantize_params(params, min_size=0)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 16)),
        jnp.int32,
    )
    ref, _ = forward(params, toks, cfg)
    got, _ = forward(qparams, toks, cfg)
    ref, got = np.asarray(ref, np.float64), np.asarray(got, np.float64)
    # logits track closely; greedy argmax agrees on the vast majority
    cos = (ref * got).sum() / (np.linalg.norm(ref) * np.linalg.norm(got))
    assert cos > 0.999
    agree = (ref.argmax(-1) == got.argmax(-1)).mean()
    assert agree > 0.9, agree
    # matmul weights halved (embeddings stay exact)
    assert quantized_bytes(qparams) < 0.65 * quantized_bytes(params)


def test_engine_int8_decode():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(4))
    prompts = [[5, 9, 2, 7]]
    kw = dict(seq_buckets=(16, 64), batch_buckets=(1,), max_seq_len=64)
    ref = GenerationEngine(cfg, params, **kw).generate_compiled(
        prompts, max_new_tokens=12, sampling=SamplingParams.make())
    q = GenerationEngine(cfg, params, quant="int8", **kw).generate_compiled(
        prompts, max_new_tokens=12, sampling=SamplingParams.make())
    assert len(q.sequences[0]) == len(ref.sequences[0])
    # greedy decode off random weights is chaotic under perturbation; the
    # engine-level guarantee is that the int8 path runs the full compiled
    # loop and emits valid tokens (accuracy is pinned above at logit level)
    assert all(0 <= t < cfg.vocab_size for t in q.sequences[0])
    with pytest.raises(ValueError):
        GenerationEngine(cfg, params, quant="nf4", **kw)


def test_int8_kv_cache_prefill_decode():
    """int8 KV cache: prefill+decode logits stay close to the full-precision
    cache path, the cache stores int8 + scales, and bytes roughly halve."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(6))
    from tensorlink_tpu.models import forward
    from tensorlink_tpu.models.base import KVCache

    toks = jnp.asarray(
        np.random.default_rng(1).integers(1, cfg.vocab_size, (2, 12)),
        jnp.int32,
    )
    ref_cache = KVCache.init(cfg, 2, max_len=32)
    q_cache = KVCache.init(cfg, 2, max_len=32, quantized=True)
    assert q_cache.quantized and q_cache.k.dtype == jnp.int8
    kv_bytes = lambda c: c.k.nbytes + c.v.nbytes + (
        (c.k_scale.nbytes + c.v_scale.nbytes) if c.quantized else 0
    )
    # fp32 reference cache vs int8+scales: ~72% smaller here; vs the bf16
    # cache real configs use it is ~47%
    assert kv_bytes(q_cache) < 0.5 * kv_bytes(ref_cache)

    ref_lg, ref_cache = forward(params, toks, cfg, cache=ref_cache)
    q_lg, q_cache = forward(params, toks, cfg, cache=q_cache)
    np.testing.assert_allclose(
        np.asarray(q_lg), np.asarray(ref_lg), rtol=0.15, atol=0.08
    )
    # random-init logits are nearly flat, so near-ties may flip under int8
    # noise — require strong (not perfect) argmax agreement
    agree = (
        np.asarray(ref_lg).argmax(-1) == np.asarray(q_lg).argmax(-1)
    ).mean()
    assert agree > 0.8, agree

    # decode steps through the quantized cache track the reference
    step = jnp.asarray([[7], [9]], jnp.int32)
    ref_lg2, _ = forward(params, step, cfg, cache=ref_cache)
    q_lg2, _ = forward(params, step, cfg, cache=q_cache)
    np.testing.assert_allclose(
        np.asarray(q_lg2), np.asarray(ref_lg2), rtol=0.2, atol=0.1
    )


def test_engine_int8_kv_mode():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(4))
    prompts = [[5, 9, 2, 7]]
    kw = dict(seq_buckets=(16, 64), batch_buckets=(1,), max_seq_len=64)
    ref = GenerationEngine(cfg, params, **kw).generate_compiled(
        prompts, max_new_tokens=12, sampling=SamplingParams.make())
    q = GenerationEngine(cfg, params, quant="int8+kv", **kw)
    assert q.cache_quant
    r = q.generate_compiled(prompts, max_new_tokens=12,
                            sampling=SamplingParams.make())
    assert len(r.sequences[0]) == len(ref.sequences[0])
    assert all(0 <= t < cfg.vocab_size for t in r.sequences[0])


def test_int8_kv_model_routes_to_paged_engine():
    """The config.py:83 gate, fixed: a model spec requesting the int8 KV
    cache ("int8+kv") is NOT unpageable anymore — the hosting-time
    routing predicate accepts it and the continuous engine ACCEPTS the
    cache_quant engine, auto-forcing int8 pages. (Construction only —
    compiles nothing; the end-to-end decode is the slow twin below.)"""
    from tensorlink_tpu.engine.continuous import (
        ContinuousEngine, paged_unsupported,
    )

    cfg = tiny_cfg()
    # the routing predicate the validator consults at host time
    assert paged_unsupported(cfg) is None  # int8+kv rides the same cfg
    assert "sliding-window" in paged_unsupported(
        cfg.with_(sliding_window=8)
    )

    params = init_params(cfg, jax.random.PRNGKey(4))
    kw = dict(seq_buckets=(16, 64), batch_buckets=(1,), max_seq_len=64)
    eng = GenerationEngine(cfg, params, quant="int8+kv", **kw)
    assert eng.cache_quant
    ce = ContinuousEngine(eng, max_slots=2, page_size=8, chunk_steps=4)
    # the dense engine's int8-KV preference forces int8 pages
    assert ce.kv_quant == "int8" and ce.cache.quantized
    assert ce.cache.k.dtype == jnp.int8
    assert ce.serving_snapshot()["kv_quant"] == "int8"
    ce.close()


@pytest.mark.slow  # compiles the int8 step program for this model shape
# — tier-1 wall-time; CI's engine job runs this file unfiltered
def test_int8_kv_model_serves_end_to_end():
    """The slow twin of the routing regression: the cache_quant engine
    actually decodes through the paged int8 path, conservation holds."""
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(4))
    kw = dict(seq_buckets=(16, 64), batch_buckets=(1,), max_seq_len=64)
    eng = GenerationEngine(cfg, params, quant="int8+kv", **kw)
    ce = ContinuousEngine(eng, max_slots=2, page_size=8, chunk_steps=4)
    try:
        req = ce.submit([5, 9, 2, 7], max_new_tokens=6, seed=1)
        ce.run_until_idle()
        assert req.finished
        assert all(0 <= t < cfg.vocab_size for t in req.tokens)
        ce.check_page_conservation()
    finally:
        ce.close()


def test_quantize_kv_roundtrip_error():
    """The paged KV cache's quantize site (models/quant.py::quantize_kv):
    per-(position, head) symmetric int8 over head_dim — error bounded by
    scale/2 per element, deterministic, and exactly invertible through
    dequantize_kv's fused multiply."""
    from tensorlink_tpu.models.quant import dequantize_kv, quantize_kv

    x = jax.random.normal(jax.random.PRNGKey(7), (4, 16, 2, 32), jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (4, 16, 2)
    err = np.abs(np.asarray(dequantize_kv(q, s)) - np.asarray(x))
    assert float(err.max()) <= float(np.asarray(s).max()) * 0.51
    # deterministic: the same row quantizes to the same bytes + scale no
    # matter what else rides the batch (the framing-invariance property
    # the paged cache's bitwise contract stands on)
    q2, s2 = quantize_kv(x[:1])
    assert np.array_equal(np.asarray(q[:1]), np.asarray(q2))
    assert np.array_equal(np.asarray(s[:1]), np.asarray(s2))


def test_kv_cache_serialization_roundtrip():
    from tensorlink_tpu.core import serialization as ser
    from tensorlink_tpu.models.base import KVCache

    cfg = tiny_cfg()
    c = KVCache.init(cfg, 1, max_len=8, quantized=True)
    c2 = ser.decode(ser.encode(c))
    assert c2.quantized
    np.testing.assert_array_equal(np.asarray(c2.k), np.asarray(c.k))
    np.testing.assert_array_equal(np.asarray(c2.k_scale), np.asarray(c.k_scale))
    plain = KVCache.init(cfg, 1, max_len=8)
    p2 = ser.decode(ser.encode(plain))
    assert not p2.quantized


def test_quantized_moe_router_and_dense_mlp():
    cfg = tiny_cfg(n_experts=4, n_experts_per_tok=2)
    params = init_params(cfg, jax.random.PRNGKey(5))
    qparams = quantize_params(params, min_size=0)
    # 4D expert weights stay exact (einsum path), router may quantize
    assert not isinstance(qparams["layers"]["mlp"]["w_gate"], QTensor)
    toks = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    ref, _ = forward(params, toks, cfg)
    got, _ = forward(qparams, toks, cfg)
    cos = float(
        (np.asarray(ref, np.float64) * np.asarray(got, np.float64)).sum()
        / (np.linalg.norm(np.asarray(ref, np.float64))
           * np.linalg.norm(np.asarray(got, np.float64)))
    )
    assert cos > 0.99


def test_int8_sharded_mesh_parity(cpu_devices):
    """int8 (+int8 KV) composes with a tensor/data mesh (r3 weak #4): the
    sharded engine's greedy decode must match the single-device int8 engine
    token for token — quantization is elementwise, so sharding commutes
    with it up to matmul reduction order."""
    from jax.sharding import NamedSharding
    from tensorlink_tpu.models.transformer import cache_specs, partition_specs
    from tensorlink_tpu.parallel.mesh import build_mesh

    # dims sized so the stacked layer weights clear quantize_params'
    # min_size and actually quantize
    cfg = ModelConfig(
        family="llama", vocab_size=512, d_model=128, n_layers=4, n_heads=4,
        n_kv_heads=2, head_dim=32, d_ff=256, max_seq_len=128,
        dtype=jnp.float32, tie_embeddings=False,
    )
    params = init_params(cfg, jax.random.PRNGKey(6))
    prompts = [[5, 9, 2, 7, 11, 3]]
    kw = dict(seq_buckets=(16, 64), batch_buckets=(1,), max_seq_len=64)

    for quant in ("int8", "int8+kv"):
        ref = GenerationEngine(cfg, params, quant=quant, **kw)
        r = ref.generate_compiled(prompts, max_new_tokens=10)

        mesh = build_mesh({"data": 2, "tensor": 2}, cpu_devices[:4])
        specs = partition_specs(cfg, tensor_axis="tensor")
        sharded = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs,
        )
        eng = GenerationEngine(
            cfg, sharded, quant=quant, mesh=mesh,
            cache_specs=cache_specs(cfg, data_axis=None, tensor_axis="tensor"),
            **kw,
        )
        # quantized-on-sharded: QTensor leaves carry GSPMD shardings
        from tensorlink_tpu.models.quant import QTensor

        qleaves = [
            l for l in jax.tree.leaves(
                eng.params, is_leaf=lambda x: isinstance(x, QTensor)
            )
            if isinstance(l, QTensor)
        ]
        assert qleaves, "sharded engine must hold quantized weights"
        assert any(
            "tensor" in str(l.q.sharding.spec) for l in qleaves
        ), "q payloads must stay tensor-sharded"

        g = eng.generate_compiled(prompts, max_new_tokens=10)
        assert g.sequences == r.sequences, (quant, g.sequences, r.sequences)


# ---------------------------------------------------------------------------
# packed int4 KV primitives + the kv_quant default flip (density serving)
# ---------------------------------------------------------------------------
def test_mlconfig_kv_quant_default_is_int8():
    """PR 7 shipped int8 pages default-off for one release; that window
    has elapsed — int8 IS the default paged KV storage now, with "none"
    as the explicit opt-out and "int4" as the density step beyond.
    Pinned so a config refactor can't silently regress the density
    default."""
    from tensorlink_tpu.core.config import MLConfig

    assert MLConfig().kv_quant == "int8"
    # both explicit modes remain constructible engine-side
    for mode in ("none", "int8", "int4"):
        assert MLConfig(kv_quant=mode).kv_quant == mode


def test_quantize_kv4_roundtrip_and_determinism():
    """The int4 page-write primitive: packed two-per-byte payload, error
    bounded by scale/2 per element, and deterministic per row — the same
    row quantizes to the same bytes + scale regardless of its neighbors
    (the property the bitwise cache contract stands on)."""
    from tensorlink_tpu.models.quant import dequantize_kv4, quantize_kv4

    rng = np.random.default_rng(41)
    x = jnp.asarray(rng.normal(size=(4, 2, 32)).astype(np.float32))
    q, s = quantize_kv4(x)
    assert q.dtype == jnp.int8 and q.shape == (4, 2, 16)  # hd/2 bytes
    assert s.shape == (4, 2)
    err = np.abs(np.asarray(dequantize_kv4(q, s)) - np.asarray(x))
    assert (err <= np.asarray(s)[..., None] / 2 + 1e-6).all()
    q2, s2 = quantize_kv4(x[:1])
    assert np.array_equal(np.asarray(q2), np.asarray(q[:1]))
    assert np.array_equal(np.asarray(s2), np.asarray(s[:1]))


def test_paged_cache_int4_layout_and_capacity():
    """The int4 pool really is denser: packed payload is hd/2 bytes per
    (position, head) + the same f32 scale rows as int8 — on a bf16-model
    geometry that is >= 1.8x fewer bytes per page than int8 and ~3.8x
    fewer than bf16 (the capacity math docs/SERVING.md quotes)."""
    from tensorlink_tpu.engine.paged import PagedKVCache

    cfg = ModelConfig(
        family="llama", vocab_size=512, d_model=64, n_layers=3, n_heads=4,
        n_kv_heads=2, head_dim=64, d_ff=128, max_seq_len=128,
        dtype=jnp.float32, tie_embeddings=False,
    )

    def page_bytes(kv_quant):
        c = PagedKVCache.init(cfg, 2, page_size=8, max_len=32,
                              kv_quant=kv_quant)
        b = c.k.nbytes + c.v.nbytes
        if c.quantized:
            b += c.k_scale.nbytes + c.v_scale.nbytes
        return b // c.n_pages

    b8, b4 = page_bytes("int8"), page_bytes("int4")
    c4 = PagedKVCache.init(cfg, 2, page_size=8, max_len=32,
                           kv_quant="int4")
    assert c4.k.shape[-1] == cfg.head_dim // 2 and c4.k.dtype == jnp.int8
    assert b8 / b4 >= 1.8, (b8, b4)
    # odd head_dim cannot pack: loud, never a silent mis-layout
    with pytest.raises(ValueError, match="even"):
        odd = ModelConfig(
            family="llama", vocab_size=512, d_model=64, n_layers=3,
            n_heads=4, n_kv_heads=2, head_dim=9, d_ff=128, max_seq_len=128,
            dtype=jnp.float32, tie_embeddings=False,
        )
        PagedKVCache.init(odd, 2, page_size=8, max_len=32, kv_quant="int4")


def test_weight_quant_serves_on_paged_engine_with_int4_kv():
    """Weight-only int8 serving composes with quantized pages on the
    continuous path: a quant="int8" engine (weights halved through
    quant.matmul) hosts a ContinuousEngine with int4 KV — weights AND
    KV shrink together, and the snapshot/serving_modes surface both
    knobs for operators. Construction + snapshot only: zero compiles."""
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(seq_buckets=(16,), batch_buckets=(1,), max_seq_len=32)
    eng = GenerationEngine(cfg, params, quant="int8", **kw)
    assert not eng.cache_quant  # weights only — pages come from kv_quant
    ce = ContinuousEngine(eng, max_slots=2, page_size=8, kv_quant="int4")
    snap = ce.serving_snapshot()
    assert snap["kv_quant"] == "int4"
    assert snap["weight_quant"] == "int8"
    ce.close()
    # "int8+kv" still forces quantized pages when kv_quant is opted out
    eng2 = GenerationEngine(cfg, params, quant="int8+kv", **kw)
    ce2 = ContinuousEngine(eng2, max_slots=2, page_size=8, kv_quant="none")
    assert ce2.kv_quant == "int8" and ce2.cache.quantized
    assert ce2.serving_snapshot()["weight_quant"] == "int8+kv"
    ce2.close()
