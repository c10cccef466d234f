"""Explicit tensor parallelism for the paged serving path
(parallel/mesh.py ``serving_mesh``, models/transformer.py
``tp_partition_specs``, engine/paged.py ``make_tp_ragged_step``,
engine/continuous.py ``tensor_parallel=``) and the zero1 × TP training
composition (engine/training.py ``tp_axis=``).

The contract under test (docs/SHARDING.md): a tp=N engine serves
streams BIT-IDENTICAL to the single-device engine — greedy, sampled and
speculative alike — because weights shard by head-major-contiguous
output columns, activations reassemble with exact tiled all_gathers in
a fixed order, and every control-state array stays host-replicated.
Plus the compile-set bound (ONE ragged program per shard degree), the
per-shard KV page layout, and the train step's bitwise equality with
~1/(dp·tp) resident optimizer bytes.

Runs on the virtual 8-device CPU mesh (conftest forces
``xla_force_host_platform_device_count=8``). Engine-compiling tests are
marked ``slow`` — the dedicated CI tensor-parallel leg runs them
unfiltered on every PR.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.engine.continuous import ContinuousEngine
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.sampling import SamplingParams
from tensorlink_tpu.models import ModelConfig, init_params
from tensorlink_tpu.models.transformer import (
    tp_partition_specs,
    tp_shardable,
)

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >= 4 (virtual) devices"
)

# a repetitive prompt so prompt-lookup drafting actually accepts tokens
# (the bit-identity contract holds for any prompt; this makes the
# speculative leg of the parity tests real, mirroring test_continuous)
# tlint: disable=TL006(read-only repetitive-prompt fixture data)
REP = [5, 9, 5, 9, 5, 9, 5, 9]


def _cfg(**kw):
    base = dict(
        family="llama", vocab_size=128, d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=64,
        dtype=jnp.float32, tie_embeddings=False,
    )
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def tiny():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params):
    # each ContinuousEngine gets a FRESH GenerationEngine: a TP engine
    # re-places engine.params onto its mesh, which must not leak into a
    # sibling single-device engine's layout
    return GenerationEngine(
        cfg, params, seq_buckets=(8, 32), batch_buckets=(1,), max_seq_len=64
    )


def _cont(cfg, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("spec_decode", True)
    kw.setdefault("spec_draft", 4)
    return ContinuousEngine(_engine(cfg, params), **kw)


# tlint: disable=TL006(read-only request-mix fixture table)
MIXES = [
    # (prompt, n, sampling, seed, speculative) — greedy, sampled and a
    # speculating stream co-resident in one engine
    ([1, 2, 3], 10, SamplingParams.make(), 0, False),
    ([4, 5, 6, 7], 8, SamplingParams.make(temperature=0.8, top_k=5), 3, False),
    (REP, 12, SamplingParams.make(), 7, True),
    (REP, 9, SamplingParams.make(temperature=0.9, top_p=0.9), 11, True),
]


def _serve(ce):
    reqs = [
        ce.submit(p, max_new_tokens=n, sampling=sp, seed=seed,
                  speculative=spec)
        for p, n, sp, seed, spec in MIXES
    ]
    ce.run_until_idle()
    assert all(r.finished for r in reqs)
    return [r.tokens for r in reqs]


# ---------------------------------------------------------------------------
# the acceptance pin: tp=2 streams are bitwise the tp=1 streams
# ---------------------------------------------------------------------------
@pytest.mark.slow
@needs4
def test_tp2_streams_bit_identical(tiny):
    cfg, params = tiny
    ref = _serve(_cont(cfg, params))
    tp = _cont(cfg, params, tensor_parallel=2)
    assert tp.tensor_parallel == 2
    assert _serve(tp) == ref


@pytest.mark.slow
@needs4
def test_tp4_streams_bit_identical():
    # tp=4 needs 4-way-divisible head counts; a distinct tiny config
    cfg = _cfg(n_heads=4, n_kv_heads=4)
    params = init_params(cfg, jax.random.PRNGKey(1))
    ref = _serve(_cont(cfg, params))
    assert _serve(_cont(cfg, params, tensor_parallel=4)) == ref


# ---------------------------------------------------------------------------
# per-shard KV pages + page conservation + compile-set bound
# ---------------------------------------------------------------------------
@pytest.mark.slow
@needs4
def test_tp_kv_shards_and_page_conservation(tiny):
    """KV pages shard by kv head — every device holds ALL pages over
    n_kv/tp local heads — the sharding survives chunk donation, the
    host-side conservation equation holds, and the hot loop stays ONE
    compiled ragged program a width of the packed block (at most two) for
    the shard degree."""
    cfg, params = tiny
    ce = _cont(cfg, params, tensor_parallel=2)
    _serve(ce)
    # MIXES' prompts fit a page: a longer one packs the wide block too
    ce.submit([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], max_new_tokens=6, seed=2)
    ce.run_until_idle()
    assert {r["block_rows"] for r in ce.recorder.records()} == set(
        ce.block_widths)
    k = ce.cache.k  # [L, n_pages, n_kv, page, hd]
    assert k.sharding.spec == jax.sharding.PartitionSpec(None, None, "tp")
    for shard in k.addressable_shards:
        assert shard.data.shape[1] == ce.cache.n_pages  # pages replicated
        assert shard.data.shape[2] == cfg.n_kv_heads // 2  # heads split
    ce.check_page_conservation()
    sizes = ce.jit_cache_sizes()
    assert sizes["tp_ragged_step"] == len(ce.block_widths) == 2
    _serve(ce)  # and churn over both adds none
    assert ce.jit_cache_sizes()["tp_ragged_step"] == 2
    # control state stays host-replicated: block tables shard nowhere
    assert ce.cache.block_tables.sharding.spec == jax.sharding.PartitionSpec()
    snap = ce.serving_snapshot()
    assert snap["tensor_parallel"] == 2


# ---------------------------------------------------------------------------
# host-gap budget on the decode critical path (rot guard)
# ---------------------------------------------------------------------------
def test_host_gap_span_recorded(tiny):
    """The host work between chunk syncs (admission, grant assembly,
    draft lookup, packing) is measured every chunk: the flight-recorder
    fields and the per-phase counters at /stats and /metrics must stay
    wired — this test rots loudly if the measurement is dropped."""
    cfg, params = tiny
    ce = _cont(cfg, params)
    ce.submit([1, 2, 3], max_new_tokens=4)
    ce.run_until_idle()
    recs = ce.recorder.records()
    assert recs and recs[-1]["host_ms"] == pytest.approx(
        recs[-1]["admit_ms"] + recs[-1]["pack_ms"])
    snap = ce.serving_snapshot()
    host_us = snap["chunk_us_admit"] + snap["chunk_us_pack"]
    assert host_us == round(sum(r["host_ms"] for r in recs) * 1e3) > 0
    text = ce.metrics.render()
    assert "tlink_engine_chunk_us_admit_total" in text
    assert "tlink_engine_chunk_us_pack_total" in text


# ---------------------------------------------------------------------------
# gates: what refuses to shard, and how
# ---------------------------------------------------------------------------
def test_tp_shardable_gates():
    cfg = _cfg()
    assert tp_shardable(cfg, 1) is None
    assert tp_shardable(cfg, 2) is None
    assert "n_heads" in tp_shardable(cfg, 3)
    assert "n_kv_heads" in tp_shardable(_cfg(n_heads=4, n_kv_heads=1), 2)
    assert "vocab_size" in tp_shardable(
        _cfg(vocab_size=127, n_heads=2), 2
    )
    moe = _cfg(n_experts=4)
    assert "MoE" in tp_shardable(moe, 2)
    with pytest.raises(ValueError):
        tp_partition_specs(moe)


def test_tp_engine_refusals(tiny):
    """Unshardable configs and bad knob combinations refuse with
    ValueError — the worker's hosting seam turns that into the static
    fallback, never a crash."""
    cfg, params = tiny
    with pytest.raises(ValueError, match="n_heads"):
        _cont(cfg, params, tensor_parallel=3)
    with pytest.raises(ValueError, match="devices"):
        _cont(cfg, params, tensor_parallel=len(jax.devices()) * 2)


def test_tp_partition_specs_match_param_tree(tiny):
    """Every param leaf has exactly one spec leaf at the same path (the
    loader walks specs by dot-path; a drifting key structure would fail
    load-time placement)."""
    cfg, params = tiny
    specs = tp_partition_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    )


# ---------------------------------------------------------------------------
# zero1 × TP: the train step serves the same shards it trains
# ---------------------------------------------------------------------------
@pytest.mark.slow
@needs4
def test_zero1_tp_train_step_bitwise(tiny):
    """On a (dp=2, tp=2) mesh with n_micro == dp, two zero1 × TP steps
    are BITWISE the unsharded reference's — loss, grad norm and every
    parameter — while params hold the serving shard layout throughout
    and dim-0-shardable optimizer state lives 1/(dp·tp) per device."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorlink_tpu.engine.training import make_optimizer, make_train_step
    from tensorlink_tpu.parallel.mesh import serving_mesh

    cfg, params0 = tiny
    params = jax.tree.map(jnp.copy, params0)
    params_tp = jax.tree.map(jnp.copy, params0)
    rng = np.random.RandomState(0)
    batch = {
        "tokens": jnp.asarray(rng.randint(1, 127, size=(4, 16)), jnp.int32)
    }
    opt = make_optimizer("adamw", lr=1e-3, grad_clip=1.0)

    ref = make_train_step(cfg, opt, n_micro=2, remat=False)
    rs = ref.init_state(params)
    rp, rs, rm = ref.step_fn(params, rs, batch)
    rp, rs, rm = ref.step_fn(rp, rs, batch)

    mesh = serving_mesh(2, dp=2)
    ts = make_train_step(
        cfg, opt, n_micro=2, remat=False, zero1=True, mesh=mesh,
        dp_axis="data", tp_axis="tp",
    )
    tp_params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params_tp, tp_partition_specs(cfg),
    )
    state = ts.init_state(tp_params)
    p1, s1, m1 = ts.step_fn(tp_params, state, batch)
    p2, s2, m2 = ts.step_fn(p1, s1, batch)

    assert np.array_equal(np.asarray(rm["loss"]), np.asarray(m2["loss"]))
    assert np.array_equal(
        np.asarray(rm["grad_norm"]), np.asarray(m2["grad_norm"])
    )
    flat_ref = jax.tree_util.tree_flatten_with_path(rp)[0]
    flat_tp = jax.tree_util.tree_flatten_with_path(p2)[0]
    for (kp, a), (_, b) in zip(flat_ref, flat_tp):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            jax.tree_util.keystr(kp)
        )
    # params keep the serving shard layout through the step — the
    # serve-train hot-swap publishes them with no relayout
    assert p2["layers"]["attn"]["wq"].sharding.spec == P(None, None, "tp")
    # bounded compile set: cold entry + steady state, nothing per-step
    assert ts.n_programs() <= 2
    # resident optimizer bytes: every dim-0-shardable state leaf holds
    # exactly 1/(dp·tp) of its global bytes on device 0
    world = 4
    dev0 = jax.devices()[0]
    for leaf in jax.tree.leaves(s2):
        shape = tuple(leaf.shape)
        local = sum(
            int(np.prod(s.data.shape)) for s in leaf.addressable_shards
            if s.device == dev0
        )
        if shape and shape[0] >= world and shape[0] % world == 0:
            assert local * world == int(np.prod(shape)), shape
        else:
            assert local == int(np.prod(shape)), shape


def test_tp_axis_requires_zero1(tiny):
    from tensorlink_tpu.engine.training import make_optimizer, make_train_step
    from tensorlink_tpu.parallel.mesh import serving_mesh

    cfg, _ = tiny
    opt = make_optimizer("adamw", lr=1e-3)
    with pytest.raises(ValueError, match="zero1"):
        make_train_step(cfg, opt, tp_axis="tp", mesh=serving_mesh(2, dp=2))


# ---------------------------------------------------------------------------
# the quantized tiled gather the tp_quant path rides
# ---------------------------------------------------------------------------
def test_quantized_all_gather_tiled_fixed_order():
    """``quantized_all_gather(tiled=True)`` concatenates per-shard
    dequantized chunks in axis-index order: every participant computes
    the identical result, each shard's rows carry only ITS OWN
    quantization error, and a replicated input round-trips within the
    int8 bound."""
    from tensorlink_tpu.parallel.mesh import build_mesh
    from tensorlink_tpu.parallel.ring import quantized_all_gather

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from jax.sharding import PartitionSpec as P

    mesh = build_mesh({"tp": 2}, jax.devices()[:2])
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 8), jnp.float32)  # [rows, 2 shards of 4]

    # the gather is replicated by construction (every shard concatenates
    # the same chunks), which the VMA check cannot infer through the
    # dequantize — so the check is off, as for any hand-written collective
    fn = jax.shard_map(
        lambda a: quantized_all_gather(a, "tp", axis=1, tiled=True),
        mesh=mesh, in_specs=(P(None, "tp"),), out_specs=P(),
        check_vma=False,
    )
    out = np.asarray(fn(x))
    assert out.shape == x.shape
    # per-row, per-shard int8 quantization: |err| <= scale/2 per element
    for col0 in (0, 4):
        blk = np.asarray(x)[:, col0 : col0 + 4]
        scale = np.abs(blk).max(axis=1, keepdims=True) / 127.0
        err = np.abs(out[:, col0 : col0 + 4] - blk)
        assert (err <= scale * 0.5 + 1e-7).all()
    # both participants hold the identical gathered value (fixed order):
    # keep the output replicated and compare the two devices' copies
    # bitwise
    rep = fn(x)
    shards = list(rep.addressable_shards)
    assert len(shards) == 2
    assert np.array_equal(
        np.asarray(shards[0].data), np.asarray(shards[1].data)
    )
