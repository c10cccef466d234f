"""A tensor-parallel serving job, from its load to its logits, on virtual
CPU devices at a tiny Qwen2.5 shape: seven query heads on each kv head
(``G`` = 7), as many kv heads as shards so each chip holds one, q/k/v
biases, an untied vocabulary head.

* seeded weights made under the serving layout are the unsharded draw, leaf
  by leaf, and no device ever holds more of a leaf than its share;
* a worker that loads for a ``tensor_parallel`` deployment keeps ONE copy of
  the weights a device, in the layout the tp step reads;
* the served path (prefill through the ragged pass, then decode through the
  paged cache) gives the plain reference's logits
  (``benchmarks/reference/decoder.py``: float32 full forward, no cache),
  with biases that are not the zeros of a fresh init — and would not if a
  bias were dropped, a query head read the wrong kv head, or the vocabulary
  shards were gathered out of order.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from benchmarks.reference import decoder
from tensorlink_tpu.core.config import MLConfig, WorkerConfig
from tensorlink_tpu.engine.continuous import (
    ContinuousEngine,
    device_bytes,
    tp_serving_refusal,
)
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.paged import (
    PagedKVCache,
    make_logits_probe,
    tp_cache_specs,
    tp_gather_costs,
)
from tensorlink_tpu.models.base import ModelConfig
from tensorlink_tpu.models.transformer import init_params, tp_partition_specs
from tensorlink_tpu.parallel.mesh import serving_mesh

PAGE, CHUNK, SLOTS, MAX_LEN = 8, 32, 4, 256


def qwen25_tiny(tp: int, dtype=jnp.float32) -> ModelConfig:
    return ModelConfig(
        family="qwen2", vocab_size=512, d_model=112, n_layers=2,
        n_heads=7 * tp, n_kv_heads=tp, head_dim=16, d_ff=256,
        max_seq_len=MAX_LEN, attn_bias=True, tie_embeddings=False,
        rope_theta=1e6, dtype=dtype,
    )


def tp_shardings(cfg, tp):
    mesh = serving_mesh(tp)
    return mesh, jax.tree.map(
        lambda s: NamedSharding(mesh, s), tp_partition_specs(cfg)
    )


def with_seeded_biases(params, shardings=None):
    """q/k/v biases drawn from a seed, each placed as its leaf is."""
    attn = dict(params["layers"]["attn"])
    key = jax.random.PRNGKey(11)
    for name in ("bq", "bk", "bv"):
        key, k = jax.random.split(key)
        b = (0.5 * jax.random.normal(k, attn[name].shape, jnp.float32)).astype(
            attn[name].dtype
        )
        if shardings is not None:
            b = jax.device_put(b, shardings["layers"]["attn"][name])
        attn[name] = b
    return {**params, "layers": {**params["layers"], "attn": attn}}


# -- (b) sharded init == unsharded init, shard by shard -----------------------
@pytest.mark.parametrize("tp", [2, 4])
def test_sharded_seeded_init_is_the_unsharded_draw(tp):
    cfg = qwen25_tiny(tp, jnp.bfloat16)
    key = jax.random.PRNGKey(3)
    whole = init_params(cfg, key)
    mesh, shardings = tp_shardings(cfg, tp)
    made = init_params(cfg, key, shardings=shardings)
    specs = tp_partition_specs(cfg)
    leaves = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b), (_, spec) in zip(
        leaves(whole), leaves(made),
        leaves(specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)),
    ):
        name = jax.tree_util.keystr(path)
        assert np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        ), name
        share = b.nbytes // (tp if "tp" in spec else 1)
        assert len(b.addressable_shards) == tp, name
        assert max(s.data.nbytes for s in b.addressable_shards) == share, name
        assert b.sharding.is_equivalent_to(NamedSharding(mesh, spec), b.ndim)


# -- (c) the worker's load: one copy a device, in the step's layout ----------
class _Bridge:
    def __init__(self):
        self.responses = []

    def request(self, verb, payload, timeout=None):
        if verb == "respond":
            self.responses.append(payload)
        return True

    def notify(self, verb, payload):
        pass


class _Node:
    def __init__(self, ml):
        self.config = WorkerConfig(ml=ml)
        self.bridge = _Bridge()
        self.node_id = "f" * 64


def _load(tp: int, cfg: ModelConfig, mesh_axes: dict, **ml_over):
    from tensorlink_tpu.ml.worker import DistributedWorker

    ml = MLConfig(
        tensor_parallel=tp, max_seq_len=MAX_LEN, seq_buckets=(64, 128, 256),
        cont_max_slots=SLOTS, prefill_chunk=CHUNK, cont_page_size=PAGE,
        cont_chunk_steps=4, **ml_over,
    )
    node = _Node(ml)
    w = DistributedWorker(node)
    w._handle("load_stage", {
        "job_id": "j1",
        "model": {"name": "t", "config": cfg.to_json(), "seed": 5},
        "stage": {"layer_lo": 0, "layer_hi": cfg.n_layers, "first": True,
                  "last": True, "holds_head": True, "worker_id": "w",
                  "mesh_axes": mesh_axes, "coworkers": []},
        "peer": "user", "rid": "r0",
    })
    return node, w


@pytest.mark.parametrize("tp", [2, 4])
def test_a_tp_load_keeps_one_copy_of_the_weights_a_device(tp):
    import gc

    from tensorlink_tpu.core.trace import get_tracer

    cfg = qwen25_tiny(tp, jnp.bfloat16)
    gc.collect()
    before = device_bytes(jax.live_arrays())
    # the planner gives a multi-device worker a GSPMD tensor axis on its
    # own: the two layouts used to stay resident side by side
    node, w = _load(tp, cfg, {"tensor": tp})
    assert node.bridge.responses[-1]["body"].get("ok") is True
    rt = w.jobs["j1"]
    cont = w._ensure_cont(rt)
    assert cont is not None and cont.tensor_parallel == tp
    assert rt.params is rt.engine.params  # the stage holds no other layout
    whole = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    specs = tp_partition_specs(cfg)
    share = sum(
        x.size * x.dtype.itemsize // (tp if "tp" in s else 1)
        for x, s in zip(jax.tree.leaves(whole), jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    )
    assert cont.weights_bytes_device == [share] * tp
    snap = cont.serving_snapshot()
    assert snap["weights_bytes_device_max"] == share
    assert snap["weights_bytes_device_min"] == share
    # live buffers: the share of the weights, the pages, the histograms and
    # nothing of the weights twice (a second copy would add `share` again)
    pages = max(device_bytes(cont.cache).values())
    gc.collect()
    after = device_bytes(jax.live_arrays())
    devs = list(cont._tp_mesh.devices.flat)
    for d in devs:
        grown = after.get(d, 0) - before.get(d, 0)
        assert share + pages <= grown < share + pages + 0.5 * share, (
            d, grown, share, pages)
    # the served stream is the unsharded engine's, so the layout is the
    # step's own (same seed, tp=1)
    prompt = [int(t) for t in np.random.default_rng(1).integers(0, 512, 40)]
    # _ensure_cont returned with the wide program built and the narrow one
    # on its thread (build_steps): joined here, so that the widths below
    # do not depend on how long this machine compiles
    assert cont._build is not None
    cont._join_build()
    req = cont.submit(prompt, max_new_tokens=8)
    cont.run_until_idle()
    one = ContinuousEngine(
        GenerationEngine(cfg, init_params(cfg, jax.random.PRNGKey(5)),
                         max_seq_len=MAX_LEN, seq_buckets=(64, 128, 256)),
        max_slots=SLOTS, page_size=PAGE, chunk_steps=4, prefill_chunk=CHUNK,
        kv_quant="int8",
    )
    ref = one.submit(prompt, max_new_tokens=8)
    one.run_until_idle()
    assert req.tokens == ref.tokens
    assert one.stats["tp_gather_bytes"] == 0 == one.stats["tp_gather_calls"]
    # the gathers counted from the shapes: two chunks of prefill (40 > 32)
    # and the decode steps of 8 tokens
    rows, head, calls = tp_gather_costs(cfg, tp)
    assert calls == 4 * cfg.n_layers + 1
    item, got = 2, (tp - 1) / tp
    assert rows == cfg.n_layers * (cfg.q_dim + 2 * cfg.d_model + cfg.d_ff) * item * got
    assert head == cfg.vocab_size * item * got
    # three chunks: 32 prompt tokens alone (one pass, no decode step), then
    # the last 8 with the first token and three continuation steps, then
    # four steps more: the last two pack the narrow block (no grant longer
    # than its 16 rows), and the gathers follow the width
    assert cont.stats["decode_steps"] == 8
    assert cont.stats["tp_gather_calls"] == calls * 9
    S, C, W = SLOTS, CHUNK, cont.spec_width
    assert cont.block_widths == (16, C)
    assert cont.stats["tp_gather_bytes"] == int(rows * S * C + head * S * W) + 2 * int(
        rows * (S * 16 + 3 * S) + head * S * (W + 3))
    assert (cont.stats["ragged_blocks"], cont.stats["ragged_blocks_narrow"]) == (3, 2)
    span = [s for s in get_tracer().collect("j1") if s["name"] == "load_stage"][-1]
    assert span["tp"] == tp and span["weights_bytes_device_max"] == share
    assert span["weights_bytes_device_min"] == share


def test_a_load_that_cannot_shard_fails_with_the_reason():
    """A width that does not divide: the deployment asked for tp=4, the
    engine cannot take it, and the planner's layout cannot shard either —
    the load fails and says why, it does not replicate."""
    cfg = dataclasses.replace(qwen25_tiny(4), d_ff=254)
    assert "d_ff=254" in tp_serving_refusal(cfg, 4)
    with pytest.raises(ValueError, match="tensor_parallel > 1: not replicating"):
        _load(4, cfg, {"tensor": 4})


def test_without_tensor_parallel_the_load_is_the_planners():
    cfg = qwen25_tiny(2)
    node, w = _load(1, cfg, {})
    rt = w.jobs["j1"]
    assert rt.mesh is None
    want = init_params(cfg, jax.random.PRNGKey(5))
    for a, b in zip(jax.tree.leaves(rt.params), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert len(a.addressable_shards) == 1


# -- (a) logits against the plain reference ----------------------------------
def _arch(cfg: ModelConfig) -> dict:
    return {"heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "eps": cfg.norm_eps,
            "theta": cfg.rope_theta, "qk_norm": False, "tied": False,
            "layers": cfg.n_layers}


def _served_logits(cfg, tp, params, mesh, kv_quant, seqs, n_prompt):
    """Teacher-forced logits of the served path for ``seqs`` (one a slot):
    the prompt through the ragged pass in chunks, then one decode step a
    token through the paged cache. ``[slot, len(seq) - n_prompt + 1, V]``:
    the positions ``n_prompt - 1 ..`` of each sequence."""
    ragged, decode = make_logits_probe(mesh, cfg)
    n_pp = MAX_LEN // PAGE
    cache = jax.jit(
        lambda: PagedKVCache.init(cfg, SLOTS, page_size=PAGE, max_len=MAX_LEN,
                                  kv_quant=kv_quant),
        out_shardings=jax.tree.map(lambda s: NamedSharding(mesh, s),
                                   tp_cache_specs(kv_quant != "none")),
    )()
    # every slot owns its pages, dealt out of order
    bt = np.random.default_rng(2).permutation(
        np.arange(1, 1 + SLOTS * n_pp)).reshape(SLOTS, n_pp).astype(np.int32)
    cache = dataclasses.replace(cache, block_tables=jnp.asarray(bt))
    seqs = np.asarray(seqs, np.int32)
    out = []
    for lo in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - lo)
        blk = np.zeros((SLOTS, CHUNK), np.int32)
        blk[:, :n] = seqs[:, lo:lo + n]
        logits, cache = ragged(
            params, jnp.asarray(blk), cache,
            jnp.full((SLOTS,), lo, jnp.int32), jnp.full((SLOTS,), n, jnp.int32))
    out.append(np.asarray(logits, np.float32))
    for t in range(n_prompt, seqs.shape[1]):
        logits, cache = decode(params, jnp.asarray(seqs[:, t]), cache,
                               jnp.ones((SLOTS,), bool))
        out.append(np.asarray(logits, np.float32))
    assert np.array_equal(np.asarray(cache.lengths), [seqs.shape[1]] * SLOTS)
    return np.stack(out, axis=1)


# Both sides compute in float32 from the same float32 weights (the
# reference at "highest" precision, the served path in XLA's CPU f32): what
# is left is the order of the sums, 4e-6 at worst on logits of deviation 1,
# held to 5e-5. With int8 pages each K and V row is rounded to 1/254 of its
# largest element: 2.3e-2 at worst here, held to 6e-2. A dropped bias, a
# query head on the wrong kv head or swapped vocabulary shards move logits
# by 3 to 6: fifty times either tolerance and more; ten is asserted below.
TOL = {"none": 5e-5, "int8": 6e-2}  # tlint: disable=TL006(read-only table)


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
@pytest.mark.parametrize("tp", [2, 4])
def test_tp_served_logits_are_the_references(tp, kv_quant):
    cfg = qwen25_tiny(tp)
    mesh, shardings = tp_shardings(cfg, tp)
    params = with_seeded_biases(
        init_params(cfg, jax.random.PRNGKey(5), shardings=shardings), shardings)
    n_prompt, n_new = 45, 12  # two prefill chunks, then twelve decode steps
    seqs = np.random.default_rng(7).integers(
        0, cfg.vocab_size, (SLOTS, n_prompt + n_new))
    got = _served_logits(cfg, tp, params, mesh, kv_quant, seqs, n_prompt)
    arch = _arch(cfg)
    positions = slice(n_prompt - 1, n_prompt + n_new)

    def reference(p):
        return decoder.forward_logits(p, seqs, arch, positions)

    want = reference(params)
    assert got.shape == want.shape == (SLOTS, n_new + 1, cfg.vocab_size)
    err = np.abs(got - want).max()
    assert err <= TOL[kv_quant], err
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.9

    # the tolerance tells a wrong forward from a right one
    host = jax.tree.map(np.asarray, params)
    attn = host["layers"]["attn"]
    hd, G = cfg.head_dim, cfg.n_heads // cfg.n_kv_heads

    def broken(**attn_over):
        return {**host, "layers": {**host["layers"],
                                   "attn": {**attn, **attn_over}}}

    no_bias = broken(**{b: np.zeros_like(attn[b]) for b in ("bq", "bk", "bv")})
    # query head h reads kv head h // G: shift the query heads by one, so
    # the last of each group of seven reads its neighbour's kv head
    shifted = broken(
        wq=np.roll(attn["wq"], hd, axis=-1), bq=np.roll(attn["bq"], hd, axis=-1),
        wo=np.roll(attn["wo"], hd, axis=-2))
    assert G == 7
    V = cfg.vocab_size
    swapped = {**host, "lm_head": np.roll(host["lm_head"], V // tp, axis=-1)}
    for name, p in (("bias dropped", no_bias), ("kv-head map", shifted),
                    ("vocabulary shards", swapped)):
        off = np.abs(got - reference(p)).max()
        assert off > 10 * TOL[kv_quant], (name, off)
