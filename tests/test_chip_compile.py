"""The serving path's Pallas kernels compile for the chip — checked without
one.

libtpu compiles for a TPU that is described, not attached
(``jax.experimental.topologies``), so what the chip's compiler would refuse
— a block shape the tiling rejects, too much VMEM, an op Mosaic cannot
lower — fails here, at no chip time. Interpret mode on a CPU cannot show
any of it: the quantized-page kernels passed every interpret-mode test
while their scale operands were refused by the real lowering.

Shapes are what a default ``MLConfig`` worker runs for qwen3-4b: 8 slots,
``prefill_chunk`` 128, 32/8 heads of 128, page 16, 256 pages per slot; the
tensor-parallel case is one tp=4 shard of it (8/2 heads). Nothing runs, so
this says nothing about results or times — ``chip_smoke.py`` does that on
the chip. (tests/conftest.py keeps the persistent compile cache off: a
compile for a described device is written to it but cannot be read back
without the chip — it would warn and compile again.)
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tensorlink_tpu.ops import attention as A

S, PAGE, N_PP, HD = 8, 16, 256, 128
P = 1 + S * N_PP
SCALE = HD**-0.5
MODES = ("bf16", "int8", "int4")
# the pools a kernel is handed: one layer's [P, ...], or every layer's
# [L, P, ...] with a layer index, as the step's layer loop carries them
pools = pytest.mark.parametrize("layers", [None, 36], ids=["layer", "stack"])


@pytest.fixture(scope="module")
def v5e_chips():
    """The four described devices of a v5e 2x2 host; skipped where libtpu
    cannot describe the topology."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / no topology support in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def v5e(v5e_chips):
    """One described v5e chip, as a sharding."""
    return SingleDeviceSharding(v5e_chips[0])


def _pages(dev, mode: str, hkv: int, hd: int = HD, layers: int | None = None):
    """(k/v page spec, {k_scale, v_scale[, layer]} specs) for a page
    storage mode; with ``layers`` the pools are stacked and a traced
    layer index goes with them."""
    stack = () if layers is None else (layers,)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(stack + shape, dt, sharding=dev)

    kw = {} if layers is None else {"layer": _i32(dev)}
    if mode == "bf16":
        return sds((P, hkv, PAGE, hd), jnp.bfloat16), kw
    hdk = hd // 2 if mode == "int4" else hd  # int4: two values per byte
    sc = sds((P, hkv, PAGE), jnp.float32)
    return sds((P, hkv, PAGE, hdk), jnp.int8), {
        "k_scale": sc, "v_scale": sc, **kw}


def _compiles_with_kernel(fn, *args, **kw) -> None:
    compiled = fn.lower(*args, scale=SCALE, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _i32(dev, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)


def _q(dev, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=dev)


# (Hq, Hkv): the whole model on one chip, and one tensor_parallel=4 shard
FULL, TP4_SHARD = (32, 8), (8, 2)


@pools
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize(
    "heads,C",
    [(FULL, 128), (FULL, 8), (TP4_SHARD, 128)],
    ids=["full-C128", "full-C8", "tp4shard-C128"],
)
def test_ragged_kernel_compiles_for_v5e(v5e, mode, heads, C, layers):
    """THE step program's kernel: the packed ``[slots, chunk]`` block
    (chunk 128 as served; 8 = a decode/verify-only width), on one layer's
    pool and on the stack with a layer index (what Mosaic makes of a copy
    out of ``.at[layer, page]`` shows here, not in interpret mode)."""
    hq, hkv = heads
    kv, scales = _pages(v5e, mode, hkv, layers=layers)
    _compiles_with_kernel(
        A.ragged_paged_attention, _q(v5e, S, C, hq, HD), kv, kv,
        _i32(v5e, S, N_PP), _i32(v5e, S), _i32(v5e, S), **scales,
    )


@pools
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("heads", [FULL, TP4_SHARD], ids=["full", "tp4shard"])
def test_decode_kernel_compiles_for_v5e(v5e, mode, heads, layers):
    """The decode continuation's per-token kernel, on one layer's pool
    and on the stack with a layer index."""
    hq, hkv = heads
    kv, scales = _pages(v5e, mode, hkv, layers=layers)
    _compiles_with_kernel(
        A.paged_attention, _q(v5e, S, hq, HD), kv, kv,
        _i32(v5e, S, N_PP), _i32(v5e, S), **scales,
    )


# (Hq, Hkv, head_dim) of other presets a worker may be asked to serve:
# group sizes of one and seven query rows a kv head (under and off the
# 8-row tile: the row slices), 32 kv heads (a slot's heads do not fit one
# grid step's VMEM at chunk 128: the head blocks), head widths under and
# over a lane row (the lane padding, the buffers)
OTHER_HEADS = (
    ("olmo2-7b", (32, 32, 128)),
    ("phi3-mini", (32, 32, 96)),
    ("gemma-7b", (16, 16, 256)),
    ("12x12-hd64", (12, 12, 64)),
    ("qwen2p5-7b", (28, 4, 128)),
    ("qwen2p5-7b-tp4shard", (7, 1, 128)),
    ("laguna-full", (48, 8, 128)),
    ("laguna-sliding", (72, 8, 128)),
    # lfm2's heads of 64 lie beside their values in rows of 128: the walk
    # sees (32, 8, 128) with one pool (the shared_kv cases below)
)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("kernel", ["decode", "ragged-C128"])
@pytest.mark.parametrize(
    "heads", [h for _, h in OTHER_HEADS], ids=[n for n, _ in OTHER_HEADS]
)
def test_walk_compiles_at_other_head_shapes(v5e, heads, kernel, mode):
    """Both kernels of the step program at head shapes other than the
    benchmark's: nothing falls back to the CPU, so a shape Mosaic
    refuses would fail a worker's warm-up on the chip."""
    hq, hkv, hd = heads
    kv, scales = _pages(v5e, mode, hkv, hd)
    if kernel == "decode":
        _compiles_with_kernel(
            A.paged_attention, _q(v5e, S, hq, hd), kv, kv,
            _i32(v5e, S, N_PP), _i32(v5e, S), **scales,
        )
    else:
        _compiles_with_kernel(
            A.ragged_paged_attention, _q(v5e, S, 128, hq, hd), kv, kv,
            _i32(v5e, S, N_PP), _i32(v5e, S), _i32(v5e, S), **scales,
        )


def _bf16_pool(dev, pages, hkv, width, layers=4):
    return jax.ShapeDtypeStruct(
        (layers, pages, hkv, PAGE, width), jnp.bfloat16, sharding=dev)


# the ragged pass's walk of each cell, as its engine calls it: (slots,
# pages a slot, C, Hq, Hkv, row width, pools, further keywords)
_CELL_WALKS = (
    ("qwen3-4b-int8-C16", (8, 256, 16, 32, 8, 128, "int8", {})),
    ("qwen3-4b-int8-C128", (8, 256, 128, 32, 8, 128, "int8", {})),
    ("olmo-30-heads-G1", (8, 512, 128, 30, 30, 128, "kv", {})),
    # a head's 64 keys beside its 64 values in one row: one pool
    ("lfm2-heads-of-64", (16, 1024, 128, 32, 8, 128, "k", {})),
    ("laguna-G6", (16, 1024, 128, 48, 8, 128, "kv", {})),
    ("laguna-window-G9", (16, 41, 128, 72, 8, 128, "kv", {"window": 512})),
    # one slot a call, 128 heads on one latent row (engine/latent.py)
    ("latent-128-heads", (1, 1024, 128, 128, 1, 576, "k", {
        "latent": (512, 512, 512, 2048)})),
)


@pytest.mark.parametrize(
    "walk", [w for _, w in _CELL_WALKS], ids=[n for n, _ in _CELL_WALKS])
def test_two_height_walk_compiles_at_the_cells_shapes(v5e, walk):
    """The ragged pass's walk at each cell's published heads, slots and
    context: the kernel that holds a tall and a short row block behind a
    scalar branch (``G`` = 1, 4, 6, 9, 128 rows in the short one)
    compiles for the described v5e, and its trace holds both bodies
    (four matrix products)."""
    slots, n_pp, C, hq, hkv, width, pool, kw = walk
    pages = 1 + slots * n_pp
    scales = {"layer": _i32(v5e)}
    if pool == "int8":  # qwen3-4b's 36 layers of int8 pages and scales
        k = v = jax.ShapeDtypeStruct(
            (36, pages, hkv, PAGE, width), jnp.int8, sharding=v5e)
        scales["k_scale"] = scales["v_scale"] = jax.ShapeDtypeStruct(
            (36, pages, hkv, PAGE), jnp.float32, sharding=v5e)
    else:
        k = _bf16_pool(v5e, pages, hkv, width)
        v = k if pool == "kv" else None
    args = (_q(v5e, slots, C, hq, width), k, v, _i32(v5e, slots, n_pp),
            _i32(v5e, slots), _i32(v5e, slots))
    _compiles_with_kernel(A.ragged_paged_attention, *args, **scales, **kw)
    traced = jax.make_jaxpr(functools.partial(
        A.ragged_paged_attention, scale=SCALE, **kw))(*args, **scales)
    assert str(traced).count("dot_general") == 4


@pytest.mark.parametrize(
    "call", ["decode-int8", "decode-G1", "latent-first-rows", "window",
             "block-sparse"])
def test_a_one_position_call_traces_one_body(v5e, call):
    """Where the query block is one position (every continuation step,
    the latent walk's first rows, the block-sparse walk: ``per_head`` is
    always such a call, so that variant stays on the tall path) nothing
    is shorter: the kernel's trace holds ONE body (two matrix products,
    no branch on the slot's count) and compiles. (PR 61's builder
    compared these calls' lowered and compiled texts with the parent's,
    locations off as the benchmark runs them: the same, byte for
    byte.)"""
    bt, n = _i32(v5e, S, N_PP), _i32(v5e, S)
    if call == "decode-int8":
        kv, kw = _pages(v5e, "int8", 8, layers=36)
        fn, args = A.paged_attention, (_q(v5e, S, 32, HD), kv, kv, bt, n)
    elif call == "decode-G1":
        kv = _bf16_pool(v5e, P, 30, HD)
        fn, args = A.paged_attention, (_q(v5e, S, 30, HD), kv, kv, bt, n)
        kw = {"layer": _i32(v5e)}
    elif call == "latent-first-rows":
        fn, args = A.paged_attention, (
            _q(v5e, S, 128, 576), _bf16_pool(v5e, P, 1, 576), None, bt, n)
        kw = {"layer": _i32(v5e), "latent": (512, 512, 512, 2048)}
    elif call == "window":
        fn, args = A.paged_attention, (
            _q(v5e, S, 64, 576), _bf16_pool(v5e, P, 1, 576), None, bt, n)
        kw = {"layer": _i32(v5e), "window": 513}
    else:
        kv = _bf16_pool(v5e, P, 2, HD)
        fn, args = A.block_sparse_attention, (
            _q(v5e, S, 32, HD), kv, kv, _i32(v5e, S, 2, 64), _i32(v5e, S, 2))
        kw = {"layer": _i32(v5e)}
    _compiles_with_kernel(fn, *args, **kw)
    traced = str(jax.make_jaxpr(
        functools.partial(fn, scale=SCALE, **kw))(*args))
    assert traced.count("dot_general") == 2


def test_flash_kernel_compiles_for_v5e(v5e):
    """The dense engine's fresh-cache prefill kernel (no pages)."""
    hq, hkv = FULL
    _compiles_with_kernel(
        A.flash_attention, _q(v5e, 1, 512, hq, HD),
        _q(v5e, 1, 512, hkv, HD), _q(v5e, 1, 512, hkv, HD),
    )


# ---------------------------------------------------------------------------
# the whole step program (slow: ~half a minute of TPU compiler each)
# ---------------------------------------------------------------------------
V5E_HBM = 16 * 1024**3  # one v5e chip


def _nbytes(tree) -> int:
    """Logical bytes of a tree of shapes."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


NARROW = 16  # the ladder's other width at pages of 16 and 9 verify rows


def _step_operands(cfg, place, place_cache, kv_quant: str = "int8",
                   width: int = 128):
    """Abstract operands of the ragged step at a default MLConfig worker's
    shapes for ``cfg`` (weights first, the cache third); ``width``: the
    packed block's (``prefill_chunk``, or the narrow one a chunk in which
    nobody prefills packs: ``ContinuousEngine.block_widths``)."""
    from tensorlink_tpu.engine.paged import PagedKVCache
    from tensorlink_tpu.models.transformer import init_params

    params = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.PRNGKey(0)
    )
    cache = jax.eval_shape(
        lambda: PagedKVCache.init(
            cfg, S, page_size=PAGE, max_len=N_PP * PAGE, kv_quant=kv_quant
        )
    )
    return _packed_operands(cfg, params, cache, place, place_cache, width)


def _packed_operands(cfg, params, cache, place, place_cache, width: int = 128):
    """``(params, ctl, cache, counts)`` as shapes: the step program's
    operands for a cache of any kind, the slots counted off its block
    tables."""
    from tensorlink_tpu.engine.paged import CTL_COLS

    slots = cache.block_tables.shape[0]

    def ctl(*shape):
        return place(jax.ShapeDtypeStruct(shape, jnp.int32))

    return (
        place(params),
        ctl(slots, width + CTL_COLS + cache.block_tables.shape[1]),
        place_cache(cache),
        ctl(slots, cfg.vocab_size),
    )


def _on(sharding):
    """Place every leaf of a tree of shapes under ``sharding``."""
    return lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _assert_pools_stay_put(compiled, cache, params, shards: int = 1) -> None:
    """The compiled step holds the kernel and moves no pool: no ``copy``
    whose result has a page pool's shape (the parent's step copied both
    pools once a continuation step, a layer's slice of each in and out a
    layer-call), and temporaries under a quarter of the pool beside the
    one thing the compiler does re-lay once an execution, outside the
    loops: the q/k/v projection weights (at the parent as well; 1.13 GB
    at full depth). ``shards``: devices the kv heads and those weights
    are split over (``memory_analysis`` is one device's)."""
    import re

    text = compiled.as_text()
    assert "tpu_custom_call" in text
    L, n_pages, hkv, page, hdk = cache.k.shape
    pool_shape = f"[{L},{n_pages},{hkv // shards},{page},{hdk}]"
    copies = re.findall(
        rf"^\s*\S+ = \w+{re.escape(pool_shape)}\S* copy\(.*$", text, re.M
    )
    assert not copies, copies[:2]
    if cache.k_scale is not None:
        # the stacked scale planes keep the layer as their major-most
        # dimension wherever they appear: with the layer minor (seen in
        # the tp decode loop when a block's scale write was used for
        # single positions too) writing one layer's plane touches every
        # tile of the stack, 32 us a call on the chip
        shape = (L, n_pages, hkv // shards, page)
        planes = "f32[" + ",".join(map(str, shape)) + "]"
        layouts = set(re.findall(re.escape(planes) + r"\{([\d,]+)", text))
        majors = {  # minor to major, dimensions of one left out
            lay: [d for d in map(int, lay.split(",")) if shape[d] > 1][-1]
            for lay in layouts
        }
        assert majors and set(majors.values()) == {0}, majors
    qkv = _nbytes([params["layers"]["attn"][w] for w in ("wq", "wk", "wv")])
    temp = compiled.memory_analysis().temp_size_in_bytes
    pool = _nbytes((cache.k, cache.v, cache.k_scale, cache.v_scale))
    assert temp < (pool // 4 + qkv) // shards, (temp, pool, qkv)


@pytest.mark.parametrize(
    "kv_quant,tp,width,flat",
    [("int8", 1, 128, 0), ("none", 1, 128, 0), ("int8", 4, 128, 0),
     ("int8", 1, NARROW, 0), ("int8", 4, NARROW, 0),
     ("int8", 1, 128, 256), ("int8", 4, 128, 256)],
    ids=["int8", "bf16", "int8-tp4", "int8-narrow", "int8-tp4-narrow",
         "int8-flat", "int8-tp4-flat"],
)
def test_ragged_step_moves_no_pool(v5e_chips, kv_quant, tp, width, flat):
    """qwen3-4b's widths and page pool (8 slots x 4096, 2,049 pages a
    layer) with the depth cut to twelve layers (a third: what the step
    keeps beside the pools, ~35 MB of logits and control state a chip,
    does not shrink with the depth): the layer loops carry the
    pools and the walk takes a layer index, so neither loop of the
    compiled step copies a pool, slices a layer's out or stacks one
    back. int8 and bf16 pages on one chip; the tensor-parallel step over
    the described 2x2, where a chip's pool holds two kv heads. The same
    at the narrow width of the block (the second program of the step: two
    page merges a slot and layer for nine), on one chip and over the 2x2,
    and on the flat rung (the third: 256 live rows through the layers,
    ``[8, 128]`` only at the page write and the walk: the expand and
    collect gathers move activations and no pool)."""
    import dataclasses

    from tensorlink_tpu.engine.paged import (
        CTL_COLS, flat_rung_rows, paged_ragged_step)
    from tensorlink_tpu.models.registry import config_presets

    assert flat in (0, flat_rung_rows(S, 128, 9))
    cfg = dataclasses.replace(config_presets()["qwen3-4b"], n_layers=12)
    if tp == 1:
        place = _on(SingleDeviceSharding(v5e_chips[0]))
        ops = _step_operands(cfg, place, place, kv_quant, width)
        compiled = paged_ragged_step.lower(
            *ops, cfg, 8, 9, True, flat).compile()
    else:
        compiled, ops = _tp4_step_compiled(v5e_chips, cfg, width, flat)
    if flat:  # the residual stream is the row list
        assert f"bf16[1,{flat},{cfg.d_model}]" in compiled.as_text()
    assert ops[1].shape == (S, width + CTL_COLS + N_PP)
    _assert_pools_stay_put(compiled, ops[2], ops[0], tp)


@pytest.mark.slow
def test_ragged_step_fits_one_v5e_beside_the_weights(v5e):
    """qwen3-4b, all 36 layers, int8 pages, spec width 9: the step program
    compiles for one chip with the kernel in it, stores weights + pages at
    their logical size (the scale planes are not lane-padded in HBM), and
    moves no pool: 1.17 GB of temporaries, of which 1.13 GB the re-laid
    q/k/v projections (3.80 GB when the pools were the scan's ``xs`` and
    ``ys``; 7.2 GB, past the chip, under the position-major scatter before
    that)."""
    from tensorlink_tpu.engine.paged import paged_ragged_step
    from tensorlink_tpu.models.registry import config_presets

    cfg = config_presets()["qwen3-4b"]
    ops = _step_operands(cfg, _on(v5e), _on(v5e))
    compiled = paged_ragged_step.lower(*ops, cfg, 8, 9, True).compile()
    _assert_pools_stay_put(compiled, ops[2], ops[0])
    ma = compiled.memory_analysis()
    resident = _nbytes((ops[0], ops[2]))
    assert ma.argument_size_in_bytes <= 1.01 * resident + 8 * 2**20, ma
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM, ma


def _tp4_step_compiled(v5e_chips, cfg, width: int = 128, flat_rows: int = 0):
    """The tensor-parallel step for ``cfg`` compiled over the four
    described chips at a default MLConfig worker's shapes: (compiled,
    its abstract operands)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from tensorlink_tpu.engine.paged import make_tp_ragged_step, tp_cache_specs
    from tensorlink_tpu.models.transformer import tp_partition_specs

    mesh = Mesh(np.array(v5e_chips).reshape(1, 4), ("data", "tp"))

    def on(spec_tree):
        return lambda tree: jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)
            ),
            tree, spec_tree,
        )

    def place(tree):  # weights by their tp specs, control rows replicated
        if isinstance(tree, dict):
            return on(tp_partition_specs(cfg))(tree)
        return jax.ShapeDtypeStruct(
            tree.shape, tree.dtype,
            sharding=NamedSharding(mesh, PartitionSpec()),
        )

    ops = _step_operands(cfg, place, on(tp_cache_specs(True)), width=width)
    step = make_tp_ragged_step(mesh, cfg, n_steps=8, spec_width=9, kernel=True,
                               flat_rows=flat_rows)
    return step.lower(*ops).compile(), ops


@pytest.mark.slow
def test_tp4_ragged_step_compiles_for_a_v5e_2x2_mesh(v5e_chips):
    """The tensor-parallel step over the four described chips: kernel and
    all-gathers present, each device holding about a quarter."""
    from tensorlink_tpu.models.registry import config_presets

    compiled, ops = _tp4_step_compiled(
        v5e_chips, config_presets()["qwen3-4b"])
    resident = _nbytes((ops[0], ops[2]))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    ma = compiled.memory_analysis()
    # a quarter of what shards, plus the replicated embedding table
    assert ma.argument_size_in_bytes < 0.35 * resident, (ma, resident)


@pytest.mark.parametrize("width", [128, NARROW], ids=["wide", "narrow"])
def test_qwen2p5_7b_tp4_step_compiles_at_its_published_shapes(v5e_chips,
                                                              width):
    """Qwen2.5-7B as ``qwen2p5-7b-tp4.decode-closed`` serves it: 28/4 heads
    of 128 (one kv head and seven query heads a chip), d_ff 18944 / 4, an
    untied vocabulary head of 152064 / 4 columns, q/k/v biases, int8 pages,
    8 slots x 4096. Not slow-marked: it is the one guard, off the chip, of
    the only four-chip cell. The step holds the Pallas walk and the
    gathers, and a chip's arguments are its share: a quarter of the layers
    and the head, all of the embedding table, a quarter of the pages. Both
    programs of the step: the block at ``prefill_chunk`` and at the narrow
    width a chunk in which nobody prefills packs."""
    import dataclasses

    from tensorlink_tpu.models.registry import config_presets

    cfg = dataclasses.replace(config_presets()["qwen2p5-7b"], max_seq_len=4096)
    compiled, ops = _tp4_step_compiled(v5e_chips, cfg, width)
    _assert_pools_stay_put(compiled, ops[2], ops[0], shards=4)
    resident = _nbytes((ops[0], ops[2]))
    text = compiled.as_text()
    assert "all-gather" in text
    ma = compiled.memory_analysis()
    embed = cfg.vocab_size * cfg.d_model * 2
    share = (resident - embed) / 4 + embed
    print(f"qwen2p5-7b tp=4, block {width} wide, on a described v5e 2x2: arguments a chip "
          f"{ma.argument_size_in_bytes / 1e9:.3f} GB (share {share / 1e9:.3f}), "
          f"temp {ma.temp_size_in_bytes / 1e9:.3f} GB, "
          f"{text.count('all-gather(')} all-gathers, "
          f"{text.count('tpu_custom_call')} kernel calls")
    assert 0.98 * share < ma.argument_size_in_bytes < 1.02 * share + 2**26, ma
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 0.6 * V5E_HBM, ma


def test_dots3_note_step_fits_one_v5e_with_its_window_kernel(v5e):
    """The benchmark's ``dots3-note-prev-ep8`` at its published widths (5
    layers, 32 of 256 experts held, 16 slots x 16,384 positions of bf16
    latent pages): the step compiles for one described v5e with the
    windowed latent kernel in it (one call a sliding layer of the
    continuation step: rows of 1,152 values, 64 query heads on one row),
    weights + pools + temporaries fit the chip, no operation copies a
    latent pool, and the entry computation holds the three phase loops in
    order (the ragged pass's layers run as ONE loop). ~60 s: the one
    program the new cell serves from (a patterned model's block has one
    width, ``ContinuousEngine.block_widths``), compiled nowhere else in
    tier-1."""
    import json
    import re
    from pathlib import Path

    from tensorlink_tpu.engine.latent import WINDOW_KERNEL, LatentPagedCache
    from tensorlink_tpu.engine.paged import (
        STEP_PHASES, paged_ragged_step, tiled_rows)
    from tensorlink_tpu.models.registry import config_from_hf
    from tensorlink_tpu.models.transformer import init_params

    hf = json.loads((Path(__file__).parent.parent / "benchmarks" / "configs"
                     / "dots3-note-prev-ep8.json").read_text())
    cfg = config_from_hf(hf)
    slots = hf["deployment"]["ml"]["cont_max_slots"]
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: LatentPagedCache.init(
        cfg, slots, page_size=PAGE, max_len=hf["deployment"]["seq_len"]))
    place = _on(v5e)
    ops = _packed_operands(cfg, params, cache, place, place)
    # the program that serves: the tiled pass, the one wide program
    compiled = paged_ragged_step.lower(
        *ops, cfg, 8, 9, True, tiled_rows(slots, 128)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3 and WINDOW_KERNEL in text
    ma = compiled.memory_analysis()
    weights = _nbytes(ops[0])
    # bf16 but the float32 selection bias: 2 more bytes x 256 x 4 layers
    assert weights == 2 * cfg.held_param_count() + 2 * 256 * 4
    assert 8.1e9 < weights < 8.3e9
    pools = _nbytes((cache.full, cache.index, cache.slide))
    assert 2.5e9 < pools < 2.7e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM, ma
    assert ma.temp_size_in_bytes < pools, ma
    for pool in (cache.full, cache.index, cache.slide):
        shape = "[" + ",".join(map(str, pool.shape)) + "]"
        copies = re.findall(
            rf"^\s*\S+ = \w+{re.escape(shape)}\S* copy\(.*$", text, re.M)
        assert not copies, copies[:2]
    whiles = [ln for ln in text[text.index("ENTRY"):].splitlines()
              if " while(" in ln]
    assert [re.search(r'op_name="[^"]*/(tlink\.\w+)/while"', ln).group(1)
            for ln in whiles] == list(STEP_PHASES)


def test_deepseek_v2_step_fits_one_v5e_with_its_walk_in_both_passes(v5e):
    """The benchmark's ``deepseek-v2-ep8`` at its published widths (6
    layers, routing group 0 = 20 of 160 experts held, 16 slots x 16,384
    positions of bf16 latent pages): the latent walk compiles at 128
    heads on one 640-wide row, one query position a slot and a slot's
    block of 128 x 128 = 16,384 query rows (groups of rows over a third
    grid axis: the block does not fit VMEM whole, ROADMAP R2), and the
    whole step compiles for one described v5e with that kernel in BOTH
    passes (three calls a traced layer: the first rows and the blocks of
    the ragged pass, the continuation step), no pool for a selector or a
    sliding kind, weights + pool + temporaries inside the chip, no
    operation copying the pool, no ``[rows, heads, capacity]`` score array,
    and the three phase loops in order. ~45 s."""
    import json
    import re
    from pathlib import Path

    from tensorlink_tpu.engine.latent import (
        FULL_GROUP_ROWS, FULL_KERNEL, FULL_KV_TILE, FULL_ROWS,
        LatentPagedCache)
    from tensorlink_tpu.engine.paged import STEP_PHASES, paged_ragged_step
    from tensorlink_tpu.models.registry import config_from_hf
    from tensorlink_tpu.models.transformer import init_params

    hf = json.loads((Path(__file__).parent.parent / "benchmarks" / "configs"
                     / "deepseek-v2-ep8.json").read_text())
    cfg = config_from_hf(hf)
    la = cfg.latent_of("full")
    slots = hf["deployment"]["ml"]["cont_max_slots"]
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: LatentPagedCache.init(
        cfg, slots, page_size=PAGE, max_len=hf["deployment"]["seq_len"]))
    assert cache.index is None and cache.slide is None
    place = _on(v5e)
    pool, n_pp = place(cache.full), cache.pages_per_slot
    shape = (la.kv_rank, FULL_KV_TILE, FULL_ROWS, FULL_GROUP_ROWS)
    kw = dict(scale=la.softmax_scale, layer=_i32(v5e), name=FULL_KERNEL,
              latent=shape)
    for fn, args in (
        (A.paged_attention, (_q(v5e, slots, la.n_heads, la.pool_dim), pool,
                             None, _i32(v5e, slots, n_pp), _i32(v5e, slots))),
        (A.ragged_paged_attention, (
            _q(v5e, 1, 128, la.n_heads, la.pool_dim), pool, None,
            _i32(v5e, 1, n_pp), _i32(v5e, 1), _i32(v5e, 1))),
    ):
        text = fn.lower(*args, **kw).compile().as_text()
        assert "tpu_custom_call" in text and FULL_KERNEL in text
    ops = _packed_operands(cfg, params, cache, place, place)
    compiled = paged_ragged_step.lower(*ops, cfg, 8, 9, True).compile()
    text = compiled.as_text()
    # lead layer + traced period: (first rows, blocks) + a continuation step
    assert text.count("tpu_custom_call") == 6
    assert len(re.findall(rf'custom-call\([^\n]*{FULL_KERNEL}', text)) == 6 \
        or text.count(f"/{FULL_KERNEL}/pallas_call") >= 6
    ma = compiled.memory_analysis()
    weights = _nbytes(ops[0])
    assert weights == 2 * cfg.held_param_count()
    assert 7.6e9 < weights < 7.7e9
    pools = _nbytes(cache.full)
    assert 2.0e9 < pools < 2.02e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 0.75 * V5E_HBM, ma
    assert ma.temp_size_in_bytes < pools, ma
    dims = "[" + ",".join(map(str, cache.full.shape)) + "]"
    copies = re.findall(
        rf"^\s*\S+ = \w+{re.escape(dims)}\S* copy\(.*$", text, re.M)
    assert not copies, copies[:2]
    # no score array over a slot's capacity: [.., 16384] float32 of rows x heads
    assert not re.findall(r"f32\[(?:\d+,)*128,128,16384\]", text)
    whiles = [ln for ln in text[text.index("ENTRY"):].splitlines()
              if " while(" in ln]
    assert [re.search(r'op_name="[^"]*/(tlink\.\w+)/while"', ln).group(1)
            for ln in whiles] == list(STEP_PHASES)


def test_laguna_step_fits_one_v5e_with_both_walks_in_both_passes(v5e):
    """The benchmark's ``laguna-s-2.1-ep8`` at its published widths (layers
    0-8, 32 of 256 experts held, 16 slots x 16,384 positions of bf16 pages
    for the 3 full layers, a ring of 41 pages a slot for the 6 sliding
    ones): the step compiles for one described v5e with both grouped-query
    walks in both passes (groups of 6 and 9 query heads a kv head, the
    window's walk from its first key on), weights + pools + rings +
    temporaries fit the chip, and no operation copies a pool or a ring.
    ~80 s: the one program the cell serves from."""
    import json
    import re
    from pathlib import Path

    from tensorlink_tpu.engine.latent import LatentPagedCache
    from tensorlink_tpu.engine.paged import (
        STEP_PHASES, paged_ragged_step, tiled_rows)
    from tensorlink_tpu.models.registry import config_from_hf
    from tensorlink_tpu.models.transformer import init_params

    hf = json.loads((Path(__file__).parent.parent / "benchmarks" / "configs"
                     / "laguna-s-2.1-ep8.json").read_text())
    cfg = config_from_hf(hf)
    slots = hf["deployment"]["ml"]["cont_max_slots"]
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: LatentPagedCache.init(
        cfg, slots, page_size=PAGE, max_len=hf["deployment"]["seq_len"]))
    assert cache.wk.shape == (6, 1 + 16 * 41, 8, 16, 128)
    assert cache.k.shape == (3, 1 + 16 * 1024, 8, 16, 128)
    place = _on(v5e)
    ops = _packed_operands(cfg, params, cache, place, place)
    # the program that serves: the tiled pass, the one wide program
    compiled = paged_ragged_step.lower(
        *ops, cfg, 8, 1, True, tiled_rows(slots, 128)).compile()
    text = compiled.as_text()
    for name in ("gqa_full_attention", "gqa_window_attention"):
        assert text.count(name) >= 2, name  # a call a layer and pass
    ma = compiled.memory_analysis()
    weights = _nbytes(ops[0])
    # bf16 but the float32 selection bias: 2 more bytes x 256 x 8 layers
    assert weights == 2 * cfg.held_param_count() + 2 * 256 * 8
    pools = _nbytes((cache.k, cache.v))
    rings = _nbytes((cache.wk, cache.wv))
    assert 3.2e9 < pools < 3.3e9 and 0.25e9 < rings < 0.27e9
    print(f"laguna-s-2.1-ep8 on a described v5e: arguments "
          f"{ma.argument_size_in_bytes / 1e9:.3f} GB, temp "
          f"{ma.temp_size_in_bytes / 1e9:.3f} GB, "
          f"{text.count('tpu_custom_call')} kernel calls")
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM, ma
    assert ma.temp_size_in_bytes < pools, ma
    for pool in (cache.k, cache.wk):
        shape = "[" + ",".join(map(str, pool.shape)) + "]"
        copies = re.findall(
            rf"^\s*\S+ = \w+{re.escape(shape)}\S* copy\(.*$", text, re.M)
        assert not copies, copies[:2]
    whiles = [ln for ln in text[text.index("ENTRY"):].splitlines()
              if " while(" in ln]
    assert [re.search(r'op_name="[^"]*/(tlink\.\w+)/while"', ln).group(1)
            for ln in whiles] == list(STEP_PHASES)


def test_lfm2_step_fits_one_v5e_and_pads_no_pool(v5e):
    """The benchmark's ``lfm2-8b-a1b-l12`` at its published widths (layers
    0-11, 32 experts held whole, 16 slots x 16,384 positions of bf16 pages
    for the 3 attention layers, a tail of 2 positions a slot for the 9 conv
    layers): the step compiles for one described v5e with the walk at a
    head of 64 in both passes, keys beside values in rows of 128 lanes, so
    that NOTHING pads or copies the pool (``ops/attention.py::_lane_pad``
    would a pool 64 wide, a call and layer), and weights + pool +
    temporaries fit the chip. ~90 s: the one program the cell serves
    from."""
    import json
    import re
    from pathlib import Path

    from tensorlink_tpu.engine.latent import LatentPagedCache
    from tensorlink_tpu.engine.paged import (
        STEP_PHASES, paged_ragged_step, tiled_rows)
    from tensorlink_tpu.models.registry import config_from_hf
    from tensorlink_tpu.models.transformer import init_params

    hf = json.loads((Path(__file__).parent.parent / "benchmarks" / "configs"
                     / "lfm2-8b-a1b-l12.json").read_text())
    cfg = config_from_hf(hf)
    slots = hf["deployment"]["ml"]["cont_max_slots"]
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: LatentPagedCache.init(
        cfg, slots, page_size=PAGE, max_len=hf["deployment"]["seq_len"]))
    assert cache.k.shape == (3, 1 + 16 * 1024, 8, 16, 128) and cache.v is None
    assert cache.state.shape == (9, 16, 2, 2048)
    place = _on(v5e)
    ops = _packed_operands(cfg, params, cache, place, place)
    # the program that serves: the tiled pass, the one wide program
    compiled = paged_ragged_step.lower(
        *ops, cfg, 8, 1, True, tiled_rows(slots, 128)).compile()
    text = compiled.as_text()
    assert text.count("gqa_full_attention") >= 2  # a call a layer and pass
    ma = compiled.memory_analysis()
    weights = _nbytes(ops[0])
    # bf16 but the float32 selection bias: 2 more bytes x 32 x 10 layers
    assert weights == 2 * cfg.held_param_count() + 2 * 32 * 10
    pool = _nbytes(cache.k)
    assert 1.6e9 < pool < 1.62e9 and _nbytes(cache.state) == 16 * 73_728
    print(f"lfm2-8b-a1b-l12 on a described v5e: arguments "
          f"{ma.argument_size_in_bytes / 1e9:.3f} GB, temp "
          f"{ma.temp_size_in_bytes / 1e9:.3f} GB, "
          f"{text.count('tpu_custom_call')} kernel calls")
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM, ma
    assert ma.temp_size_in_bytes < pool, ma
    # one layer's pool or the stack, copied or padded
    for shape in (cache.k.shape, cache.k.shape[1:], (1,) + cache.k.shape[1:]):
        shape = "[" + ",".join(map(str, shape)) + "]"
        moved = re.findall(
            rf"^\s*\S+ = \w+{re.escape(shape)}\S* (?:copy|pad)\(.*$", text,
            re.M)
        assert not moved, moved[:2]
    whiles = [ln for ln in text[text.index("ENTRY"):].splitlines()
              if " while(" in ln]
    assert [re.search(r'op_name="[^"]*/(tlink\.\w+)/while"', ln).group(1)
            for ln in whiles] == list(STEP_PHASES)


def test_olmo_hybrid_step_fits_one_v5e_and_copies_no_state(v5e):
    """The benchmark's ``olmo-hybrid-7b-l16`` at its published widths (layers
    0-15, 8 slots x 8,192 positions of bf16 pages for the 4 full layers at
    30 kv heads and ONE query head a kv head, a float32 state ``[96, 30 x
    192]`` and a bf16 tail of 3 positions a slot for the 12 gated-delta
    layers): the step compiles for one described v5e with both recurrence
    kernels and the walk at G = 1 in both passes, weights + pages + states
    + temporaries fit the chip, and NOTHING copies or pads the state array
    (its layout holds whole lane rows). ~90 s: the one program the cell
    serves from."""
    import json
    import re
    from pathlib import Path

    from tensorlink_tpu.engine.latent import LatentPagedCache
    from tensorlink_tpu.engine.paged import (
        STEP_PHASES, paged_ragged_step, tiled_rows)
    from tensorlink_tpu.models.registry import config_from_hf
    from tensorlink_tpu.models.transformer import init_params

    hf = json.loads((Path(__file__).parent.parent / "benchmarks" / "configs"
                     / "olmo-hybrid-7b-l16.json").read_text())
    cfg = config_from_hf(hf)
    slots = hf["deployment"]["ml"]["cont_max_slots"]
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: LatentPagedCache.init(
        cfg, slots, page_size=PAGE, max_len=hf["deployment"]["seq_len"]))
    assert cache.k.shape == cache.v.shape == (4, 1 + 8 * 512, 30, 16, 128)
    assert cache.state.shape == (12, 8, 96, 5760)
    assert cache.state.dtype == jnp.float32
    assert cache.tail.shape == (12, 8, 3, 11520)
    place = _on(v5e)
    ops = _packed_operands(cfg, params, cache, place, place)
    # the program that serves: the tiled pass, the one wide program
    compiled = paged_ragged_step.lower(
        *ops, cfg, 8, 1, True, tiled_rows(slots, 128)).compile()
    text = compiled.as_text()
    for name in ("gqa_full_attention", "gated_delta_step",
                 "gated_delta_chunk"):
        assert text.count(name) >= 1, name
    ma = compiled.memory_analysis()
    weights = _nbytes(ops[0])
    # bf16 but the float32 A_log and dt_bias: 2 more bytes x 60 x 12 layers
    assert weights == 2 * cfg.held_param_count() + 2 * 60 * 12
    pool = _nbytes((cache.k, cache.v))
    assert 4.0e9 < pool < 4.05e9
    assert _nbytes(cache.state) == 8 * 12 * 2_211_840
    assert _nbytes(cache.tail) == 8 * 12 * 69_120
    print(f"olmo-hybrid-7b-l16 on a described v5e: arguments "
          f"{ma.argument_size_in_bytes / 1e9:.3f} GB, temp "
          f"{ma.temp_size_in_bytes / 1e9:.3f} GB, "
          f"{text.count('tpu_custom_call')} kernel calls")
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM, ma
    assert ma.temp_size_in_bytes < pool, ma
    # the stack of states or one layer's, copied or padded
    for shape in (cache.state.shape, cache.state.shape[1:],
                  (1,) + cache.state.shape[1:], cache.k.shape):
        shape = "[" + ",".join(map(str, shape)) + "]"
        moved = re.findall(
            rf"^\s*\S+ = \w+{re.escape(shape)}\S* (?:copy|pad)\(.*$", text,
            re.M)
        assert not moved, moved[:2]
    whiles = [ln for ln in text[text.index("ENTRY"):].splitlines()
              if " while(" in ln]
    assert [re.search(r'op_name="[^"]*/(tlink\.\w+)/while"', ln).group(1)
            for ln in whiles] == list(STEP_PHASES)
