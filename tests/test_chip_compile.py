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


def _pages(dev, mode: str, hkv: int, hd: int = HD):
    """(k/v page spec, {k_scale, v_scale} specs) for a page storage mode."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=dev)

    if mode == "bf16":
        return sds((P, hkv, PAGE, hd), jnp.bfloat16), {}
    hdk = hd // 2 if mode == "int4" else hd  # int4: two values per byte
    sc = sds((P, hkv, PAGE), jnp.float32)
    return sds((P, hkv, PAGE, hdk), jnp.int8), {"k_scale": sc, "v_scale": sc}


def _compiles_with_kernel(fn, *args, **kw) -> None:
    compiled = fn.lower(*args, scale=SCALE, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _i32(dev, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)


def _q(dev, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=dev)


# (Hq, Hkv): the whole model on one chip, and one tensor_parallel=4 shard
FULL, TP4_SHARD = (32, 8), (8, 2)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize(
    "heads,C",
    [(FULL, 128), (FULL, 8), (TP4_SHARD, 128)],
    ids=["full-C128", "full-C8", "tp4shard-C128"],
)
def test_ragged_kernel_compiles_for_v5e(v5e, mode, heads, C):
    """THE step program's kernel: the packed ``[slots, chunk]`` block
    (chunk 128 as served; 8 = a decode/verify-only width)."""
    hq, hkv = heads
    kv, scales = _pages(v5e, mode, hkv)
    _compiles_with_kernel(
        A.ragged_paged_attention, _q(v5e, S, C, hq, HD), kv, kv,
        _i32(v5e, S, N_PP), _i32(v5e, S), _i32(v5e, S), **scales,
    )


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("heads", [FULL, TP4_SHARD], ids=["full", "tp4shard"])
def test_decode_kernel_compiles_for_v5e(v5e, mode, heads):
    """The decode continuation's per-token kernel."""
    hq, hkv = heads
    kv, scales = _pages(v5e, mode, hkv)
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


def _step_operands(cfg, place, place_cache):
    """Abstract operands of the ragged step at a default MLConfig worker's
    shapes for ``cfg``: (operands, logical bytes of weights + page pool)."""
    from tensorlink_tpu.engine.paged import PagedKVCache
    from tensorlink_tpu.models.transformer import init_params

    params = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.PRNGKey(0)
    )
    cache = jax.eval_shape(
        lambda: PagedKVCache.init(
            cfg, S, page_size=PAGE, max_len=N_PP * PAGE, kv_quant="int8"
        )
    )
    resident = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves((params, cache))
    )

    def ctl(dt, *shape):
        return place(jax.ShapeDtypeStruct(shape, dt))

    i32, f32 = jnp.int32, jnp.float32
    return (
        place(params), ctl(i32, S, 128), place_cache(cache), ctl(i32, S),
        ctl(i32, S), ctl(i32, S), ctl(jnp.bool_, S), ctl(i32, S),
        ctl(i32, S), ctl(f32, S), ctl(i32, S), ctl(f32, S), ctl(f32, S),
        ctl(f32, S), ctl(i32, S, cfg.vocab_size), ctl(i32, S),
        ctl(i32, S, 8),
    ), resident


@pytest.mark.slow
def test_ragged_step_fits_one_v5e_beside_the_weights(v5e):
    """qwen3-4b, all 36 layers, int8 pages, spec width 9: the step program
    compiles for one chip with the kernel in it, stores weights + pages at
    their logical size (the scale planes are not lane-padded in HBM), and
    its temporaries stay under two page pools — the position-major scatter
    it replaced made the compiler keep a re-laid copy of the whole pool
    per layout (7.2 GB of temporaries, past the chip)."""
    from tensorlink_tpu.engine.paged import paged_ragged_step
    from tensorlink_tpu.models.registry import config_presets

    cfg = config_presets()["qwen3-4b"]

    def place(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e),
            tree,
        )

    ops, resident = _step_operands(cfg, place, place)
    compiled = paged_ragged_step.lower(*ops, cfg, 8, 9, True).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(ops[2]))
    assert ma.argument_size_in_bytes <= 1.01 * resident + 8 * 2**20, ma
    assert ma.temp_size_in_bytes < 2 * pool, (ma, pool)
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM, ma


def _tp4_step_compiled(v5e_chips, cfg):
    """The tensor-parallel step for ``cfg`` compiled over the four
    described chips at a default MLConfig worker's shapes: (compiled,
    logical bytes of weights + page pool)."""
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

    ops, resident = _step_operands(cfg, place, on(tp_cache_specs(True)))
    step = make_tp_ragged_step(mesh, cfg, n_steps=8, spec_width=9, kernel=True)
    return step.lower(*ops).compile(), resident


@pytest.mark.slow
def test_tp4_ragged_step_compiles_for_a_v5e_2x2_mesh(v5e_chips):
    """The tensor-parallel step over the four described chips: kernel and
    all-gathers present, each device holding about a quarter."""
    from tensorlink_tpu.models.registry import config_presets

    compiled, resident = _tp4_step_compiled(
        v5e_chips, config_presets()["qwen3-4b"])
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    ma = compiled.memory_analysis()
    # a quarter of what shards, plus the replicated embedding table
    assert ma.argument_size_in_bytes < 0.35 * resident, (ma, resident)


def test_qwen2p5_7b_tp4_step_compiles_at_its_published_shapes(v5e_chips):
    """Qwen2.5-7B as ``qwen2p5-7b-tp4.decode-closed`` serves it: 28/4 heads
    of 128 (one kv head and seven query heads a chip), d_ff 18944 / 4, an
    untied vocabulary head of 152064 / 4 columns, q/k/v biases, int8 pages,
    8 slots x 4096. Not slow-marked: it is the one guard, off the chip, of
    the only four-chip cell. The step holds the Pallas walk and the
    gathers, and a chip's arguments are its share: a quarter of the layers
    and the head, all of the embedding table, a quarter of the pages."""
    import dataclasses

    from tensorlink_tpu.models.registry import config_presets

    cfg = dataclasses.replace(config_presets()["qwen2p5-7b"], max_seq_len=4096)
    compiled, resident = _tp4_step_compiled(v5e_chips, cfg)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    ma = compiled.memory_analysis()
    embed = cfg.vocab_size * cfg.d_model * 2
    share = (resident - embed) / 4 + embed
    print(f"qwen2p5-7b tp=4 on a described v5e 2x2: arguments a chip "
          f"{ma.argument_size_in_bytes / 1e9:.3f} GB (share {share / 1e9:.3f}), "
          f"temp {ma.temp_size_in_bytes / 1e9:.3f} GB, "
          f"{text.count('all-gather(')} all-gathers, "
          f"{text.count('tpu_custom_call')} kernel calls")
    assert 0.98 * share < ma.argument_size_in_bytes < 1.02 * share + 2**26, ma
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 0.6 * V5E_HBM, ma
