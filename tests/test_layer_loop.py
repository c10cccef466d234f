"""The step's layer loops carry the page pools whole
(engine/paged.py ``_scan_layers``): the write lands at ``(layer, page,
head, offset)`` of the stack and attention reads layer ``layer`` of it.

Held here against the loop this replaced, kept in this file: the pools
as the scan's ``xs``/``ys``, each layer handed its own ``[P, ...]`` slice
(as a stack of one, so the blocks under test are the program's own) and
the updated slices stacked back. Tokens, counters and the WHOLE returned
cache — payload pools, scale planes, lengths — must be byte-equal, so a
row written into, or a page read from, the wrong layer cannot pass.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.engine import paged
from tensorlink_tpu.models import ModelConfig, init_params
from tensorlink_tpu.models.transformer import tp_partition_specs
from tensorlink_tpu.ops import attention
from tensorlink_tpu.parallel.mesh import serving_mesh

S, C, PAGE, N_PP, N_STEPS = 4, 8, 8, 4, 3


def _sliced_scan_layers(params, x, cache, block):
    """The layer loop before the pools were carried: a layer sees its own
    slice, the scan stacks the updated slices into a new pool."""
    def scan_fn(carry, xs):
        lp, kv = xs[0], tuple(a[None] for a in xs[1:])
        y, kv = block(carry, lp, jnp.int32(0), kv)
        return y, tuple(a[0] for a in kv)

    return jax.lax.scan(
        scan_fn, x, (params["layers"], *paged._cache_kv(cache))
    )


def _inputs(cfg, kv_quant: str, spec: bool):
    """A block with every kind of slot over a cache with history: a
    decode slot, a fresh prefill, a slot that verifies three drafts (one
    plain decode row when ``spec`` is off), an idle slot."""
    rng = np.random.default_rng(5)
    cache = paged.PagedKVCache.init(
        cfg, S, page_size=PAGE, max_len=N_PP * PAGE, kv_quant=kv_quant
    )

    def noise(a):  # pages hold history, each layer's its own
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.uniform(0.01, 1.0, a.shape), a.dtype)

    kv = tuple(noise(a) for a in paged._cache_kv(cache))
    starts = jnp.asarray([5, 0, 11, 0], jnp.int32)
    bt = 1 + rng.permutation(S * N_PP).reshape(S, N_PP).astype(np.int32)
    cache = paged._with_kv(
        cache, kv, block_tables=jnp.asarray(bt), lengths=starts + 0
    )
    n_spec = np.asarray([0, 0, 3 if spec else 0, 0], np.int32)
    i32 = functools.partial(np.asarray, dtype=np.int32)
    f32 = functools.partial(np.asarray, dtype=np.float32)
    ctl = paged.pack_control(
        rng.integers(1, cfg.vocab_size, (S, C)), np.asarray(starts),
        i32([1, C, 1, 0]) + n_spec, n_spec,
        np.asarray([True, True, True, False]),
        i32([3, 4, 5, 6]), i32([0, 0, 2, 0]),  # seeds, steps
        f32([0, 0.8, 0, 0]), i32([0, 5, 0, 0]), f32([1, 0.9, 1, 1]),
        f32([0, 0.1, 0, 0]), f32([0, 0.1, 0, 0]),
        i32([9, 9, 9, 9]), np.full((S, 2), -1, np.int32),
        pages_per_slot=N_PP,
    )
    return ctl, cache, jnp.zeros((S, cfg.vocab_size), jnp.int32)


def _step(monkeypatch, cfg, params, tp: int, kernel: bool, W: int):
    """The step program built anew from the module as it stands (so a
    patched loop is traced, not a cached program): ``(step, params)``."""
    if tp == 1:
        monkeypatch.setattr(paged, "paged_decode_step", jax.jit(
            paged._decode_step_impl, static_argnames=("cfg", "kernel")))
        return jax.jit(functools.partial(
            paged._ragged_step_impl, cfg=cfg, n_steps=N_STEPS,
            spec_width=W, kernel=kernel,
        )), params
    monkeypatch.setattr(paged, "_TP_RAGGED_CACHE", {})
    mesh = serving_mesh(tp)
    step = paged.make_tp_ragged_step(
        mesh, cfg, n_steps=N_STEPS, spec_width=W, kernel=kernel
    )
    put = functools.partial(jax.tree.map, lambda x, s: jax.device_put(
        x, jax.sharding.NamedSharding(mesh, s)))
    specs = paged.tp_cache_specs

    def sharded(params, ctl, cache, counts):
        return step(params, ctl, put(cache, specs(cache.quantized)), counts)

    return sharded, put(params, tp_partition_specs(cfg))


@pytest.mark.parametrize("tp", [1, 4])
@pytest.mark.parametrize("kv_quant", ["none", "int8", "int4"])
@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("kernel", [False, True], ids=["ref", "kernel"])
def test_carried_pools_equal_the_sliced_loop(
    monkeypatch, kernel, spec, kv_quant, tp
):
    """The whole step — ragged pass, verify walk, two continuation steps
    — over carried pools returns what the sliced loop returns, byte for
    byte. Heads of 128 values: plain and int8 pools reach the kernel as
    the whole stack, packed int4 has its layer cut out and padded."""
    if tp > len(jax.devices()):
        pytest.skip("needs 4 (virtual) devices")
    cfg = ModelConfig(
        family="qwen3", vocab_size=96, d_model=32, n_layers=3, n_heads=8,
        n_kv_heads=4, head_dim=128, d_ff=64, max_seq_len=N_PP * PAGE,
        qk_norm=True, dtype=jnp.float32, tie_embeddings=False,
    )
    params = init_params(cfg, jax.random.PRNGKey(1))
    if kernel:  # the kernels interpreted, where the step picked them up
        for name in ("ragged_paged_attention", "paged_attention"):
            monkeypatch.setattr(paged, name, functools.partial(
                getattr(attention, name), interpret=True))
    W = 4 if spec else 1
    before = np.asarray(_inputs(cfg, kv_quant, spec)[1].k)

    def run():  # on inputs of its own: the tp step donates cache and counts
        step, placed = _step(monkeypatch, cfg, params, tp, kernel, W)
        args = _inputs(cfg, kv_quant, spec)
        return jax.tree.map(np.asarray, step(placed, *args))

    got = run()
    monkeypatch.setattr(paged, "_scan_layers", _sliced_scan_layers)
    want = run()
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(g, w)
    # the block did something to compare: every emitting slot drew
    # tokens, and the pools changed in every layer
    (_tokens, n_tok, _m, _n_exec, _stats), cache = paged.unpack_results(
        got[0], N_STEPS, W), got[1]
    assert (n_tok[:3] >= 1).all() and n_tok[3] == 0, n_tok
    assert all((cache.k[i] != before[i]).any() for i in range(cfg.n_layers))


@pytest.mark.parametrize(
    "C,page,starts,n_valid",
    [
        # the served shape in small: decode rows, a fresh and a mid-page
        # prefill, an idle slot
        (8, 8, [13, 0, 11, 0], [1, 8, 5, 0]),
        # a chunk longer than a page, every offset class, a full block
        # ending on the slot's last position
        (16, 4, [0, 3, 6, 16], [16, 9, 1, 16]),
        # a chunk shorter than a page: inside one page, and across an edge
        (5, 8, [2, 6, 24, 9], [5, 5, 3, 0]),
        # nothing valid anywhere: only the scratch page may change
        (8, 8, [5, 0, 11, 0], [0, 0, 0, 0]),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16])
def test_page_merge_writes_what_the_row_scatter_writes(
    C, page, starts, n_valid, dtype
):
    """A block written page by page (``_merge_pages`` under
    ``_page_write_plan``) leaves every layer and every page but the
    scratch page byte for byte as the row scatter at
    ``_ragged_write_indices``' targets leaves them: valid rows land where
    they did, every other position of a touched page keeps its bytes."""
    rng = np.random.default_rng(3)
    L, Hkv, hd, n_pp = 3, 2, 128, 8
    n_slots = len(starts)
    P = 1 + n_slots * n_pp
    pool = jnp.asarray(rng.integers(-127, 128, (L, P, Hkv, page, hd)), dtype)
    rows = jnp.asarray(rng.integers(-127, 128, (n_slots, C, Hkv, hd)), dtype)
    bt = jnp.asarray(
        1 + rng.permutation(n_slots * n_pp).reshape(n_slots, n_pp), jnp.int32
    )
    st, nv = jnp.asarray(starts, jnp.int32), jnp.asarray(n_valid, jnp.int32)
    pg, off, _pos, _valid = paged._ragged_write_indices(
        bt, st, nv, page, n_pp, C
    )
    plan = paged._page_write_plan(bt, st, nv, page, n_pp, C)
    for layer in (0, L - 1):
        want = pool.at[
            layer, pg[..., None], jnp.arange(Hkv), off[..., None]
        ].set(rows)
        got = jax.jit(paged._merge_pages)(pool, jnp.int32(layer), plan, rows)
        np.testing.assert_array_equal(
            np.asarray(got[:, 1:], np.float32),
            np.asarray(want[:, 1:], np.float32),
        )
        if sum(n_valid):  # and something was written
            assert (got[layer, 1:] != pool[layer, 1:]).any()
