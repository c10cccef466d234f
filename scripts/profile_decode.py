"""Decode-step breakdown on the chip (legacy dense ``GenerationEngine`` loop).

Times each piece of the B=1 decode step separately so the gap between a
measured decode rate and the HBM roofline can be attributed:
layers-vs-head, attention-vs-mlp, sampling, while_loop overhead, and the
practically achievable HBM bandwidth. This process owns the chip while it
runs (``chiprun -- python scripts/profile_decode.py``).
"""

import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from tensorlink_tpu.core.devices import configure_compile_cache

configure_compile_cache()  # re-profiling iterations pay each compile once

import jax.numpy as jnp
import numpy as np

from tensorlink_tpu.engine.generate import (
    GenerationEngine, _decode_step,
)
from tensorlink_tpu.engine.sampling import SamplingParams, sample
from tensorlink_tpu.models import init_params
from tensorlink_tpu.models.base import KVCache
from tensorlink_tpu.models.registry import config_presets
from tensorlink_tpu.models.transformer import _stage_impl, head_forward

dev = jax.devices()[0]
print("device:", dev, dev.device_kind)

if dev.platform == "cpu":  # script-logic smoke mode (tiny config, fp32)
    cfg = config_presets()["qwen3-1p7b"].with_(
        dtype=jnp.float32, n_layers=2, d_model=256, d_ff=512,
        n_heads=4, n_kv_heads=2, head_dim=64, vocab_size=1024,
    )
    prompt_len, gen = 16, 16
else:
    cfg = config_presets()["qwen3-4b"].with_(dtype=jnp.bfloat16)
    prompt_len, gen = 128, 128
max_len = prompt_len + gen

params = init_params(cfg, jax.random.PRNGKey(0))
jax.block_until_ready(params)
pbytes = cfg.param_count() * 2
print(f"params: {cfg.param_count()/1e9:.2f}B = {pbytes/1e9:.2f} GB")


def timeit(fn, n=20, warmup=2):
    for _ in range(warmup):
        r = fn()
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n


# -- 0. achievable HBM bandwidth probe: reduce every param leaf ------------
@jax.jit
def touch_all(p):
    return sum(jnp.sum(l, dtype=jnp.float32) for l in jax.tree.leaves(p))

dt = timeit(lambda: touch_all(params))
print(f"[bw-probe] read all params: {dt*1e3:.2f} ms -> {pbytes/dt/1e9:.0f} GB/s")

# -- 1. end-to-end compiled decode loop ------------------------------------
eng = GenerationEngine(cfg, params, seq_buckets=(prompt_len, max_len),
                      batch_buckets=(1,), max_seq_len=max_len)
rng = np.random.default_rng(0)
prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()]
greedy = SamplingParams.make()
eng.generate_compiled(prompts, max_new_tokens=gen, sampling=greedy)  # compile

jax.block_until_ready(eng.prefill(prompts)[:2])
t0 = time.perf_counter()
jax.block_until_ready(eng.prefill(prompts)[:2])
prefill_dt = time.perf_counter() - t0

t0 = time.perf_counter()
r = eng.generate_compiled(prompts, max_new_tokens=gen, sampling=greedy)
loop_dt = time.perf_counter() - t0 - prefill_dt
ntok = sum(len(s) for s in r.sequences)
print(f"[loop] {ntok} toks in {loop_dt*1e3:.1f} ms -> "
      f"{ntok/loop_dt:.2f} tok/s, {loop_dt/ntok*1e3:.2f} ms/tok "
      f"(prefill {prefill_dt*1e3:.1f} ms)")

# -- 2. host-driven single decode step (dispatch + full fwd + no sample) ---
cache = KVCache.init(cfg, 1, max_len=max_len)
logits, cache = _decode_step(params, jnp.zeros((1,), jnp.int32), cache, cfg)

def step():
    global cache
    lg, cache = _decode_step(params, jnp.zeros((1,), jnp.int32), cache, cfg)
    return lg

dt_step = timeit(step, n=30)
print(f"[step] host-driven decode step: {dt_step*1e3:.2f} ms/tok")

# -- 3. layers-only (no final norm / logits head) --------------------------
@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def stage_fwd(p, cfg, cache):
    return _stage_impl(
        p, cfg, tokens=jnp.zeros((1, 1), jnp.int32), cache=cache,
        first=True, last=False, remat=False,
    )

cache2 = KVCache.init(cfg, 1, max_len=max_len)
hid, cache2 = stage_fwd(params, cfg, cache2)

def layers_only():
    global cache2
    h, cache2 = stage_fwd(params, cfg, cache2)
    return h

dt_layers = timeit(layers_only, n=30)
print(f"[layers] scan-over-layers only: {dt_layers*1e3:.2f} ms")

# -- 4. head only ----------------------------------------------------------
hidf = jnp.zeros((1, 1, cfg.d_model), cfg.dtype)
dt_head = timeit(lambda: head_forward(params, hidf, cfg), n=30)
print(f"[head] final norm + logits: {dt_head*1e3:.2f} ms")

# -- 5. sampling on [1, V] logits ------------------------------------------
lg = jnp.zeros((1, cfg.vocab_size), jnp.float32)
key = jax.random.PRNGKey(0)
samp = jax.jit(sample)
samp(lg, key, greedy)
dt_samp = timeit(lambda: samp(lg, key, greedy), n=30)
print(f"[sample] greedy sample: {dt_samp*1e3:.2f} ms")

# -- 6. isolate attention vs mlp: mlp-only matmul chain --------------------
L, d, f = cfg.n_layers, cfg.d_model, cfg.d_ff
wg = params["layers"]["mlp"]["w_gate"]
wu = params["layers"]["mlp"]["w_up"]
wd = params["layers"]["mlp"]["w_down"]

@jax.jit
def mlp_chain(x, wg, wu, wd):
    def body(x, ws):
        g, u, w = ws
        y = (jax.nn.silu(x @ g) * (x @ u)) @ w
        return x + y, None
    out, _ = jax.lax.scan(body, x, (wg, wu, wd))
    return out

x1 = jnp.zeros((1, d), cfg.dtype)
mlp_chain(x1, wg, wu, wd)
dt_mlp = timeit(lambda: mlp_chain(x1, wg, wu, wd), n=30)
mlp_bytes = L * 3 * d * f * 2
print(f"[mlp] {L}-layer gemv chain: {dt_mlp*1e3:.2f} ms "
      f"({mlp_bytes/1e9:.2f} GB -> {mlp_bytes/dt_mlp/1e9:.0f} GB/s)")

# batched variant: does a taller batch change per-token bandwidth?
x8 = jnp.zeros((8, d), cfg.dtype)
mlp_chain(x8, wg, wu, wd)
dt_mlp8 = timeit(lambda: mlp_chain(x8, wg, wu, wd), n=30)
print(f"[mlp B=8] {dt_mlp8*1e3:.2f} ms ({mlp_bytes/dt_mlp8/1e9:.0f} GB/s)")

# -- summary ---------------------------------------------------------------
print("\nsummary ms/tok: loop", round(loop_dt/ntok*1e3, 2),
      "| step", round(dt_step*1e3, 2),
      "| layers", round(dt_layers*1e3, 2),
      "| head", round(dt_head*1e3, 2),
      "| sample", round(dt_samp*1e3, 2))
