"""The plain reference against the served path at a tiny size of each
family, on the CPU: it agrees with what the slot engine serves, and the
check that decides ``correct`` fails when the reference's mathematics loses
a bias, a q/k-norm scale or its rope offset: it is tight enough to tell a
wrong forward from a right one."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.cluster import model_config_json
from benchmarks.harness.spec import BENCH_DIR
from benchmarks.reference import decoder
from benchmarks.tests.fixtures import tiny_config

TOL = json.loads((BENCH_DIR / "reference" / "tolerance.json").read_text())["max_gap_sigmas"]


def served(model_type: str, kv_quant: str = "int8"):
    """Greedy tokens from the program's slot engine on seeded weights whose
    biases and norm scales are not the trivial 0 and 1 of a fresh init."""
    from tensorlink_tpu.engine.continuous import ContinuousEngine
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models.base import ModelConfig
    from tensorlink_tpu.models.transformer import init_params

    hf = tiny_config(model_type)
    cfg = ModelConfig.from_json(model_config_json(hf))
    params = init_params(cfg, jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(4)
    attn = params["layers"]["attn"]
    for name in ("bq", "bk", "bv", "q_norm", "k_norm"):
        if name in attn:
            key, k = jax.random.split(key)
            noise = 0.5 * jax.random.normal(k, attn[name].shape, jnp.float32)
            base = 1.0 if name.endswith("norm") else 0.0
            attn[name] = (base + noise).astype(attn[name].dtype)
    for ln in ("ln1", "ln2"):
        key, k = jax.random.split(key)
        s = params["layers"][ln]["scale"]
        params["layers"][ln]["scale"] = (
            1.0 + 0.3 * jax.random.normal(k, s.shape, jnp.float32)).astype(s.dtype)
    ce = ContinuousEngine(
        GenerationEngine(cfg, params, max_seq_len=256, seq_buckets=(64, 128, 256),
                         batch_buckets=(1, 2, 4)),
        max_slots=2, page_size=8, chunk_steps=4, prefill_chunk=32,
        kv_quant=kv_quant,
    )
    rng = np.random.default_rng(5)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 45)]
    req = ce.submit(prompt, max_new_tokens=16)
    ce.run_until_idle()
    assert req.finished and len(req.tokens) == 16
    return params, prompt, list(req.tokens), decoder.arch_of(hf)


@pytest.fixture(scope="module", params=["qwen3", "qwen2"])
def case(request):
    return request.param, served(request.param)


def test_reference_agrees_with_the_served_path(case):
    _, (params, prompt, tokens, arch) = case
    gaps = decoder.served_gaps(params, [prompt], [tokens], arch)[0]
    assert gaps.shape == (16,) and np.isfinite(gaps).all()
    assert gaps.max() <= TOL, gaps
    # most served tokens are the reference's own greedy choice
    assert (gaps == 0).sum() >= 12


def test_check_fails_when_the_rope_offset_is_wrong(case, monkeypatch):
    _, (params, prompt, tokens, arch) = case
    real = decoder._rope
    # a shift of queries and keys together leaves attention unchanged, so
    # the keys alone are roped 16 positions (one page) off
    calls = {"n": 0}

    def shifted(x, theta):
        calls["n"] += 1
        if calls["n"] % 2 == 0:  # the second call of a block ropes k
            pad = jnp.zeros_like(x[:, :16])
            return real(jnp.concatenate([pad, x], 1), theta)[:, 16:]
        return real(x, theta)

    monkeypatch.setattr(decoder, "_rope", shifted)
    jax.clear_caches()
    gaps = decoder.served_gaps(params, [prompt], [tokens], arch)[0]
    jax.clear_caches()
    assert gaps.max() > TOL, gaps


def drop(params, names, value):
    out = jax.tree.map(lambda x: x, params)
    attn = dict(out["layers"]["attn"])
    for n in names:
        attn[n] = jnp.full_like(attn[n], value)
    out["layers"] = {**out["layers"], "attn": attn}
    return out


def test_check_fails_when_a_bias_or_a_norm_scale_is_dropped(case):
    model_type, (params, prompt, tokens, arch) = case
    if model_type == "qwen2":
        broken = drop(params, ("bq", "bk", "bv"), 0.0)
    else:
        broken = drop(params, ("q_norm", "k_norm"), 1.0)
    gaps = decoder.served_gaps(broken, [prompt], [tokens], arch)[0]
    assert gaps.max() > TOL, gaps


def test_check_fails_on_a_wrong_token_stream(case):
    _, (params, prompt, tokens, arch) = case
    wrong = [(t + 1) % 512 for t in tokens]
    assert decoder.served_gaps(params, [prompt], [wrong], arch).max() > TOL
