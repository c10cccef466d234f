"""``laguna-s-2.1-ep8`` in the harness at a tiny size on the CPU: the new
files load by name, the cell's plan runs end to end through
``POST /request-model`` and the slot engine with ``correct`` decided by the
configuration's own reference (served tokens; a full layer through pages, a
sliding layer through the ring and a restored snapshot, cached keys and
values, the routed experts), the bytes and operation functions read
hand-made chunks, and the reference's named faults each read over the
fixture's limit. No number from here is a device metric."""

import json

import numpy as np
import pytest

from benchmarks.bytes_fns.gqa_full_bytes import (
    by_pass as full_by_pass, gqa_full_bytes, position_bytes)
from benchmarks.bytes_fns.gqa_full_flops import (
    by_pass as flops_by_pass, flops_per_row_position)
from benchmarks.bytes_fns.gqa_window_bytes import (
    by_pass as window_by_pass, gqa_window_bytes)
from benchmarks.harness import cluster, spec

CELL = "laguna-s-2.1-ep8.long-doc-sessions"
SIBLING = "dots3-note-prev-ep8.long-doc-sessions"

TINY = dict(
    model_type="laguna", hidden_size=64, intermediate_size=128,
    num_hidden_layers=9, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, vocab_size=512, max_position_embeddings=320,
    rms_norm_eps=1e-6, num_experts=4, num_experts_per_tok=3,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    norm_topk_prob=True, gating="per-head", sliding_window=24,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 3,
    mlp_layer_types=["dense"] + ["sparse"] * 11,
    gating_types=["per_head"] * 12,
    num_attention_heads_per_layer=[4, 6, 6, 6] * 3,
    moe_routed_scaling_factor=2.5, moe_router_logit_softcapping=0,
    moe_apply_router_weight_on_input=False, tie_word_embeddings=False,
    published={"num_experts": 16}, expert_group={"first_expert": 4},
    served_name="tiny-laguna", torch_dtype="float32",
    correct={"reference": "laguna", "tolerance": "fixture_laguna"},
    deployment={"chips": 1, "seq_len": 320, "ml": {
        "max_seq_len": 320, "seq_buckets": [64, 128, 320],
        "cont_max_slots": 4, "prefill_chunk": 32, "cont_page_size": 8,
        "cont_chunk_steps": 4, "kv_quant": "none"}},
)


def tiny_cell():
    bench = spec.load_benchmark()
    traffic = {**spec.load_traffic("long-doc-sessions"), "clients": 2,
               "turns": 2, "system_tokens": 128, "user_tokens": [8, 24],
               "answer_tokens": [4, 8], "cycles": 8}
    return spec.make_cell(
        name=CELL, config=dict(TINY), traffic=traffic, chips=1,
        config_name="tiny-laguna", traffic_name="long-doc-sessions",
        bench=bench)


def deployed_model() -> dict:
    cfg = spec.load_cell(CELL).config
    return cluster.deployed_model(cfg, cluster.ml_config(cfg["deployment"]))


def test_the_new_cell_resolves_all_its_files_by_name():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "laguna-s-2.1-ep8", "long-doc-sessions", 1)
    assert cell.traffic == spec.load_cell(SIBLING).traffic  # unchanged
    assert (cell.traffic["system_tokens"], cell.traffic["clients"],
            cell.traffic["turns"]) == (12288, 16, 4)
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms.sessions", "out_tok_s.sessions", "setup_s"}
    for m in cell.per_layer:
        kind = spec.load_layer_metric(m["name"])["kind"]
        assert spec.reader(kind).read
    assert spec.reference(cell.config).__name__.endswith("laguna")
    tol = spec.load_tolerance(cell.config)
    assert tol["prompt_tokens"] >= cell.traffic["system_tokens"]
    assert {"max_full_gap", "max_window_gap", "max_row_gap",
            "max_expert_gap", "max_route_gap"} <= set(tol)
    ml = cluster.ml_config(cell.config["deployment"])
    assert (ml.kv_quant, ml.cont_max_slots, ml.max_seq_len) == (
        "none", 16, 16384)
    model = cluster.model_config_json(cell.config)
    assert model["family"] == "laguna"
    assert model["layer_kinds"].count("gqa_window") == 6
    names = {m["name"] for m in cell.per_layer}
    new = {"gqa_full_attention_share", "gqa_full_attention_roofline_share",
           "gqa_window_attention_share",
           "gqa_window_attention_roofline_share", "window_pool_gb",
           "window_restore_share", "window_replay_share"}
    assert new | {"cont_step_ms.sessions", "ragged_pass_ms.sessions",
                  "device_idle_share.sessions", "expert_row_fill_share",
                  "expert_load_max_over_mean", "experts_touched_share",
                  "window_page_share"} <= names
    # what it has not: latent pools, a selector, recurrent states
    assert not names & {"select_keep_share", "latent_pool_gb",
                        "latent_full_attention_share", "state_pool_gb",
                        "lightning_attention_share", "hbm_peak_gb",
                        "attn_kernel_share.sessions"}
    # and no other cell reads the new metrics
    assert not {m["name"] for m in spec.load_cell(SIBLING).per_layer} & new
    for fn in ("gqa_full_bytes", "gqa_full_flops", "gqa_window_bytes"):
        assert callable(spec.bytes_fn(fn))


def test_the_catalog_keys_are_in_the_file_as_published():
    cfg = spec.load_cell(CELL).config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "laguna-s-2.1-ep8")
    assert entry["reduced"] == list(cfg["reduced_why"]) == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size",
        "max_position_embeddings"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json")
    row = None
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        for line in f:
            if json.loads(line)["name"] == "Laguna-S-2.1":
                row = json.loads(line)["config"]
    if row is None:
        pytest.skip("no catalog here")
    for k, v in row.items():
        if k not in entry["reduced"]:
            assert cfg[k] == v, k
    assert cfg["layer_types"] == row["layer_types"][:9]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (9, 32, 12544, 16384)
    assert cfg["published"]["num_experts"] == 256
    assert cfg["expert_group"] == {**cfg["expert_group"], "chips": 8,
                                   "first_expert": 0, "experts_per_chip": 32}
    assert "8 chips share each layer" in cfg["expert_group"]["what"]
    assert set(cfg["assumed"]) >= {"router", "qk_norm", "shared_expert",
                                   "rotary_layout"}
    assert cfg["deployment"]["ml"] == {
        "kv_quant": "none", "cont_max_slots": 16, "max_seq_len": 16384}


@pytest.fixture(scope="module")
def float32_hosting():
    import jax.numpy as jnp

    from tensorlink_tpu.models.registry import config_from_hf

    mp = pytest.MonkeyPatch()
    mp.setattr(cluster, "model_config_json",
               lambda c: config_from_hf(dict(c), dtype=jnp.float32).to_json())
    yield
    mp.undo()


def test_cpu_rehearsal_of_the_cell(float32_hosting):
    """The cell's plan at a tiny size through the whole harness: hosted by
    ``/request-model``, served by the slot engine over pages, rings and
    window snapshots, judged by ``laguna.py``; every counter metric of the
    new cell reads."""
    from benchmarks import run

    out = run.run_cell(tiny_cell(), 2**31 + 77, 4.0, True, platform="cpu")
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["gap_sigmas_max"]["value"] < 0.01
    m = out["metrics"]
    assert m["compiles_in_window.sessions"]["value"] == 0.0
    assert m["window_pool_gb"]["value"] > 0
    assert m["window_restore_share"]["value"] > 50  # a tiny pool evicts
    assert 0 <= m["window_replay_share"]["value"] < 30
    assert 0 < m["window_page_share"]["value"] < 100
    assert 0 < m["expert_row_fill_share"]["value"] <= 100
    # the CPU runs the XLA forms: no kernel of those names, nothing read
    assert m["gqa_full_attention_share"]["value"] == 0.0
    assert m["gqa_window_attention_share"]["value"] == 0.0
    assert "gqa_full_attention_roofline_share" not in m
    assert "gqa_window_attention_roofline_share" not in m


CHUNKS = [
    # two decoding slots that grow by the chunk's 8 steps (one past the
    # window, one under it), one mid-prefill slot, one free slot
    {"t0": 10.0, "decode_steps": 8, "ctx_before": [13000, 100, 12288, 0],
     "ctx_after": [13008, 108, 12288, 0]},
    # a chunk that only prefills: one pass
    {"t0": 11.0, "decode_steps": 1, "ctx_after": [40, 16, 0, 0]},
]


def test_bytes_and_operations_of_hand_made_chunks():
    model = deployed_model()
    assert position_bytes(model) == 2 * 8 * 128 * 2 == 4096
    assert flops_per_row_position(model) == 2 * 48 * 256 == 24576
    ragged = 13000 + 100 + 12288
    step = 13004 + 104
    got = full_by_pass(CHUNKS, model)
    assert got == [ragged * 4096 * 3] + [step * 4096 * 3] * 7 + [
        56 * 4096 * 3]
    assert gqa_full_bytes(CHUNKS, model) == sum(got)
    win = window_by_pass(CHUNKS, model)
    assert win == [(512 + 100 + 512) * 4096 * 6] + [
        (512 + 104) * 4096 * 6] * 7 + [56 * 4096 * 6]
    assert gqa_window_bytes(CHUNKS, model) == sum(win)
    per = 24576 * 3
    assert flops_by_pass(CHUNKS, model) == (
        [ragged * per] + [step * per] * 7 + [56 * per])
    granted = [{**CHUNKS[0], "prefill_granted": 130}, CHUNKS[1]]
    mean = ragged / 3
    assert flops_by_pass(granted, model)[0] == (
        ragged + 127 * (mean - 64)) * per
    # a continuation step is bandwidth bound, a prefill block compute bound
    b, f = full_by_pass(granted, model), flops_by_pass(granted, model)
    assert b[1] / 819e9 > f[1] / 197e12 and b[0] / 819e9 < f[0] / 197e12


CONTROLS = [("window_delta", 1, "window"), ("window_delta", -1, "window"),
            ("sliding_heads", 4, "window"), ("no_yarn", True, "full"),
            ("full_rotary", True, "full"), ("gate", False, "full"),
            ("router", "softmax", "route"), ("routed_scale", 1.0, "experts"),
            ("int8_rows", True, "rows")]


@pytest.mark.parametrize("key,value,held", CONTROLS,
                         ids=[f"{c[0]}-{c[1]}" for c in CONTROLS])
def test_each_planted_fault_reads_over_its_limit(key, value, held):
    """The reference with one fault (the program sound) through
    ``layer_gaps`` at the tiny size: the held number of that mechanism
    reads over the fixture's limit, by far; sound reads under 1e-4."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import laguna as ref
    from tensorlink_tpu.models.registry import config_from_hf
    from tensorlink_tpu.models.transformer import init_params

    cfg = config_from_hf(TINY, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(0, 512, size=210)
    tol = spec.load_tolerance(TINY)
    sound = ref.layer_gaps(params, tokens, ref.arch_of(TINY), 6)
    assert all(sound[n] < 1e-4 for n, _ in ref.HELD) and sound["agree"] == 1.0
    bad = ref.layer_gaps(params, tokens, {**ref.arch_of(TINY), key: value}, 6)
    assert bad[held] > 3 * tol[dict(ref.HELD)[held]], (key, bad)
