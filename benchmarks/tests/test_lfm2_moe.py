"""``lfm2-8b-a1b-l12`` in the harness at a tiny size on the CPU: the new
files load by name, the cell's plan runs end to end through
``POST /request-model`` and the slot engine with ``correct`` decided by the
configuration's own reference (served tokens; a conv layer across a chunk's
edge and a restored snapshot, an attention layer through pages of keys
beside values, cached rows, the picks, the picked weights and the routed
sum), and the operations function reads hand-made chunks. The planted
faults are held in tier-1 (tests/test_lfm2.py). No number from here is a
device metric."""

import json

import pytest

from benchmarks.bytes_fns.gqa_full_bytes import (
    by_pass as full_by_pass, position_bytes)
from benchmarks.bytes_fns.gqa_full_flops_h64 import (
    by_pass as flops_by_pass, flops_per_row_position, gqa_full_flops_h64)
from benchmarks.harness import cluster, spec

CELL = "lfm2-8b-a1b-l12.long-doc-sessions"
SIBLING = "laguna-s-2.1-ep8.long-doc-sessions"

TINY = dict(
    model_type="lfm2_moe", hidden_size=64, intermediate_size=128,
    num_hidden_layers=12, num_attention_heads=4, num_key_value_heads=2,
    head_dim=64, vocab_size=512, max_position_embeddings=320, norm_eps=1e-5,
    conv_L_cache=3, conv_bias=False, num_dense_layers=2, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32, norm_topk_prob=True,
    use_expert_bias=True, routed_scaling_factor=1, rope_theta=1000000,
    layer_types=["conv", "conv", "full_attention", "conv"] * 3,
    tie_word_embeddings=True,
    served_name="tiny-lfm2", torch_dtype="float32",
    correct={"reference": "lfm2_moe", "tolerance": "fixture_lfm2_moe"},
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
        config_name="tiny-lfm2", traffic_name="long-doc-sessions",
        bench=bench)


def deployed_model() -> dict:
    cfg = spec.load_cell(CELL).config
    return cluster.deployed_model(cfg, cluster.ml_config(cfg["deployment"]))


def test_the_new_cell_resolves_all_its_files_by_name():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "lfm2-8b-a1b-l12", "long-doc-sessions", 1)
    assert cell.traffic == spec.load_cell(SIBLING).traffic  # unchanged
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms.sessions", "out_tok_s.sessions", "setup_s"}
    for m in cell.per_layer:
        kind = spec.load_layer_metric(m["name"])["kind"]
        assert spec.reader(kind).read
    assert spec.reference(cell.config).__name__.endswith("lfm2_moe")
    tol = spec.load_tolerance(cell.config)
    assert tol["prompt_tokens"] >= cell.traffic["system_tokens"]
    assert {"max_conv_gap", "max_full_gap", "max_row_gap", "max_pick_gap",
            "max_route_gap", "max_expert_gap"} <= set(tol)
    ml = cluster.ml_config(cell.config["deployment"])
    assert (ml.kv_quant, ml.cont_max_slots, ml.max_seq_len) == (
        "none", 16, 16384)
    model = cluster.model_config_json(cell.config)
    assert model["family"] == "lfm2_moe"
    assert model["layer_kinds"].count("conv") == 9
    names = {m["name"] for m in cell.per_layer}
    new = {"conv_restore_share", "conv_replay_share", "conv_pool_gb",
           "gqa_full_attention_roofline_share.h64"}
    assert new | {"cont_step_ms.sessions", "ragged_pass_ms.sessions",
                  "device_idle_share.sessions", "expert_row_fill_share",
                  "expert_load_max_over_mean", "experts_touched_share",
                  "gqa_full_attention_share"} <= names
    # what it has not: rings, latent pools, a selector, recurrent states,
    # a head count a layer (the accepted roofline's operations function)
    assert not names & {"window_pool_gb", "window_page_share",
                        "gqa_window_attention_share", "latent_pool_gb",
                        "gqa_full_attention_roofline_share", "state_pool_gb",
                        "select_keep_share", "attn_kernel_share.sessions"}
    # and no other cell reads the new metrics
    assert not {m["name"] for m in spec.load_cell(SIBLING).per_layer} & new
    for fn in ("gqa_full_bytes", "gqa_full_flops_h64"):
        assert callable(spec.bytes_fn(fn))


def test_the_catalog_keys_are_in_the_file_as_published():
    cfg = spec.load_cell(CELL).config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "lfm2-8b-a1b-l12")
    assert entry["reduced"] == list(cfg["reduced_why"]) == [
        "num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    row = None
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        for line in f:
            if json.loads(line)["name"] == "LFM2-8B-A1B":
                row = json.loads(line)["config"]
    if row is None:
        pytest.skip("no catalog here")
    for k, v in row.items():
        if k not in entry["reduced"]:
            assert cfg[k] == v, k
    assert cfg["layer_types"] == row["layer_types"][:12]
    assert cfg["published"]["layer_types"] == row["layer_types"]
    assert (cfg["num_hidden_layers"], cfg["max_position_embeddings"]) == (
        12, 16384)
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["max_position_embeddings"]) == (24, 128000)
    assert cfg["pipeline"] == {**cfg["pipeline"], "chips": 2, "stage": 0,
                               "layers_per_stage": 12}
    assert set(cfg["assumed"]) >= {
        "tie_word_embeddings", "head_dim", "qk_norm", "conv_operator",
        "router", "expert_bias_seed", "tail_dtype"}
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
    ``/request-model``, served by the slot engine over pages, tails and
    tail snapshots, judged by ``lfm2_moe.py``; every counter metric of the
    new cell reads."""
    from benchmarks import run

    out = run.run_cell(tiny_cell(), 2**31 + 77, 4.0, True, platform="cpu")
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["gap_sigmas_max"]["value"] < 0.01
    m = out["metrics"]
    assert m["compiles_in_window.sessions"]["value"] == 0.0
    assert m["conv_pool_gb"]["value"] > 0
    assert m["conv_restore_share"]["value"] > 50  # a tiny pool evicts
    assert 0 <= m["conv_replay_share"]["value"] < 30
    assert 0 < m["expert_row_fill_share"]["value"] <= 100
    assert 0 < m["experts_touched_share"]["value"] <= 100
    # the CPU runs the XLA forms: no kernel of that name, nothing read
    assert m["gqa_full_attention_share"]["value"] == 0.0
    assert "gqa_full_attention_roofline_share.h64" not in m


CHUNKS = [
    # two decoding slots that grow by the chunk's 8 steps, one mid-prefill
    # slot, one free slot
    {"t0": 10.0, "decode_steps": 8, "ctx_before": [13000, 100, 12288, 0],
     "ctx_after": [13008, 108, 12288, 0]},
    # a chunk that only prefills: one pass
    {"t0": 11.0, "decode_steps": 1, "ctx_after": [40, 16, 0, 0]},
]


def test_bytes_and_operations_of_hand_made_chunks():
    model = deployed_model()
    assert position_bytes(model) == 2 * 8 * 64 * 2 == 2048
    assert flops_per_row_position(model) == 2 * 32 * 128 == 8192
    ragged = 13000 + 100 + 12288
    step = 13004 + 104
    assert full_by_pass(CHUNKS, model) == (
        [ragged * 2048 * 3] + [step * 2048 * 3] * 7 + [56 * 2048 * 3])
    per = 8192 * 3
    got = flops_by_pass(CHUNKS, model)
    assert got == [ragged * per] + [step * per] * 7 + [56 * per]
    assert gqa_full_flops_h64(CHUNKS, model) == sum(got)
    granted = [{**CHUNKS[0], "prefill_granted": 130}, CHUNKS[1]]
    mean = ragged / 3
    assert flops_by_pass(granted, model)[0] == (
        ragged + 127 * (mean - 64)) * per
    # a continuation step is bandwidth bound (4 FLOP a byte), and at 32
    # heads of 64 so is a block with ONE prefilling slot of 128 rows beside
    # two long decoding ones (0.19 ms of bytes, 0.14 ms of operations)
    b, f = full_by_pass(granted, model), flops_by_pass(granted, model)
    assert b[1] / 819e9 > f[1] / 197e12 and b[0] / 819e9 > f[0] / 197e12
