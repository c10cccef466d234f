"""``olmo-hybrid-7b-l16`` in the harness at a tiny size on the CPU: the new
files load by name, the cell's plan runs end to end through
``POST /request-model`` and the slot engine with ``correct`` decided by the
configuration's own reference (served tokens; a gated-delta layer across a
chunk's edge and a restored snapshot of state and tail, the state itself, an
attention layer through pages, cached rows), the bytes and operations
functions read hand-made chunks at the published sizes, and the traffic
file's plan never passes the context. The planted faults are held in tier-1
(tests/test_olmo_hybrid.py). No number from here is a device metric."""

import json

import pytest

from benchmarks.bytes_fns import (
    gated_delta_chunk_bytes, gated_delta_chunk_flops, gated_delta_flops,
    gated_delta_state_bytes, gated_delta_step_bytes, gated_delta_step_flops,
    gqa_full_flops_g1,
)
from benchmarks.bytes_fns.gqa_full_bytes import (
    by_pass as full_by_pass, position_bytes)
from benchmarks.harness import cluster, spec

CELL = "olmo-hybrid-7b-l16.agent-tool-sessions"
SIBLING = "lfm2-8b-a1b-l12.long-doc-sessions"

TINY = dict(
    model_type="olmo_hybrid", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=4, hidden_act="silu", max_position_embeddings=320,
    attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
    layer_types=["linear_attention"] * 3 + ["full_attention"]
    + ["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={"rope_theta": None},
    served_name="tiny-olmo-hybrid", torch_dtype="float32",
    correct={"reference": "olmo_hybrid", "tolerance": "fixture_olmo_hybrid"},
    deployment={"chips": 1, "seq_len": 320, "ml": {
        "max_seq_len": 320, "seq_buckets": [64, 128, 320],
        "cont_max_slots": 4, "prefill_chunk": 32, "cont_page_size": 8,
        "cont_chunk_steps": 4, "kv_quant": "none"}},
)


def tiny_cell():
    bench = spec.load_benchmark()
    traffic = {**spec.load_traffic("agent-tool-sessions"), "clients": 2,
               "turns": 2, "system_tokens": 128, "user_tokens": [8, 24],
               "answer_tokens": [4, 8], "cycles": 8}
    return spec.make_cell(
        name=CELL, config=dict(TINY), traffic=traffic, chips=1,
        config_name="tiny-olmo-hybrid", traffic_name="agent-tool-sessions",
        bench=bench)


def deployed_model() -> dict:
    cfg = spec.load_cell(CELL).config
    return cluster.deployed_model(cfg, cluster.ml_config(cfg["deployment"]))


NEW = {"gated_delta_attention_share", "gated_delta_step_roofline_share",
       "gated_delta_chunk_roofline_share",
       "gqa_full_attention_roofline_share.g1"}


def test_the_new_cell_resolves_all_its_files_by_name():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "olmo-hybrid-7b-l16", "agent-tool-sessions", 1)
    assert {m["name"] for m in cell.end_to_end} >= {
        "tpot_p50_ms.sessions", "out_tok_s.sessions", "setup_s"}
    for m in cell.per_layer:
        met = spec.load_layer_metric(m["name"])
        assert spec.reader(met["kind"]).read
        for key in ("bytes_fn", "flops_fn"):
            if key in met:
                assert callable(spec.bytes_fn(met[key]))
    assert spec.reference(cell.config).__name__.endswith("olmo_hybrid")
    tol = spec.load_tolerance(cell.config)
    assert tol["prompt_tokens"] >= cell.traffic["system_tokens"]
    assert {"max_delta_gap", "max_state_gap", "max_state0_gap",
            "max_full_gap", "max_row_gap"} <= set(tol)
    ml = cluster.ml_config(cell.config["deployment"])
    assert (ml.kv_quant, ml.cont_max_slots, ml.max_seq_len) == (
        "none", 8, 8192)
    model = cluster.model_config_json(cell.config)
    assert model["family"] == "olmo_hybrid"
    assert model["layer_kinds"].count("gated_delta") == 12
    assert model["norm_position"] == "post"
    names = {m["name"] for m in cell.per_layer}
    assert NEW | {"cont_step_ms.sessions", "ragged_pass_ms.sessions",
                  "device_idle_share.sessions", "gqa_full_attention_share",
                  "state_restore_share", "state_replay_share",
                  "state_pool_gb", "step_build_s",
                  "step_build_waited_s"} <= names
    # what it has not: experts, rings, tails counted as conv_*, a head of 64
    assert not names & {"window_pool_gb", "conv_pool_gb", "latent_pool_gb",
                        "expert_row_fill_share", "lightning_attention_share",
                        "gqa_full_attention_roofline_share",
                        "gqa_full_attention_roofline_share.h64"}
    # and no other cell reads the new metrics
    assert not {m["name"] for m in spec.load_cell(SIBLING).per_layer} & NEW


def test_the_catalog_keys_are_in_the_file_as_published():
    cfg = spec.load_cell(CELL).config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "olmo-hybrid-7b-l16")
    assert entry["reduced"] == list(cfg["reduced_why"]) == [
        "num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    row = None
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        for line in f:
            if json.loads(line)["name"] == "Olmo-Hybrid-7B":
                row = json.loads(line)["config"]
    if row is None:
        pytest.skip("no catalog here")
    for k, v in row.items():
        if k not in entry["reduced"]:
            assert cfg[k] == v, k
    assert cfg["layer_types"] == row["layer_types"][:16]
    assert cfg["published"]["layer_types"] == row["layer_types"]
    assert (cfg["num_hidden_layers"], cfg["max_position_embeddings"]) == (
        16, 8192)
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["max_position_embeddings"]) == (32, 65536)
    assert cfg["pipeline"] == {**cfg["pipeline"], "chips": 2, "stage": 0,
                               "layers_per_stage": 16}
    assert set(cfg["assumed"]) >= {
        "head_dim", "norm_after_branch", "qk_norm_full", "no_rotary",
        "gated_delta_layer", "state_dtype"}
    assert cfg["deployment"]["ml"] == {
        "kv_quant": "none", "cont_max_slots": 8, "max_seq_len": 8192}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 77, 2**32 - 1])
def test_the_traffic_files_contexts_never_pass_the_context(seed):
    """Every turn's prompt (the system prompt, the session's history, the
    new message, a chat template's tokens a message) and its answer fit the
    8,192 positions of a slot, whatever the seed."""
    cell = spec.load_cell(CELL)
    t = cell.traffic
    assert (t["clients"], t["turns"], t["system_tokens"], t["user_tokens"],
            t["answer_tokens"], t["spacing"], t["cycles"],
            t["plan_seed"]) == (8, 4, 4096, [192, 640], [64, 128], "linear",
                                16, 11)
    plan = spec.generator(t["kind"]).plan(
        t, cell.params, seed, 10.0, cell.config["deployment"])
    assert plan.mode == "closed" and len(plan.clients) == 8
    template = 32  # a generous count of template tokens a message
    worst = least = None
    for reqs in plan.clients:
        ctx = {}
        for r in reqs:
            used = ctx.get(r.session, plan.system_tokens + template)
            used += r.prompt_tokens + template  # the prompt as sent
            least = used if least is None else min(least, used)
            used += r.output_tokens + template
            ctx[r.session] = used
            worst = max(worst or 0, used)
    assert worst <= 8192 and least >= 4096 + 192
    assert worst > 6000  # and the contexts are the cell's 4.4k-7.3k


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
    ``/request-model``, served by the slot engine over pages, states, tails
    and two-array snapshots, judged by ``olmo_hybrid.py``; every counter
    metric of the new cell reads."""
    from benchmarks import run

    out = run.run_cell(tiny_cell(), 2**31 + 77, 4.0, True, platform="cpu")
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["gap_sigmas_max"]["value"] < 0.01
    m = out["metrics"]
    assert m["compiles_in_window.sessions"]["value"] == 0.0
    assert m["state_pool_gb"]["value"] > 0
    assert m["state_restore_share"]["value"] > 50  # a tiny pool evicts
    assert 0 <= m["state_replay_share"]["value"] < 30
    # the CPU runs the XLA forms: no kernel of that name, nothing read
    assert m["gqa_full_attention_share"]["value"] == 0.0
    assert m["gated_delta_attention_share"]["value"] == 0.0
    assert not set(m) & (NEW - {"gated_delta_attention_share"})


CHUNKS = [
    # two decoding slots that grow by the chunk's 8 steps, one mid-prefill
    # slot, one free slot
    {"t0": 10.0, "decode_steps": 8, "ctx_before": [7000, 100, 4096, 0],
     "ctx_after": [7008, 108, 4096, 0]},
    # a chunk that only prefills: one pass
    {"t0": 11.0, "decode_steps": 1, "ctx_after": [40, 16, 0, 0]},
]


def test_bytes_and_operations_of_hand_made_chunks():
    model = deployed_model()
    # a slot and layer: 30 heads of 96 x 192 float32, unpadded
    one = 4 * 30 * 96 * 192
    assert gated_delta_state_bytes.state_bytes(model) == one == 2_211_840
    assert gated_delta_state_bytes.layers_of(model) == 12
    slot = 2 * one * 12  # read and written, every gated-delta layer
    both = gated_delta_state_bytes.by_pass(CHUNKS, model)
    assert both == [3 * slot] + [2 * slot] * 7 + [2 * slot]
    assert gated_delta_state_bytes.gated_delta_state_bytes(
        CHUNKS, model) == sum(both)
    # each kernel's share counts its own passes: the chunk form the ragged
    # passes, the step the continuation steps
    chunk = gated_delta_chunk_bytes.by_pass(CHUNKS, model)
    step = gated_delta_step_bytes.by_pass(CHUNKS, model)
    assert chunk == [3 * slot] + [0] * 7 + [2 * slot]
    assert step == [0] + [2 * slot] * 7 + [0]
    assert [a + b for a, b in zip(chunk, step)] == both
    row = 6 * 96 * 192 * 30 * 12
    assert gated_delta_flops.flops_per_row(model) == row
    granted = [{**CHUNKS[0], "prefill_granted": 130}, CHUNKS[1]]
    assert gated_delta_flops.by_pass(granted, model) == (
        [130 * row] + [2 * row] * 7 + [2 * row])
    assert gated_delta_chunk_flops.by_pass(granted, model) == (
        [130 * row] + [0] * 7 + [2 * row])
    assert gated_delta_step_flops.by_pass(granted, model) == (
        [0] + [2 * row] * 7 + [0])
    assert gated_delta_chunk_flops.gated_delta_chunk_flops(
        granted, model) == 132 * row
    # both kernels are bandwidth bound on the state by these counts: a
    # block of 130 rows does 0.17 ms of operations beside 0.19 ms of bytes
    assert 3 * slot / 819e9 > 130 * row / 197e12
    # the walk: 30 kv heads of 128, one query head a kv head
    assert position_bytes(model) == 2 * 30 * 128 * 2 == 15_360
    assert gqa_full_flops_g1.flops_per_row_position(model) == (
        2 * 30 * 256) == 15_360
    ragged, grew = 7000 + 100 + 4096, 7004 + 104
    assert full_by_pass(CHUNKS, model) == (
        [ragged * 15_360 * 4] + [grew * 15_360 * 4] * 7 + [56 * 15_360 * 4])
    got = gqa_full_flops_g1.by_pass(CHUNKS, model)
    assert got == [ragged * 15_360 * 4] + [grew * 15_360 * 4] * 7 + [
        56 * 15_360 * 4]
    assert gqa_full_flops_g1.gqa_full_flops_g1(CHUNKS, model) == sum(got)
    # a continuation step is bandwidth bound (1 FLOP a byte)
    b = full_by_pass(CHUNKS, model)
    assert b[1] / 819e9 > got[1] / 197e12
