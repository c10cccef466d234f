"""``deepseek-v2-ep8`` in the harness at a tiny size on the CPU: the new
files load by name, the cell's plan runs end to end through
``POST /request-model`` and the slot engine with ``correct`` decided by the
configuration's own reference, the new reader, the two functions it calls
and each new layer-metric file read hand-made chunks and traces, and the
reference's named faults each read over the tolerance's limit. No number
from here is a device metric."""

import json

import numpy as np
import pytest

from benchmarks.bytes_fns.latent_full_bytes import (
    by_pass as bytes_by_pass, latent_full_bytes, row_bytes)
from benchmarks.bytes_fns.latent_full_flops import (
    by_pass as flops_by_pass, flops_per_row_position, latent_full_flops)
from benchmarks.harness import cluster, spec, xplane
from benchmarks.harness.obs import Obs
from benchmarks.readers import trace_roofline_max
from benchmarks.tests.test_collectives_readers import trace_of

CELL = "deepseek-v2-ep8.long-doc-sessions"
SIBLING = "dots3-note-prev-ep8.long-doc-sessions"

TINY = dict(
    model_type="deepseek_v2", hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, rope_theta=1e4,
    rope_scaling=dict(
        type="yarn", factor=40, original_max_position_embeddings=16,
        beta_fast=32, beta_slow=1, mscale=0.707, mscale_all_dim=0.707),
    first_k_dense_replace=1, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=4, n_shared_experts=2, num_experts_per_tok=3,
    norm_topk_prob=False, routed_scaling_factor=16, scoring_func="softmax",
    topk_method="group_limited_greedy", n_group=4, topk_group=2,
    rms_norm_eps=1e-6, vocab_size=512, max_position_embeddings=256,
    tie_word_embeddings=False,
    published={"n_routed_experts": 16}, expert_group={"first_expert": 4},
    served_name="tiny-deepseek-v2", torch_dtype="float32",
    correct={"reference": "deepseek_v2", "tolerance": "fixture_deepseek_v2"},
    deployment={"chips": 1, "seq_len": 256, "ml": {
        "max_seq_len": 256, "seq_buckets": [64, 128, 256],
        "cont_max_slots": 4, "prefill_chunk": 32, "cont_page_size": 8,
        "cont_chunk_steps": 4, "kv_quant": "none"}},
)


def tiny_cell():
    bench = spec.load_benchmark()
    traffic = {**spec.load_traffic("long-doc-sessions"), "clients": 2,
               "turns": 2, "system_tokens": 96, "user_tokens": [8, 24],
               "answer_tokens": [4, 8], "cycles": 8}
    return spec.make_cell(
        name=CELL, config=dict(TINY), traffic=traffic, chips=1,
        config_name="tiny-deepseek-v2", traffic_name="long-doc-sessions",
        bench=bench)


def deployed_model() -> dict:
    cfg = spec.load_cell(CELL).config
    return cluster.deployed_model(cfg, cluster.ml_config(cfg["deployment"]))


def test_the_new_cell_resolves_all_its_files_by_name():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "deepseek-v2-ep8", "long-doc-sessions", 1)
    # the sibling's traffic file, unchanged: request for request
    assert cell.traffic == spec.load_cell(SIBLING).traffic
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms.sessions", "out_tok_s.sessions", "setup_s"}
    for m in cell.per_layer:
        kind = spec.load_layer_metric(m["name"])["kind"]
        assert spec.reader(kind).read
    assert spec.reference(cell.config).__name__.endswith("deepseek_v2")
    tol = spec.load_tolerance(cell.config)
    assert tol["prompt_tokens"] > cell.traffic["system_tokens"]
    assert {"max_row_gap", "max_full_gap", "max_route_gap",
            "max_expert_gap"} <= set(tol)
    ml = cluster.ml_config(cell.config["deployment"])
    assert (ml.kv_quant, ml.cont_max_slots, ml.max_seq_len) == ("none", 16, 16384)
    model = cluster.model_config_json(cell.config)
    assert model["family"] == "deepseek_v2" and model["experts_held"] == 20
    assert (model["n_experts"], model["moe_n_group"]) == (160, 8)
    names = {m["name"] for m in cell.per_layer}
    assert {"latent_full_attention_share",
            "latent_full_attention_roofline_share", "expert_group_reach_share",
            "expert_load_max_over_mean.ep20", "latent_rows_read_share",
            "expert_row_fill_share", "experts_touched_share", "latent_pool_gb",
            "cont_step_ms.sessions", "ragged_pass_ms.sessions"} <= names
    # what it has not: a selector, a window, 32 held experts
    assert not names & {"select_keep_share", "window_page_share",
                        "latent_window_attention_share",
                        "latent_window_attention_roofline_share",
                        "expert_load_max_over_mean",
                        "attn_kernel_share.sessions"}
    # and the sibling's cell reads none of the new metrics
    assert not {m["name"] for m in spec.load_cell(SIBLING).per_layer} & {
        "latent_full_attention_share", "expert_group_reach_share",
        "expert_load_max_over_mean.ep20", "latent_rows_read_share"}
    for fn in ("latent_full_bytes", "latent_full_flops"):
        assert callable(spec.bytes_fn(fn))


def test_the_catalog_keys_are_in_the_file_as_published():
    """Every number of the catalog row's ``config`` under the same key but
    the four reduced ones, each of those with a reason, the published
    counts and the group-a-chip deployment."""
    cfg = spec.load_cell(CELL).config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "deepseek-v2-ep8")
    assert set(entry["reduced"]) == set(cfg["reduced_why"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json")
    row = None
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        for line in f:
            if json.loads(line)["name"] == "DeepSeek-V2":
                row = json.loads(line)["config"]
    if row is None:
        pytest.skip("no catalog here")
    for k, v in row.items():
        if k not in entry["reduced"]:
            assert cfg[k] == v, k
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["max_position_embeddings"]) == (
                6, 20, 12800, 16384)
    pub, group = cfg["published"], cfg["expert_group"]
    assert (pub["n_routed_experts"], pub["num_hidden_layers"],
            pub["vocab_size"]) == (160, 60, 102400)
    assert (group["chips"], group["first_expert"],
            group["experts_per_chip"]) == (8, 0, 20)
    assert cfg["n_routed_experts"] * group["chips"] == pub["n_routed_experts"]
    assert pub["n_routed_experts"] // cfg["n_group"] == group["experts_per_chip"]
    assert cfg["assumed"] and cfg["deployment"]["ml"]["kv_quant"] == "none"


def test_cpu_rehearsal_of_the_cell(monkeypatch):
    """The cell's plan at a tiny size through the whole harness: hosted by
    ``/request-model``, served by the slot engine over latent pages of one
    kind, judged by ``deepseek_v2.py``; every counter metric of the new
    cell reads. Hosted in float32 (at a width of 64, bfloat16 noise turns
    the experts' picks and the streams part)."""
    import jax.numpy as jnp

    from benchmarks import run
    from tensorlink_tpu.models.registry import config_from_hf

    monkeypatch.setattr(
        cluster, "model_config_json",
        lambda c: config_from_hf(dict(c), dtype=jnp.float32).to_json())
    out = run.run_cell(tiny_cell(), 2**31 + 77, 4.0, True, platform="cpu")
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["gap_sigmas_max"]["value"] < 0.01
    m = out["metrics"]
    assert m["compiles_in_window.sessions"]["value"] == 0.0
    assert 0 < m["expert_row_fill_share"]["value"] <= 100
    assert m["expert_load_max_over_mean.ep20"]["value"] > 0
    assert 0 < m["experts_touched_share"]["value"] <= 100
    assert 0 < m["expert_group_reach_share"]["value"] < 100
    assert 0 < m["latent_rows_read_share"]["value"] < 100
    assert m["latent_pool_gb"]["value"] > 0
    # the CPU runs the XLA fallback: no kernel of that name, nothing read
    assert m["latent_full_attention_share"]["value"] == 0.0
    assert "latent_full_attention_roofline_share" not in m
    assert "select_keep_share" not in m and "window_page_share" not in m


CHUNKS = [
    # 16-slot shapes in small: two decoding slots that grow by the chunk's
    # 8 steps, one mid-prefill slot (its context stands), one free slot
    {"t0": 10.0, "decode_steps": 8, "ctx_before": [12800, 100, 12416, 0],
     "ctx_after": [12808, 108, 12416, 0]},
    # a chunk that only prefills: one pass
    {"t0": 11.0, "decode_steps": 1, "ctx_after": [40, 16, 0, 0]},
]


def test_bytes_and_operations_of_hand_made_chunks():
    model = deployed_model()
    assert row_bytes(model) == 640 * 2
    assert flops_per_row_position(model) == 2 * 128 * (576 + 512) == 278528
    per_b, per_f = 1280 * 6, 278528 * 6
    ragged0 = 12800 + 100 + 12416
    step0 = 12804 + 104  # the slots that grew, at their mean context
    got_b = bytes_by_pass(CHUNKS, model)
    assert got_b == [ragged0 * per_b] + [step0 * per_b] * 7 + [56 * per_b]
    assert latent_full_bytes(CHUNKS, model) == sum(got_b)
    # no grant recorded: one row a slot that holds context
    got_f = flops_by_pass(CHUNKS, model)
    assert got_f == [ragged0 * per_f] + [step0 * per_f] * 7 + [56 * per_f]
    # 131 rows granted over 3 slots: 128 more rows at the mean context
    # less half a block
    granted = [{**CHUNKS[0], "prefill_granted": 131}, CHUNKS[1]]
    more = flops_by_pass(granted, model)
    assert more[0] == (ragged0 + 128 * (ragged0 / 3 - 64)) * per_f
    assert more[1:] == got_f[1:]
    assert latent_full_flops(granted, model) == sum(more)
    # a continuation step sits by a hair on the bandwidth side of the ridge
    assert 0.9 < (step0 * per_f / 197e12) / (step0 * per_b / 819e9) < 1.0


def test_trace_roofline_max_reads_a_hand_made_trace():
    model = deployed_model()
    ops = [xplane.Op("%latent_full_attention.3 = custom-call()", 0.0, 1.0),
           xplane.Op("%latent_full_attention.4 = custom-call()", 1.0, 2.0),
           xplane.Op("%fusion.7 = fusion()", 2.0, 8.0)]
    records = [{"t0": 10.0004, "prefill_granted": 131, "step": 5},
               {"t0": 11.3, "prefill_granted": 999, "step": 6}]  # not near
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    obs = Obs(mode="closed", recs=[], t0=0, t1=1, grace=0, stats0={},
              stats1={}, trace=trace_of(ops), chunks=CHUNKS, model=model,
              peaks=peaks, recorder=records)
    stamped = trace_roofline_max.with_grants(CHUNKS, records)
    assert stamped[0]["prefill_granted"] == 131
    assert "prefill_granted" not in stamped[1]
    assert "prefill_granted" not in CHUNKS[0]  # the record is not edited

    def read(name, o=obs):
        s = spec.load_layer_metric(name)
        return spec.reader(s["kind"]).read(o, s)

    assert read("latent_full_attention_share") == pytest.approx(25.0)
    b, f = bytes_by_pass(stamped, model), flops_by_pass(stamped, model)
    least = sum(max(x / 819e9, y / 197e12) for x, y in zip(b, f))
    # the ragged pass of the first chunk is compute bound (128 prefill
    # rows), its continuation steps bandwidth bound: a sum of both kinds
    assert f[0] / 197e12 > b[0] / 819e9 and f[1] / 197e12 < b[1] / 819e9
    assert read("latent_full_attention_roofline_share") == pytest.approx(
        least / 2.0 * 100.0)
    # a program without the kernel (the parent): nothing to read, no error
    bare = Obs(mode="closed", recs=[], t0=0, t1=1, grace=0, stats0={},
               stats1={}, trace=trace_of(ops[2:]), chunks=CHUNKS, model=model,
               peaks=peaks)
    assert read("latent_full_attention_roofline_share", bare) is None
    assert read("latent_full_attention_share", bare) == pytest.approx(0.0)


def test_counter_metrics_read_a_fixture():
    s0 = dict.fromkeys(("moe_rows_routed_local", "moe_rows_busiest_expert",
                        "moe_rows_in_group", "moe_rows_valid",
                        "latent_rows_read", "latent_rows_capacity"), 10)
    s1 = {"moe_rows_routed_local": 10 + 120, "moe_rows_busiest_expert": 10 + 18,
          "moe_rows_in_group": 10 + 375, "moe_rows_valid": 10 + 1000,
          "latent_rows_read": 10 + 12800, "latent_rows_capacity": 10 + 16384}
    obs = Obs(mode="closed", recs=[], t0=0, t1=1, grace=0, stats0=s0, stats1=s1)

    def read(name, o=obs):
        s = spec.load_layer_metric(name)
        return spec.reader(s["kind"]).read(o, s)

    assert read("expert_group_reach_share") == pytest.approx(37.5)
    assert read("expert_load_max_over_mean.ep20") == pytest.approx(18 / (120 / 20))
    assert read("latent_rows_read_share") == pytest.approx(78.125)
    # the parent has none of these counters: nothing to read, no error
    empty = Obs(mode="closed", recs=[], t0=0, t1=1, grace=0, stats0={}, stats1={})
    for name in ("expert_group_reach_share", "latent_rows_read_share",
                 "expert_load_max_over_mean.ep20"):
        assert read(name, empty) is None


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from tensorlink_tpu.models.registry import config_from_hf
    from tensorlink_tpu.models.transformer import init_params

    cfg = config_from_hf(TINY, dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


# a fault is the REFERENCE's: the program's side of the layer-matched
# comparison is built from the sound file (``arch["config"]``)
FAULTS = {
    "rotary positions without YaRN": {"yarn": False},
    "m^2 left out of the softmax scale": {"mscale": False},
    "group limit dropped": {"group_limit": False},
    "one shared expert for two": {"shared_halved": True},
    "int8 rows": {"int8_rows": True},
}
# each has to fail by a layer-matched number of its own, whatever the
# tokens say (a served token is discrete: on the chip a sound stream read
# up to 1.37 deviations and the dropped group limit 1.28 on one prompt):
# the column after the tokens' it reads over in
LAYER_MATCHED = {"int8 rows": 0, "rotary positions without YaRN": 1,
                 "m^2 left out of the softmax scale": 1,
                 "group limit dropped": 2, "one shared expert for two": 3}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_named_fault_reads_over_the_limit(tiny_model, fault):
    """Tokens chosen greedily by the sound reference are the "served"
    stream; a reference with one fault ranks them under its own maximum or
    parts from the program's layers, over ``fixture_deepseek_v2.json``'s
    limits, while the sound one reads 0 on its own choices. ``served_gaps``
    is what ``harness/correct.py`` calls: its last two columns are the
    program's layers on the reference's own hidden states (cached rows;
    what a layer's attention adds; the share of rows routed otherwise; what
    the experts add), each on ``max_gap_sigmas``' scale."""
    from benchmarks.reference import deepseek_v2 as ref

    cfg, params = tiny_model
    tol = spec.load_tolerance(TINY)
    limit = float(tol["max_gap_sigmas"])
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, size=60).tolist() for _ in range(2)]
    arch = ref.arch_of(TINY)
    served = []
    for p in prompts:
        seq = list(p)
        for _ in range(8):
            lg = ref.forward_logits(params, np.asarray([seq]), arch,
                                    slice(len(seq) - 1, len(seq)))
            seq.append(int(lg[0, 0].argmax()))
        served.append(seq[len(p):])
    sound = ref.served_gaps(params, prompts, served, arch)
    assert sound.shape == (2, 8 + len(ref.HELD))
    assert sound[:, :8].max() == 0.0 and sound[:, 8:].max() < limit / 100
    gaps = ref.served_gaps(params, prompts, served, {**arch, **FAULTS[fault]})
    assert gaps.max() > limit, fault
    col = 8 + LAYER_MATCHED[fault]
    assert gaps[:, col].max() > limit, (fault, gaps[:, 8:])
