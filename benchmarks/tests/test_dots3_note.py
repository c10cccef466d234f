"""``dots3-note-prev-ep8`` in the harness at a tiny size on the CPU: the new
files load by name, the cell's plan runs end to end through
``POST /request-model`` and the slot engine with ``correct`` decided by the
configuration's own reference, each new layer-metric file reads a fixture,
and the reference's named faults each read over the tolerance's limit. No
number from here is a device metric."""

import json

import numpy as np
import pytest

from benchmarks.bytes_fns.latent_window_bytes import (
    latent_window_bytes, row_bytes, sliding_layers)
from benchmarks.harness import cluster, spec, xplane
from benchmarks.harness.obs import Obs
from benchmarks.tests.test_collectives_readers import trace_of

CELL = "dots3-note-prev-ep8.long-doc-sessions"
CONTROL = "qwen3-4b.long-cache-decode"

TINY = dict(
    model_type="dots3_note", hidden_size=64, num_hidden_layers=5,
    layer_types=["full_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention"],
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, rope_theta=8e7,
    swa_num_attention_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=24,
    swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=8, swa_v_head_dim=8,
    swa_rope_theta=5e4, sliding_window_size=17, index_n_heads=4,
    index_head_dim=16, index_topk=24, apply_mla_qkv_lora_rescale=True,
    first_k_dense_replace=1, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
    norm_topk_prob=True, routed_scaling_factor=1, scoring_func="sigmoid",
    rms_norm_eps=1e-5, vocab_size=512, max_position_embeddings=256,
    tie_word_embeddings=False,
    published={"n_routed_experts": 16}, expert_group={"first_expert": 4},
    served_name="tiny-dots3", torch_dtype="float32",
    correct={"reference": "dots3_note", "tolerance": "fixture_dots3"},
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
        config_name="tiny-dots3", traffic_name="long-doc-sessions", bench=bench)


def test_the_new_files_load_by_name():
    for name in (CELL, CONTROL):
        cell = spec.load_cell(name)
        assert cell.traffic["kind"] == "sessions"
        e2e = {m["name"] for m in cell.end_to_end}
        # the new configuration's first-token median spread 2.62% over six
        # runs against half its bound, 2.5%: it is not among its cell's
        # end-to-end metrics (PERF.md section 6, PR 32)
        assert e2e == {"tpot_p50_ms.sessions", "out_tok_s.sessions",
                       "setup_s"} | ({"ttft_p50_ms"} if name == CONTROL else set())
        for m in cell.per_layer:
            kind = spec.load_layer_metric(m["name"])["kind"]
            assert spec.reader(kind).read
    cell = spec.load_cell(CELL)
    assert spec.reference(cell.config).__name__.endswith("dots3_note")
    tol = spec.load_tolerance(cell.config)
    assert tol["prompt_tokens"] > cell.traffic["system_tokens"]
    ml = cluster.ml_config(cell.config["deployment"])
    assert (ml.kv_quant, ml.cont_max_slots, ml.max_seq_len) == ("none", 16, 16384)
    model = cluster.model_config_json(cell.config)
    assert model["family"] == "dots3_note" and model["experts_held"] == 32
    names = {m["name"] for m in cell.per_layer}
    assert {"expert_row_fill_share", "expert_load_max_over_mean",
            "experts_touched_share", "select_keep_share", "window_page_share",
            "latent_pool_gb", "latent_window_attention_share",
            "latent_window_attention_roofline_share"} <= names
    # nothing reads the selection's time yet (plain XLA fusions, and a TPU
    # op event carries no scope): no metric stands in for it
    assert "index_select_share" not in names
    # the paged-attention metrics of the dense GQA cells read nothing here
    assert not names & {"attn_kernel_share.sessions",
                        "attn_roofline_share.sessions"}
    assert "attn_kernel_share.sessions" in {
        m["name"] for m in spec.load_cell(CONTROL).per_layer}


def test_the_catalog_keys_are_in_the_file_as_published():
    """Every published width of the row, the five reduced keys with
    reasons, the published counts and the deployment."""
    cfg = spec.load_cell(CELL).config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "dots3-note-prev-ep8")
    assert set(entry["reduced"]) == set(cfg["reduced_why"]) == {
        "num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    widths = {
        "hidden_size": 5120, "intermediate_size": 13824,
        "moe_intermediate_size": 1536, "num_attention_heads": 128,
        "q_lora_rank": 1024, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "index_n_heads": 64,
        "index_head_dim": 128, "index_topk": 2048,
        "swa_num_attention_heads": 64, "swa_q_lora_rank": 1024,
        "swa_kv_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
        "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128,
        "sliding_window_size": 513, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "first_k_dense_replace": 1,
        "rope_theta": 80000000, "swa_rope_theta": 50000,
    }
    assert {k: cfg[k] for k in widths} == widths
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 32, 19008)
    assert cfg["layer_types"] == ["full_attention"] * 2 + ["sliding_attention"] * 3
    pub = cfg["published"]
    assert (pub["n_routed_experts"], pub["num_hidden_layers"],
            pub["vocab_size"]) == (256, 46, 152064)
    assert cfg["expert_group"]["chips"] == 8 and cfg["assumed"]


def test_cpu_rehearsal_of_the_cell(monkeypatch):
    """The cell's plan at a tiny size through the whole harness: hosted by
    ``/request-model``, served by the slot engine over latent pages, judged
    by ``dots3_note.py``; every counter metric of the new layers reads.
    Hosted in float32: at a width of 64, bfloat16 noise turns the discrete
    steps (which 24 positions, which 2 experts) and the streams part."""
    import jax.numpy as jnp

    from benchmarks import run
    from tensorlink_tpu.models.registry import config_from_hf

    monkeypatch.setattr(
        cluster, "model_config_json",
        lambda c: config_from_hf(dict(c), dtype=jnp.float32).to_json())
    cell = tiny_cell()
    out = run.run_cell(cell, 2**31 + 77, 4.0, True, platform="cpu")
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["gap_sigmas_max"]["value"] < 0.01
    m = out["metrics"]
    assert m["compiles_in_window.sessions"]["value"] == 0.0
    assert 0 < m["expert_row_fill_share"]["value"] <= 100
    assert m["expert_load_max_over_mean"]["value"] >= 1
    assert 0 < m["experts_touched_share"]["value"] <= 100
    assert 0 < m["select_keep_share"]["value"] < 100
    assert 0 < m["window_page_share"]["value"] < 100
    assert m["latent_pool_gb"]["value"] > 0
    for name in ("ragged_pass_ms.sessions", "verify_emit_ms.sessions",
                 "cont_step_ms.sessions"):
        assert name not in m or m[name]["value"] >= 0


def test_trace_metrics_of_the_new_kernel_read_a_fixture():
    model = cluster.deployed_model(
        spec.load_cell(CELL).config,
        cluster.ml_config(spec.load_cell(CELL).config["deployment"]))
    assert row_bytes(model) == 1152 * 2 and sliding_layers(model) == 3
    chunks = [{"decode_steps": 8, "ctx_before": [12800, 100, 0],
               "ctx_after": [12808, 108, 0]},
              {"decode_steps": 1, "ctx_after": [40, 16, 0]}]
    # a chunk's first step is the ragged pass: 7 kernel steps; a context
    # counts up to the window
    assert latent_window_bytes(chunks, model) == 7 * (513 + 104) * 1152 * 2 * 3
    ops = [xplane.Op("%latent_window_attention.3 = custom-call()", 0.0, 1.0),
           xplane.Op("%sort.36 = sort()", 1.0, 1.5),
           xplane.Op("%fusion.7 = fusion()", 1.5, 4.0)]
    obs = Obs(mode="closed", recs=[], t0=0, t1=1, grace=0, stats0={},
              stats1={}, trace=trace_of(ops), chunks=chunks, model=model,
              peaks={"hbm_bytes_per_s": 819e9})

    def read(name):
        s = spec.load_layer_metric(name)
        return spec.reader(s["kind"]).read(obs, s)

    assert read("latent_window_attention_share") == pytest.approx(25.0)
    assert read("latent_window_attention_roofline_share") == pytest.approx(
        7 * 617 * 1152 * 2 * 3 / 819e9 / 1.0 * 100)


def test_counter_metrics_read_a_fixture():
    s0 = dict.fromkeys(("moe_rows_routed_local", "moe_rows_computed",
                        "moe_rows_busiest_expert", "moe_experts_touched",
                        "moe_experts_held", "sparse_positions_kept",
                        "sparse_positions_scored", "window_pages_walked",
                        "window_pages_context"), 10)
    s1 = {"moe_rows_routed_local": 10 + 128, "moe_rows_computed": 10 + 1024,
          "moe_rows_busiest_expert": 10 + 12, "moe_experts_touched": 10 + 13,
          "moe_experts_held": 10 + 32, "sparse_positions_kept": 10 + 2048,
          "sparse_positions_scored": 10 + 12800, "window_pages_walked": 43,
          "window_pages_context": 810, "latent_pool_bytes": 2.5e9}
    obs = Obs(mode="closed", recs=[], t0=0, t1=1, grace=0, stats0=s0, stats1=s1)

    def read(name):
        s = spec.load_layer_metric(name)
        return spec.reader(s["kind"]).read(obs, s)

    assert read("expert_row_fill_share") == pytest.approx(12.5)
    assert read("expert_load_max_over_mean") == pytest.approx(12 / (128 / 32))
    assert read("experts_touched_share") == pytest.approx(13 / 32 * 100)
    assert read("select_keep_share") == pytest.approx(16.0)
    assert read("window_page_share") == pytest.approx(33 / 800 * 100)
    assert read("latent_pool_gb") == pytest.approx(2.5)
    # the parent has none of these counters: nothing to read, no error
    empty = Obs(mode="closed", recs=[], t0=0, t1=1, grace=0, stats0={}, stats1={})
    for name in ("expert_row_fill_share", "select_keep_share", "latent_pool_gb"):
        s = spec.load_layer_metric(name)
        assert spec.reader(s["kind"]).read(empty, s) is None


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
    "dropped selection": lambda hf: ("arch", {"select": False}),
    "window off by one": lambda hf: ("hf", {"sliding_window_size": 16}),
    "unnormalised top-k": lambda hf: ("hf", {"norm_topk_prob": False}),
    "missing shared expert": lambda hf: ("arch", {"shared": False}),
    "int8 rows": lambda hf: ("arch", {"int8_rows": True}),
}
# what the served tokens cannot tell from bf16 noise at published widths
# (PERF.md section 6, PR 32): each has to fail by a layer-matched number
# of its own, whatever the tokens say
LAYER_MATCHED = {"int8 rows": 0, "window off by one": 1}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_named_fault_reads_over_the_limit(tiny_model, fault):
    """Tokens chosen greedily by the sound reference are the "served"
    stream; a reference with one fault ranks them far under its own
    maximum, over ``fixture_dots3.json``'s limit, while the sound one reads
    0 on its own choices. ``served_gaps`` is what ``harness/correct.py``
    calls: its last two columns are the program's layers through the pages
    on the reference's own hidden states (cached rows; a sliding layer's
    attention), each on ``max_gap_sigmas``' scale."""
    from benchmarks.reference import dots3_note as ref

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
    where, change = FAULTS[fault](TINY)
    bad = ({**arch, **change} if where == "arch"
           else {**ref.arch_of({**TINY, **change}), "config": dict(TINY)})
    gaps = ref.served_gaps(params, prompts, served, bad)
    assert gaps.max() > limit, fault
    if fault in LAYER_MATCHED:
        col = 8 + LAYER_MATCHED[fault]
        assert gaps[:, col].max() > limit, (fault, gaps[:, 8:])
