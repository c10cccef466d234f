"""``minicpm-sala-l16`` in the harness at a tiny size on the CPU: the new
files load by name, the cell's plan runs end to end through
``POST /request-model`` and the slot engine with ``correct`` decided by the
configuration's own reference (served tokens, a sparse layer's added
stream, a lightning layer's state after a snapshot and a restore), the
bytes and operation functions read hand-made chunks, and the reference's
named faults each read over the tolerance's limit. No number from here is a
device metric."""

import json

import numpy as np
import pytest

from benchmarks.bytes_fns.block_sparse_bytes import (
    block_sparse_bytes, by_pass as sparse_by_pass, kept_positions,
    position_bytes)
from benchmarks.bytes_fns.lightning_flops import (
    by_pass as flops_by_pass, flops_per_row)
from benchmarks.bytes_fns.lightning_state_bytes import (
    by_pass as state_by_pass, state_bytes)
from benchmarks.harness import cluster, spec

CELL = "minicpm-sala-l16.long-context-sessions"
SIBLING = "dots3-note-prev-ep8.long-doc-sessions"

TINY = dict(
    model_type="minicpm_sala", hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
    num_hidden_layers=5,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
                 "lightning-attn"],
    vocab_size=512, max_position_embeddings=320, rms_norm_eps=1e-6,
    rope_theta=10000, scale_emb=12, scale_depth=1.4, dim_model_base=16,
    qk_norm=True, use_output_gate=True, use_output_norm=True,
    attn_use_output_gate=True, lightning_use_rope=True, attn_use_rope=False,
    tie_word_embeddings=False, published={"num_hidden_layers": 32},
    sparse_config=dict(kernel_size=16, kernel_stride=8, block_size=32,
                       init_blocks=1, window_size=64, topk=6, dense_len=96),
    served_name="tiny-minicpm-sala", torch_dtype="float32",
    correct={"reference": "minicpm_sala",
             "tolerance": "fixture_minicpm_sala"},
    deployment={"chips": 1, "seq_len": 320, "ml": {
        "max_seq_len": 320, "seq_buckets": [64, 128, 320],
        "cont_max_slots": 4, "prefill_chunk": 32, "cont_page_size": 8,
        "cont_chunk_steps": 4, "kv_quant": "none"}},
)


def tiny_cell():
    bench = spec.load_benchmark()
    traffic = {**spec.load_traffic("long-context-sessions"), "clients": 2,
               "turns": 2, "system_tokens": 128, "user_tokens": [8, 24],
               "answer_tokens": [4, 8], "cycles": 8}
    return spec.make_cell(
        name=CELL, config=dict(TINY), traffic=traffic, chips=1,
        config_name="tiny-minicpm-sala",
        traffic_name="long-context-sessions", bench=bench)


def deployed_model() -> dict:
    cfg = spec.load_cell(CELL).config
    return cluster.deployed_model(cfg, cluster.ml_config(cfg["deployment"]))


def test_the_new_cell_resolves_all_its_files_by_name():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "minicpm-sala-l16", "long-context-sessions", 1)
    assert (cell.traffic["system_tokens"], cell.traffic["clients"],
            cell.traffic["turns"]) == (32768, 8, 4)
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p50_ms.sessions", "out_tok_s.sessions", "setup_s"}
    for m in cell.per_layer:
        kind = spec.load_layer_metric(m["name"])["kind"]
        assert spec.reader(kind).read
    assert spec.reference(cell.config).__name__.endswith("minicpm_sala")
    tol = spec.load_tolerance(cell.config)
    assert tol["prompt_tokens"] >= cell.traffic["system_tokens"]
    assert {"max_sparse_gap", "max_state_gap"} <= set(tol)
    ml = cluster.ml_config(cell.config["deployment"])
    assert (ml.kv_quant, ml.cont_max_slots, ml.max_seq_len) == (
        "none", 8, 36864)
    model = cluster.model_config_json(cell.config)
    assert model["family"] == "minicpm_sala"
    assert model["layer_kinds"].count("lightning") == 12
    names = {m["name"] for m in cell.per_layer}
    new = {"block_keep_share", "block_sparse_attention_share",
           "block_sparse_attention_roofline_share",
           "lightning_attention_share", "lightning_attention_roofline_share",
           "state_pool_gb", "state_restore_share", "state_replay_share"}
    assert new | {"cont_step_ms.sessions", "ragged_pass_ms.sessions",
                  "device_idle_share.sessions"} <= names
    # what it has not: experts, latent pools, a position selector, windows
    assert not names & {"select_keep_share", "window_page_share",
                        "latent_pool_gb", "expert_row_fill_share",
                        "latent_full_attention_share", "hbm_peak_gb",
                        "attn_kernel_share.sessions"}
    # and no other cell reads the new metrics
    assert not {m["name"] for m in spec.load_cell(SIBLING).per_layer} & new
    for fn in ("block_sparse_bytes", "lightning_state_bytes",
               "lightning_flops"):
        assert callable(spec.bytes_fn(fn))


def test_the_catalog_keys_are_in_the_file_as_published():
    cfg = spec.load_cell(CELL).config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "minicpm-sala-l16")
    assert set(entry["reduced"]) == set(cfg["reduced_why"]) == {
        "num_hidden_layers", "mixer_types", "max_position_embeddings"}
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    row = None
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        for line in f:
            if json.loads(line)["name"] == "MiniCPM-SALA":
                row = json.loads(line)["config"]
    if row is None:
        pytest.skip("no catalog here")
    for k, v in row.items():
        if k not in entry["reduced"]:
            assert cfg[k] == v, k
    assert cfg["mixer_types"] == row["mixer_types"][9:25]
    assert (cfg["num_hidden_layers"], cfg["max_position_embeddings"]) == (
        16, 36864)
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["stage"]["chips_sharing_a_layer"] == 1
    assert cfg["assumed"] and cfg["deployment"]["ml"]["kv_quant"] == "none"


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
    ``/request-model``, served by the slot engine over pages, key sums,
    states and snapshots, judged by ``minicpm_sala.py``; every counter
    metric of the new cell reads."""
    from benchmarks import run

    out = run.run_cell(tiny_cell(), 2**31 + 77, 4.0, True, platform="cpu")
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["gap_sigmas_max"]["value"] < 0.01
    m = out["metrics"]
    assert m["compiles_in_window.sessions"]["value"] == 0.0
    assert 0 < m["block_keep_share"]["value"] < 100
    assert m["state_pool_gb"]["value"] > 0
    assert m["state_restore_share"]["value"] > 50  # a tiny pool evicts
    assert 0 <= m["state_replay_share"]["value"] < 20
    # the CPU runs the XLA forms: no kernel of those names, nothing read
    assert m["block_sparse_attention_share"]["value"] == 0.0
    assert m["lightning_attention_share"]["value"] == 0.0
    assert "block_sparse_attention_roofline_share" not in m
    assert "lightning_attention_roofline_share" not in m


CHUNKS = [
    # two decoding slots that grow by the chunk's 8 steps (one past
    # dense_len, one under it), one mid-prefill slot, one free slot
    {"t0": 10.0, "decode_steps": 8, "ctx_before": [33000, 100, 32768, 0],
     "ctx_after": [33008, 108, 32768, 0]},
    # a chunk that only prefills: one pass
    {"t0": 11.0, "decode_steps": 1, "ctx_after": [40, 16, 0, 0]},
]


def test_bytes_and_operations_of_hand_made_chunks():
    model = deployed_model()
    assert position_bytes(model) == 2 * 2 * 128 * 2 * 4 == 4096
    assert state_bytes(model) == 4 * 32 * 128 * 128 * 12 == 25_165_824
    assert flops_per_row(model) == 4 * 128 * 128 * 32 * 12
    # past dense_len: 63 whole blocks and the query's own
    assert kept_positions(33000, model) == 63 * 64 + (32999 % 64) + 1
    assert kept_positions(32768, model) == 63 * 64 + 64
    assert kept_positions(100, model) == 100
    ragged = kept_positions(33000, model) + 100 + kept_positions(32768, model)
    step = kept_positions(33004, model) + 104
    got = sparse_by_pass(CHUNKS, model)
    assert got == [ragged * 4096] + [step * 4096] * 7 + [56 * 4096]
    assert block_sparse_bytes(CHUNKS, model) == sum(got)
    per = 2 * state_bytes(model)
    assert state_by_pass(CHUNKS, model) == [3 * per] + [2 * per] * 7 + [2 * per]
    rows = flops_per_row(model)
    assert flops_by_pass(CHUNKS, model) == (
        [3 * rows] + [2 * rows] * 7 + [2 * rows])
    granted = [{**CHUNKS[0], "prefill_granted": 130}, CHUNKS[1]]
    assert flops_by_pass(granted, model)[0] == (3 + 127) * rows
    # the state's bytes bound every pass here: 50 MB a slot against 25 MFLOP
    # a row at 197 TFLOP/s
    assert all(b / 819e9 > f / 197e12 for b, f in zip(
        state_by_pass(granted, model), flops_by_pass(granted, model)))


CONTROLS = [("select", False, "sparse"), ("force_window", False, "sparse"),
            ("decay_reversed", True, "state"), ("state_bf16", True, "state"),
            ("snapshot_short", True, "state")]


@pytest.mark.parametrize("key,value,held", CONTROLS,
                         ids=[c[0] for c in CONTROLS])
def test_each_planted_fault_reads_over_its_limit(key, value, held):
    """The reference with one fault (the program sound) through
    ``layer_gaps`` at the tiny size: the held number of that mechanism
    reads over the fixture's limit, by far; sound reads under 1e-4."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import minicpm_sala as ref
    from tensorlink_tpu.models.registry import config_from_hf
    from tensorlink_tpu.models.transformer import init_params

    cfg = config_from_hf(TINY, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(0, 512, size=210)
    tol = spec.load_tolerance(TINY)
    sound = ref.layer_gaps(params, tokens, ref.arch_of(TINY), 6)
    assert sound["sparse"] < 1e-4 and sound["state"] < 1e-4
    assert min(sound["by_layer"]["agree"].values()) == 1.0
    bad = ref.layer_gaps(params, tokens, {**ref.arch_of(TINY), key: value}, 6)
    assert bad[held] > 3 * tol[f"max_{held}_gap"], (key, bad)
