"""A tiny configuration and tiny traffic for the CPU rehearsal. It is not
in ``BENCHMARK.json``'s ``workloads`` and no number from it is a device
metric."""

from benchmarks.harness import spec

TINY_ML = {
    "max_seq_len": 256, "seq_buckets": [64, 128, 256], "cont_max_slots": 4,
    "prefill_chunk": 32, "cont_page_size": 8, "cont_chunk_steps": 4,
}


def tiny_config(model_type: str = "qwen3", tp: int = 1) -> dict:
    cfg = {
        "model_type": model_type, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2 if tp == 1 else 4,
        "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512,
        "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0, "tie_word_embeddings": model_type == "qwen3",
        "served_name": f"tiny-{model_type}",
        "deployment": {"chips": tp, "seq_len": 256, "ml": dict(TINY_ML)},
    }
    if tp > 1:
        cfg["deployment"]["ml"]["tensor_parallel"] = tp
    return cfg


TINY_TRAFFIC = {
    "closed": {"kind": "closed", "clients": "per_slot",
               "request_set": [[80, 24]], "count_template": True,
               "stagger": True, "rounds": 8},
    "open": {"kind": "open", "prompt_tokens": [8, 64], "output_tokens": [4, 16],
             "spacing": "log", "jitter": 0.5, "lead_in_s": 1},
    "sessions": {"kind": "sessions", "clients": 2, "turns": 2,
                 "system_tokens": 48, "user_tokens": [8, 24],
                 "answer_tokens": [4, 8], "spacing": "linear", "cycles": 8},
}


def tiny_cell(kind: str, model_type: str = "qwen3", tp: int = 1):
    bench = spec.load_benchmark()
    # every metric of the real benchmark, so that each reader runs
    bench = {
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in bench["end_to_end"]],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"}
                      for m in bench["per_layer"]],
    }
    return spec.make_cell(
        name=f"tiny.{kind}", config=tiny_config(model_type, tp),
        traffic=TINY_TRAFFIC[kind], chips=tp, config_name=f"tiny-{model_type}",
        traffic_name=kind, params={"rate_rps": 2.0}, bench=bench,
    )
