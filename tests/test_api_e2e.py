"""REST API end-to-end against a live validator endpoint (reference
tests/test_model_api.py:54-396): preload via /request-model, then generate in
simple + OpenAI shapes, SSE streaming with [DONE], chat completions, status
and stats routes."""

import http.client
import json
import socket
import time

import jax.numpy as jnp
import pytest

from tensorlink_tpu.core.config import ValidatorConfig, WorkerConfig
from tensorlink_tpu.models import ModelConfig

pytestmark = pytest.mark.e2e

MODEL = "tiny-test"


def tiny_cfg_json():
    return ModelConfig(
        family="llama",
        vocab_size=258,  # byte tokenizer range + BOS/EOS
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        max_seq_len=256,
        dtype=jnp.float32,
    ).to_json()


@pytest.fixture(scope="module")
def api_cluster(tmp_path_factory):
    from tensorlink_tpu.nodes.runners import ValidatorNode, WorkerNode

    tmp = tmp_path_factory.mktemp("api_cluster")
    common = dict(
        local_test=True,
        key_dir=str(tmp / "keys"),
        log_dir=str(tmp / "logs"),
        env_file=str(tmp / ".env"),
    )
    validator = ValidatorNode(
        ValidatorConfig(endpoint=True, endpoint_port=0, **common)
    ).start()
    worker = WorkerNode(
        WorkerConfig(seed_validators=[["127.0.0.1", validator.port]], **common)
    ).start()
    worker2 = WorkerNode(
        WorkerConfig(seed_validators=[["127.0.0.1", validator.port]],
                     **{**common, "key_dir": str(tmp / "keys2")})
    ).start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if len(validator.status()["peers"]) >= 2:
            break
        time.sleep(0.2)
    validator.test_workers = [worker, worker2]  # for capacity-shrink tests
    # every test counts on MODEL, whichever a process of xdist draws first
    status, body = _req(
        validator.api, "POST", "/request-model",
        {"hf_name": MODEL, "config": tiny_cfg_json(), "seq_len": 256},
    )
    assert status == 200 and body["status"] == "ready", body
    yield validator
    worker.stop()
    worker2.stop()
    validator.stop()


def _req(api, method, path, body=None, timeout=200.0):
    conn = http.client.HTTPConnection("127.0.0.1", api.port, timeout=timeout)
    payload = json.dumps(body).encode() if body is not None else None
    headers = {"Content-Type": "application/json"} if payload else {}
    conn.request(method, path, body=payload, headers=headers)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, json.loads(data) if data else {}


def _sse(api, path, body, timeout=200.0):
    """POST and parse the SSE stream into a list of data payloads."""
    s = socket.create_connection(("127.0.0.1", api.port), timeout=timeout)
    payload = json.dumps(body).encode()
    s.sendall(
        f"POST {path} HTTP/1.1\r\nHost: x\r\nContent-Type: application/json\r\n"
        f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
    )
    buf = b""
    while b"\r\n\r\n" not in buf:
        buf += s.recv(65536)
    head, buf = buf.split(b"\r\n\r\n", 1)
    status = int(head.split(b" ")[1])
    assert b"text/event-stream" in head
    while True:
        chunk = s.recv(65536)
        if not chunk:
            break
        buf += chunk
    s.close()
    events = []
    for block in buf.decode().split("\n\n"):
        block = block.strip()
        if block.startswith("data: "):
            events.append(block[len("data: "):])
    return status, events


def test_health_and_preload(api_cluster):
    api = api_cluster.api
    status, body = _req(api, "GET", "/health")
    assert status == 200 and body["status"] == "ok"

    status, body = _req(
        api, "POST", "/request-model",
        {"hf_name": MODEL, "config": tiny_cfg_json(), "seq_len": 256},
    )
    assert status == 200, body
    assert body["status"] == "ready"

    status, body = _req(api, "GET", f"/model-status/{MODEL}")
    assert body["status"] == "ready"
    status, body = _req(api, "GET", "/models")
    assert any(
        m["name"] == MODEL and m["status"] == "ready" for m in body["models"]
    )
    # OpenAI-compatible listing
    status, body = _req(api, "GET", "/v1/models")
    assert status == 200 and body["object"] == "list"
    assert any(m["id"] == MODEL for m in body["data"])


def test_generate_simple(api_cluster):
    api = api_cluster.api
    status, body = _req(
        api, "POST", "/v1/generate",
        {"hf_name": MODEL, "message": "hi", "max_new_tokens": 8,
         "do_sample": False},
    )
    assert status == 200, body
    assert "response" in body
    u = body["usage"]
    assert u["prompt_tokens"] > 0 and 0 < u["completion_tokens"] <= 8
    assert u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"]


def test_concurrent_requests_batched(api_cluster):
    """Concurrent /v1/generate requests complete correctly through the
    dynamic batcher (ml/batching.py) — the reference would queue them
    strictly serially behind one model lock."""
    import threading

    api = api_cluster.api
    results: list[tuple[int, dict]] = []

    def one(n):
        results.append(_req(
            api, "POST", "/v1/generate",
            {"hf_name": MODEL, "message": f"req {n}", "max_new_tokens": 4 + n,
             "do_sample": False},
        ))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert len(results) == 3
    for status, body in results:
        assert status == 200, body
        assert 0 < body["usage"]["completion_tokens"] <= 7


def test_generate_lookahead_matches_vanilla(api_cluster):
    """lookahead:true on /v1/generate (speculative decode, greedy) must
    return EXACTLY the vanilla greedy text — speculation is a speed hint,
    never a semantic one — and the request round-trips the full product
    path (API -> batcher -> worker -> engine.generate_lookahead)."""
    api = api_cluster.api
    base = {"hf_name": MODEL, "message": "repeat repeat repeat repeat",
            "max_new_tokens": 12, "do_sample": False}
    status, vanilla = _req(api, "POST", "/v1/generate", base)
    assert status == 200, vanilla
    status, spec = _req(
        api, "POST", "/v1/generate", {**base, "lookahead": True}
    )
    assert status == 200, spec
    assert spec["response"] == vanilla["response"]
    assert spec["usage"]["completion_tokens"] == vanilla["usage"]["completion_tokens"]
    # sampling requests ignore the hint rather than failing
    status, body = _req(
        api, "POST", "/v1/generate",
        {**base, "lookahead": True, "do_sample": True, "temperature": 0.8},
    )
    assert status == 200, body


def test_stop_sequences_truncate_and_stream(api_cluster):
    """OpenAI-style stop sequences are APPLIED (the reference only declares
    the field): the answer cuts at the earliest occurrence, finish_reason
    is "stop", and the SSE stream never emits past the match even when the
    stop spans delta boundaries."""
    api = api_cluster.api
    base = {"hf_name": MODEL, "message": "tell", "max_new_tokens": 16,
            "do_sample": False}
    status, ref = _req(api, "POST", "/v1/generate", base)
    assert status == 200, ref
    text = ref["response"]
    if len(text) < 4:
        pytest.skip("reference output too short to carve a stop from")
    stop_s = text[2:4]
    expected = text[: text.find(stop_s)]

    status, body = _req(api, "POST", "/v1/generate", {**base, "stop": stop_s})
    assert status == 200, body
    assert body["response"] == expected

    # finish_reason rides the OpenAI format
    status, body = _req(
        api, "POST", "/v1/generate",
        {**base, "stop": stop_s, "output_format": "openai"},
    )
    assert status == 200, body
    choice = body["choices"][0]
    assert choice["message"]["content"] == expected
    assert choice["finish_reason"] == "stop"

    # streaming: joined deltas equal the truncated text, nothing beyond
    status, events = _sse(
        api, "/v1/generate", {**base, "stop": [stop_s], "stream": True}
    )
    assert status == 200
    pieces = [json.loads(e).get("token", "") for e in events if e != "[DONE]"]
    assert "".join(pieces) == expected

    # billing: completion_tokens counts tokens THROUGH the stop match,
    # not the full decode budget (OpenAI semantics; the r4 divergence)
    status, body = _req(api, "POST", "/v1/generate", {**base, "stop": stop_s})
    assert body["usage"]["completion_tokens"] < ref["usage"]["completion_tokens"], body

    # validation: >4 stops rejected
    status, body = _req(
        api, "POST", "/v1/generate", {**base, "stop": ["a"] * 5}
    )
    assert status == 400


def test_stop_sequences_cancel_pipelined_decode(api_cluster):
    """On a 2-stage (host-driven session) model a confirmed stop match
    CANCELS the row mid-loop — the decode stops at the match instead of
    burning the remaining budget (observable via completion_tokens and
    the truncated stream)."""
    api = api_cluster.api
    _host_two_stage(api_cluster)
    base = {"hf_name": "tiny-2stage", "message": "go", "max_new_tokens": 24,
            "do_sample": False}
    status, ref = _req(api, "POST", "/v1/generate", base)
    assert status == 200, ref
    text = ref["response"]
    if len(text) < 4:
        pytest.skip("reference output too short to carve a stop from")
    stop_s = text[2:4]
    expected = text[: text.find(stop_s)]
    status, events = _sse(
        api, "/v1/generate", {**base, "stop": [stop_s], "stream": True}
    )
    assert status == 200
    final = json.loads(events[-2]) if events[-1] == "[DONE]" else None
    pieces = [json.loads(e).get("token", "") for e in events if e != "[DONE]"]
    assert "".join(pieces) == expected
    if final and "usage" in final:
        assert final["usage"]["completion_tokens"] < 24


def test_repetition_penalties_over_api(api_cluster):
    """presence/frequency penalties ride /v1/generate into the compiled
    sampler (the reference declares the fields but never applies them): a
    maximal presence penalty forces greedy decode to emit pairwise-distinct
    tokens, where the unpenalized greedy repeats eventually; invalid ranges
    are rejected."""
    api = api_cluster.api
    base = {"hf_name": MODEL, "message": "aa", "max_new_tokens": 24,
            "do_sample": False}
    status, plain = _req(api, "POST", "/v1/generate", base)
    assert status == 200, plain
    status, pen = _req(
        api, "POST", "/v1/generate", {**base, "presence_penalty": 2.0},
    )
    assert status == 200, pen
    assert pen["response"] != plain["response"]  # the knob bites

    status, body = _req(
        api, "POST", "/v1/generate", {**base, "frequency_penalty": 3.0},
    )
    assert status == 400  # out of [-2, 2]


def _host_two_stage(api_cluster) -> None:
    """Host (or reuse) 'tiny-2stage' as a genuinely 2-stage pipelined
    model: shrink each worker's capacity so a 6-layer model must split
    (the planner works from FREE bytes = capacity - reservations of models
    hosted by earlier tests), host over REST, then restore capacities."""
    job = api_cluster.executor.hosted.get("tiny-2stage")
    if job is not None and job.status == "ready":
        assert job.model.plan.n_stages == 2, job.model.plan
        return
    api = api_cluster.api
    stats = api_cluster.executor.bridge.request("stats_workers", timeout=15.0)
    reserved = {
        s["id"]: float(s["hbm_bytes"]) - float(s["free_bytes"]) for s in stats
    }
    for w in api_cluster.test_workers:
        res = reserved.get(w.node_id, max(reserved.values(), default=0.0))
        w.send_request(
            "set_capacity",
            {"hbm_bytes": res + 3_400_000.0, "n_devices": 1},
        )
    try:
        cfg = ModelConfig(
            family="llama", vocab_size=258, d_model=128, n_layers=6,
            n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256,
            max_seq_len=256, dtype=jnp.float32,
        ).to_json()
        status, body = _req(
            api, "POST", "/request-model",
            {"hf_name": "tiny-2stage", "config": cfg, "seq_len": 64},
        )
        assert status == 200 and body["status"] == "ready", body
        job = api_cluster.executor.hosted["tiny-2stage"]
        assert job.model.plan.n_stages == 2, job.model.plan
    finally:
        for w in api_cluster.test_workers:
            w.send_request("set_capacity", w.executor.capacity())


def test_repetition_penalties_pipelined_over_api(api_cluster):
    """Penalties against a 2-STAGE hosted model (r4 weak #5 / directive 5:
    these requests used to 400): the knob both works and bites."""
    api = api_cluster.api
    _host_two_stage(api_cluster)
    base = {"hf_name": "tiny-2stage", "message": "aa bb aa bb",
            "max_new_tokens": 16, "do_sample": False}
    status, plain = _req(api, "POST", "/v1/generate", base)
    assert status == 200, plain
    status, pen = _req(
        api, "POST", "/v1/generate", {**base, "presence_penalty": 2.0},
    )
    assert status == 200, pen  # used to be a 400 on multi-stage
    assert pen["response"] != plain["response"]  # the knob bites

    # beam search works on the pipelined distribution too (r4: 400)
    status, beam = _req(
        api, "POST", "/v1/generate",
        {**base, "num_beams": 3, "presence_penalty": 0.0},
    )
    assert status == 200, beam
    assert beam["usage"]["completion_tokens"] > 0

    # speculative decode too: {"lookahead": true} on a pipelined model
    # emits exactly the vanilla greedy text (fewer pipeline round trips)
    status, spec = _req(
        api, "POST", "/v1/generate", {**base, "lookahead": True},
    )
    assert status == 200, spec
    assert spec["response"] == plain["response"]


def test_moe_model_serves_over_api(api_cluster):
    """A Mixtral-family (sparse-MoE) model hosts and generates through the
    full REST -> validator -> worker -> engine path (r4 weak #6: MoE
    serving was unproven end-to-end on any backend)."""
    api = api_cluster.api
    cfg = ModelConfig(
        family="mixtral", vocab_size=258, d_model=64, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
        max_seq_len=256, n_experts=4, n_experts_per_tok=2,
        dtype=jnp.float32,
    ).to_json()
    status, body = _req(
        api, "POST", "/request-model",
        {"hf_name": "tiny-moe", "config": cfg, "seq_len": 128},
    )
    assert status == 200 and body["status"] == "ready", body
    base = {"hf_name": "tiny-moe", "message": "route me",
            "max_new_tokens": 8, "do_sample": False}
    status, body = _req(api, "POST", "/v1/generate", base)
    assert status == 200, body
    assert body["usage"]["completion_tokens"] == 8
    # deterministic: greedy repeats exactly
    status, again = _req(api, "POST", "/v1/generate", base)
    assert again["response"] == body["response"]
    # and sampled decode works on the MoE path too
    status, s = _req(api, "POST", "/v1/generate",
                     {**base, "do_sample": True, "temperature": 0.8})
    assert status == 200, s


def test_generate_openai_format(api_cluster):
    api = api_cluster.api
    status, body = _req(
        api, "POST", "/v1/generate",
        {"hf_name": MODEL, "message": "hi", "max_new_tokens": 4,
         "do_sample": False, "output_format": "openai"},
    )
    assert status == 200
    assert body["object"] == "chat.completion"
    assert body["choices"][0]["finish_reason"] in ("stop", "length")


def test_chat_completions(api_cluster):
    api = api_cluster.api
    status, body = _req(
        api, "POST", "/v1/chat/completions",
        {"model": MODEL, "max_tokens": 4,
         "messages": [{"role": "user", "content": "hello"}]},
    )
    assert status == 200, body
    assert body["object"] == "chat.completion"
    assert isinstance(body["choices"][0]["message"]["content"], str)


def test_streaming_sse_with_done(api_cluster):
    api = api_cluster.api
    status, events = _sse(
        api, "/v1/generate",
        {"hf_name": MODEL, "message": "go", "max_new_tokens": 6,
         "do_sample": False, "stream": True, "output_format": "openai"},
    )
    assert status == 200
    assert events[-1] == "[DONE]"
    parsed = [json.loads(e) for e in events[:-1]]
    assert all(p["object"] == "chat.completion.chunk" for p in parsed)
    final = parsed[-1]
    assert final["choices"][0]["finish_reason"] in ("stop", "length")
    assert "usage" in final
    text = "".join(
        p["choices"][0]["delta"].get("content", "") for p in parsed[:-1]
    )
    assert isinstance(text, str)


def test_generate_absent_model_503_triggers_load(api_cluster):
    api = api_cluster.api
    status, body = _req(
        api, "POST", "/v1/generate",
        {"hf_name": "nonexistent-model", "message": "x"},
    )
    assert status == 503
    assert body["status"] in ("loading", "failed")


def test_validation_errors(api_cluster):
    api = api_cluster.api
    status, body = _req(api, "POST", "/v1/generate", {"message": "no model"})
    assert status == 400
    status, body = _req(api, "POST", "/v1/generate", None)
    assert status == 400
    status, body = _req(api, "GET", "/nope")
    assert status == 404


def test_beam_search_over_api(api_cluster):
    """num_beams rides /v1/generate into the engine's beam decode (the
    reference forwards it to HF generate): num_beams=1 equals plain greedy,
    num_beams=4 answers successfully, and invalid combos are 400s."""
    api = api_cluster.api
    base = {"hf_name": MODEL, "message": "beam", "max_new_tokens": 10,
            "do_sample": False}
    status, plain = _req(api, "POST", "/v1/generate", base)
    assert status == 200, plain
    status, b1 = _req(api, "POST", "/v1/generate", {**base, "num_beams": 1})
    assert status == 200 and b1["response"] == plain["response"]
    status, b4 = _req(api, "POST", "/v1/generate", {**base, "num_beams": 4})
    assert status == 200, b4
    assert b4["usage"]["completion_tokens"] > 0

    status, _ = _req(api, "POST", "/v1/generate", {**base, "num_beams": 9})
    assert status == 400
    status, _ = _req(
        api, "POST", "/v1/generate",
        {**base, "num_beams": 2, "stream": True},
    )
    assert status == 400


def test_beam_search_no_head_of_line_blocking(api_cluster):
    """A long beam decode advances in bounded chunks on the worker
    (ml/worker.py::_beam_step), so a small concurrent request completes
    BEFORE the beam request instead of queueing behind its whole decode."""
    import threading

    api = api_cluster.api
    done_at = {}

    def beam():
        st, b = _req(api, "POST", "/v1/generate",
                     {"hf_name": MODEL, "message": "long beam",
                      "max_new_tokens": 200, "do_sample": False,
                      "num_beams": 4})
        assert st == 200, b
        done_at["beam"] = time.monotonic()

    t = threading.Thread(target=beam)
    t.start()
    time.sleep(0.3)  # let the beam request reach the worker
    in_flight = t.is_alive()
    st, b = _req(api, "POST", "/v1/generate",
                 {"hf_name": MODEL, "message": "quick",
                  "max_new_tokens": 4, "do_sample": False})
    assert st == 200, b
    done_at["quick"] = time.monotonic()
    t.join(timeout=120)
    assert "beam" in done_at, "beam request never completed"
    if not in_flight:
        pytest.skip("beam finished before the probe dispatched — ordering "
                    "not observable on this host")
    assert done_at["quick"] < done_at["beam"], (
        "small request was head-of-line-blocked behind the beam decode"
    )


def test_chat_completions_n_choices(api_cluster):
    """OpenAI ``n``: one request returns n choices (dispatched concurrently
    so the batcher coalesces them into one decode); sampled choices differ,
    validation rejects n with streaming and out-of-range n."""
    api = api_cluster.api
    body = {
        "model": MODEL,
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 12, "temperature": 0.9, "n": 3,
    }
    status, resp = _req(api, "POST", "/v1/chat/completions", body)
    assert status == 200, resp
    choices = resp["choices"]
    assert [c["index"] for c in choices] == [0, 1, 2]
    texts = [c["message"]["content"] for c in choices]
    assert len(set(texts)) >= 2  # sampling: near-certainly distinct
    assert resp["usage"]["completion_tokens"] >= 3

    status, resp = _req(
        api, "POST", "/v1/chat/completions", {**body, "stream": True}
    )
    assert status == 400
    status, resp = _req(
        api, "POST", "/v1/chat/completions", {**body, "n": 9}
    )
    assert status == 400


def _req_raw(api, method, path, body=None, headers=None, timeout=200.0):
    """Like _req but returns (status, response headers, raw bytes) — for
    the text /metrics exposition and the X-Request-Id echo."""
    conn = http.client.HTTPConnection("127.0.0.1", api.port, timeout=timeout)
    payload = json.dumps(body).encode() if body is not None else None
    hdrs = dict(headers or {})
    if payload:
        hdrs.setdefault("Content-Type", "application/json")
    conn.request(method, path, body=payload, headers=hdrs)
    resp = conn.getresponse()
    data = resp.read()
    out_headers = {k.lower(): v for k, v in resp.getheaders()}
    conn.close()
    return resp.status, out_headers, data


def test_healthz_metrics_trace_and_request_id(api_cluster):
    """The observability surface (docs/SERVING.md "Telemetry"):

    - /healthz answers {status, hosted_models, draining} with no
      ML-process round trip;
    - every response echoes X-Request-Id (honoring a client-minted one);
    - a generated request's id resolves at /trace/<rid> with spans from
      the worker that served it (they rode the GENERATE_RESP home);
    - /metrics parses as Prometheus text exposition and carries the
      hosted model's engine counters;
    - error bodies (the 429/404 family) carry the trace_id.
    """
    api = api_cluster.api
    status, body = _req(api, "GET", "/healthz")
    assert status == 200
    assert body["status"] == "ok"
    assert MODEL in body["hosted_models"]
    assert body["draining"] is False

    # X-Request-Id: minted when absent, echoed verbatim when supplied
    status, hdrs, _ = _req_raw(api, "GET", "/healthz")
    assert status == 200 and hdrs.get("x-request-id")
    rid = "e2e-trace-0001"
    status, hdrs, raw = _req_raw(
        api, "POST", "/v1/generate",
        {"hf_name": MODEL, "message": "trace me", "max_new_tokens": 6,
         "do_sample": False},
        headers={"X-Request-Id": rid},
    )
    assert status == 200, raw[:300]
    assert hdrs.get("x-request-id") == rid

    # the trace stitched: worker-side engine spans (shipped on the
    # GENERATE_RESP) are queryable under the request id
    status, body = _req(api, "GET", f"/trace/{rid}")
    assert status == 200 and body["trace_id"] == rid
    names = {s["name"] for s in body["spans"]}
    assert {"queue_wait", "first_token", "decode"} <= names, names
    sites = {s["site"] for s in body["spans"] if s["name"] == "decode"}
    assert sites, body["spans"]  # recorded by the serving worker
    status, _ = _req(api, "GET", "/trace/no-such-trace")
    assert status == 404

    # /metrics: valid Prometheus exposition with the model's counters
    from test_metrics import parse_exposition

    status, hdrs, raw = _req_raw(api, "GET", "/metrics")
    assert status == 200
    assert hdrs.get("content-type", "").startswith("text/plain")
    fams = parse_exposition(raw.decode())
    assert fams["tlink_http_requests_total"]["type"] == "counter"
    # the hosted model serves remote-mode: its engine snapshot (riding
    # every GENERATE_RESP) flattens into labeled gauges
    engine_fams = [f for f in fams if f.startswith("tlink_engine_")]
    assert engine_fams, sorted(fams)
    assert any(
        f'model="{MODEL}"' in s
        for f in engine_fams for s in fams[f]["samples"]
    )

    # error bodies carry the trace id (the 429 contract shares this path)
    status, hdrs, raw = _req_raw(api, "GET", "/no-such-route")
    assert status == 404
    err = json.loads(raw)
    assert err["trace_id"] == hdrs.get("x-request-id")


def test_stats_and_node_info(api_cluster):
    api = api_cluster.api
    status, body = _req(api, "GET", "/stats")
    assert status == 200 and "peers" in body
    # hosted entries surface their plan topology (pipelined jobs also
    # report chain_forwards once the worker-to-worker chain has run)
    status, body = _req(api, "GET", "/models")
    hosted = {m["name"]: m for m in body["models"]}
    assert hosted[MODEL].get("stages") == 1
    status, body = _req(api, "GET", "/node-info")
    assert body["role"] == "validator" and MODEL in body["hosted_models"]
    status, body = _req(
        api, "POST", "/v1/generate",
        {"hf_name": MODEL, "message": "hi", "max_new_tokens": 2,
         "do_sample": False},
    )
    assert status == 200, body
    status, body = _req(api, "GET", "/model-demand")
    assert body["demand"].get(MODEL, 0) >= 1
    status, body = _req(api, "GET", "/network-history")
    assert "current" in body
