#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

One process (it owns the chip; the node runners' spawned network processes
are JAX-free) drives the system's main path once through the entry points a
user calls: a ``ValidatorNode`` with the HTTP endpoint and one ``WorkerNode``,
``POST /request-model`` for qwen3-4b at its published widths and all 36
layers (weights drawn from the job's seed), then ``POST /v1/generate`` — a
cold greedy request, the same request again (prefix-cache hit), one that
leaves the cached prefix mid-page (copy-on-write), four concurrent requests of different lengths (one longer than ``prefill_chunk``,
one speculative) and one SSE stream read to ``[DONE]`` — under the repo's
default ``MLConfig`` (continuous batching, int8 KV pages, page 16, 8 slots,
speculative decode armed).

It fails (exit code != 0, no result line) when JAX finds no accelerator, when
any phase fails, or outside the repo. Phases:

* ``kernels``: the two paged Pallas kernels against their ``jnp``
  references at the model's head widths, bf16 / int8 / packed-int4 pages:
  a small input, then the served cells' shapes (8 slots, chunk 128, 256
  pages a slot, contexts 250 and 1,200), there also at the head shapes of
  two other presets (seven query rows a kv head; 32 kv heads of width 96).
* ``serve`` (default, one chip): the traffic above, then the checks — the
  worker advertises the accelerator; every request went through the
  ``ContinuousEngine`` with the Pallas kernel in its lowered step programs
  (``tpu_custom_call``; one program a width of the packed block); the
  repeated greedy request reproduced its token stream; every stream has the requested length; page conservation is clean;
  no program was built after warm-up and ``jit_cache_sizes()`` did not move;
  ``/stats`` shows int8 pages and prefix-cache hits.
* ``--chips 4``: only the tensor-parallel path and what it is compared
  with — Qwen2.5-7B at its published widths (28/4 heads of 128: one kv head
  and seven query heads a chip; 15.2 GB of bf16 weights, which one chip
  cannot hold) hosted with ``tensor_parallel=4``, the same traffic, the
  per-device memory spread (one copy of the weights a chip), the kernel and
  the collectives in the lowered step; then, with the hosted job's q/k/v
  biases overwritten from a seed (a fresh init leaves them zero, and a zero
  bias proves nothing about how a bias is sharded), the step's two passes
  teacher-forced over seeded sequences at the benchmark cell's shapes (eight
  prompts of 128 decoded to 384; one context near 4,096) and their logits
  held against the plain float32 reference's full forward
  (``benchmarks/reference/decoder.py``) under ``tolerance.json``'s rule.
  There is no tp=1 replay: no one chip holds this model, and stream identity
  does not survive bf16 near-ties anyway (PR 21).

Timings printed on the way are smoke timings (cold compiles included), not
benchmark numbers. The last line of stdout is the result the driver reads.
"""

from __future__ import annotations

import argparse
import http.client
import json
import socket
import sys
import tempfile
import threading
import time

MODEL = "qwen3-4b"
TP_MODEL = "qwen2p5-7b"  # what --chips 4 hosts: the model that needs four
# the logits comparison of --chips 4: (prompt tokens, decode steps,
# sequences) — the four-chip cell's shape, then one context near 4,096 so
# the kernels' walk crosses some 250 pages at seven query rows a kv head
LOGIT_CASES = ((128, 256, 8), (4000, 48, 1))
# inline ModelConfig JSON for /request-model; None = the registry preset
MODEL_CONFIG: dict | None = None
PLATFORM = "tpu"  # what jax.devices()[0].platform must say
SEQ_LEN = 4096  # context planned for (MLConfig.max_seq_len's default)
SEED = 0  # the hosted job's weight seed (host_model's default)

_LONG = (
    "Summarise the following log for the on-call engineer. "
    + "worker w3 page pool at 91 percent, slot 5 preempted for an "
    "interactive arrival, prefix trie evicted 12 pages, decode chunk 8. " * 3
)
# (name, message, max_new_tokens, extra body fields)
WARM = ("warm", "Tell me about the paged KV cache of this server, briefly.", 16, {})
# shares WARM's first pages and leaves it mid-page: the copy-on-write path
COUSIN = ("cousin", "Tell me about the paged KV cache of this server, at length.", 16, {})
CONCURRENT = (
    ("short", "Hello there.", 24, {}),
    ("medium", "List three things a TPU does well, one line each please.", 16, {}),
    ("long", _LONG, 32, {}),
    ("spec", "la la la la la la la la la la la la la la la la la la", 12,
     {"speculative": True}),
)
STREAM = ("stream", "Stream me a short answer about block tables.", 20, {})


class SmokeFailure(Exception):
    """A phase failed; the message says which check and what it saw."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


# -- HTTP ---------------------------------------------------------------
def _http(port: int, method: str, path: str, body=None, timeout=600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = json.dumps(body).encode() if body is not None else None
        headers = {"Content-Type": "application/json"} if payload else {}
        conn.request(method, path, body=payload, headers=headers)
        resp = conn.getresponse()
        data = resp.read()
    finally:
        conn.close()
    return resp.status, (json.loads(data) if data else {})


def _sse(port: int, path: str, body: dict, timeout=600.0) -> list[str]:
    """POST and return the SSE ``data:`` payloads in order."""
    payload = json.dumps(body).encode()
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.sendall(
            f"POST {path} HTTP/1.1\r\nHost: smoke\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
        )
        buf = b""
        while chunk := s.recv(65536):
            buf += chunk
    head, _, rest = buf.partition(b"\r\n\r\n")
    if b" 200 " not in head.split(b"\r\n", 1)[0]:
        raise SmokeFailure(f"SSE request refused: {head[:200]!r}")
    return [
        blk.strip()[len("data: "):]
        for blk in rest.decode().split("\n\n")
        if blk.strip().startswith("data: ")
    ]


def _gen_body(message: str, n: int, extra: dict) -> dict:
    return {"hf_name": MODEL, "message": message, "max_new_tokens": n,
            "do_sample": False, **extra}


# -- the deployment -------------------------------------------------------
def tap_streams() -> list:
    """Passive tap on ``ContinuousEngine.submit``: every request admitted
    to a slot engine in this process, in order. The HTTP API returns text
    (and the byte tokenizer of a seed-weight model drops most ids), so the
    token-level checks read the engine's own request records instead. A
    request served by the static fallback never shows up here."""
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    seen: list = []
    orig = ContinuousEngine.submit

    def submit(self, prompt, **kw):
        req = orig(self, prompt, **kw)
        seen.append(req)
        return req

    ContinuousEngine.submit = submit
    return seen


def start_cluster(ml, tmp: str):
    from tensorlink_tpu.core.config import ValidatorConfig, WorkerConfig
    from tensorlink_tpu.nodes.runners import ValidatorNode, WorkerNode

    common = dict(local_test=True, log_dir=f"{tmp}/logs", env_file=f"{tmp}/.env")
    validator = ValidatorNode(ValidatorConfig(
        endpoint=True, endpoint_port=0, key_dir=f"{tmp}/keys_v", ml=ml, **common
    )).start()
    try:
        worker = WorkerNode(WorkerConfig(
            seed_validators=[["127.0.0.1", validator.port]],
            key_dir=f"{tmp}/keys_w", ml=ml, **common,
        )).start()
    except BaseException:
        validator.stop()
        raise
    deadline = time.monotonic() + 30
    while not validator.status()["peers"]:
        if time.monotonic() > deadline:
            stop_cluster(validator, worker)
            raise SmokeFailure("worker never connected to the validator")
        time.sleep(0.2)
    ipc = type(validator.queues.cmd).__name__
    print(f"ipc: {'native shm ring' if ipc == 'RingChannel' else 'mp.Queue'} "
          f"({ipc})", flush=True)
    return validator, worker


def stop_cluster(validator, worker) -> None:
    worker.stop()
    validator.stop()


def host_model(port: int) -> float:
    t0 = time.monotonic()
    body = {"hf_name": MODEL, "seq_len": SEQ_LEN}
    if MODEL_CONFIG is not None:
        body["config"] = MODEL_CONFIG
    status, out = _http(port, "POST", "/request-model", body)
    check(status == 200 and out.get("status") == "ready",
          f"/request-model {MODEL} ready ({status} {out})")
    _, hz = _http(port, "GET", "/healthz")
    check(MODEL in hz.get("hosted_models", []), f"/healthz hosts {MODEL}")
    return time.monotonic() - t0


def drive(port: int, taps: list, built: list, get_engine):
    """The traffic. Returns ``{name: (request record, response text)}`` and
    what was compiled when warm-up ended: ``{"jit": the engine's
    jit_cache_sizes(), "built": how many programs had been built}``
    (``get_engine`` fetches the slot engine once the first request built
    it)."""
    out: dict = {}

    def one(name, message, n, extra):
        before = len(taps)
        t0 = time.monotonic()
        status, body = _http(
            port, "POST", "/v1/generate", _gen_body(message, n, extra)
        )
        dt = time.monotonic() - t0
        if status != 200:
            raise SmokeFailure(f"{name}: /v1/generate -> {status} {body}")
        usage = body.get("usage", {})
        print(f"  {name}: prompt {usage.get('prompt_tokens')} tok, "
              f"completion {usage.get('completion_tokens')}/{n} tok, "
              f"{dt:.2f}s (smoke timing)", flush=True)
        if usage.get("completion_tokens") != n:
            raise SmokeFailure(f"{name}: {usage} != {n} completion tokens")
        return before, usage, body.get("response", "")

    print("requests:", flush=True)
    # warm-up, one of each program the hot loop owns: cold prefill, then the
    # same again (prefix-cache hit; promotion to the trie happens when the
    # first slot tears down), then a prompt that leaves the cached prefix
    # mid-page (copy-on-write)
    for name, spec in (("warm", WARM), ("repeat", WARM), ("cousin", COUSIN)):
        before, usage, text = one(name, *spec[1:])
        out[name] = (_tapped(taps, before, usage, name), text)
    warm = {"jit": dict(get_engine().jit_cache_sizes()), "built": len(built)}
    step = max((b for b in built if "ragged_step" in b[0]),
               key=lambda b: b[1], default=None)
    if step is not None:
        print(f"  (first build of {step[0]}: {step[1]:.1f}s, compile or "
              "cache fetch)", flush=True)

    # four at once: mixed prefill+decode ragged steps, chunked prefill of
    # the long prompt, draft rows for the speculative one
    before = len(taps)
    results: dict = {}

    def run(spec):
        try:
            results[spec[0]] = one(*spec)
        except BaseException as e:  # surfaced on the main thread below
            results[spec[0]] = e

    threads = [threading.Thread(target=run, args=(s,)) for s in CONCURRENT]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    for name, *_ in CONCURRENT:
        r = results.get(name)
        if r is None or isinstance(r, BaseException):
            raise SmokeFailure(f"{name}: {r!r}")
        out[name] = (_tapped(taps, before, r[1], name), r[2])

    # SSE, read to [DONE]
    before = len(taps)
    name, message, n, extra = STREAM
    t0 = time.monotonic()
    events = _sse(port, "/v1/generate",
                  {**_gen_body(message, n, extra), "stream": True})
    if not events or events[-1] != "[DONE]":
        raise SmokeFailure(f"stream: no [DONE] (last events {events[-2:]})")
    final = json.loads(events[-2])
    usage = final.get("usage", {})
    print(f"  stream: {len(events)} SSE events, completion "
          f"{usage.get('completion_tokens')}/{n} tok, "
          f"{time.monotonic() - t0:.2f}s (smoke timing)", flush=True)
    if usage.get("completion_tokens") != n:
        raise SmokeFailure(f"stream: {usage} != {n} completion tokens")
    out["stream"] = (_tapped(taps, before, usage, name), "")
    return out, warm


def _tapped(taps: list, before: int, usage: dict, name: str):
    """The engine-side record of one HTTP request: among the requests
    admitted since ``before``, the one with this prompt length (the
    concurrent prompts all differ in length)."""
    hits = [r for r in taps[before:]
            if len(r.prompt) == usage.get("prompt_tokens")]
    if len(hits) != 1:
        raise SmokeFailure(
            f"{name}: {len(hits)} slot-engine admissions match the request "
            f"({len(taps) - before} since it was sent) — served by the "
            "static fallback?"
        )
    return hits[0]


def engine_of(worker):
    """The one hosted job's slot engine on the worker (built lazily at the
    first continuous request)."""
    jobs = list(worker.executor.jobs.values())
    if len(jobs) != 1 or jobs[0].cont is None:
        raise SmokeFailure(
            f"worker holds {len(jobs)} job(s), slot engine "
            f"{'absent' if jobs and jobs[0].cont is None else 'n/a'} — "
            "the request was not served by the ContinuousEngine"
        )
    return jobs[0].cont


def kernel_in_program(cont) -> bool:
    """The Pallas kernel is in the step programs this engine dispatches
    (one a width of the packed block)."""
    return all("tpu_custom_call" in cont.lower_step(w).as_text()
               for w in cont.block_widths)


def conservation_error(cont) -> str:
    try:
        cont.check_page_conservation()
    except AssertionError as e:
        return str(e)
    return ""


def common_checks(port: int, worker, cont, taps: list, built: list,
                  res: dict, warm: dict) -> None:
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    late = built[warm["built"]:]
    check(not late, f"no program built after warm-up ({len(built)} before; "
          f"after: {late or 'none'})")
    jit_now = dict(cont.jit_cache_sizes())
    if cont.tensor_parallel == 1:
        check(jit_now == warm["jit"],
              f"compile set unchanged since warm-up: {jit_now}")
    elif jit_now != warm["jit"]:
        # sharded, jit's dispatch cache keys on how a placement is SPELLED
        # (P(None, None, 'tp') vs its rank-expanded form), so the count can
        # grow with no program built — the line above is the hard check
        print(f"  note: dispatch-cache entries grew with no build: "
              f"{warm['jit']} -> {jit_now}", flush=True)
    cap = worker.executor.capacity()
    check(cap["platform"] == PLATFORM,
          f"worker advertises platform {cap['platform']!r} "
          f"({cap['n_devices']} device(s), {cap['hbm_bytes'] / 1e9:.2f} GB)")
    check(isinstance(cont, ContinuousEngine) and cont.use_kernel,
          f"served by {type(cont).__name__}, use_kernel={cont.use_kernel}")
    check(kernel_in_program(cont),
          f"lowered step programs (block widths {cont.block_widths}) each "
          "contain the Pallas kernel (tpu_custom_call)")
    n_http = 3 + len(CONCURRENT) + 1
    check(len(taps) == n_http,
          f"{len(taps)} slot-engine admissions for {n_http} HTTP requests")
    bad = [
        f"{name}: finished={req.finished} error={req.error!r} "
        f"{len(req.tokens)}/{req.budget} tokens"
        for name, (req, _text) in res.items()
        if not (req.finished and req.error is None
                and len(req.tokens) == req.budget
                and all(0 <= t < cont.cfg.vocab_size for t in req.tokens))
    ]
    check(not bad, "every stream finished at its requested length, ids in "
          f"vocab (bad: {bad or 'none'})")
    (w_req, w_text), (r_req, r_text) = res["warm"], res["repeat"]
    check(w_req.tokens == r_req.tokens and w_text == r_text,
          f"repeated greedy request reproduced its stream {w_req.tokens[:6]}…")
    err = conservation_error(cont)
    check(not err, f"page conservation clean {err}")
    _, stats = _http(port, "GET", "/stats")
    eng = next(
        (m.get("serving", {}).get("engine") for m in stats.get("models", [])
         if m.get("name") == MODEL), None,
    ) or {}
    check(eng.get("kv_quant") == "int8" and eng.get("prefill_tokens_skipped", 0) > 0,
          f"/stats engine: kv_quant={eng.get('kv_quant')!r} "
          f"prefill_tokens_skipped={eng.get('prefill_tokens_skipped')} "
          f"spec_drafted={eng.get('spec_drafted')} "
          f"kv_page_bytes={eng.get('kv_page_bytes')}")


# -- phases ---------------------------------------------------------------
def kernels_phase(cfg) -> None:
    """Kernel vs ``jnp`` reference on the device at the model's head
    widths: what interpret mode on a CPU cannot show. A small input, then
    the served cells' shapes (8 slots, chunk 128, 256 pages a slot,
    contexts 250 and 1,200): block edges and VMEM limits only show
    there. At those shapes also the heads of two other presets, which
    take the walk's other paths: qwen2.5-7b's 28/4 (seven query rows a
    kv head, off the 8-row tile) and phi3-mini's 32/32 of width 96 (one
    row a head, kv heads in blocks, pages under a lane row)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorlink_tpu.models.quant import quantize_kv, quantize_kv4
    from tensorlink_tpu.ops import attention as A

    print("phase kernels:", flush=True)
    page = 16
    model = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
    # (label, chunk, pages a slot, starts, n_valid, decode lengths)
    small = ("small", 16, 4, [37, 0, 21, 0], [1, 16, 9, 0], [38, 16, 30, 0])
    cells = ("cells", 128, 256, [249, 1199, 1072, 0, 122, 0, 245, 3968],
             [1, 1, 128, 128, 128, 0, 5, 128],
             [250, 1200, 1200, 128, 250, 0, 250, 4096])
    shapes = (
        # decode slot, fresh prefill, mid-page prefill offset, idle slot
        (model, *small),
        # decode rows at both contexts, a chunk ending at 1,200, a fresh
        # and a mid-page chunk, an idle slot, verify rows, a full slot
        (model, *cells),
        ((28, 4, 128), "cells, 28/4 heads", *cells[1:]),
        ((32, 32, 96), "cells, 32/32 heads of 96", *cells[1:]),
    )
    for (Hq, Hkv, hd), label, C, n_pp, starts, n_valid, lengths in shapes:
        scale = hd ** -0.5
        S = len(starts)
        P = 1 + S * n_pp
        rng = np.random.default_rng(SEED)
        q = jnp.asarray(rng.normal(size=(S, C, Hq, hd)), jnp.bfloat16)
        kf = jnp.asarray(
            rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
        vf = jnp.asarray(
            rng.normal(size=(P, Hkv, page, hd)).astype(np.float32))
        bt = jnp.asarray(
            rng.permutation(np.arange(1, P)).reshape(S, n_pp), jnp.int32)
        starts, n_valid, lengths = (
            jnp.asarray(x, jnp.int32) for x in (starts, n_valid, lengths))
        modes = {
            "bf16": (kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16), {}),
        }
        for name, quant in (("int8", quantize_kv), ("int4", quantize_kv4)):
            (k8, ks), (v8, vs) = quant(kf), quant(vf)
            modes[name] = (k8, v8, {"k_scale": ks, "v_scale": vs})
        for name, (kp, vp, kw) in modes.items():
            cases = {
                "ragged": (A.ragged_paged_attention,
                           A.ragged_paged_attention_ref,
                           (q, kp, vp, bt, starts, n_valid)),
                "decode": (A.paged_attention, A.paged_attention_ref,
                           (q[:, 0], kp, vp, bt, lengths)),
            }
            for kname, (kern, ref, args) in cases.items():
                got = np.asarray(kern(*args, scale=scale, **kw), np.float32)
                with jax.default_matmul_precision("highest"):
                    want = np.asarray(ref(*args, scale=scale, **kw), np.float32)
                err = float(np.abs(got - want).max())
                # outputs are O(1) averages of unit-normal values delivered
                # in bf16: 2e-2 (+2%) admits a couple of ulps of that
                # rounding, not a wrong scale row, head or page
                check(np.isfinite(got).all()
                      and np.allclose(got, want, rtol=2e-2, atol=2e-2),
                      f"{label}: {kname} kernel, {name} pages: max |kernel - "
                      f"ref| = {err:.2e}")


def serve_phase(ml, tmp: str, built: list) -> None:
    import jax

    taps = tap_streams()
    validator, worker = start_cluster(ml, tmp)
    try:
        port = validator.api.port
        print(f"phase serve: load {host_model(port):.1f}s "
              "(plan, recruit, init weights from seed)", flush=True)
        res, warm = drive(port, taps, built, lambda: engine_of(worker))
        print("checks:", flush=True)
        common_checks(port, worker, engine_of(worker), taps, built, res, warm)
    finally:
        stop_cluster(validator, worker)
    for d in jax.local_devices()[:1]:
        st = d.memory_stats() or {}
        print(f"memory {d}: peak_bytes_in_use "
              f"{st.get('peak_bytes_in_use', 0) / 1e9:.2f} GB of "
              f"{st.get('bytes_limit', 0) / 1e9:.2f} GB", flush=True)


def _arch(cfg) -> dict:
    """``decoder.arch_of``'s keys from the program's config."""
    return {"heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "eps": cfg.norm_eps,
            "theta": cfg.rope_theta, "qk_norm": cfg.qk_norm,
            "tied": cfg.tie_embeddings, "layers": cfg.n_layers}


def seed_biases(params: dict) -> None:
    """Overwrite q/k/v biases in place with seeded values, each placed as
    the leaf it replaces."""
    import jax
    import jax.numpy as jnp

    attn = params["layers"]["attn"]
    key = jax.random.PRNGKey(SEED + 11)
    for name in ("bq", "bk", "bv"):
        if name not in attn:
            continue
        key, k = jax.random.split(key)
        old = attn[name]
        new = (0.5 * jax.random.normal(k, old.shape, jnp.float32)).astype(old.dtype)
        attn[name] = jax.device_put(new, old.sharding)


def logits_phase(cont, ml) -> None:
    """The served path's logits against the plain reference's, on the
    hosted job's own weights: each case's prompt through the ragged pass
    in chunks, then one continuation step a token through the paged cache
    (``engine/paged.py::make_logits_probe``: the step program's two passes,
    returning logits where the step samples), teacher-forced on seeded
    tokens; the reference is one full forward, float32, no cache."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from benchmarks.reference import decoder
    from benchmarks.harness.spec import BENCH_DIR
    from tensorlink_tpu.engine.paged import (
        PagedKVCache, make_logits_probe, tp_cache_specs,
    )

    with open(BENCH_DIR / "reference" / "tolerance.json") as f:
        tol = float(json.load(f)["max_gap_sigmas"])
    cfg, params, mesh = cont.cfg, cont.engine.params, cont._tp_mesh
    S, C, page = cont.max_slots, cont.prefill_chunk, cont.page_size
    seed_biases(params)
    print("phase logits: q/k/v biases overwritten from the seed", flush=True)
    ragged, decode = make_logits_probe(mesh, cfg, kernel=cont.use_kernel)
    arch = _arch(cfg)
    quant = cont.kv_quant != "none"
    rng = np.random.default_rng(SEED + 12)
    worst_gap = worst_diff = 0.0
    for n_prompt, n_new, n_seq in LOGIT_CASES:
        t0 = time.monotonic()
        seqs = rng.integers(0, cfg.vocab_size, (n_seq, n_prompt + n_new))
        want = decoder.forward_logits(
            params, seqs, arch, slice(n_prompt - 1, n_prompt + n_new))
        sigma = want.std(axis=-1)  # [n_seq, n_new + 1]
        t_ref = time.monotonic() - t0
        # a cache of the probe's own, sharded as the engine's: the engine's
        # pages hold prefixes computed under the old biases
        cache = jax.jit(
            lambda: PagedKVCache.init(
                cfg, S, page_size=page, max_len=cont.max_seq_len,
                kv_quant=cont.kv_quant),
            out_shardings=jax.tree.map(
                lambda sp: NamedSharding(mesh, sp), tp_cache_specs(quant)),
        )()
        n_pp = cache.pages_per_slot
        bt = rng.permutation(np.arange(1, 1 + S * n_pp)).reshape(S, n_pp)
        cache = dataclasses.replace(
            cache, block_tables=jnp.asarray(bt, jnp.int32))
        live = np.arange(S) < n_seq
        rows = np.zeros((S, n_prompt + n_new), np.int64)
        rows[:n_seq] = seqs

        gaps, diffs = [], []

        def hold(pos: int, logits) -> None:
            got = np.asarray(logits, np.float32)[:n_seq]
            ref = want[:, pos - (n_prompt - 1)]
            sg = sigma[:, pos - (n_prompt - 1)]
            pick = got.argmax(-1)
            gaps.append((ref.max(-1) - ref[np.arange(n_seq), pick]) / sg)
            diffs.append(np.abs(got - ref).max(-1) / sg)

        for lo in range(0, n_prompt, C):
            n = min(C, n_prompt - lo)
            blk = np.zeros((S, C), np.int32)
            blk[:, :n] = rows[:, lo:lo + n]
            logits, cache = ragged(
                params, jnp.asarray(blk), cache,
                jnp.asarray(np.where(live, lo, 0), jnp.int32),
                jnp.asarray(np.where(live, n, 0), jnp.int32))
        hold(n_prompt - 1, logits)
        for t in range(n_prompt, n_prompt + n_new):
            logits, cache = decode(
                params, jnp.asarray(rows[:, t], jnp.int32), cache,
                jnp.asarray(live))
            hold(t, logits)
        gaps, diffs = np.stack(gaps, 1), np.stack(diffs, 1)
        worst_gap = max(worst_gap, float(gaps.max()))
        worst_diff = max(worst_diff, float(diffs.max()))
        print(f"  {n_seq} x (prompt {n_prompt} + {n_new} decode steps): "
              f"served argmax under the reference maximum by max "
              f"{gaps.max():.4f}, mean {gaps.mean():.4f} deviations "
              f"({int((gaps == 0).sum())}/{gaps.size} are the reference's "
              f"argmax); max |served - reference| logit {diffs.max():.4f}, "
              f"mean of maxima {diffs.mean():.4f} deviations; reference "
              f"{t_ref:.1f}s, served {time.monotonic() - t0 - t_ref:.1f}s "
              "(smoke timing)", flush=True)
        if (n_prompt, n_new, n_seq) == LOGIT_CASES[0]:
            # the control: the same comparison against a reference that
            # leaves the biases out must fail, or the seeded biases (and
            # how they are sharded) were never under test
            attn = params["layers"]["attn"]
            bare = {**params, "layers": {**params["layers"], "attn": {
                k: v for k, v in attn.items() if k not in ("bq", "bk", "bv")}}}
            ref0 = decoder.forward_logits(
                bare, seqs[:1, :n_prompt], arch, slice(n_prompt - 1, n_prompt))
            got0 = np.asarray(want[:1, :1])
            off = float((np.abs(got0 - ref0).max(-1) / sigma[:1, :1]).max())
            check(off > 4 * tol,
                  f"control: a reference without the biases lies {off:.3f} "
                  "deviations from the one with them")
        del cache, want
    # tolerance.json's rule, on every compared position; and no single
    # logit further off than the same distance (bf16 activations over int8
    # pages against float32: 0.074 deviations at worst on the chip, PERF.md
    # section 6, PR 26; a reference without the biases lies 5.5 away)
    check(worst_gap <= tol,
          f"served argmax within {tol} deviations of the reference maximum "
          f"everywhere (worst {worst_gap:.4f})")
    check(worst_diff <= tol,
          f"no logit further than {tol} deviations from the reference "
          f"(worst {worst_diff:.4f})")


def tp_phase(ml, tmp: str, built: list, degree: int) -> None:
    """``tensor_parallel=degree`` through the nodes: load, traffic, checks,
    memory a device, then the logits comparison on the hosted job."""
    import dataclasses

    import jax

    from tensorlink_tpu.engine.continuous import device_bytes

    taps = tap_streams()
    validator, worker = start_cluster(
        dataclasses.replace(ml, tensor_parallel=degree), tmp)
    try:
        port = validator.api.port
        print(f"phase tp={degree}: load {host_model(port):.1f}s", flush=True)
        res, warm = drive(port, taps, built, lambda: engine_of(worker))
        cont = engine_of(worker)
        print("checks:", flush=True)
        check(cont.tensor_parallel == degree and cont._tp_step is not None,
              f"engine runs tensor_parallel={cont.tensor_parallel}")
        common_checks(port, worker, cont, taps, built, res, warm)
        for width in cont.block_widths:
            text = cont.lower_step(width).as_text()
            check("all_gather" in text,
                  f"lowered tp step, block {width} wide: "
                  f"{text.count('tpu_custom_call')} kernel call(s), "
                  f"{text.count('all_gather')} all_gather op(s)")
        # one copy of the weights a device, each holding its share: the
        # engine's own arrays beside what the runtime reports in use
        devs = list(cont._tp_mesh.devices.flat)
        weights = device_bytes(cont.engine.params)
        pages = device_bytes(cont.cache)
        total = sum(x.nbytes for x in jax.tree.leaves(cont.engine.params))
        print(f"  unsharded weights: {total / 1e9:.2f} GB", flush=True)
        in_use = {}
        for d in devs:
            st = d.memory_stats() or {}
            in_use[d] = st.get("bytes_in_use", 0)
            print(f"  {d}: weights {weights[d] / 1e9:.2f} GB, pages "
                  f"{pages[d] / 1e9:.2f} GB, bytes_in_use "
                  f"{in_use[d] / 1e9:.2f} GB, peak "
                  f"{st.get('peak_bytes_in_use', 0) / 1e9:.2f} GB", flush=True)
        lo, hi = min(weights.values()), max(weights.values())
        check(hi <= 1.05 * lo and hi < 0.45 * total,
              f"weights spread evenly: {lo / 1e9:.2f}-{hi / 1e9:.2f} GB a "
              "device (replicated embeddings included)")
        if PLATFORM == "tpu":  # the CPU reports no bytes_in_use
            slack = 0.5e9  # histograms, control rows, the runtime's own
            check(all(in_use[d] < weights[d] + pages[d] + slack for d in devs),
                  "one copy of the weights a device: bytes_in_use is under "
                  f"weights + pages + {slack / 1e9:.1f} GB on every device")
        logits_phase(cont, ml)
    finally:
        stop_cluster(validator, worker)
        worker.executor.jobs.clear()
        taps.clear()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the tensor-parallel path and its logits "
                    "against the reference (needs a four-chip host)")
    args = ap.parse_args(argv)
    global MODEL
    if args.chips == 4 and MODEL_CONFIG is None:
        MODEL = TP_MODEL

    import jax
    import jaxlib

    from tensorlink_tpu.core.config import MLConfig
    from tensorlink_tpu.core.devices import configure_compile_cache
    from tensorlink_tpu.models.base import ModelConfig
    from tensorlink_tpu.models.registry import config_presets
    from tensorlink_tpu.native import load_tlring

    cache_dir = configure_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    # every executable built (compiled, or fetched from the persistent
    # cache) in this process, in order: (function name, seconds)
    built: list[tuple[str, float]] = []

    def on_duration(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            built.append((str(kw.get("fun_name", "?")), float(duration)))

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    t_start = time.monotonic()
    devs = jax.devices()  # a backend that does not come up raises here
    dev = devs[0]
    try:
        import libtpu

        libtpu_v = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_v = "absent"
    print(f"versions: python {sys.version.split()[0]}, jax {jax.__version__}, "
          f"jaxlib {jaxlib.__version__}, libtpu {libtpu_v}")
    stats = dev.memory_stats() or {}
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(devs)}, "
          f"bytes_limit {stats.get('bytes_limit', 0) / 1e9:.2f} GB")
    print(f"compile cache: {cache_dir}")
    print(f"native ring library: "
          f"{'built' if load_tlring() is not None else 'NOT built (no g++?) — nodes use mp.Queue'}",
          flush=True)
    try:
        if dev.platform != PLATFORM:
            raise SmokeFailure(
                f"needs a {PLATFORM} device, JAX found {dev.platform!r}"
            )
        if len(devs) < args.chips:
            raise SmokeFailure(
                f"--chips {args.chips} needs as many devices, found {len(devs)}"
            )
        cfg = (ModelConfig.from_json(MODEL_CONFIG) if MODEL_CONFIG is not None
               else config_presets()[MODEL])
        print(f"model: {MODEL} d_model {cfg.d_model}, {cfg.n_layers} layers, "
              f"{cfg.n_heads}/{cfg.n_kv_heads} heads, hd {cfg.head_dim}, "
              f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; weights from seed "
              f"{SEED}", flush=True)
        ml = MLConfig()  # the repo's defaults are what is being proven
        print(f"MLConfig: continuous={ml.continuous_batching} "
              f"kv_quant={ml.kv_quant} page={ml.cont_page_size} "
              f"slots={ml.cont_max_slots} prefill_chunk={ml.prefill_chunk} "
              f"spec_decode={ml.spec_decode} max_seq_len={ml.max_seq_len}",
              flush=True)
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            if args.chips == 1:
                kernels_phase(cfg)
                serve_phase(ml, tmp, built)
            else:
                tp_phase(ml, tmp, built, args.chips)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"compile cache: {cache['hits']} hit(s), {cache['misses']} miss(es); "
          f"{len(built)} program(s) built, {sum(b[1] for b in built):.1f}s")
    print(f"total {time.monotonic() - t_start:.1f}s (smoke timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
