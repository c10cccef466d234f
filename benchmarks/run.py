#!/usr/bin/env python3
"""One run of one benchmark cell on the machine this is started on.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the cell's chips. It starts a ``ValidatorNode`` with
the HTTP endpoint and one ``WorkerNode``, hosts the cell's configuration
through ``POST /request-model`` (weights made on the device from the job's
seed), warms exactly the programs the hot loop owns, does the set-up the
traffic needs, drives the traffic through ``POST /v1/generate`` with
``stream: true`` for ``--seconds``, checks correctness **after** the window
and prints one JSON object as the last line of its standard output. With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.

It fails (exit code != 0, no result line) when JAX finds no TPU or fewer
chips than the cell asks for, on a ``device_kind`` without published peaks,
outside a checkout that holds the program, or when a request was served by
anything but the ``ContinuousEngine`` with ``tpu_custom_call`` in its
lowered step program.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # the printed lines count from here; set-up is
# judged from the moment the backend was up (``e2e.setup_clock``)

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

GRACE_S = 15.0  # the wait after the window for first tokens and completions
TRACE_CHUNKS = 14  # the traced part of a --trace 1 window, in whole chunks:
# stopping the profiler costs by the device event, so a count of chunks keeps
# a traced run as long as it is however fast the program gets (PERF.md, 3)
TRACE_AT = 0.85  # ... which starts at this share of the window: stopping the
# profiler and writing the trace goes on beside the engine; late in the window
# most of that falls after it
WARM = dict(prompt_tokens=64, shared=40, output_tokens=16)
TRACE_DIR = ROOT / ".bench_trace"


def say(msg: str) -> None:
    print(msg, flush=True)


def warm_up(cl, drv, model: str, vocab: int, seed: int) -> None:
    """One request that builds the step program, and its cousin, which
    leaves the cached prefix mid-page and so builds ``copy_page``: the two
    programs the hot loop owns. Nothing else is sent in warm-up."""
    from benchmarks.harness.cluster import BenchFailure, http_json
    from benchmarks.harness.plan import Req, content_seed
    from benchmarks.harness.tokenizer import random_text

    text = random_text(content_seed(seed, 7), WARM["prompt_tokens"], vocab)
    # not streamed: a cold compile of the step program can outlast the
    # server's 30 s limit between two stream events
    status, body = http_json(cl.port, "POST", "/v1/generate", {
        "hf_name": model, "message": text, "do_sample": False,
        "max_new_tokens": WARM["output_tokens"]})
    usage = body.get("usage", {}) if status == 200 else {}
    if usage.get("completion_tokens") != WARM["output_tokens"]:
        raise BenchFailure(f"warm request: {status} {body}")
    drv.template_tokens = usage["prompt_tokens"] - len(text)
    cousin = text[:WARM["shared"]] + random_text(
        content_seed(seed, 8), WARM["prompt_tokens"] - WARM["shared"], vocab)
    rec = drv.one(Req(idx=-2, prompt_tokens=len(cousin), content_seed=0,
                      output_tokens=WARM["output_tokens"]), message=cousin)
    if rec.status != "ok" or len(rec.stamps) != rec.asked:
        raise BenchFailure(f"cousin request: {rec.status} {rec.detail} "
                           f"{len(rec.stamps)}/{rec.asked}")


def engine_counters(cont) -> dict:
    snap = {**cont.stats, **cont.serving_snapshot()}
    return {k: v for k, v in snap.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def last_chunk(cont) -> int:
    recs = cont.recorder.records()
    return recs[-1]["step"] if recs else 0


def longest_chunk(records: list[dict]) -> str:
    """The slowest of the window's chunks by the engine's own record, phase
    by phase, and the longest time from one chunk's start to the next: where
    a stall sat when a run reads low (the device: ``wait``; the host: any
    other phase; neither: the way to the client)."""
    if not records:
        return "chunks: no record in the window"
    phases = [k for k in records[0] if k.endswith("_ms")
              and k not in ("chunk_ms", "host_ms")]
    worst = max(records, key=lambda r: sum(r[k] for k in phases))
    starts = [r["t0"] for r in records]
    gap = max((b - a for a, b in zip(starts, starts[1:])), default=0.0)
    return (f"chunks: {len(records)} in the window; the slowest took "
            f"{sum(worst[k] for k in phases):.0f} ms ("
            + " ".join(f"{k[:-3]} {worst[k]:.0f}" for k in phases)
            + f"; {worst['live_slots']} live slots); longest start-to-start "
            f"{gap * 1e3:.0f} ms")


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", overrides: dict | None = None,
             keep_trace: str | None = None) -> dict:
    """The whole run of ``cell``; returns the result object. ``platform`` is
    what ``jax.devices()[0].platform`` must say: the command always asks for
    ``"tpu"``; the CPU rehearsal in ``benchmarks/tests`` passes ``"cpu"``."""
    import jax

    from benchmarks.harness import client, cluster, e2e, taps
    from benchmarks.harness import spec as specs
    from benchmarks.harness.cluster import BenchFailure
    from benchmarks.harness.correct import after_window_checks
    from benchmarks.harness.obs import Obs
    from benchmarks.harness.peaks import peaks_for
    from benchmarks.harness.tokenizer import random_text
    from tensorlink_tpu.core.devices import configure_compile_cache

    t_imported = time.monotonic()
    cache_dir = configure_compile_cache()
    builds = taps.BuildCounter()
    devs = jax.devices()  # a backend that does not come up raises here
    t_backend = time.monotonic()  # ``setup_s`` starts here
    dev = devs[0]
    if dev.platform != platform:
        raise BenchFailure(f"needs a {platform} device, JAX found {dev.platform!r}")
    if len(devs) < cell.chips:
        raise BenchFailure(f"cell {cell.name} needs {cell.chips} chip(s), "
                           f"JAX found {len(devs)}")
    peaks = peaks_for(dev.device_kind) if platform == "tpu" else {}
    say(f"device: {dev.platform} {dev.device_kind!r} x{len(devs)}; compile "
        f"cache: {cache_dir}; {t_backend - T_PROCESS:.2f}s since start "
        f"(imports {t_imported - T_PROCESS:.2f}s, backend up "
        f"{t_backend - t_imported:.2f}s)")

    deployment = dict(cell.config.get("deployment", {}))
    ml = cluster.ml_config(deployment)
    model = cell.config.get("served_name", cell.config_name)
    vocab = int(cell.config["vocab_size"])
    plan = specs.generator(cell.traffic["kind"]).plan(
        cell.traffic, {**cell.params, **(overrides or {})}, seed, seconds,
        deployment)

    submitted = taps.tap_submit()
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    tracer = taps.ChunkTracer(str(TRACE_DIR), TRACE_CHUNKS)
    cluster.install_tokenizer(vocab)
    state: dict = {}

    with tempfile.TemporaryDirectory(prefix="bench_") as tmp:
        t = time.monotonic()
        cl = cluster.Cluster(ml, tmp)
        try:
            cluster.cache_every_program()  # after the worker set its own rule
            say(f"setup: nodes up {time.monotonic() - t:.2f}s")
            t = time.monotonic()
            cl.host(model, cluster.model_config_json(cell.config),
                    int(deployment.get("seq_len", ml.max_seq_len)))
            say(f"setup: model ready {time.monotonic() - t:.2f}s "
                "(plan, recruit, weights from the seed on the device)")

            t = time.monotonic()
            drv = client.LoadDriver(cl.port, model, vocab, seed)
            warm_up(cl, drv, model, vocab, seed)
            cont = cl.engine()
            jit = dict(cont.jit_cache_sizes())
            say(f"setup: warm requests {time.monotonic() - t:.2f}s; chat "
                f"template {drv.template_tokens} tokens; programs {jit}; "
                f"{len(builds.built)} built, cache {builds.cache}")
            if not jit.get("copy_page"):
                say("WARNING: the copy-on-write program was not built in warm-up")

            if plan.system_tokens:
                drv.system_text = random_text(plan.system_seed,
                                              plan.system_tokens, vocab)
            t = time.monotonic()
            for req in plan.setup:
                rec = drv.one(req)
                if rec.status != "ok":
                    raise BenchFailure(f"set-up request: {rec.status} {rec.detail}")
            if plan.setup:
                say(f"setup: cache fill {time.monotonic() - t:.2f}s")

            def on_open(t0: float) -> None:
                state["stats0"] = engine_counters(cont)
                state["chunk0"] = last_chunk(cont)
                state["setup"] = e2e.setup_clock(T_PROCESS, t_imported,
                                                 t_backend, t0)
                say(f"setup: window opens after {t0 - T_PROCESS:.2f}s, "
                    f"{state['setup']['setup_s']:.2f}s since the backend was "
                    f"up; {len(builds.built)} programs built, cache "
                    f"{builds.cache}")
                if trace:
                    timer = threading.Timer(seconds * TRACE_AT, tracer.arm)
                    timer.daemon = True
                    timer.start()

            def on_close(t1: float) -> None:
                state["stats1"] = engine_counters(cont)
                state["recorder"] = [r for r in cont.recorder.records()
                                     if r["step"] > state["chunk0"]]

            runner = client.run_open if plan.mode == "open" else client.run_closed
            t0, t1 = runner(drv, plan, seconds, GRACE_S, on_open=on_open,
                            on_close=on_close)
            tracer.finish()
            if trace:
                say(f"trace: {len(tracer.traced)} chunks; starting and "
                    f"stopping the profiler took {tracer.stall_s} s")
            late = [b for b in builds.built if t0 <= b[2] < t1]
            if late:
                say(f"WARNING: {len(late)} program(s) built INSIDE the window: "
                    f"{[(b[0], round(b[1], 2)) for b in late]}")

            res = e2e.summarize(drv.recs, mode=plan.mode, t0=t0, t1=t1,
                                grace=GRACE_S)
            say(f"window: attempted {res['attempted']} failed {res['failed']} "
                f"completed {res['completed']} tokens {res['tokens_in_window']} "
                f"in flight mid/close {res['in_flight_mid']}/{res['in_flight_close']}; "
                + " ".join(f"{k}={v:.2f}" for k, v in res["metrics"].items()))
            say("ttfts_ms (idx:ms, by due time): " + " ".join(
                f"{r.idx}:{v:.0f}" for r, v in zip(
                    e2e.due_in_window(drv.recs, t0, t1),
                    e2e.ttfts_ms(drv.recs, t0, t1, t1 + GRACE_S))))
            for r in e2e.due_in_window(drv.recs, t0, t1):
                if e2e.is_failed(r, t1 + GRACE_S):
                    say(f"  failed: idx {r.idx} {r.status} {r.detail} "
                        f"{len(r.stamps)}/{r.asked}")

            say(longest_chunk(state["recorder"]))
            window_recs = list(drv.recs)
            t = time.monotonic()
            ok, notes, compared = after_window_checks(
                drv, cont, submitted, cell, seed,
                require_kernel=(platform == "tpu"))
            for n in notes:
                say(f"check: {n}")
            say(f"checks took {time.monotonic() - t:.2f}s")

            spans = {}
            if trace:
                for r in e2e.due_in_window(window_recs, t0, t1):
                    status, body = cluster.http_json(cl.port, "GET", f"/trace/{r.rid}")
                    if status == 200:
                        spans[r.rid] = body.get("spans", [])
            mem_peak = max(
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in devs[:cell.chips])
        finally:
            cl.stop()

    out = {"correct": bool(ok), "attempted": res["attempted"],
           "failed": res["failed"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    if trace:
        obs = Obs(
            mode=plan.mode, recs=window_recs, t0=t0, t1=t1, grace=GRACE_S,
            stats0=state["stats0"], stats1=state["stats1"],
            recorder=state["recorder"], spans=spans,
            trace=traced_window(platform, cell.name, keep_trace,
                                len(tracer.traced)),
            chunks=tracer.traced, builds_in_window=late,
            memory_peak_bytes=mem_peak, peaks=peaks,
            model=cluster.deployed_model(cell.config, ml), client=res["metrics"],
            setup=state["setup"],
        )
        out["metrics"] = per_layer_metrics(cell, obs)
        device |= {"busy_s": obs.trace.busy_s, "window_s": obs.trace.window_s}
        out["traced_chunks"] = len(tracer.traced)
        out["breakdown"] = {"device_ops": obs.trace.top_ops(10),
                            "idle_gaps": obs.trace.idle_gaps(10)}
    else:
        # a name's part after a "." tells cells apart, not quantities:
        # out_tok_s.sessions is out_tok_s, under a bound of its own
        values = {**res["metrics"], "setup_s": state["setup"]["setup_s"]}
        stat = {m["name"]: m["name"].split(".")[0] for m in cell.end_to_end}
        missing = [n for n, s in stat.items() if s not in values]
        if missing:
            raise BenchFailure(f"no sample for end-to-end metric(s) {missing}")
        out["metrics"] = {m["name"]: {"value": values[stat[m["name"]]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["device"] = device
    out["compared"] = compared  # each number that decided ``correct``, last
    return out


def traced_window(platform: str, cell_name: str, keep: str | None,
                  n_chunks: int):
    """The profiler's trace of this run, reduced to the ``n_chunks`` chunks
    the harness recorded; the trace is removed."""
    from benchmarks.harness import xplane
    from benchmarks.harness.cluster import BenchFailure

    path = xplane.find_xplane(str(TRACE_DIR))
    tr = None
    if path is not None:
        profile = xplane.load_profile(path)
        tr = xplane.reduce_profile(profile, cpu_as_device=(platform == "cpu"),
                                   n_chunks=n_chunks)
        if keep:  # to look at a trace by hand before trusting a pattern
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, f"{cell_name}.xplane.pb"))
            with open(os.path.join(keep, f"{cell_name}.describe.txt"), "w") as f:
                f.write(xplane.describe(profile))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    if tr is None or tr.busy_s <= 0:
        raise BenchFailure("the traced window holds no device operation")
    return tr


def per_layer_metrics(cell, obs) -> dict:
    """Each per-layer metric of the cell through the reader its file names;
    a reader that finds nothing to read returns nothing and the metric is
    left out of the line."""
    from benchmarks.harness import spec as specs

    out = {}
    for m in cell.per_layer:
        spec = specs.load_layer_metric(m["name"])
        val = specs.reader(spec["kind"]).read(obs, spec)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="override one of the cell's own numbers (the rate "
                    "sweep uses it; the driver never does)")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1: copy the .xplane.pb and a listing "
                    "of its planes, lines and commonest events to DIR")
    args = ap.parse_args(argv)

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # one fixed path inside the checkout; the program's own rule
        # (core/devices.py) takes the variable when it is set
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    try:  # no result line on any failure
        from benchmarks.harness import spec as specs

        cell = specs.load_cell(args.workload)
        overrides = {k: float(v) for k, v in (s.split("=", 1) for s in args.set)}
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       overrides=overrides, keep_trace=args.keep_trace)
    except Exception as e:
        import traceback

        traceback.print_exc()
        print(f"FAILED: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in out["compared"].items():  # the last lines on stderr
        print(f"compared: {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
