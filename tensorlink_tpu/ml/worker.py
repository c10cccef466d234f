"""DistributedWorker — the ML-process executor on a worker node.

Reference: ml/worker.py:147 (``DistributedWorker``), a 1 kHz poll loop over
five IPC queues per module (main_loop:1349-1437). Here the executor blocks on
one event queue and runs **compiled** programs:

- a *stage* job executes ``stage_forward`` over its contiguous layer slice
  (sharded over the worker's local mesh when it has >1 device),
- a whole-model job additionally serves ``generate`` through the
  :class:`~tensorlink_tpu.engine.generate.GenerationEngine` (compiled
  prefill/decode pair) with per-token streaming over the TOKEN relay,
- decode sessions keep per-stage KV caches on device, keyed by session id —
  the explicit replacement for torch's implicit autograd/cache state
  (reference stores ``intermediates`` per micro-batch, module.py:1543).

Weights come from a checkpoint reference (selective per-stage safetensors
reads, engine/loader.py — the reference's selective shard loading idea,
ml/worker.py:542-638) or from seeded random init for tests/benchmarks; no
pickled modules ever cross the wire (reference trusted mode,
ml/worker.py:473-476, deliberately dropped — SURVEY §7.4).
"""

from __future__ import annotations

import functools
import os
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

import numpy as np

from tensorlink_tpu.core import serialization as ser
from tensorlink_tpu.core.faults import FaultCrash, FaultPlan
from tensorlink_tpu.core.logging import get_logger
from tensorlink_tpu.nodes.ipc import CHUNK_DONE
from tensorlink_tpu.p2p import protocol as proto


@dataclass
class StageRuntime:
    """One loaded job stage: config + params + live decode sessions."""

    job_id: str
    cfg: Any  # ModelConfig
    stage: dict  # StagePlan as dict (layer_lo/hi, first, last, holds_head)
    params: Any
    # the ORIGINAL model spec this stage was shipped with (name, config,
    # ckpt/seed, quant, flash) — what a drain re-ships to the destination
    # worker so it can load an identical stage before adopting slots
    model_spec: dict = field(default_factory=dict)
    mesh: Any = None
    engine: Any = None  # GenerationEngine for whole-model jobs
    sessions: dict[str, Any] = field(default_factory=dict)  # session -> KVCache
    training: bool = False
    cache_quant: bool = False  # int8 decode-session KV caches ("int8+kv")
    # activation store for cross-host backward: tag -> (bwd_key, inputs,
    # wrt_input) — the explicit replacement for torch's implicit autograd
    # graph the reference replays on the worker (ml/worker.py:233-291).
    # Backward runs a COMPILED (params, x, mask, g) -> grads program cached
    # in ``bwd_cache`` (recomputing the forward inside the program, which
    # remat was doing anyway) instead of replaying an eager vjp closure
    # op-by-op per request.
    saved: dict[str, Any] = field(default_factory=dict)
    bwd_cache: dict[Any, Any] = field(default_factory=dict)
    grad_accum: Any = None  # summed param cotangents across micro-batches
    n_accum: int = 0
    opt: Any = None  # optax transform
    opt_state: Any = None
    # proof-of-learning log: one chained entry per optimizer step
    # (platform/proofs.py; the monitor pulls it via PROOF_REQ)
    proof_log: list = field(default_factory=list)
    opt_steps: int = 0
    # in-flight chunked beam-search sessions: rid -> (BeamState, payload,
    # effective K). A long beam decode advances _BEAM_CHUNK_STEPS at a
    # time and requeues itself, so queued co-batched generates interleave
    # instead of head-of-line-blocking behind it
    beam_sessions: dict[str, Any] = field(default_factory=dict)
    # continuous-batching slot engine (engine/continuous.py) for whole-model
    # jobs: GENERATE requests flagged "continuous" submit into its slot
    # batch and a cont_continue marker drives chunked decode through the
    # work queue — new requests admit at chunk boundaries (FIFO interleave,
    # same shape as the beam chunking above)
    cont: Any = None
    cont_scheduled: bool = False
    # per-session [B, V] context token counts for OpenAI presence/frequency
    # penalties on PIPELINED decode: the head-holding worker samples with
    # them and folds each sampled token back in, so penalized requests work
    # on multi-stage jobs too (the engine path carries its own counts)
    penalty_counts: dict[str, Any] = field(default_factory=dict)
    # idempotency ledger for sequence-numbered session ops: dedup key
    # ("{session}:{phase}") -> last applied seq, and -> the op's cached
    # outcome so a duplicate delivery (frame dup on the wire, RPC retry
    # after a lost reply) re-sends the SAME result instead of re-applying
    # the op's KV writes (ml/module.py drives retries on these seqs)
    session_seq: dict[str, int] = field(default_factory=dict)
    session_resp: dict[str, tuple] = field(default_factory=dict)
    # control-plane crash safety (docs/FAILURE_MODEL.md "Control plane"):
    # journal rid -> live ContinuousRequest for every continuous stream
    # admitted with a jrid, so a recovered validator/client can re-attach
    # to the still-decoding slot (the orphaned-stream survival half of
    # the validator journal)...
    jstreams: dict[str, Any] = field(default_factory=dict)
    # ...and jrid -> {"tokens", "base", "finished", "t"} for streams that
    # FINISHED while orphaned (their GENERATE_RESP went to a dead peer) —
    # a bounded ledger (MLConfig.orphan_keep / orphan_ttl_s) the re-attach
    # ladder drains exactly-once
    orphans: dict[str, dict] = field(default_factory=dict)

    @property
    def n_layers(self) -> int:
        return self.stage["layer_hi"] - self.stage["layer_lo"]

    @property
    def whole_model(self) -> bool:
        return (
            self.stage["first"]
            and self.stage["last"]
            and self.stage["holds_head"]
        )


# beam-search chunk size: steps a beam session may run per trip through the
# worker's serial loop before requeueing itself behind waiting work. Small
# enough that a queued co-batched generate waits one chunk, large enough to
# amortize the session bookkeeping.
_BEAM_CHUNK_STEPS = 32


class DistributedWorker:
    """Event-driven executor; one instance per WorkerNode."""

    def __init__(self, node):
        self.node = node
        self.bridge = node.bridge
        self.log = get_logger(f"ml.worker{node.config.duplicate}")
        self.jobs: dict[str, StageRuntime] = {}
        self._lock = threading.Lock()
        # drain state (live slot migration): set to the DRAIN verb's
        # destination {"id", "addr"} — new continuous requests are
        # redirected there instead of admitted, and the recruiting
        # capacity is zeroed. None = serving normally.
        self.draining: dict | None = None
        # disaggregated prefill/decode (docs/SERVING.md): the decode-pool
        # memberships a prefill-role worker hands completed prefills to —
        # pushed by the validator (HANDOFF frames). Keyed PER JOB (the
        # recruit-time push names the job it was planned for — a job
        # recruited before any decode worker existed must NOT start
        # shipping its streams to another job's pool), with "" as the
        # worker-wide fallback an operator's set_handoff_pool installs.
        # Empty = no handoffs (mixed-style serving even under
        # worker_role="prefill").
        self._handoff_pools: dict[str, list[dict]] = {}
        self._handoff_rr = 0  # round-robin cursor over the pool
        # fleet serving (docs/SERVING.md "Fleet serving"): the sibling-
        # replica memberships pushed by the validator (REPLICA_SET
        # frames, keyed by this worker's job id) — the destination a
        # DRAIN with no explicit dest falls back to, so a rolling-deploy
        # drain lands on a replica that already serves the same model
        self._replica_sets: dict[str, list[dict]] = {}
        # destinations already probed loaded/ready per job — skips the
        # per-handoff MODULE-ship round trip on the steady-state path;
        # invalidated on any ship failure so a restarted destination is
        # re-prepared instead of redirected into blind
        self._handoff_dest_ready: set[tuple[str, str]] = set()
        # (job, dest) prepares currently in flight (the warm-up thread or
        # the run loop): a second prepare for the same key must neither
        # block nor double-ship — a duplicate MODULE load REPLACES the
        # destination's runtime, killing any stream adopted in between
        self._handoff_preparing: set[tuple[str, str]] = set()
        self._handoff_prep_lock = threading.Lock()
        # shared multi-tenant KV page pools (engine/paged.py::
        # SharedPagePool), keyed by page GEOMETRY so only models that can
        # physically share pages do — created lazily at the first
        # continuous engine when MLConfig.cont_pool_pages > 0. Touched
        # only from the serial run loop (the pool's single-driver
        # contract holds because every job's engine steps there too).
        self._kv_pools: dict = {}
        # the work item that ended a chunk's intake (``_intake``): the
        # run loop handles it before it reads the queue again
        self._held: tuple | None = None
        # per-node fault plan (core/faults.py) — an INSTANCE, not the module
        # global, so several worker nodes living in one test process never
        # share fault counters; None (the default) keeps the hot paths free
        # of fault-site calls entirely
        fspec = getattr(node.config, "faults", None)
        self.faults: FaultPlan | None = (
            FaultPlan.from_dict(fspec) if fspec else None
        )
        # join the multi-controller runtime BEFORE first device use when the
        # deployment spans hosts of one slice (parallel/multihost.py) — then
        # jax.devices() is global and planned meshes may span the slice
        ml = node.config.ml
        from tensorlink_tpu.parallel.multihost import maybe_initialize

        maybe_initialize(
            ml.coordinator_address, ml.num_processes, ml.process_id
        )

    # -- capacity -------------------------------------------------------
    def capacity(self) -> dict:
        """What this worker advertises (reference STATS-RESPONSE payload,
        worker_thread.py:245-268): HBM bytes + device count. The backend
        initialises here, in this process (core/devices.py); one that does
        not come up raises and the node does not start."""
        from tensorlink_tpu.core.devices import (
            acquire_devices,
            device_hbm_bytes,
        )

        probe = acquire_devices()
        devs = probe.devices
        cap = sum(device_hbm_bytes(d) for d in devs)
        if not cap:
            # backends that report no memory limit (the CPU)
            gb = self.node.config.ml.max_memory_gb or 4.0
            cap = gb * 1e9 * len(devs)
        if self.node.config.ml.max_memory_gb:
            cap = min(cap, self.node.config.ml.max_memory_gb * 1e9 * len(devs))
        out = {
            "hbm_bytes": cap,
            "n_devices": len(devs),
            "platform": probe.platform,
            "training": True,
            # disaggregated prefill/decode: the pool this worker serves
            # in ("prefill" | "decode" | "mixed") — the validator's
            # placement reads it off every stats sweep (decode workers
            # are reserved as handoff destinations, docs/SERVING.md)
            "serving_role": str(
                getattr(self.node.config.ml, "worker_role", "mixed")
                or "mixed"
            ),
            # explicit tensor parallelism (docs/SHARDING.md): the shard
            # degree this worker's continuous engines run at — the
            # planner/validator treat the whole tp mesh as ONE placement
            # unit (a tp=4 worker is one engine over 4 chips, not 4
            # engines)
            "tensor_parallel": int(
                getattr(self.node.config.ml, "tensor_parallel", 1) or 1
            ),
        }
        # hosts of one TPU slice share an ICI domain: advertise the slice so
        # the planner can merge co-slice workers into one mesh
        # (parallel/planner.py::_merge_co_slice). Configurable override for
        # deployments where the runtime does not expose slice topology.
        sid = self.node.config.ml.slice_id or ""
        if not sid and devs:
            # auto-detect only when TPU_NAME names the pod: a bare
            # slice_index collides across unrelated pods and would merge
            # workers that share no ICI
            sidx = getattr(devs[0], "slice_index", None)
            pod = os.environ.get("TPU_NAME")
            if sidx is not None and probe.platform == "tpu" and pod:
                sid = f"{pod}:{sidx}"
            elif sidx is not None and probe.platform == "tpu":
                # slice topology IS visible but unnamed — without the gate
                # co-slice merging silently never triggers; tell the
                # operator what to set instead of leaving it a mystery
                self.log.info(
                    "TPU slice detected (slice_index=%s) but TPU_NAME is "
                    "unset — not advertising a slice_id; set TPU_NAME (or "
                    "MLConfig.slice_id) to enable co-slice merged planning",
                    sidx,
                )
        if sid:
            out["slice_id"] = sid
        return out

    # -- main loop ------------------------------------------------------
    def run(self) -> None:
        while True:
            # what ended a chunk's intake comes first: the queue's order
            # is kept item for item (``_intake``)
            item, self._held = self._held, None
            if item is None:
                item = self.bridge.get_work(timeout=1.0)
            if item is None:
                continue
            kind, payload = item
            if kind == "_stop":
                return
            if kind == CHUNK_DONE:
                continue  # of a chunk whose intake had ended before it
            try:
                self._handle_guarded(kind, payload)
            except FaultCrash as e:
                # injected node death: kill the network process abruptly so
                # every peer sees a dropped connection (the repair paths'
                # trigger), and exit this loop — no error reply, exactly
                # like a real worker loss mid-request
                self.log.warning("fault injection: %s — node going down", e)
                self.node.crash()
                return

    def _handle_guarded(self, kind: str, payload: dict) -> None:
        """``_handle`` for one work item, a failure answered to whoever
        asked (the run loop's items, and the GENERATE frames a chunk's
        intake takes in ahead of it). An injected crash passes (a
        ``BaseException``): the run loop takes the node down."""
        try:
            self._handle(kind, payload)
        except Exception as e:
            self.log.exception("work %s failed", kind)
            rid, peer = payload.get("rid"), payload.get("peer")
            if rid and peer:
                resp_tag = {
                    proto.FORWARD: proto.FORWARD_RESP,
                    proto.BACKWARD: proto.BACKWARD_RESP,
                    proto.GENERATE: proto.GENERATE_RESP,
                    proto.OPTIMIZER: proto.OPTIMIZER_RESP,
                    proto.PARAMS_REQ: proto.PARAMETERS,
                    proto.CHECKPOINT: proto.CHECKPOINT_RESP,
                    proto.PROOF_REQ: proto.PROOF_RESP,
                    proto.MIGRATE: proto.MIGRATE_RESP,
                    proto.DRAIN: proto.DRAIN_RESP,
                    "load_stage": proto.MODULE_LOADED,
                    "beam_continue": proto.GENERATE_RESP,
                }.get(kind, proto.FORWARD_RESP)
                # a chained hop's requester is the ORIGINATOR, not the
                # previous worker — route the error to it (it holds the
                # rid future) and name the failing worker for repair
                err_peer = payload.get("reply_to") or peer
                try:
                    self._respond(
                        err_peer, resp_tag, rid,
                        {"error": f"{type(e).__name__}: {e}",
                         "worker": self.node.node_id},
                    )
                except Exception as e2:
                    # the requester died too (the chaos suite's
                    # validator kill lands here: the work item fails
                    # BECAUSE the peer is gone, so the error reply
                    # fails the same way) — an undeliverable reply
                    # must never kill this loop; the worker keeps
                    # serving and re-announces on the re-handshake
                    self.log.warning(
                        "error reply for %s to %s undeliverable: %s",
                        kind, str(err_peer)[:8], e2,
                    )

    def _handle(self, kind: str, p: dict) -> None:
        if kind == "load_stage":
            self._load_stage(p)
        elif kind == proto.FORWARD:
            self._forward(p)
        elif kind == proto.GENERATE:
            self._generate(p)
        elif kind == "beam_continue":
            self._beam_step(p["job_id"], p["rid"])
        elif kind == "cont_continue":
            self._cont_step(p["job_id"])
        elif kind == proto.PARAMS_REQ:
            self._params_req(p)
        elif kind == proto.TRAIN_MODE:
            self._train_mode(p)
        elif kind == proto.BACKWARD:
            self._backward(p)
        elif kind == proto.OPTIMIZER:
            self._optimizer(p)
        elif kind == proto.PROOF_REQ:
            self._proof_req(p)
        elif kind == proto.CHECKPOINT:
            self._checkpoint(p)
        elif kind == proto.DRAIN:
            self._drain(p)
        elif kind == proto.MIGRATE:
            self._migrate_in(p)
        elif kind == proto.HANDOFF:
            self._set_handoff_pool(p)
        elif kind == proto.REPLICA_SET:
            self._set_replica_set(p)
        elif kind == "shutdown_job":
            jid = p.get("job_id", "")
            with self._lock:
                rt = self.jobs.pop(jid, None)
            # drop the job's handoff state with it: its decode-pool list
            # and per-destination readiness would otherwise pin per dead
            # job id for the process lifetime (same lifecycle gap the
            # shared KV pools had)
            self._handoff_pools.pop(jid, None)
            self._replica_sets.pop(jid, None)
            with self._handoff_prep_lock:  # vs the warm thread's add
                self._handoff_dest_ready = {
                    k for k in self._handoff_dest_ready if k[0] != jid
                }
            if rt is not None and rt.cont is not None:
                # fail queued/in-flight continuous requests fast rather
                # than letting their clients wait out the RPC timeout
                rt.cont.close(RuntimeError("job shut down"))
                rt.cont = None
                # close() detached the tenant: a now-empty shared pool
                # must release its page arrays, not pin HBM forever
                self._gc_kv_pools()
        elif kind == "token":
            pass  # token relays are user/validator side
        else:
            self.log.warning("unhandled work kind %s", kind)

    def _respond(self, peer: str, tag: str, rid: str, body: dict) -> None:
        self.bridge.request(
            "respond", {"peer": peer, "tag": tag, "rid": rid, "body": body}
        )

    # -- loading --------------------------------------------------------
    def _load_stage(self, p: dict) -> None:
        import jax

        from tensorlink_tpu.models.base import ModelConfig
        from tensorlink_tpu.models.transformer import (
            init_params,
            slice_stage_params,
        )

        t0 = time.monotonic()
        job_id = p["job_id"]
        if p.get("attach_only"):
            # validator re-handshake after a control-plane restart
            # (DistributedModel.from_job(..., attach_only=True)): if the
            # stage is already live, ACK without rebuilding — a full load
            # would swap the engine and kill every live slot, which is
            # exactly what recovery must not do. The ack re-announces this
            # worker's live/orphaned streams so the recovered validator
            # can reconcile its journal (worker wins for tokens). A worker
            # that ALSO restarted falls through to the normal full load.
            with self._lock:
                rt = self.jobs.get(job_id)
            if rt is not None:
                body = {
                    "job_id": job_id, "ok": True, "attached": True,
                    "n_layers": rt.n_layers,
                    "live_slots": (
                        rt.cont.live_slots if rt.cont is not None else 0
                    ),
                    "orphans": self._orphan_report(rt),
                }
                self._respond(p["peer"], proto.MODULE_LOADED, p["rid"], body)
                return
        model = p["model"]
        stage = p["stage"]
        cfg = ModelConfig.from_json(model["config"])
        lo, hi = stage["layer_lo"], stage["layer_hi"]
        first, holds_head = stage["first"], stage["holds_head"]

        training = bool(p.get("training", False))
        quant = model.get("quant")
        # a stage the tensor-parallel slot engine will serve gets its
        # weights made (or read) shard by shard, straight into the layout
        # that engine's step reads: no leaf is ever whole on one device —
        # a model one chip cannot hold loads — and the job keeps one copy
        # a chip (docs/SHARDING.md "Loading a tensor-parallel job")
        serve_tp = self._serving_tp(cfg, stage, training, quant)
        mesh = None
        if serve_tp > 1:
            from tensorlink_tpu.parallel.mesh import serving_mesh

            mesh = serving_mesh(serve_tp)

        if model.get("ckpt"):
            from tensorlink_tpu.engine.loader import load_params

            _, full = load_params(
                model["ckpt"], cfg, layer_range=(lo, hi),
                tensor_parallel=serve_tp,
            )
            # loader returns embed/final_norm/head too; keep what the stage owns
            params = {"layers": full["layers"]} if hi > lo else {}
            if first:
                params["embed"] = full["embed"]
            if holds_head:
                params["final_norm"] = full["final_norm"]
                if "lm_head" in full:
                    params["lm_head"] = full["lm_head"]
                elif "embed" not in params:
                    params["embed"] = full["embed"]
        else:
            seed = int(model.get("seed", 0))
            shardings = None
            if serve_tp > 1:
                from tensorlink_tpu.models.transformer import tp_partition_specs
                from tensorlink_tpu.parallel.mesh import shard

                shardings = jax.tree.map(
                    lambda spec: shard(mesh, spec), tp_partition_specs(cfg)
                )
            full = init_params(
                cfg, jax.random.PRNGKey(seed), shardings=shardings
            )
            params = slice_stage_params(
                full, lo, hi, first=first, holds_head=holds_head
            )
            del full

        if serve_tp == 1:
            # a patterned model (models/latent.py) is served whole on one
            # device: its tree has no partition specs yet (ROADMAP R1: an
            # expert axis over the host's chips)
            mesh = None if cfg.patterned else self._build_stage_mesh(cfg, stage)
            if mesh is not None:
                params = self._shard_params(params, cfg, stage, mesh)
        if self.node.config.ml.collective_quant and not training:
            # EQuARX-style quantized collectives (parallel/ring.py): the
            # sequence-parallel ring rotates int8 K/V + scales over ICI.
            # SERVING only — quantize_kv's round() has a zero gradient,
            # so a training vjp through a quantized ring would silently
            # lose the K/V gradient (same rule as weight quant below:
            # training needs exact math)
            cfg = cfg.with_(collective_quant=True)
        if model.get("flash"):
            # Pallas flash prefill for this job's serving ENGINE — i.e.
            # whole-model stages only (ops/attention.py; the engine gates it
            # to fresh-cache prefills, and a sharded engine routes the
            # kernel through shard_map over data/tensor since GSPMD has no
            # partitioning rule for a pallas_call). The multi-stage session
            # path never reaches the flash gate — say so instead of
            # silently serving einsum.
            if (
                stage["first"] and stage["last"] and stage["holds_head"]
            ):
                cfg = cfg.with_(flash_attention=True)
            else:
                self.log.warning(
                    "flash_attention ignored on a pipelined (multi-stage) "
                    "job — only whole-model serving engines take the "
                    "flash prefill path"
                )
        cache_quant = False
        if quant:
            # weight-only int8 serving (models/quant.py): quantize the
            # stage's matmul weights in place — every serving path
            # (stage_forward, the generation engine) dequantizes on the fly
            # through quant.matmul. "+kv" also stores decode-session and
            # engine KV caches int8. Training needs exact weights for the
            # optimizer. Sharded stages compose: quantizing the
            # already-sharded tree keeps GSPMD shardings on q and scale.
            if quant not in ("int8", "int8+kv"):
                # fail the MODULE load (the user sees the error) rather
                # than silently serving a mode they didn't ask for
                raise ValueError(f"unknown quant mode {quant!r}")
            if training:
                self.log.warning("quant=%s ignored for a TRAINING job", quant)
            else:
                from tensorlink_tpu.models.quant import quantize_params

                params = quantize_params(params)
                cache_quant = quant == "int8+kv"
        rt = StageRuntime(
            job_id=job_id,
            cfg=cfg,
            stage=stage,
            params=params,
            model_spec=dict(model),
            mesh=mesh,
            training=training,
            cache_quant=cache_quant,
        )
        if rt.whole_model:
            from tensorlink_tpu.engine.generate import GenerationEngine

            ml_cfg = self.node.config.ml
            rt.engine = GenerationEngine(
                cfg,
                params,  # already quantized above when quant was requested
                mesh=mesh,
                # batch buckets include 1, so never shard cache batch on the
                # data axis here; kv heads ride the tensor axis
                cache_specs=(
                    self._cache_specs_for(rt, batch=1, serve_tp=serve_tp)
                    if mesh is not None else None
                ),
                max_seq_len=min(cfg.max_seq_len, ml_cfg.max_seq_len),
                seq_buckets=ml_cfg.seq_buckets,
                batch_buckets=ml_cfg.batch_buckets,
                # params are pre-quantized above (quantize_params is
                # idempotent, so the engine's own pass is a no-op); this
                # sets the engine's cache mode for "+kv" AND records the
                # weight mode the serving snapshot / serving_modes report
                # (weights-only "int8" used to pass None here, so the
                # paged engine couldn't tell operators it was quantized)
                quant=quant if not training else None,
            )
        warm_toks = self.node.config.ml.warmup_tokens
        if rt.engine is not None and warm_toks and not training:
            # BEFORE the runtime is installed and the load acknowledged: a
            # warm-up that fails (a program that does not compile or fit
            # on this device) fails the load, where the operator sees it,
            # instead of a model that reports ready and cannot serve. The
            # warm compile counts against the deploy wait
            # (MODULE_LOAD_TIMEOUT); the persistent compile cache
            # (core/devices.py) makes every start after the first cheap.
            dt = rt.engine.warmup(max_new_tokens=warm_toks)
            self.log.info(
                "warmed serving programs in %.1fs (%d tokens)", dt, warm_toks
            )
        with self._lock:
            old = self.jobs.get(job_id)
            self.jobs[job_id] = rt
        if old is not None and old.cont is not None:
            # a re-shipped stage replaces the runtime: fail the old slot
            # engine's in-flight requests fast (their KV died with the old
            # engine) instead of leaving clients to wait out the RPC timeout
            old.cont.close(RuntimeError("stage reloaded"))
            old.cont = None
        from tensorlink_tpu.core.trace import get_tracer
        from tensorlink_tpu.engine.continuous import device_bytes

        held = device_bytes(params).values()
        dt = time.monotonic() - t0
        self.log.info(
            "loaded %s layers [%d,%d) first=%s head=%s tp=%d in %.1fs; "
            "weights a device %.2f-%.2f GB",
            model.get("name", "?"), lo, hi, first, holds_head, serve_tp, dt,
            min(held) / 1e9, max(held) / 1e9,
        )
        # the load as a span, under the job's id (no request rides a load)
        get_tracer().record(
            job_id, "load_stage", site=str(self.node.node_id or ""),
            dur_s=dt, tp=serve_tp, weights_bytes_device_max=max(held),
            weights_bytes_device_min=min(held),
        )
        self._respond(
            p["peer"], proto.MODULE_LOADED, p["rid"],
            {"job_id": job_id, "ok": True, "n_layers": hi - lo},
        )

    def _build_stage_mesh(self, cfg, stage: dict):
        """Build this stage's local device mesh from the plan's axis sizes
        (TP/FSDP/DP/EP inside one worker — GSPMD shards, XLA inserts the
        collectives; SURVEY §2.2 capability upgrades the reference lacks)."""
        from tensorlink_tpu.core.devices import acquire_devices

        axes = {k: int(v) for k, v in (stage.get("mesh_axes") or {}).items()}
        n = 1
        for v in axes.values():
            n *= v
        if n <= 1:
            return None
        devs = acquire_devices().devices
        from tensorlink_tpu.parallel.multihost import is_multihost

        if stage.get("coworkers") and is_multihost():
            # a MERGED co-slice stage spans the pooled devices of every
            # process in the jax.distributed runtime — the GLOBAL list
            # (identically ordered on every process, so all members build
            # the same mesh). Gated on the stage actually being merged: a
            # multihost-joined worker running an ordinary local stage must
            # never mesh over other processes' (non-addressable) devices.
            import jax

            devs = jax.devices()
        if n > len(devs):
            self.log.warning(
                "plan wants %d-device mesh, have %d — running unsharded",
                n, len(devs),
            )
            return None
        from tensorlink_tpu.parallel.mesh import build_mesh

        return build_mesh(axes, devs[:n])

    def _serving_tp(self, cfg, stage: dict, training: bool, quant) -> int:
        """The shard degree of the slot engine that will serve this stage:
        ``MLConfig.tensor_parallel`` when a whole-model serving job can
        take it, else 1 (the planner's GSPMD layout). An operator who asked
        for ``tensor_parallel > 1`` and cannot have it is told why here,
        at the load, not at the first request."""
        ml = self.node.config.ml
        tp = int(getattr(ml, "tensor_parallel", 1) or 1)
        whole = stage["first"] and stage["last"] and stage["holds_head"]
        if tp <= 1 or training or not whole or not ml.continuous_batching:
            return 1
        from tensorlink_tpu.engine.continuous import tp_serving_refusal

        reason = tp_serving_refusal(
            cfg, tp, shared_pool=int(ml.cont_pool_pages or 0) > 0,
            weight_quant=bool(quant),
        )
        if reason is not None:
            self.log.warning(
                "tensor_parallel=%d not taken for this job (%s): planner "
                "layout, static serving", tp, reason,
            )
            return 1
        return tp

    def _shard_params(self, params, cfg, stage: dict, mesh):
        from tensorlink_tpu.parallel.mesh import put
        from tensorlink_tpu.parallel.planner import StagePlan, stage_param_specs

        specs = stage_param_specs(cfg, StagePlan(**stage))
        try:
            return put(mesh, params, specs)
        except ValueError as e:
            if int(getattr(self.node.config.ml, "tensor_parallel", 1) or 1) > 1:
                # the deployment asked for a sharded model: replicating one
                # that was sized to need the mesh is an OOM three calls
                # later, with the reason lost
                raise ValueError(
                    f"stage parameters do not shard over {dict(mesh.shape)} "
                    f"({e}) and the deployment asks for tensor_parallel > 1: "
                    "not replicating"
                ) from e
            self.log.warning("param sharding failed (%s); replicating", e)
            return params

    def _cache_specs_for(self, rt: StageRuntime, batch: int, serve_tp: int = 1):
        """KV-cache PartitionSpecs on this stage's mesh: kv heads on tensor
        (when they divide), batch on data only when the batch divides it —
        serving batches of 1 must not fail against a data axis. Under the
        serving tp layout the mesh is ``serving_mesh`` and kv heads ride
        its ``tp`` axis."""
        from tensorlink_tpu.models.transformer import cache_specs

        if serve_tp > 1:
            return cache_specs(
                rt.cfg, data_axis=None, tensor_axis="tp",
                quantized=rt.cache_quant,
            )
        axes = rt.stage.get("mesh_axes") or {}
        tp = axes.get("tensor", 1)
        dp = axes.get("data", 1)
        return cache_specs(
            rt.cfg,
            data_axis="data" if dp > 1 and batch % dp == 0 else None,
            tensor_axis="tensor" if tp > 1 and rt.cfg.n_kv_heads % tp == 0 else None,
            quantized=rt.cache_quant,
        )

    def _runtime(self, job_id: str) -> StageRuntime:
        rt = self.jobs.get(job_id)
        if rt is None:
            raise KeyError(f"job {job_id} not loaded")
        return rt

    # -- multihost (co-slice merged mesh) transfers ----------------------
    @staticmethod
    def _spans_processes(mesh) -> bool:
        """True when this stage's mesh includes devices of OTHER processes
        (a co-slice merged plan under jax.distributed)."""
        if mesh is None:
            return False
        import jax

        pi = jax.process_index()
        return any(d.process_index != pi for d in mesh.devices.flat)

    def _to_host(self, rt: "StageRuntime", arr):
        """Device → host. On a process-spanning mesh a plain device_get
        would fail on non-addressable shards — gather the full value
        instead (a collective: every member process executes this inside
        the same mirrored work item, so launches stay lockstep)."""
        import jax

        if self._spans_processes(rt.mesh):
            from jax.experimental import multihost_utils

            # tiled=True: for a global jax.Array this returns the FULL
            # global value (per-process host data would be stacked instead)
            return np.asarray(
                multihost_utils.process_allgather(arr, tiled=True)
            )
        return np.asarray(jax.device_get(arr))

    def _to_device(self, rt: "StageRuntime", arr):
        """Host → device. On a process-spanning mesh, commit host data
        replicated over the stage mesh (every member received the same
        bytes in its mirrored work item); otherwise a plain local array."""
        import jax
        import jax.numpy as jnp

        if self._spans_processes(rt.mesh):
            from jax.sharding import NamedSharding, PartitionSpec

            host = np.asarray(arr)
            # rank-expanded replicated spec — the canonical jit cache-key
            # spelling (PartitionSpec() is the same placement but a
            # DIFFERENT key, the PR 17 recompile class; TL101)
            spec = PartitionSpec(*([None] * host.ndim))
            return jax.device_put(host, NamedSharding(rt.mesh, spec))
        return jnp.asarray(np.asarray(arr))

    def _stage_fwd_fn(
        self,
        rt: StageRuntime,
        seq_mesh,
        pp_size: int,
        apply_head: bool,
        *,
        remat: bool = False,
        n_micro: int = 1,
    ):
        """Build the ``(params, x, attn_mask) -> out`` function for this
        stage's layer slice, where ``x`` is tokens (first stage) or hidden
        (later stages). All varying data is an ARGUMENT (not captured) so
        jitted wrappers of the closure are safely cacheable per shape.

        Dispatch, in order: a plan mesh with a ``stage`` axis runs the slice
        through the in-mesh GPipe program (parallel/pipeline.py); a ``seq``
        axis runs ring attention inside ``stage_forward``; otherwise the
        plain compiled stage program. All three are differentiable — the
        training backward is a cached jit of ``jax.vjp`` over this closure
        (the explicit replacement for the reference's torch-autograd replay,
        ml/worker.py:233-291)."""
        from tensorlink_tpu.models.transformer import stage_forward

        first = rt.stage["first"]
        cfg = rt.cfg
        axes = rt.stage.get("mesh_axes") or {}
        if cfg.moe and remat and int(axes.get("expert", 1)) > 1:
            # TRAINING forwards with an expert axis take the capacity-factor
            # sparse dispatch (parallel/expert.py); eval forwards, decode
            # sessions, and the GenerationEngine stay on exact dense
            # dispatch — capacity overflow drops tokens, which must never
            # silently change served/eval logits. Expert-axis sharding
            # still applies to the dense path via GSPMD.
            cfg = cfg.with_(moe_dispatch="sparse")

        if pp_size > 1:
            from tensorlink_tpu.parallel.pipeline import pipelined_stage_forward

            def fwd(params, x, attn_mask):
                out, _ = pipelined_stage_forward(
                    params,
                    cfg,
                    rt.mesh,
                    tokens=x if first else None,
                    hidden=None if first else x,
                    attn_mask=attn_mask,
                    n_micro=n_micro,
                    first=first,
                    last=apply_head,
                    remat=remat,
                )
                return out

            return fwd

        def fwd(params, x, attn_mask):
            out, _ = stage_forward(
                params,
                cfg,
                tokens=x if first else None,
                hidden=None if first else x,
                attn_mask=attn_mask,
                first=first,
                last=apply_head,
                remat=remat,
                seq_mesh=seq_mesh,
            )
            return out

        return fwd

    @staticmethod
    def _pp_n_micro(pp_size: int, batch: int) -> int:
        """Prefer 2 micro-batches per stage (keeps the bubble small),
        degrade to whatever divides the batch; this in-mesh micro count is
        sized to THIS stage's mesh, independent of the cross-worker
        plan.n_micro grad-accumulation knob."""
        for cand in (2 * pp_size, pp_size, 2, 1):
            if batch % cand == 0:
                return cand
        return 1

    def _train_programs(self, rt: StageRuntime, flags: tuple, shapes: tuple):
        """Cached jitted (fwd, bwd) programs for one training configuration.

        ``bwd(params, x, mask, g)`` takes ``jax.vjp`` of the stage closure
        INSIDE jit — the forward recomputes within the compiled program
        (what remat was doing through the eager vjp anyway), so backward is
        one cached XLA execution instead of an op-by-op eager replay per
        request."""
        import jax

        key = (flags, shapes)
        progs = rt.bwd_cache.get(key)
        if progs is not None:
            return progs
        seq_on, pp_size, apply_head, remat, n_micro, wrt_input = flags
        fwd = self._stage_fwd_fn(
            rt,
            rt.mesh if seq_on else None,
            pp_size,
            apply_head,
            remat=remat,
            n_micro=n_micro,
        )
        if wrt_input:

            def bwd(params, x, mask, g):
                _, vjp = jax.vjp(lambda p, xx: fwd(p, xx, mask), params, x)
                return vjp(g)  # (grad_params, grad_x)

        else:  # first stage: tokens are int — grads wrt params only

            def bwd(params, x, mask, g):
                _, vjp = jax.vjp(lambda p: fwd(p, x, mask), params)
                return vjp(g)[0], None

        progs = (jax.jit(fwd), jax.jit(bwd))
        rt.bwd_cache[key] = progs
        return progs

    # -- forward --------------------------------------------------------
    def _forward(self, p: dict) -> None:
        """op="stage": run my layer slice (optionally with a decode-session
        KV cache). op="head": final norm + logits (tied-embedding hop).
        ``train=True`` + ``tag`` records the vjp for a later BACKWARD."""
        import jax
        import jax.numpy as jnp

        from tensorlink_tpu.models.base import KVCache
        from tensorlink_tpu.models.transformer import head_forward, stage_forward

        rt = self._runtime(p["job_id"])
        op = p.get("op", "stage")
        if op == "end_session":
            sid = p.get("session")
            rt.sessions.pop(sid, None)
            rt.penalty_counts.pop(sid, None)
            for phase in ("s", "h"):
                rt.session_seq.pop(f"{sid}:{phase}", None)
                rt.session_resp.pop(f"{sid}:{phase}", None)
            self._respond(p["peer"], proto.FORWARD_RESP, p["rid"], {"ok": True})
            return
        if p.get("session") is not None and p.get("seq") is not None:
            # sequence-numbered session op: a duplicate delivery (frame dup
            # on the wire, RPC retry after a lost reply) must never re-apply
            # the KV writes — re-send the cached outcome instead
            if self._session_dup(rt, p):
                return
        if self.faults is not None and p.get("session") is not None:
            # fault site "worker.session_step" (core/faults.py): counted per
            # APPLIED op so transport dups never perturb the plan's decisions
            self.faults.inject("worker.session_step", op)
        if p.get("trace"):
            # session-op trace propagation (core/trace.py): the admission
            # op carries the admitted requests' trace ids — record this
            # stage's hop under each so pipelined traces name the workers
            # a request's prefill touched
            from tensorlink_tpu.core.trace import get_tracer

            tracer = get_tracer()
            for tid in p["trace"]:
                tracer.record(
                    str(tid), "session_prefill", site=self.node.node_id,
                    layers=f"{rt.stage['layer_lo']}-{rt.stage['layer_hi']}",
                )
        train = bool(p.get("train", False))
        tag = p.get("tag", "")
        if op == "chain" and p.get("head_hop"):
            # final hop of a worker-to-worker chain looping back for the
            # tied-embedding head (ml/module.py::_forward_chain)
            hidden = jnp.asarray(np.asarray(p["hidden"]))
            logits = head_forward(rt.params, hidden, rt.cfg)
            self._finish_fwd(rt, p, logits, True)
            return
        if op == "head":
            hidden = jnp.asarray(np.asarray(p["hidden"]))
            logits = head_forward(rt.params, hidden, rt.cfg)
            if train:
                rt.saved[tag + ".head"] = ("head", None, hidden, None, True)
                self._respond(
                    p["peer"], proto.FORWARD_RESP, p["rid"],
                    {"out": np.asarray(jax.device_get(logits))},
                )
                return
            self._finish_fwd(rt, p, logits, True)
            return

        stage = rt.stage
        first = stage["first"]
        apply_head = stage["last"] and stage["holds_head"]
        kw: dict[str, Any] = {}
        if first:
            kw["tokens"] = self._to_device(rt, np.asarray(p["tokens"], np.int32))
        else:
            kw["hidden"] = self._to_device(rt, np.asarray(p["hidden"]))
        if p.get("attn_mask") is not None:
            kw["attn_mask"] = self._to_device(
                rt, np.asarray(p["attn_mask"], bool)
            )

        # product-path SP/PP (VERDICT r1 #3): a plan whose mesh carries a
        # seq axis runs ring attention inside stage_forward; a stage axis
        # runs the layer slice through the in-mesh GPipe program. Neither
        # applies to the KV-cache (serving session) path — the planner never
        # emits these axes for serving jobs.
        axes = stage.get("mesh_axes") or {}
        seq_mesh = (
            rt.mesh
            if rt.mesh is not None
            and int(axes.get("seq", 1)) > 1
            and kw.get("attn_mask") is None
            else None
        )
        pp_size = int(axes.get("stage", 1)) if rt.mesh is not None else 1
        x_in = kw["tokens"] if first else kw["hidden"]
        mask = kw.get("attn_mask")
        n_micro = self._pp_n_micro(pp_size, int(x_in.shape[0])) if pp_size > 1 else 1

        if train:
            # no KV cache in training; record the inputs keyed by the
            # driver's (batch, micro) tag — cotangents arrive via BACKWARD
            # and run the cached compiled bwd program over these inputs
            flags = (
                seq_mesh is not None, pp_size, apply_head, True, n_micro,
                not first,
            )
            shapes = (
                x_in.shape, str(x_in.dtype),
                None if mask is None else mask.shape,
            )
            fwd_prog, _ = self._train_programs(rt, flags, shapes)
            out = fwd_prog(rt.params, x_in, mask)
            rt.saved[tag] = ("stage", flags, x_in, mask, not first)
            self._respond(
                p["peer"], proto.FORWARD_RESP, p["rid"],
                {"out": np.asarray(jax.device_get(out)), "is_logits": apply_head},
            )
            return

        if p.get("session") is None and (pp_size > 1 or seq_mesh is not None):
            fwd = self._stage_fwd_fn(
                rt, seq_mesh, pp_size, apply_head, n_micro=n_micro
            )
            out = fwd(rt.params, x_in, mask)
            self._finish_fwd(rt, p, out, apply_head)
            return

        session = p.get("session")
        cache = None
        if session is not None:
            cache = rt.sessions.get(session)
            if cache is not None and p.get("reset_rows"):
                # pipelined slot admission (ml/batching.py
                # PipelinedSlotSession): rows whose previous request
                # finished are recycled by zeroing their write offset —
                # the stale KV beyond it is invisible (attention masks by
                # length) and the admitted prompt overwrites it
                rows = jnp.asarray(np.asarray(p["reset_rows"], np.int32))
                cache = KVCache(
                    k=cache.k, v=cache.v,
                    length=cache.length.at[rows].set(0),
                    k_scale=cache.k_scale, v_scale=cache.v_scale,
                )
            if cache is not None and p.get("reset_len") is not None:
                # pipelined speculative decode: roll back the REJECTED
                # draft positions of the previous verify pass by resetting
                # the write offset (stale KV beyond it is invisible —
                # attention masks by length). Rides the forward body like
                # reorder_idx: no extra per-stage round-trip.
                cache = KVCache(
                    k=cache.k, v=cache.v,
                    length=jnp.full_like(
                        cache.length, int(p["reset_len"])
                    ),
                    k_scale=cache.k_scale, v_scale=cache.v_scale,
                )
            if cache is not None and p.get("reorder_idx") is not None:
                # pipelined beam search: this step's cache rows follow
                # their beam's source row (the same [:, idx] gather the
                # engine-side beam session does) — the permutation rides
                # the forward body, so no extra per-stage round-trip
                gidx = jnp.asarray(np.asarray(p["reorder_idx"], np.int32))
                cache = KVCache(
                    k=cache.k[:, gidx], v=cache.v[:, gidx],
                    length=cache.length[gidx],
                    k_scale=None if cache.k_scale is None
                    else cache.k_scale[:, gidx],
                    v_scale=None if cache.v_scale is None
                    else cache.v_scale[:, gidx],
                )
            if cache is None:
                batch = (kw.get("tokens") if first else kw["hidden"]).shape[0]
                scfg = rt.cfg.with_(n_layers=rt.n_layers)
                cache = KVCache.init(
                    scfg, batch,
                    max_len=int(p.get("cache_len", rt.cfg.max_seq_len)),
                    quantized=rt.cache_quant,
                )
                if rt.mesh is not None:
                    from tensorlink_tpu.parallel.mesh import put

                    cache = put(rt.mesh, cache, self._cache_specs_for(rt, batch))
        out, new_cache = stage_forward(
            rt.params, rt.cfg, cache=cache, first=first, last=apply_head, **kw
        )
        if session is not None:
            rt.sessions[session] = new_cache
        self._finish_fwd(rt, p, out, apply_head)

    # chain fields every forwarded hop must carry onward
    _CHAIN_KEYS = (
        "job_id", "session", "cache_len", "attn_mask", "sample",
        "last_idx", "reply_to", "reorder_idx", "reset_len", "reset_rows",
        "seq", "trace",
    )

    # -- session-op idempotency (seq dedup) ------------------------------
    @staticmethod
    def _session_dedup_key(p: dict) -> str:
        # a first+head-holding stage sees TWO ops per decode step (its
        # stage slice, then the tied-embedding head hop) under the same
        # seq — separate phases so the head hop is not mistaken for a dup
        return f"{p['session']}:{'h' if p.get('head_hop') else 's'}"

    def _session_dup(self, rt: "StageRuntime", p: dict) -> bool:
        """True when this seq was already applied for its session/phase.
        For the latest applied seq the cached outcome is re-delivered: a
        direct response is re-sent under the retry's rid, and a mid-chain
        hop re-drives the chain from its cached output (so a retry whose
        original died downstream still reaches the final hop without any
        stage recomputing or re-absorbing KV)."""
        key = self._session_dedup_key(p)
        seq = int(p["seq"])
        if seq > rt.session_seq.get(key, -1):
            return False
        cached = rt.session_resp.get(key)
        if cached is not None and cached[0] == seq:
            _, kind, payload = cached
            if kind == "resp" and p.get("rid"):
                self._respond(
                    p.get("reply_to") or p["peer"], proto.FORWARD_RESP,
                    p["rid"], payload,
                )
            elif kind == "chain":
                body = dict(payload["body"], _rid=p.get("rid"))
                self.bridge.request(
                    "chain_send", {**payload, "body": body}, timeout=150.0
                )
        return True

    def _session_applied(self, rt: "StageRuntime", p: dict, kind: str, payload) -> None:
        """Record a completed session op (seq watermark + cached outcome).
        Recorded at COMPLETION, not at entry, so a failed op stays
        retryable instead of its retry being swallowed as a dup."""
        if p.get("session") is None or p.get("seq") is None:
            return
        key = self._session_dedup_key(p)
        rt.session_seq[key] = int(p["seq"])
        rt.session_resp[key] = (int(p["seq"]), kind, payload)

    def _finish_fwd(self, rt: "StageRuntime", p: dict, out, is_logits: bool) -> None:
        """Deliver a (non-training) forward result: forward to the next
        chain hop worker-to-worker (ml/module.py::_forward_chain — the
        activation never transits the user), sample on-device when this hop
        produced the final logits of a decode step, or respond with the
        array. ``reply_to`` names the chain's originator; per-hop requests
        have none and answer their direct peer."""
        import jax
        import numpy as np

        chain = p.get("chain") or []
        if p.get("op") == "chain" and chain:
            nxt = chain[0]
            body = {
                k: p[k] for k in self._CHAIN_KEYS if p.get(k) is not None
            }
            body.update(
                op="chain",
                chain=chain[1:],
                head_hop=bool(nxt.get("head")),
                hidden=np.asarray(jax.device_get(out)),
                _rid=p["rid"],  # the originator's future resolves on this
            )
            req = {"addr": list(nxt["addr"]), "tag": proto.FORWARD,
                   "body": body}
            self._session_applied(rt, p, "chain", req)
            self.bridge.request(
                "chain_send", req,
                # generous: a multi-GB activation over DCN outlives the
                # 30 s IPC default, and a spurious timeout here would race
                # an error reply against the still-progressing chain
                timeout=150.0,
            )
            return
        reply_peer = p.get("reply_to") or p["peer"]

        def respond_final(body: dict) -> None:
            if p.get("trace"):
                # ship this process's spans for the op's trace ids home
                # (the pipelined admission op carries them): the client
                # ingests, so /trace names the workers the prefill
                # touched. Mid-chain stages in OTHER processes keep
                # their hop spans local — only the responding process's
                # tracer rides this reply.
                from tensorlink_tpu.core.trace import get_tracer

                tracer = get_tracer()
                body["trace_spans"] = {
                    str(t): tracer.collect(str(t)) for t in p["trace"]
                }
            self._session_applied(rt, p, "resp", body)
            self._respond(reply_peer, proto.FORWARD_RESP, p["rid"], body)

        if p.get("sample") is not None and is_logits:
            samp = p["sample"]
            if samp.get("verify"):
                # pipelined speculative decode: ship the ARGMAX id at
                # EVERY position of this step — the driver accepts the
                # matched draft prefix plus the correction token
                # (engine/generate.py::generate_lookahead semantics)
                import jax.numpy as jnp_

                ids = self._to_host(rt, jnp_.argmax(out, axis=-1))
                respond_final({"verify_ids": np.asarray(ids, np.int32)})
                return
            if samp.get("beam_k"):
                # pipelined beam search: ship K x (K+n_eos) candidate
                # (score, id) pairs from an on-device top-k — not [K, V]
                # logits — to the frontier driver (ml/module.py)
                vals, idx = self._beam_topk_from_logits(rt, out, p)
                respond_final({"beam_vals": vals, "beam_idx": idx})
                return
            # final logits of a decode step: sample on-worker and ship one
            # token id per row — the per-token logits transfer (~600 KB at
            # a 151k vocab) never leaves the device host
            tok = self._sample_from_logits(rt, out, p)
            respond_final({"token": tok})
            return
        host_out = self._to_host(rt, out)  # collective on spanning meshes —
        # must run on EVERY member, so it happens before the mirror check
        if p.get("mirror"):
            # co-slice member of a mirrored work item: the launches above
            # were this process's half of the SPMD programs; only the
            # primary's response carries the payload
            self._respond(
                reply_peer, proto.FORWARD_RESP, p["rid"], {"ok": True}
            )
            return
        respond_final({"out": host_out, "is_logits": is_logits})

    def _beam_topk_from_logits(self, rt: "StageRuntime", logits, p: dict):
        """Head-worker half of PIPELINED beam search: gather each row's
        step logits, take the top-(K+n_eos) of the log-softmax on device
        (engine/generate.py::_beam_topk — tie-break parity with stable
        argsort is pinned there) and return host arrays."""
        import jax.numpy as jnp

        from tensorlink_tpu.engine.generate import _beam_topk

        samp = p["sample"]
        last_idx = p.get("last_idx")
        if logits.ndim == 3:
            B = logits.shape[0]
            if last_idx is not None:
                gidx = jnp.asarray(np.asarray(last_idx, np.int32))
            else:
                gidx = jnp.full((B,), logits.shape[1] - 1, jnp.int32)
            step_logits = logits[jnp.arange(B), gidx]
        else:
            step_logits = logits
        K = int(samp["beam_k"])
        kk = K + int(samp.get("beam_n_eos", 0))
        vals, idx = _beam_topk(step_logits[:K], max(kk, 1))
        return self._to_host(rt, vals), self._to_host(rt, idx)

    def _sample_from_logits(self, rt: "StageRuntime", logits, p: dict) -> np.ndarray:
        """Worker-side sampling for pipelined decode (ml/module.py
        _generate_pipelined): gather each row's last real position (prefill)
        or the single decode position, then run the jitted sampler with a
        deterministic (seed, step)-derived key.

        Presence/frequency penalties carry [B, V] context counts ACROSS the
        session's decode steps on this worker (rt.penalty_counts): step 0
        scatters the prompt ids shipped in the sample dict, and each sampled
        token folds back in — so penalized requests work on pipelined
        models instead of 400ing (the reference applies HF sampling
        uniformly regardless of distribution, ml/worker.py:359-430)."""
        import jax
        import jax.numpy as jnp

        from tensorlink_tpu.engine.sampling import SamplingParams, sample

        samp: dict = p["sample"]
        last_idx = p.get("last_idx")
        if logits.ndim == 3:
            B = logits.shape[0]
            if last_idx is not None:
                idx = jnp.asarray(np.asarray(last_idx, np.int32))
            else:
                idx = jnp.full((B,), logits.shape[1] - 1, jnp.int32)
            step_logits = logits[jnp.arange(B), idx]
        else:
            B = logits.shape[0]
            step_logits = logits
        t = samp.get("temperature", 0.0)
        pen_p = samp.get("presence_penalty", 0.0)
        pen_f = samp.get("frequency_penalty", 0.0)

        def any_nonzero(v):
            vals = v if isinstance(v, (list, tuple, np.ndarray)) else [v]
            return any(float(x or 0.0) != 0.0 for x in vals)

        penalized = any_nonzero(pen_p) or any_nonzero(pen_f)
        if samp.get("seeds") is not None:
            # pipelined slot admission (continuous batching): each row
            # samples with its OWN stateless key chain —
            # fold_in(PRNGKey(seed_r), step_r) — so a slot's stream never
            # depends on its neighbors, admission step offsets differ per
            # row, and a recovered session resumes its draws exactly.
            # (Non-penalized only; the slot scheduler routes penalized
            # requests through the co-batch path.)
            from tensorlink_tpu.engine.continuous import (
                _row_keys, _sample_rows,
            )

            def row(v, dtype, fill):
                vals = (
                    list(v) if isinstance(v, (list, tuple, np.ndarray))
                    else [v if v is not None else fill] * B
                )
                return jnp.asarray(np.asarray(vals, dtype))

            keys = _row_keys(
                row(samp["seeds"], np.int32, 0),
                row(samp.get("steps", 0), np.int32, 0),
            )
            tok = _sample_rows(
                step_logits, keys,
                row(t, np.float32, 0.0),
                row(samp.get("top_k", 0), np.int32, 0),
                row(samp.get("top_p", 1.0), np.float32, 1.0),
                row(pen_p, np.float32, 0.0),
                row(pen_f, np.float32, 0.0),
                jnp.zeros((B, rt.cfg.vocab_size), jnp.int32),
            )
            return self._to_host(rt, tok)
        if isinstance(t, (list, tuple, np.ndarray)):
            # batched serving mixes requests with different knobs: [B, 1]
            # leaves ride ONE compiled sampler (engine/sampling.py contract)
            def col(v, dtype):
                # scalars replicate across rows (NOT pad-fill — every row
                # shares the one requested value)
                if not isinstance(v, (list, tuple, np.ndarray)):
                    v = [v] * len(list(t))
                return jnp.asarray(v, dtype).reshape(-1)[:, None]

            sp = SamplingParams(
                temperature=col(t, jnp.float32),
                top_k=col(samp.get("top_k", 0), jnp.int32),
                top_p=col(samp.get("top_p", 1.0), jnp.float32),
                presence_penalty=col(pen_p, jnp.float32),
                frequency_penalty=col(pen_f, jnp.float32),
            )
        else:
            sp = SamplingParams.make(
                temperature=float(t),
                top_k=int(samp.get("top_k", 0)),
                top_p=float(samp.get("top_p", 1.0)),
                presence_penalty=float(pen_p or 0.0),
                frequency_penalty=float(pen_f or 0.0),
            )
        counts = None
        session = p.get("session")
        if penalized and session is not None:
            counts = rt.penalty_counts.get(session)
            if counts is None:
                # session start: counts = the prompt's token histogram
                pt = np.asarray(samp["prompt_tokens"], np.int64)
                pm = np.asarray(samp["prompt_mask"], bool)
                c = np.zeros((pt.shape[0], rt.cfg.vocab_size), np.int32)
                for i in range(pt.shape[0]):
                    np.add.at(c[i], pt[i][pm[i]], 1)
                counts = jnp.asarray(c)
        key = jax.random.fold_in(
            jax.random.PRNGKey(int(samp.get("seed", 0))),
            int(samp.get("step", 0)),
        )
        tok = sample(step_logits, key, sp, counts)
        if counts is not None:
            # fold the sampled token into the context for the next step
            # (rows the driver has finished keep sampling; their counts
            # drift but their outputs are discarded host-side)
            rt.penalty_counts[session] = counts.at[
                jnp.arange(counts.shape[0]), tok
            ].add(1)
        return self._to_host(rt, tok)

    # -- backward (reference _handle_backward replays torch autograd,
    # ml/worker.py:233-291; here it applies the recorded vjp) -------------
    def _backward(self, p: dict) -> None:
        import jax
        import jax.numpy as jnp

        rt = self._runtime(p["job_id"])
        tag = p.get("tag", "")
        op = p.get("op", "stage")
        key = tag + ".head" if op == "head" else tag
        entry = rt.saved.pop(key, None)
        if entry is None:
            raise KeyError(f"no saved activations for tag {key!r}")
        kind, flags, x_in, mask, wrt_input = entry
        g = self._to_device(
            rt, np.asarray(p["grad"])
        ).astype(rt.cfg.dtype)
        if kind == "head":
            grad_params, grad_input = self._head_bwd(rt)(rt.params, x_in, g)
        else:
            shapes = (
                x_in.shape, str(x_in.dtype),
                None if mask is None else mask.shape,
            )
            _, bwd_prog = self._train_programs(rt, flags, shapes)
            grad_params, grad_input = bwd_prog(rt.params, x_in, mask, g)
        self._accumulate(rt, grad_params)
        body = {"ok": True}
        if grad_input is not None:
            host_g = self._to_host(rt, grad_input)  # collective when
            # spanning — run on every member before any mirror slimming
            if not p.get("mirror"):
                body["grad"] = host_g
        self._respond(p["peer"], proto.BACKWARD_RESP, p["rid"], body)

    def _head_bwd(self, rt: StageRuntime):
        """Cached jitted backward for the tied-embedding head hop."""
        import jax

        from tensorlink_tpu.models.transformer import head_forward

        prog = rt.bwd_cache.get("head")
        if prog is None:

            def bwd(params, h, g):
                _, vjp = jax.vjp(
                    lambda prm, hh: head_forward(prm, hh, rt.cfg), params, h
                )
                return vjp(g)

            prog = jax.jit(bwd)
            rt.bwd_cache["head"] = prog
        return prog

    def _accumulate(self, rt: StageRuntime, grads) -> None:
        import jax

        if rt.grad_accum is None:
            rt.grad_accum = grads
        else:
            rt.grad_accum = jax.tree.map(
                lambda a, b: a + b, rt.grad_accum, grads
            )
        rt.n_accum += 1

    # -- optimizer (reference optimizer RPC fan-out, ml/optim.py:81-205;
    # here each stage runs optax on its own sharded params) ---------------
    def _optimizer(self, p: dict) -> None:
        import jax
        import optax

        from tensorlink_tpu.engine.training import make_optimizer

        rt = self._runtime(p["job_id"])
        op = p.get("op")
        if op == "init":
            spec = dict(p.get("spec", {}))
            name = spec.pop("name", "adamw")
            rt.opt = make_optimizer(name, **spec)
            rt.opt_state = rt.opt.init(rt.params)
            self._maybe_shard_opt_state(rt)
            body = {"ok": True, "op": op}
        elif op == "zero":
            rt.grad_accum = None
            rt.n_accum = 0
            body = {"ok": True, "op": op}
        elif op == "grad_norm":
            # this stage's raw accumulated-cotangent norm; the driver
            # combines stages into the true global norm so clipping matches
            # the single-program optimizer chain (engine/training.py)
            gn = (
                float(self._to_host(rt, optax.global_norm(rt.grad_accum)))
                if rt.grad_accum is not None
                else 0.0
            )
            body = {"ok": True, "op": op, "grad_norm": gn}
        elif op == "step":
            if self.faults is not None:
                # fault site "worker.train_step": fires BEFORE the update is
                # applied, so a crash here loses the in-flight step — the
                # situation auto-checkpointing exists to bound
                self.faults.inject("worker.train_step", op)
            if rt.opt is None:
                raise ValueError("optimizer not initialized")
            if rt.grad_accum is None:
                raise ValueError("no accumulated gradients")
            scale = float(p.get("scale", 1.0))
            if scale != 1.0:
                # driver-supplied 1/total_tokens: turns the accumulated
                # sum-NLL cotangents into the token-mean gradient
                rt.grad_accum = jax.tree.map(
                    lambda g: g * scale, rt.grad_accum
                )
            updates, rt.opt_state = rt.opt.update(
                rt.grad_accum, rt.opt_state, rt.params
            )
            rt.params = optax.apply_updates(rt.params, updates)
            if self._zero1_dp(rt) > 1:
                # sharded updates make `p + u` inherit the data-sharded
                # layout — put params back in their stage specs
                # (replicated over data) so the forward programs' input
                # layout never drifts across optimizer steps
                rt.params = self._shard_params(
                    rt.params, rt.cfg, rt.stage, rt.mesh
                )
            if rt.engine is not None:
                rt.engine.params = rt.params
            gnorm = float(self._to_host(rt, optax.global_norm(rt.grad_accum)))
            self._record_proof(rt, gnorm)
            rt.grad_accum = None
            rt.n_accum = 0
            body = {"ok": True, "op": op, "grad_norm": gnorm}
        else:
            raise ValueError(f"unknown optimizer op {op!r}")
        self._respond(p["peer"], proto.OPTIMIZER_RESP, p["rid"], body)

    def _zero1_dp(self, rt: StageRuntime) -> int:
        """The stage's ZeRO-1 data-parallel degree: >1 only when the plan
        gave this training stage a data axis (parallel/planner.py::
        training_update_mode — the one predicate) and a real mesh backs
        it. 0/1 means the unsharded optimizer layout."""
        if rt.mesh is None or not rt.training:
            return 0
        from tensorlink_tpu.parallel.planner import training_update_mode

        axes = rt.stage.get("mesh_axes") or {}
        if training_update_mode(axes, rt.training) != "zero1":
            return 0
        return int(axes.get("data", 1))

    def _maybe_shard_opt_state(self, rt: StageRuntime) -> None:
        """ZeRO-1 on the RPC training path (docs/TRAINING.md): when the
        stage mesh carries a data axis, the optimizer state is DECLARED
        sharded 1/dp over it at init (params stay in their stage specs —
        replicated over data), so the eager optax update runs sharded and
        per-replica optimizer bytes drop to ~1/dp. Same locality the
        compiled zero1 step gets, without new programs on this path."""
        dp = self._zero1_dp(rt)
        if dp <= 1:
            return
        import jax
        from jax.sharding import NamedSharding

        from tensorlink_tpu.engine.training import optimizer_state_specs
        from tensorlink_tpu.parallel.planner import (
            StagePlan,
            stage_param_specs,
        )

        pspecs = stage_param_specs(rt.cfg, StagePlan(**rt.stage))
        sspecs = optimizer_state_specs(
            rt.opt, rt.params, pspecs, dp_axis="data", dp_size=dp,
        )
        rt.opt_state = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(rt.mesh, s)),
            rt.opt_state, sspecs,
        )

    # -- proof of learning (platform/proofs.py; reference scaffolding
    # never wired, ml/proofs.py + job_monitor.py:193-207) -----------------
    MAX_PROOF_LOG = 256
    PROOF_WINDOW = 32  # entries shipped per PROOF_REQ

    def _record_proof(self, rt: StageRuntime, grad_norm: float) -> None:
        from tensorlink_tpu.platform import proofs

        rt.opt_steps += 1
        try:
            sketch = proofs.gradient_sketch(
                rt.grad_accum, seed=int(rt.job_id[:8], 16)
            )
        except Exception:  # noqa: BLE001 — the sketch is telemetry; on a
            # process-spanning mesh its per-leaf gathers may produce
            # non-addressable outputs, and the proof CHAIN (hash over
            # grad_norm) must keep growing regardless
            self.log.debug("gradient sketch unavailable", exc_info=True)
            sketch = np.zeros(0)
        prev = rt.proof_log[-1]["hash"] if rt.proof_log else ""
        rt.proof_log.append(
            proofs.proof_entry(rt.opt_steps, grad_norm, sketch, prev)
        )
        if len(rt.proof_log) > self.MAX_PROOF_LOG:
            del rt.proof_log[: -self.MAX_PROOF_LOG]

    def _proof_req(self, p: dict) -> None:
        rt = self._runtime(p["job_id"])
        window = [dict(e) for e in rt.proof_log[-self.PROOF_WINDOW:]]
        if window and len(rt.proof_log) > len(window):
            # chain root for a truncated window = hash of the entry just
            # before it, so the verifier can still check integrity
            window[0]["_chain_root"] = rt.proof_log[-len(window) - 1]["hash"]
        self._respond(
            p["peer"], proto.PROOF_RESP, p["rid"],
            {"ok": True, "log": window, "total_steps": rt.opt_steps},
        )

    # -- checkpoint (net-new vs reference: no mid-training checkpoint
    # exists there, SURVEY §5) -------------------------------------------
    def _checkpoint(self, p: dict) -> None:
        """Save/restore this stage's params (+ optimizer state). Works on
        merged (process-spanning) co-slice stages too: the work item is
        MIRRORED to every member (ml/module.py::_request_mirrored), each
        member executes the same per-leaf gathers/puts (collectives stay
        lockstep), and only the primary touches the file / carries the
        payload — the coworkers answer a slim ack."""
        import jax

        rt = self._runtime(p["job_id"])
        op = p.get("op", "save")
        mirror = bool(p.get("mirror"))
        path = Path(p["dir"]) / f"stage_{rt.stage['layer_lo']}_{rt.stage['layer_hi']}.tlts"
        if op == "save":
            # _to_host gathers the full value on process-spanning meshes
            # (plain device_get cannot see non-addressable shards); every
            # member must run the gathers even though only the primary writes
            host = jax.tree.map(
                lambda a: self._to_host(rt, a), self._exact_params(rt)
            )
            opt_host = (
                jax.tree.map(lambda a: self._to_host(rt, a), rt.opt_state)
                if rt.opt_state is not None else None
            )
            if mirror:
                self._respond(
                    p["peer"], proto.CHECKPOINT_RESP, p["rid"],
                    {"ok": True, "mirror": True},
                )
                return
            path.parent.mkdir(parents=True, exist_ok=True)
            state = {"params": host, "stage": rt.stage}
            if opt_host is not None:
                state["opt_state"] = opt_host
            ser.encode_to_file(state, path)
            body = {"ok": True, "path": str(path)}
        elif op == "restore":
            import jax.numpy as jnp

            state = ser.decode_from_file(path)
            host = jax.tree.map(np.asarray, state["params"])
            if rt.mesh is not None:
                # re-shard on the stage mesh (every member of a merged stage
                # read the same bytes and builds the same global arrays);
                # a bare jnp.asarray would silently replicate a sharded stage
                rt.params = self._shard_params(host, rt.cfg, rt.stage, rt.mesh)
            else:
                rt.params = jax.tree.map(jnp.asarray, host)
            restored_opt = False
            if "opt_state" in state and rt.opt is not None:
                from jax.sharding import NamedSharding

                tmpl = rt.opt.init(rt.params)
                flat_t, treedef = jax.tree.flatten(tmpl)
                restored = jax.tree.leaves(state["opt_state"])
                leaves = []
                for t_leaf, r in zip(flat_t, restored):
                    sh = getattr(t_leaf, "sharding", None)
                    arr = np.asarray(r)
                    # mesh-sharded template leaves (moments mirroring the
                    # sharded params) get their sharding back — on a
                    # spanning mesh a local jnp.asarray could not mix with
                    # global params in the update. Everything else (step
                    # counters etc.) stays an UNCOMMITTED array: committing
                    # a scalar to one device would conflict with the
                    # mesh-resident moments in the same eager update.
                    leaves.append(
                        jax.device_put(arr, sh)
                        if isinstance(sh, NamedSharding)
                        else jnp.asarray(arr)
                    )
                rt.opt_state = jax.tree.unflatten(treedef, leaves)
                restored_opt = True
            if rt.engine is not None:
                rt.engine.params = rt.params
            body = {"ok": True, "restored_opt": restored_opt,
                    "opt_in_checkpoint": "opt_state" in state}
            if mirror:
                body = {"ok": True, "mirror": True}
        else:
            raise ValueError(f"unknown checkpoint op {op!r}")
        self._respond(p["peer"], proto.CHECKPOINT_RESP, p["rid"], body)

    # -- generate (whole-model jobs) ------------------------------------
    def _generate(self, p: dict) -> None:
        """Compiled generation on a whole-model job. Streams token ids over
        the TOKEN relay when ``stream`` is set (reference worker streamer,
        ml/worker.py:359-447), then resolves with the full sequences."""
        from tensorlink_tpu.engine.sampling import SamplingParams

        rt = self._runtime(p["job_id"])
        if p.get("trace") and "stamp" in p:
            self._trace_way_in(rt, p)
        if rt.engine is None:
            raise ValueError("generate requires a whole-model stage")
        prompts = [list(map(int, row)) for row in p["prompts"]]
        if p.get("continuous") and self._generate_continuous(rt, p, prompts):
            return  # admitted into the slot batch; responds via on_finish
        knobs = (
            p.get("temperature", 0.0), p.get("top_k", 0), p.get("top_p", 1.0),
            p.get("presence_penalty", 0.0), p.get("frequency_penalty", 0.0),
        )
        # per-row knobs (ml/batching.py mixes requests); a scalar among
        # sequences applies to every row. Scalars are ALSO stacked to
        # [B, 1] leaves so every serving request — solo or co-batched —
        # shares the one warmed program (leaf shapes key the jit cache;
        # engine.warmup() pre-compiles exactly this shape)
        n = len(prompts)

        def rows(v):
            return list(v) if isinstance(v, (list, tuple)) else [v] * n

        per_row = [
            SamplingParams.make(
                temperature=float(t), top_k=int(k), top_p=float(tp),
                presence_penalty=float(pp), frequency_penalty=float(fp),
            )
            for t, k, tp, pp, fp in zip(*(rows(v) for v in knobs))
        ]
        sampling = SamplingParams.stack(per_row, pad_to=n)
        budgets = p.get("budgets")
        reuse_prefix = bool(p.get("reuse_prefix", False)) and len(prompts) == 1
        # prompt-lookup speculation: greedy B=1 only (it IS vanilla greedy,
        # in fewer model passes) — and penalties change greedy's choices,
        # so a penalized request must take the vanilla loop
        greedy = not isinstance(p.get("temperature", 0.0), (list, tuple)) \
            and float(p.get("temperature", 0.0)) <= 0.0
        lookahead = (
            bool(p.get("lookahead", False)) and len(prompts) == 1 and greedy
            and not any(
                isinstance(v, (list, tuple)) or float(v or 0.0) != 0.0
                for v in knobs[3:]
            )
        )
        stream_id = p.get("stream")
        peer = p["peer"]
        chunk_cfg = int(self.node.config.ml.stream_chunk_steps or 0)
        # confirmed stop-sequence cancels ride back from the driving user
        # as STREAM_CANCEL frames parked on the network server; poll them
        # every `poll_every` steps — one blocking IPC round trip per chunk,
        # not per token — so the compiled chunked decode overruns a stop by
        # at most one chunk instead of the full token budget
        poll_every = chunk_cfg if chunk_cfg > 0 else 32
        steps_seen = 0

        def stream_cb(emitted):
            # (row, token) pairs keep attribution for batched streams; the
            # driver reconstructs the per-row emission list
            nonlocal steps_seen
            pairs = [[i, t] for i, t in enumerate(emitted) if t is not None]
            if pairs:
                # fire-and-forget: a blocking round-trip here would add a
                # full IPC latency to every decode step
                self.bridge.notify(
                    "send_token",
                    {"peer": peer, "stream": stream_id, "tokens": pairs},
                )
            steps_seen += 1
            if stream_id and steps_seen % poll_every == 0:
                try:
                    rows = self.bridge.request(
                        "poll_cancel", {"stream": stream_id}, timeout=5.0
                    )
                except Exception:
                    rows = None  # relay hiccup must not kill the decode
                return rows or None
            return None

        if int(p.get("num_beams", 1)) > 1:
            # beams ride the engine's batch axis — clamp to the largest
            # compiled bucket (a deployment-config mismatch must degrade,
            # not surface as an opaque 500) — but never SILENTLY: the API
            # schema promised [1, 8], so the clamp is logged and the
            # effective width rides the response for clients to inspect
            k = min(int(p["num_beams"]), max(rt.engine.batch_buckets))
            if k < int(p["num_beams"]):
                self.log.warning(
                    "num_beams=%d clamped to %d (largest compiled batch "
                    "bucket; configure batch_buckets to serve wider beams)",
                    int(p["num_beams"]), k,
                )
            st = rt.engine.beam_start(
                prompts,
                num_beams=k,
                max_new_tokens=int(p.get("max_new_tokens", 128)),
                eos_ids=p.get("eos_ids", ()),
            )
            rt.beam_sessions[p["rid"]] = (st, p, k)
            self._beam_step(p["job_id"], p["rid"])
            return
        if lookahead:
            result = rt.engine.generate_lookahead(
                prompts,
                max_new_tokens=int(p.get("max_new_tokens", 128)),
                eos_ids=p.get("eos_ids", ()),
                reuse_prefix=reuse_prefix,
                stream_cb=stream_cb if stream_id else None,
            )
            if stream_id:
                self.bridge.request(
                    "send_token",
                    {"peer": peer, "stream": stream_id, "tokens": [],
                     "done": True},
                )
        elif stream_id:
            chunk = int(self.node.config.ml.stream_chunk_steps or 0)
            gen_kw = dict(
                max_new_tokens=int(p.get("max_new_tokens", 128)),
                sampling=sampling,
                eos_ids=p.get("eos_ids", ()),
                seed=int(p.get("seed", 0)),
                stream_cb=stream_cb,
                budgets=budgets,
                reuse_prefix=reuse_prefix,
            )
            if chunk > 0:
                # compiled-chunk streaming: one host round trip per
                # `chunk` tokens instead of per token
                result = rt.engine.generate_chunked(
                    prompts, chunk_steps=chunk, **gen_kw
                )
            else:
                result = rt.engine.generate(prompts, **gen_kw)
            self.bridge.request(
                "send_token",
                {"peer": peer, "stream": stream_id, "tokens": [], "done": True},
            )
        else:
            # non-streaming always takes the fully-compiled loop — per-row
            # budgets ride _decode_loop's limits, so batched mixes stay on
            # device too
            result = rt.engine.generate_compiled(
                prompts,
                max_new_tokens=int(p.get("max_new_tokens", 128)),
                sampling=sampling,
                eos_ids=p.get("eos_ids", ()),
                seed=int(p.get("seed", 0)),
                budgets=budgets,
                reuse_prefix=reuse_prefix,
            )
        if stream_id:
            # release any cancel rows parked for this stream server-side
            self.bridge.notify("clear_cancels", {"stream": stream_id})
        self._respond(
            peer, proto.GENERATE_RESP, p["rid"],
            {
                "sequences": [list(map(int, s)) for s in result.sequences],
                "finished": list(map(bool, result.finished)),
            },
        )

    # -- continuous batching (engine/continuous.py) ----------------------
    def _trace_way_in(self, rt: "StageRuntime", p: dict) -> None:
        """A traced GENERATE as the ML loop takes it off the work queue:
        ``hop_in`` (the validator's stamp as ml/module.py handed the
        frame to its bridge, to ``NetBridge.post_work``'s stamp) and
        ``work_wait`` (from there to now: FIFO behind whatever the loop
        was running: for a request that arrives during a chunk's host
        phases what is left of them, until the chunk's wait takes it in
        (``_intake``), and what is left of ``step_chunk`` where it came
        too late for that). ``chunk`` is the step of the chunk waited
        behind (the id of its record and of its ``tlink:chunk``): the one
        in flight for a frame its intake takes (``recorder.next_step``),
        else the last the engine finished before this moment
        (``recorder.next_step - 1``).
        Durations only where the stamps share this host's clock; leaves
        the handler's start and its cause in ``p["_way_in"]`` for the
        ``submit`` span."""
        from tensorlink_tpu.core.trace import HOP_IN, WORK_WAIT, get_tracer

        t_in = time.monotonic()
        tracer, tid = get_tracer(), str(p["trace"])
        site = str(self.node.node_id or "")
        queued = p.get("stamp_q")
        sid = tracer.record_since(
            tid, HOP_IN, p["stamp"], end=queued or t_in, site=site,
        )
        if queued:
            sid = tracer.record_since(
                tid, WORK_WAIT, queued, end=t_in, site=site, parent=sid,
                **({"chunk": rt.cont.recorder.next_step
                    - (0 if rt.cont.taking_in else 1)}
                   if rt.cont is not None else {}),
            )
        p["_way_in"] = (t_in, sid)

    @staticmethod
    def _slot_knobs(p: dict) -> tuple:
        return (
            p.get("temperature", 0.0), p.get("top_k", 0),
            p.get("top_p", 1.0), p.get("presence_penalty", 0.0),
            p.get("frequency_penalty", 0.0),
        )

    @classmethod
    def _rides_slots(cls, p: dict) -> bool:
        """Whether a GENERATE frame flagged ``continuous`` can take the
        slot engine at all: one prompt, scalar knobs, no beams, no
        lookahead. Read off the frame alone, so that a chunk's intake can
        tell before it handles one."""
        return not (
            len(p["prompts"]) != 1
            or any(isinstance(v, (list, tuple)) for v in cls._slot_knobs(p))
            or int(p.get("num_beams", 1)) > 1
            or p.get("lookahead")
        )

    def _generate_continuous(self, rt: "StageRuntime", p: dict,
                             prompts: list[list[int]]) -> bool:
        """Admit a GENERATE flagged ``continuous`` into the job's slot
        engine. Returns False when the request can't take the continuous
        path (per-row knob lists, beams, lookahead, or a model the paged
        engine refuses) — the caller then falls through to the static
        engine paths, so the flag can never fail a request."""
        from tensorlink_tpu.engine.sampling import SamplingParams

        if not self._rides_slots(p):
            return False
        knobs = self._slot_knobs(p)
        if self.draining is not None:
            # admission fence: this worker is shedding its slots — redirect
            # the request to the drain destination (the client re-issues
            # there; an empty tokens_so_far means a plain resubmission)
            self._respond_migrated(
                rt.cont,
                {"peer": p["peer"], "rid": p["rid"],
                 "stream": p.get("stream"),
                 "trace": str(p.get("trace") or "")},
                self.draining, None, [],
            )
            return True
        built = rt.cont is None or rt.cont.engine is not rt.engine
        cont = self._ensure_cont(rt)
        if cont is None:
            return False
        tid = str(p.get("trace") or "")
        jrid = str(p.get("jrid") or "")
        want = str(p.get("reattach") or "")
        if want and self._reattach_continuous(rt, cont, p, want):
            return True
        # a re-attach MISS falls through here on purpose: the request body
        # already carries prompt+delivered and start_step, so plain
        # admission below IS the re-prefill resume rung (bit-identical by
        # the fold_in sampling contract) — no extra round trip
        t, k, tp, pp, fp = knobs
        sampling = SamplingParams.make(
            temperature=float(t), top_k=int(k), top_p=float(tp),
            presence_penalty=float(pp or 0.0),
            frequency_penalty=float(fp or 0.0),
        )
        stream_id = p.get("stream")
        peer = p["peer"]
        stream_cb, on_finish = self._cont_channels(
            rt, cont, peer=peer, rid=p["rid"], stream_id=stream_id,
            tid=tid, jrid=jrid,
        )
        # the ``submit`` span's id ahead of it: the engine's first_token
        # names it as its cause (core/trace.py)
        sid_submit = cont.tracer.new_sid() if tid else ""
        req = cont.submit(
            prompts[0],
            max_new_tokens=int(p.get("max_new_tokens", 128)),
            sampling=sampling,
            eos_ids=p.get("eos_ids", ()),
            seed=int(p.get("seed", 0)),
            start_step=int(p.get("start_step", 0)),
            priority=p.get("priority"),
            stream_cb=stream_cb if stream_id else None,
            on_finish=on_finish,
            # resume-after-migration: bind the staged KV pages instead of
            # re-prefilling (engine falls back when the ticket is stale)
            adopt=p.get("adopt") or None,
            trace_id=tid,
            trace_parent=sid_submit,
            # draft/verify opt-in (no-op unless this engine's spec_decode
            # is on; streams bit-identical either way)
            speculative=bool(p.get("speculative", False)),
            # disaggregated prefill/decode: on a prefill-pool worker with
            # a live decode pool, this admission freezes at its
            # prefill→decode boundary and _run_handoffs ships it —
            # unless the request opted out ({"handoff": false}) or is
            # itself a migration resume (adopt) bouncing through
            handoff=bool(
                self._handoff_pool_for(rt.job_id)
                and p.get("handoff", True) is not False
                and not p.get("adopt")
            ),
        )
        # transport context for live migration: a drain must redirect this
        # stream mid-flight, which needs the original peer/rid/stream —
        # the on_finish/stream closures are opaque, this is not
        req.client_meta = {
            "peer": peer, "rid": p["rid"], "stream": stream_id,
            "trace": tid, "jrid": jrid,
        }
        if tid and "_way_in" in p:
            # the handler's start to here: sampling knobs, the channels,
            # the engine's own submit, and the slot engine where this
            # request built it
            from tensorlink_tpu.core.trace import SUBMIT

            t_in, cause = p["_way_in"]
            cont.tracer.record(
                tid, SUBMIT, site=str(self.node.node_id or ""), t0=t_in,
                dur_s=req.submit_t - t_in, parent=cause, sid=sid_submit,
                **({"slot_engine_built": True} if built else {}),
            )
        if jrid:
            rt.jstreams[jrid] = req
        self._schedule_cont(rt)
        return True

    def _cont_channels(self, rt: "StageRuntime", cont, *, peer, rid,
                       stream_id, tid, jrid="", resume_base=None):
        """Build the (stream_cb, on_finish) transport-closure pair for a
        continuous stream. Shared by first admission and by the re-attach
        rebinding so both transports behave identically — the only
        difference is ``resume_base``: set on a re-attach, the final
        response carries {"reattached": True, "resume_base": base} so the
        client merges sequences (this-submission tokens) onto its
        delivered[:base] prefix exactly-once."""
        state = {"n": 0}

        def stream_cb(tok: int):
            # fire-and-forget per token; cancel frames (confirmed stop
            # matches) poll once per chunk — overrun bounded like the
            # compiled chunked stream
            msg = {"peer": peer, "stream": stream_id,
                   "tokens": [[0, int(tok)]]}
            if tid and not state["n"]:
                # a traced stream's FIRST frame alone carries the moment
                # the engine handed this token on (it left it on this
                # thread: the API's ``token_out`` starts there)
                from tensorlink_tpu.core.trace import first_token_stamp

                first = first_token_stamp.get()
                if first is not None:
                    msg["stamp"] = first
            self.bridge.notify("send_token", msg)
            state["n"] += 1
            if state["n"] % cont.chunk_steps == 0:
                try:
                    rows = self.bridge.request(
                        "poll_cancel", {"stream": stream_id}, timeout=5.0
                    )
                except Exception:
                    rows = None  # relay hiccup must not kill the decode
                return bool(rows)
            return False

        def on_finish(req):
            if stream_id:
                try:
                    self.bridge.request(
                        "send_token",
                        {"peer": peer, "stream": stream_id, "tokens": [],
                         "done": True},
                    )
                    self.bridge.notify("clear_cancels", {"stream": stream_id})
                except Exception as e:
                    self.log.debug(
                        "stream %s done-marker push failed: %s",
                        stream_id, e,
                    )
            if jrid:
                rt.jstreams.pop(jrid, None)
                if req.error is None:
                    # the GENERATE_RESP below may be going to a dead
                    # validator — keep the result in the bounded orphan
                    # ledger so a re-attach can still drain it
                    self._stash_orphan(rt, jrid, req)
            if req.error is not None:
                try:
                    self._respond(
                        peer, proto.GENERATE_RESP, rid,
                        {"error": f"{type(req.error).__name__}: {req.error}",
                         "worker": self.node.node_id},
                    )
                except Exception as e:
                    # the requester is gone — an undeliverable error reply
                    # must not propagate into step_chunk and error the
                    # ENGINE (closing it evicts every other live stream
                    # that is decoding through the validator outage)
                    self.log.warning(
                        "error response for %s undeliverable: %s", rid, e)
                return
            body = {
                "sequences": [list(map(int, req.tokens))],
                "finished": [bool(req.finished)],
                "continuous": True,
                # engine occupancy + prefix-cache counters ride every
                # response so the validator's /stats can surface them
                # without a dedicated polling RPC; beside them this
                # process's side of the wire (prompts' ids read back as
                # one array: ``tlts_lists_unpacked``)
                "serving": {**cont.serving_snapshot(), **ser.counters()},
            }
            if resume_base is not None:
                body["reattached"] = True
                body["resume_base"] = int(resume_base)
            if tid:
                # this worker's spans for the request ride home the same
                # way — the validator ingests them so /trace stitches a
                # request's hops without any polling RPC
                body["trace"] = {
                    "id": tid, "spans": cont.tracer.collect(tid),
                }
            try:
                self._respond(peer, proto.GENERATE_RESP, rid, body)
            except Exception as e:
                # dead validator (the crash-safety orphan path): the
                # result is already stashed in the orphan ledger above —
                # letting this propagate would error the ENGINE via
                # step_chunk and evict every OTHER stream still decoding
                # through the outage
                self.log.warning(
                    "final response for %s undeliverable (orphan %s kept): %s",
                    rid, jrid or "-", e)

        return stream_cb, on_finish

    def _stash_orphan(self, rt: "StageRuntime", jrid: str, req) -> None:
        """Record a finished continuous stream in the bounded orphan
        ledger (MLConfig.orphan_keep / orphan_ttl_s). If the final
        response reached a live client the entry just ages out; if the
        validator was dead it is what the re-attach ladder drains
        (popped on delivery — exactly-once)."""
        ml = self.node.config.ml
        keep = int(getattr(ml, "orphan_keep", 64))
        if keep <= 0:
            return
        now = time.monotonic()
        ttl = float(getattr(ml, "orphan_ttl_s", 180.0))
        for k in [k for k, v in rt.orphans.items() if now - v["t"] > ttl]:
            rt.orphans.pop(k, None)
        while len(rt.orphans) >= keep:  # dict preserves insertion order
            rt.orphans.pop(next(iter(rt.orphans)), None)
        rt.orphans[jrid] = {
            "tokens": [int(t) for t in req.tokens],
            "base": int(req.start_step),
            "t": now,
        }

    def _orphan_report(self, rt: "StageRuntime") -> list[dict]:
        """Per-jrid live/finished stream announcement riding the
        attach_only MODULE_LOADED ack — the worker's half of journal
        reconciliation (its token counts are authoritative; the journal's
        high-water marks are only a floor)."""
        out = []
        if rt.cont is not None:
            # the counts below are of tokens that left for their relays
            rt.cont.flush_stream()
        for jrid, req in rt.jstreams.items():
            out.append({
                "jrid": jrid,
                "n": int(req.start_step) + len(req.tokens),
                "finished": bool(req.finished),
            })
        for jrid, o in rt.orphans.items():
            out.append({
                "jrid": jrid,
                "n": int(o["base"]) + len(o["tokens"]),
                "finished": True,
            })
        return out

    def _reattach_continuous(self, rt: "StageRuntime", cont, p: dict,
                             jrid: str) -> bool:
        """Worker half of the re-attach ladder. Returns True when handled:
        a LIVE orphaned stream is rebound to the new peer/rid/stream (its
        backlog past the client's high-water mark topped up atomically —
        this runs on the same serial ML thread as decode chunks), or a
        FINISHED orphan is replayed from the ledger. False = miss; the
        caller falls through to plain admission (re-prefill resume)."""
        peer, rid = p["peer"], p["rid"]
        stream_id = p.get("stream")
        tid = str(p.get("trace") or "")
        hwm = int(p.get("hwm", 0))
        req = rt.jstreams.get(jrid)
        if req is not None:
            # what the slot settled last chunk leaves through the OLD
            # callbacks first (it may finish the request, or stop it):
            # the backlog below is cut from req.tokens, and a token still
            # pending would reach the new relay twice
            cont.flush_stream(req)
        if req is not None and not req.finished:
            base = int(req.start_step)
            stream_cb, on_finish = self._cont_channels(
                rt, cont, peer=peer, rid=rid, stream_id=stream_id,
                tid=tid, jrid=jrid, resume_base=base,
            )
            req.client_meta = {
                "peer": peer, "rid": rid, "stream": stream_id,
                "trace": tid, "jrid": jrid,
            }
            req.stream_cb = stream_cb if stream_id else None
            req.on_finish = on_finish
            if stream_id:
                # top up the fresh relay with everything the slot emitted
                # past the client's high-water mark while orphaned
                backlog = req.tokens[max(hwm - base, 0):]
                if backlog:
                    self.bridge.notify(
                        "send_token",
                        {"peer": peer, "stream": stream_id,
                         "tokens": [[0, int(t)] for t in backlog]},
                    )
            self.log.info(
                "reattached live stream jrid=%s (slot tokens=%d, "
                "client hwm=%d)", jrid, len(req.tokens), hwm,
            )
            self._schedule_cont(rt)
            return True
        orphan = rt.orphans.pop(jrid, None)
        if orphan is not None:
            toks = [int(t) for t in orphan["tokens"]]
            base = int(orphan["base"])
            if stream_id:
                try:
                    backlog = toks[max(hwm - base, 0):]
                    if backlog:
                        self.bridge.notify(
                            "send_token",
                            {"peer": peer, "stream": stream_id,
                             "tokens": [[0, int(t)] for t in backlog]},
                        )
                    self.bridge.request(
                        "send_token",
                        {"peer": peer, "stream": stream_id, "tokens": [],
                         "done": True},
                    )
                except Exception as e:
                    self.log.debug(
                        "orphan replay stream push failed: %s", e
                    )
            self._respond(
                peer, proto.GENERATE_RESP, rid,
                {"sequences": [toks], "finished": [True],
                 "continuous": True, "reattached": True,
                 "resume_base": base, "serving": cont.serving_snapshot()},
            )
            self.log.info(
                "replayed finished orphan jrid=%s (%d tokens)",
                jrid, len(toks),
            )
            return True
        return False

    def _ensure_cont(self, rt: "StageRuntime"):
        """The job's slot engine, (re)built after load_stage swapped the
        generation engine (old slots died with their engine's cache).
        None when the model can't serve continuous — callers fall back to
        the static paths."""
        cont = rt.cont
        if cont is not None and cont.engine is rt.engine:
            return cont
        from tensorlink_tpu.engine.continuous import (
            ContinuousEngine,
            PagedUnsupported,
        )

        ml = self.node.config.ml
        pool = None
        quota = 0
        if int(getattr(ml, "cont_pool_pages", 0)) > 0:
            pool = self._shared_kv_pool(rt, ml)
            quota = int(
                (rt.model_spec or {}).get("page_quota")
                or getattr(ml, "cont_pool_quota", 0)
            )
        role = str(getattr(ml, "worker_role", "mixed") or "mixed")
        try:
            rt.cont = cont = ContinuousEngine(
                rt.engine,
                # disaggregated prefill/decode: a prefill-role worker's
                # engine freezes opted-in slots at the prefill→decode
                # boundary for _run_handoffs to ship (docs/SERVING.md)
                handoff_after_prefill=(role == "prefill"),
                worker_role=role,
                # co-hosting (docs/SERVING.md): every job whose page
                # geometry matches shares ONE physical pool under a
                # per-model quota; job_id keys the tenant (unique even
                # when one model hosts twice)
                pool=pool, model_id=rt.job_id, page_quota=quota,
                # spans this engine records carry the worker's identity —
                # the cross-worker stitch /trace serves depends on it
                trace_site=str(self.node.node_id or ""),
                max_slots=int(ml.cont_max_slots),
                page_size=int(ml.cont_page_size),
                chunk_steps=int(ml.cont_chunk_steps),
                prefill_chunk=int(ml.prefill_chunk),
                prefix_cache=bool(ml.prefix_cache),
                # tiered prefix cache (engine/kvtier.py): arm the
                # host-RAM spill tier on the worker's slot engine too —
                # single-stage jobs decode here, and an unarmed worker
                # would silently destroy evicted pages while the
                # validator-side batcher advertises host_tier=True
                host_tier_pages=int(
                    getattr(ml, "cont_host_tier_pages", 0)
                ),
                # `or` before str(): a null kv_quant in an operator
                # config must read as "none", not the string "None"
                kv_quant=str(ml.kv_quant or "none"),
                spec_decode=bool(getattr(ml, "spec_decode", False)),
                spec_draft=int(getattr(ml, "spec_draft", 8)),
                spec_budget=int(getattr(ml, "spec_budget", 0)),
                default_priority=str(ml.default_priority),
                sched_queue_cap=int(ml.sched_queue_cap),
                sched_aging_ticks=int(ml.sched_aging_ticks),
                sched_preemption=bool(ml.sched_preemption),
                sched_policy=str(ml.sched_policy),
                sched_max_wait_s=float(ml.sched_max_wait_s),
                # explicit TP (docs/SHARDING.md): shard the hot path over
                # a tp mesh axis; engines that can't (MoE, indivisible
                # heads, too few devices) refuse with PagedUnsupported and
                # land in the static fallback below like any other refusal
                tensor_parallel=int(
                    getattr(ml, "tensor_parallel", 1) or 1
                ),
                # while the device runs a chunk, the job's next requests
                # are taken off the work queue and prepared for the one
                # after it (``_intake``)
                intake=functools.partial(self._intake, rt),
            )
        except PagedUnsupported as e:
            # a DECLARED refusal (sliding window, a model TP can't shard):
            # static batcher territory. Nothing else is caught — a bad
            # knob, a lowering error or an OOM fails the request loudly
            # instead of quietly serving from the other engine.
            self.log.info("continuous batching unavailable: %s", e)
            return None
        # one copy a chip: a sharded engine re-placed the weights it was
        # given (no copy when _load_stage made them in its layout); the
        # stage must not keep another layout of them alive beside it
        rt.params = rt.engine.params
        # one step program a rung of the packed block's ladder: the full
        # one is built here (a program that does not compile or fit fails
        # this request loudly, like any other lowering error), the narrow
        # and the flat one behind the first requests, which the full one
        # serves meanwhile
        cont.build_steps()
        return cont

    def _shared_kv_pool(self, rt: "StageRuntime", ml):
        """Get-or-create the shared multi-tenant page pool this job's
        engine should draw from (MLConfig.cont_pool_pages > 0). Pools are
        keyed by page GEOMETRY — (layers, kv heads, head_dim, page size,
        kv_quant, dtype) — so models that cannot physically share pages
        transparently get separate pools instead of a loud attach error
        at hosting time."""
        import jax.numpy as jnp

        from tensorlink_tpu.engine.paged import SharedPagePool

        cfg = rt.cfg
        kvq = str(ml.kv_quant or "none")
        if rt.cache_quant and kvq == "none":
            kvq = "int8"  # mirror of the engine's cache_quant forcing
        page_size = int(ml.cont_page_size)
        dtype_str = (
            "int8" if kvq in ("int8", "int4")
            else str(jnp.dtype(rt.engine.cache_dtype))
        )
        key = (
            cfg.n_layers, cfg.n_kv_heads, cfg.head_dim, page_size, kvq,
            dtype_str,
        )
        self._gc_kv_pools(keep=key)
        pool = self._kv_pools.get(key)
        if pool is None:
            pool = SharedPagePool(
                cfg, int(ml.cont_pool_pages), page_size=page_size,
                dtype=rt.engine.cache_dtype, kv_quant=kvq,
            )
            self._kv_pools[key] = pool
            self.log.info(
                "created shared KV page pool %s (%d pages, kv_quant=%s)",
                key, int(ml.cont_pool_pages), kvq,
            )
        return pool

    def _gc_kv_pools(self, keep=None) -> None:
        """Drop shared pools whose LAST tenant detached (their page
        arrays would otherwise pin HBM for the life of the process —
        a worker cycling through hosted geometries would accumulate one
        dead full-size pool per geometry key). ``keep`` spares the key
        about to be (re)used so an empty-but-wanted pool is reused, not
        rebuilt. Called from the serial run loop only."""
        for k in [
            k for k, p in self._kv_pools.items()
            if not p.tenants and k != keep
        ]:
            del self._kv_pools[k]
            self.log.info("released empty shared KV page pool %s", k)

    def _schedule_cont(self, rt: "StageRuntime") -> None:
        if not rt.cont_scheduled:
            rt.cont_scheduled = True
            self.bridge.q.work.put(("cont_continue", {"job_id": rt.job_id}))

    def _cont_step(self, job_id: str) -> None:
        """Drive the slot engine one decode chunk, then requeue — FIFO, so
        every GENERATE that arrived meanwhile is admitted before the next
        chunk (a new request starts decoding within ≤ one chunk of an
        in-flight batch; same bounded-occupancy shape as _beam_step)."""
        with self._lock:
            rt = self.jobs.get(job_id)
        if rt is None or rt.cont is None:
            return
        rt.cont_scheduled = False
        if self.faults is not None:
            # fault site "worker.cont_step" (core/faults.py): one count per
            # decode chunk over a continuously-batched slot set
            self.faults.inject("worker.cont_step", job_id)
        # set again while the chunk runs, and only then: a request its
        # intake takes in queues no ``cont_continue`` of its own, the one
        # below goes to the queue's tail when the chunk is over; whatever
        # ends the chunk (an injected error before it included) leaves
        # the flag clear, so the next request resumes the engine
        rt.cont_scheduled = True
        try:
            more = rt.cont.step_chunk()
        except FaultCrash:
            raise  # the run loop takes the node down
        except BaseException as e:  # noqa: BLE001 — fan out per request
            self.log.exception("continuous decode chunk failed")
            rt.cont.close(e)  # responds the error on every live rid
            rt.cont = None
            self._gc_kv_pools()  # release a now-tenantless shared pool
            return
        finally:
            rt.cont_scheduled = False
        # steady-state prefill→decode handoff: ship every slot the chunk
        # froze at its prefill boundary BEFORE deciding whether to
        # requeue — a frozen slot is invisible to step_chunk's has_work,
        # so resolving the manifest here is what keeps the engine free of
        # parked in-transit slots between work items. Re-check has_work
        # after: an aborted handoff resumes the slot's prefill HERE, and
        # that revived work must requeue even when the chunk saw none.
        self._run_handoffs(rt)
        if more or (rt.cont is not None and rt.cont.has_work()):
            self._schedule_cont(rt)

    def _intake(self, rt: "StageRuntime", result):
        """The slot engine's intake (``ContinuousEngine.intake``), run
        from a chunk's wait while the device computes ``result``: yields,
        one by one, the GENERATE frames of this job that arrive on the
        work queue and take the slot engine, each as the call that
        handles it (``_generate``, answered for like any work item); the
        engine submits it there and prepares its admission for the next
        chunk. It blocks in the work queue's own get: the bridge puts
        ``CHUNK_DONE`` there when ``result`` is ready, which ends it. So
        does the first item of any other kind (a DRAIN, a MIGRATE, a
        ``load_stage``, another job's frame or chunk, a GENERATE of a
        static path or one that re-attaches): it is held, and the run
        loop handles it first when the chunk has returned, so the queue's
        order is kept item for item. The chunk's ``cont_continue`` goes
        to the queue's tail only then: a frame that came too late for the
        intake is still taken before the next chunk, and no request joins
        a later chunk than with no intake at all. An engine stepped by
        hand, and not by the run loop's ``cont_continue`` (which
        ``cont_scheduled`` stands for while its chunk runs), has no
        intake: nobody would handle what it held."""
        if not rt.cont_scheduled:
            return
        token = self.bridge.watch(result)
        while True:
            item = self.bridge.get_work(timeout=1.0)
            if item is None:
                continue
            kind, payload = item
            if kind == CHUNK_DONE:
                if payload == token:
                    return
                continue  # an earlier chunk's, whose intake had ended
            if self._taken_in(rt, kind, payload):
                yield functools.partial(self._handle_guarded, kind, payload)
                continue
            self._held = item
            return

    def _taken_in(self, rt: "StageRuntime", kind: str, payload) -> bool:
        """Whether a chunk's intake handles a work item in place. A frame
        the test itself cannot read (a peer's body is relayed unchecked:
        no ``prompts``, a ``num_beams`` that is no number) is another
        kind: held, it fails under the run loop's own error reply and
        takes no stream but its own with it, where a raise from inside
        ``step_chunk`` would close the engine."""
        try:
            return bool(
                kind == proto.GENERATE
                and payload.get("job_id") == rt.job_id
                and payload.get("continuous")
                # a re-attach answers for a live stream at once, which
                # takes a chunk's edge (``flush_stream`` with no step in
                # flight)
                and not payload.get("reattach")
                and self._rides_slots(payload)
            )
        except Exception:
            return False

    # -- live slot migration + drain (docs/FAILURE_MODEL.md) -------------
    # DRAIN (validator → this worker): fence admissions, then move every
    # live continuous stream to the destination worker — KV-page shipping
    # for steady decode slots (bit-identical resume), the crash-recovery
    # re-prefill rung for everything else (mid-prefill slots, queued
    # requests, and any failed export/wire/import). The client learns via
    # a {"migrated": ...} GENERATE_RESP and re-issues at the destination;
    # a stream is never dropped, only redirected.

    # -- disaggregated prefill/decode: steady-state handoff --------------
    # (docs/SERVING.md "Disaggregated prefill/decode") A prefill-role
    # worker is permanently "draining" its completed prefills: every
    # opted-in admission freezes at the prefill→decode boundary and is
    # shipped here to a decode-pool worker through the SAME
    # export/stage/adopt path a drain uses — but with no admission
    # fence, no capacity zeroing, and a per-slot fallback ladder
    # (page-ship → re-prefill redirect at the destination → resume
    # locally) instead of a worker-wide abort. The client follows the
    # redirect exactly like a drain redirect, except the plan keeps
    # pointing HERE — this worker stays the admission point.

    def _set_replica_set(self, p: dict) -> None:
        """A REPLICA_SET push from the validator (mirrors the HANDOFF
        pool push): the other replicas of the fleet this worker's job
        belongs to, as ``[{id, addr, job_id}, ...]``. Pure wire state —
        consulted only when a DRAIN arrives with no destination."""
        peers = [
            dict(e) for e in (p.get("peers") or [])
            if e.get("id") and e.get("id") != self.node.node_id
            and e.get("addr")
        ]
        job_id = str(p.get("job_id") or "")
        self._replica_sets[job_id] = peers
        self.log.info(
            "replica set (%s): %d sibling(s) %s",
            job_id[:8] or "worker-wide", len(peers),
            [str(e["id"])[:8] for e in peers],
        )

    def _set_handoff_pool(self, p: dict) -> None:
        """A HANDOFF push from the validator: the decode-pool membership
        this (prefill-role) worker ships completed prefills to — scoped
        to the named job ("" = worker-wide operator push)."""
        pool = [
            dict(e) for e in (p.get("pool") or [])
            if e.get("id") and e.get("id") != self.node.node_id
            and e.get("addr")
        ]
        job_id = str(p.get("job_id") or "")
        self._handoff_pools[job_id] = pool
        # membership changed: stale readiness could point at a departed
        # worker, and a fresh pool deserves fresh probes — but only for
        # the job whose pool this push names (a new job's recruit must
        # not cost every OTHER job an inline re-probe on the run loop);
        # the worker-wide "" push refreshes everything
        with self._handoff_prep_lock:
            # the lock covers every mutation of _handoff_dest_ready: the
            # warm thread adds concurrently, and an unguarded add during
            # this comprehension's iteration would raise "set changed
            # size during iteration" in the control-frame handler
            if job_id:
                self._handoff_dest_ready = {
                    k for k in self._handoff_dest_ready if k[0] != job_id
                }
            else:
                self._handoff_dest_ready.clear()
        self.log.info(
            "handoff pool set (%s): %d decode worker(s) %s",
            job_id[:8] or "worker-wide", len(pool),
            [str(e["id"])[:8] for e in pool],
        )
        if pool:
            # pre-warm OFF the run loop: a cold destination's stage ship
            # can take minutes (MODULE timeout 120s), and paying it
            # inside _run_handoffs would stall every co-resident
            # stream's decode between chunks. The push arrives at
            # recruit time — usually before any traffic — so the warm
            # thread normally has the readiness cache populated before
            # the first prefill completes; a handoff that races it just
            # pays the old synchronous prepare once.
            threading.Thread(
                target=self._warm_handoff_dests, args=(job_id,),
                name="handoff-warm", daemon=True,
            ).start()

    def _warm_handoff_dests(self, job_id: str) -> None:
        """Background half of the pool push: probe/ship the job's stage
        to every decode-pool member so the run loop's _pick_handoff_dest
        finds them ready instead of preparing them inline. Job-scoped
        pushes wait briefly for the runtime (HANDOFF and MODULE race at
        recruit time); failures are dropped — the synchronous path
        re-probes on demand and the slot falls back locally at worst."""
        deadline = time.monotonic() + 30.0
        while True:
            with self._lock:
                if job_id:
                    rts = [self.jobs[job_id]] if job_id in self.jobs else []
                else:
                    rts = list(self.jobs.values())
            if rts or time.monotonic() >= deadline:
                break
            time.sleep(0.25)
        for rt in rts:
            pool = self._handoff_pool_for(rt.job_id)
            for dest in pool:
                key = (rt.job_id, str(dest.get("id", "")))
                with self._handoff_prep_lock:
                    if key in self._handoff_dest_ready \
                            or key in self._handoff_preparing:
                        continue
                    self._handoff_preparing.add(key)
                try:
                    ok = self._prepare_dest(rt, dest)
                    # the job may have been shut down during the ship
                    # (MODULE can take minutes): marking it ready now
                    # would re-pin the dead job id shutdown_job just
                    # purged
                    with self._lock:
                        alive = rt.job_id in self.jobs
                    if ok and alive:
                        with self._handoff_prep_lock:
                            self._handoff_dest_ready.add(key)
                # tlint: disable=TL005(best-effort warm-up — the handoff path re-probes on demand)
                except Exception:
                    pass
                finally:
                    with self._handoff_prep_lock:
                        self._handoff_preparing.discard(key)

    def _handoff_pool_for(self, job_id: str) -> list[dict]:
        """The decode pool a job's completed prefills ship to: the
        job-scoped push wins; the worker-wide operator push stands in
        for jobs recruited without one."""
        return (
            self._handoff_pools.get(job_id)
            or self._handoff_pools.get("")
            or []
        )

    def _pick_handoff_dest(self, rt: "StageRuntime") -> dict | None:
        """Round-robin over the job's decode pool, skipping members that
        can't host this job right now (unreachable / refusing /
        stage-load failure). Readiness is cached per (job, dest) so the
        steady-state path pays one probe per handoff, not a MODULE round
        trip."""
        pool = self._handoff_pool_for(rt.job_id)
        n = len(pool)
        for j in range(n):
            dest = pool[(self._handoff_rr + j) % n]
            key = (rt.job_id, str(dest["id"]))
            if key in self._handoff_dest_ready:
                self._handoff_rr = (self._handoff_rr + j + 1) % n
                return dest
            with self._handoff_prep_lock:
                if key in self._handoff_preparing:
                    # the warm-up thread is mid-ship to this member:
                    # waiting would stall the run loop and a second
                    # MODULE ship would replace the destination runtime
                    # — try the next member (or resume locally)
                    continue
                self._handoff_preparing.add(key)
            try:
                ok = self._prepare_dest(rt, dest)
            finally:
                with self._handoff_prep_lock:
                    self._handoff_preparing.discard(key)
            if ok:
                with self._handoff_prep_lock:
                    self._handoff_dest_ready.add(key)
                self._handoff_rr = (self._handoff_rr + j + 1) % n
                return dest
        return None

    def _run_handoffs(self, rt: "StageRuntime") -> None:
        """Ship every slot the last chunk froze at its prefill→decode
        boundary. Runs on the worker's serial run loop right after the
        chunk, so every freeze-to-ship window is one work item — no
        frozen slot ever parks across items."""
        cont = rt.cont
        if cont is None:
            return
        manifest = cont.handoff_manifest()
        if not manifest:
            return
        for slot, req in manifest:
            meta = req.client_meta
            if meta is None or self.draining is not None \
                    or not self._handoff_pool_for(rt.job_id):
                # no transport context to redirect (in-process driver),
                # or this worker is itself mid-drain (the drain ladder
                # owns its slots): finish the prefill locally
                cont.abort_handoff(slot)
                continue
            dest = self._pick_handoff_dest(rt)
            if dest is None:
                # no decode worker usable: degrade to mixed serving for
                # this slot — one grant finishes the prompt and the
                # stream decodes here, never dropped, never slower
                self.log.warning(
                    "handoff: no usable decode-pool destination; "
                    "slot %d resumes locally", slot,
                )
                cont.abort_handoff(slot)
                continue
            committed = False
            try:
                if self.faults is not None:
                    # fault site "worker.handoff": error sends the slot
                    # down the re-prefill redirect rung; crash is the
                    # prefill-worker-dies-mid-handoff chaos case
                    self.faults.inject(
                        "worker.handoff", str(meta.get("rid", ""))
                    )
                mig_id = self._ship_migration(rt, cont, slot, dest)
                moved = cont.commit_handoff(slot)
                committed = True
                self._respond_migrated(
                    cont, meta, dest, mig_id, moved.tokens, handoff=True
                )
            except FaultCrash:
                raise  # the run loop takes the node down
            except Exception as e:
                # per-slot containment: ONE failed handoff must neither
                # re-commit a torn-down slot nor abandon the rest of the
                # manifest (the popped entries would freeze forever)
                if committed:
                    # the slot already committed — its pages are staged
                    # at the destination and the redirect send was
                    # already retried (_respond_migrated); landing here
                    # means the client's relay is genuinely gone (peer
                    # hung up), so there is no one left to redirect. The
                    # staged ticket expires via the migration TTL;
                    # nothing to roll back, but say so loudly.
                    self.log.warning(
                        "handoff redirect for slot %d failed post-commit "
                        "(%s); staged ticket left to TTL expiry", slot, e,
                    )
                    continue
                # drop the readiness cache so the NEXT handoff re-probes
                # this member, and kick the warm thread so that re-probe
                # (and a possible stage re-ship to a restarted worker)
                # happens OFF the run loop instead of inline between a
                # future chunk and its handoffs
                with self._handoff_prep_lock:
                    self._handoff_dest_ready.discard(
                        (rt.job_id, str(dest["id"]))
                    )
                threading.Thread(
                    target=self._warm_handoff_dests, args=(rt.job_id,),
                    name="handoff-rewarm", daemon=True,
                ).start()
                try:
                    self._dial_dest(dest)
                except Exception:
                    # the destination is UNREACHABLE, not merely refusing
                    # the transfer: redirecting the client at it would
                    # just bounce off a dead worker — resume locally (the
                    # slot's prefilled state is intact; one grant
                    # finishes the prompt)
                    self.log.warning(
                        "handoff of slot %d failed and destination %s is "
                        "unreachable (%s); resuming locally",
                        slot, str(dest.get("id", ""))[:8], e,
                    )
                    cont.abort_handoff(slot)
                    continue
                self.log.warning(
                    "handoff of slot %d to %s failed (%s); redirecting "
                    "for re-prefill at the destination",
                    slot, str(dest.get("id", ""))[:8], e,
                )
                # the destination hosts the job and is reachable — only
                # the transfer failed. Send this stream down the
                # re-prefill rung: redirect FIRST, commit after — if the
                # redirect send itself fails, the slot is still frozen
                # and the local-resume rung below stays reachable (a
                # commit-first ordering would tear the slot down and
                # strand the stream against its RPC timeout). Its
                # prefill-region pages promote into the trie at commit,
                # so even a bounce-back re-admission here walks them for
                # free.
                try:
                    self._respond_migrated(
                        cont, meta, dest, None, req.tokens, handoff=True,
                    )
                    cont.commit_handoff(slot, fell_back=True)
                except Exception as e2:
                    # even the fallback redirect failed: keep the stream
                    # serving HERE rather than stranding it frozen
                    self.log.warning(
                        "handoff fallback redirect for slot %d failed "
                        "(%s); resuming locally", slot, e2,
                    )
                    if slot in cont.frozen_slots():
                        cont.abort_handoff(slot)

    def _drain(self, p: dict) -> None:
        dest = dict(p.get("dest") or {})
        if self.faults is not None:
            # fault site "worker.drain": a worker that dies the moment it
            # is asked to shed its slots (crash) or refuses (error)
            self.faults.inject("worker.drain", str(dest.get("id", "")))
        if not dest.get("id") or not dest.get("addr"):
            # fleet fallback (docs/SERVING.md "Fleet serving"): a DRAIN
            # with no destination drains onto a sibling replica's entry
            # worker from the REPLICA_SET push — but _drain ships EVERY
            # job to the one destination, so the fallback applies only
            # when the candidate is UNAMBIGUOUS: all pushed sets agree
            # on one sibling (a worker co-hosting two fleets must not
            # drain model A's streams onto model B's sibling)
            candidates = {
                e["id"]: dict(e)
                for peers in self._replica_sets.values()
                for e in peers
                if e.get("id") and e.get("id") != self.node.node_id
                and e.get("addr")
            }
            if len(candidates) == 1:
                dest = next(iter(candidates.values()))
        if not dest.get("id") or not dest.get("addr"):
            self._respond(
                p["peer"], proto.DRAIN_RESP, p["rid"],
                {"ok": False, "error": "drain needs a destination {id, addr}"},
            )
            return
        if dest["id"] == self.node.node_id:
            # a self-targeted drain would make this worker permanently
            # redirect every request back to itself
            self._respond(
                p["peer"], proto.DRAIN_RESP, p["rid"],
                {"ok": False, "error": "refusing to drain a worker onto itself"},
            )
            return
        self.draining = dest
        try:
            # recruiting fence: advertise zero capacity so planners stop
            # placing new stages here while the worker sheds its slots
            self.bridge.request(
                "set_capacity", {"hbm_bytes": 0.0}, timeout=10.0
            )
        except Exception as e:
            self.log.warning("drain: capacity fence failed: %s", e)
        summary = {"ok": True, "jobs": 0, "migrated": 0, "fell_back": 0,
                   "aborted": 0}
        with self._lock:
            jobs = list(self.jobs.items())
        for _job_id, rt in jobs:
            if rt.cont is None:
                continue
            summary["jobs"] += 1
            self._drain_engine(rt, dest, summary)
        if summary["aborted"]:
            # a job the destination can't host keeps serving HERE:
            # redirecting its streams into a jobless worker would drop
            # them. Lower the worker fence and restore the recruiting
            # capacity — the drain failed, loudly, with nothing lost.
            self.draining = None
            try:
                self.bridge.request(
                    "set_capacity", self.capacity(), timeout=30.0
                )
            except Exception as e:
                self.log.warning("drain abort: capacity restore failed: %s", e)
            summary["ok"] = False
            summary["error"] = (
                "destination could not host every job; drain aborted for "
                f"{summary['aborted']} job(s), streams kept serving locally"
            )
        self.log.info(
            "drained to %s: %d migrated, %d fell back, %d aborted",
            str(dest.get("id", ""))[:8], summary["migrated"],
            summary["fell_back"], summary["aborted"],
        )
        self._respond(p["peer"], proto.DRAIN_RESP, p["rid"], summary)

    def _drain_engine(self, rt: "StageRuntime", dest: dict,
                      summary: dict) -> None:
        """Shed one job's slot engine. Runs on the worker's serial run
        loop, so every freeze happens at a chunk boundary by
        construction."""
        cont = rt.cont
        cont.begin_drain()
        if not self._prepare_dest(rt, dest):
            # the destination can't host this job (unreachable, refuses,
            # stage load failed): redirecting streams there would strand
            # them against a jobless worker. Abort THIS job's drain —
            # nothing was shed yet, so lowering the fence resumes serving
            # exactly where it stood.
            cont.end_drain()
            summary["aborted"] += 1
            return
        manifest = cont.live_manifest()
        queued = cont.shed_queued()
        for kind, slot, req in manifest:
            meta = req.client_meta
            if meta is None:
                # no transport context (in-process driver): nothing to
                # redirect — the slot finishes locally under the fence
                continue
            if kind == "decode":
                try:
                    if self.faults is not None:
                        self.faults.inject(
                            "migrate.export", str(meta.get("rid", ""))
                        )
                    cont.freeze_slot(slot)
                    mig_id = self._ship_migration(rt, cont, slot, dest)
                    moved = cont.commit_migration(slot)
                    self._respond_migrated(
                        cont, meta, dest, mig_id, moved.tokens
                    )
                    summary["migrated"] += 1
                    continue
                except FaultCrash:
                    raise  # the run loop takes the node down
                except Exception as e:
                    self.log.warning(
                        "migration of slot %d failed (%s); falling back "
                        "to re-prefill on the destination", slot, e,
                    )
            # fallback ladder: mid-prefill slot, or a failed
            # export/wire/import — redirect for re-prefill resume (the
            # destination hosts the job; only the page transfer failed)
            if slot in cont.frozen_slots():
                moved = cont.commit_migration(slot, fell_back=True)
            else:
                moved = cont.shed_slot(slot)
            self._respond_migrated(
                cont, meta, dest, None, (moved or req).tokens
            )
            summary["fell_back"] += 1
        for req in queued:
            if req.client_meta is not None:
                self._respond_migrated(
                    cont, req.client_meta, dest, None, req.tokens
                )
            else:
                # an in-process submitter can't be redirected: fail fast
                # rather than strand it in a popped-from-queue limbo
                cont.fail_queued(
                    req, RuntimeError("worker draining; resubmit elsewhere")
                )

    def _dial_dest(self, dest: dict) -> str:
        """Peer id of a live connection to the destination worker (the
        network process dedupes dials by address)."""
        return self.bridge.request(
            "connect",
            {"host": dest["addr"][0], "port": int(dest["addr"][1])},
            timeout=15.0,
        )

    def _mig_request(self, peer: str, body: dict, timeout: float = 60.0):
        return self.bridge.request(
            "tensor_request",
            {"peer": peer, "tag": proto.MIGRATE, "body": body,
             "timeout": timeout},
            timeout=timeout + 10.0,
        )

    def _prepare_dest(self, rt: "StageRuntime", dest: dict) -> bool:
        """Make sure the destination can adopt this job's slots: probe it,
        and ship the stage (same model spec → same seeded params → an
        engine whose streams are bit-identical to ours) when it doesn't
        host the job yet. False = page-shipping unavailable; every slot
        takes the re-prefill rung instead."""
        try:
            peer = self._dial_dest(dest)
            pr = self._mig_request(
                peer,
                {"op": "probe", "job_id": rt.job_id,
                 "chain": np.zeros(0, np.int32), "limit": 0},
            )
            if not pr.get("ok"):
                return False
            if not pr.get("loaded"):
                resp = self.bridge.request(
                    "tensor_request",
                    {"peer": peer, "tag": proto.MODULE,
                     "body": {
                         "job_id": rt.job_id,
                         "model": rt.model_spec,
                         "stage": dict(rt.stage, worker_id=dest["id"]),
                         "training": False,
                     },
                     "timeout": 120.0},
                    timeout=130.0,
                )
                if not resp.get("ok"):
                    return False
            return True
        except Exception as e:
            self.log.warning(
                "drain destination %s unreachable/unready: %s",
                str(dest.get("id", ""))[:8], e,
            )
            return False

    def _ship_migration(self, rt: "StageRuntime", cont, slot: int,
                        dest: dict) -> str:
        """Probe + export + transfer one frozen slot's pages. Returns the
        staged ticket id the client's resume request will adopt. Raises
        on any failure — the caller falls back to re-prefill."""
        import secrets

        peer = self._dial_dest(dest)
        chain, limit = cont.migration_chain(slot)
        n_skip = 0
        try:
            pr = self._mig_request(
                peer,
                {"op": "probe", "job_id": rt.job_id,
                 "chain": np.asarray(chain, np.int32), "limit": int(limit)},
            )
            n_skip = int(pr.get("resident_pages", 0) or 0)
        except Exception as e:
            self.log.debug("migration probe failed (%s); shipping all", e)
        blob = cont.export_slot(slot, n_skip=n_skip)
        mig_id = secrets.token_hex(8)
        act = (
            self.faults.inject("migrate.wire", mig_id)
            if self.faults is not None else None
        )
        if act == "drop":
            raise RuntimeError("migrate.wire: transfer dropped")
        if isinstance(act, tuple):  # ("delay", seconds)
            time.sleep(act[1])
        reply = None
        # dup really sends the staging frame twice — idempotency by
        # mig_id is the destination's contract, chaos-tested
        for _ in range(2 if act == "dup" else 1):
            reply = self._mig_request(
                peer,
                {"op": "put", "job_id": rt.job_id, "mig": mig_id,
                 "blob": blob},
            )
        if not (reply or {}).get("ok"):
            raise RuntimeError(
                f"destination refused migration: "
                f"{(reply or {}).get('error', 'not ok')}"
            )
        return mig_id

    def _respond_migrated(self, cont, meta: dict, dest: dict,
                          mig_id: str | None, tokens, *,
                          handoff: bool = False) -> None:
        """Tell the waiting client its stream moved: where to re-issue,
        which staged ticket to adopt (None = plain re-prefill resume), and
        the authoritative emitted-so-far list (fire-and-forget stream
        frames may have dropped — the client tops up exactly-once from
        this). ``cont`` may be None (the admission-fence redirect fires
        before any slot engine exists). ``handoff`` marks a steady-state
        prefill→decode redirect: the client follows it for THIS request
        only and keeps its plan pointed at this worker — the admission
        point — instead of rewriting the plan like a drain redirect."""
        tid = str(meta.get("trace") or "")
        body = {
            "migrated": {
                "worker": dest["id"],
                "addr": list(dest["addr"]),
                "mig": mig_id,
                "tokens_so_far": [int(t) for t in tokens],
                "handoff": bool(handoff),
                # the redirect carries the request's trace id (and, below,
                # the source worker's spans): the client re-issues at the
                # destination under the SAME id, so both halves stitch
                "trace_id": tid or None,
            },
        }
        if cont is not None:
            body["serving"] = cont.serving_snapshot()
            if tid:
                body["trace"] = {"id": tid, "spans": cont.tracer.collect(tid)}
        # the redirect IS the stream at this point — on the handoff path
        # the slot is already torn down and its pages staged at the
        # destination, so a transiently failed send here would strand the
        # client against its RPC timeout (not the recovery ladder, which
        # only catches lost-worker shapes). Absorb transient relay
        # hiccups with short retries — handoffs only: a drain's manifest
        # may hold many slots with hung-up clients, and serializing
        # blocking backoffs across it would stall the run loop for the
        # healthy streams (the drain path keeps its fail-fast shape).
        # The client matches by rid, so a duplicate delivery is dropped
        # as stale.
        attempts = 3 if handoff else 1
        for attempt in range(attempts):
            try:
                self._respond(
                    meta["peer"], proto.GENERATE_RESP, meta["rid"], body
                )
                break
            except Exception as e:
                if attempt == attempts - 1:
                    raise
                self.log.warning(
                    "handoff redirect send failed (attempt %d/%d): %s",
                    attempt + 1, attempts, e,
                )
                time.sleep(0.25 * (attempt + 1))
        if meta.get("stream"):
            try:
                # close the relay so a streaming client's drain loop
                # unblocks immediately instead of riding out its timeout
                self.bridge.request(
                    "send_token",
                    {"peer": meta["peer"], "stream": meta["stream"],
                     "tokens": [], "done": True},
                )
            except Exception as e:
                self.log.debug("migrate stream close failed: %s", e)

    def _migrate_in(self, p: dict) -> None:
        """Destination side of a migration: ``probe`` answers whether the
        job is loaded and how many leading pages of the chain are
        prefix-cache-resident (the exporter skips shipping those);
        ``put`` stages the blob's pages into this engine (idempotent by
        mig id). The staged ticket is adopted by the client's resume
        request (``adopt`` on GENERATE)."""
        op = p.get("op")
        rt = self.jobs.get(p.get("job_id", ""))
        if op in ("probe", "put") and self.draining is not None:
            # worker-level fence: a draining worker must not adopt inbound
            # streams — its engines are fenced, so a staged ticket here
            # could never be adopted (the resume gets redirected away) and
            # its pages would pin until process exit
            self._respond(
                p["peer"], proto.MIGRATE_RESP, p["rid"],
                {"ok": False, "error": "destination is draining"},
            )
            return
        if op == "probe":
            loaded = rt is not None and rt.engine is not None
            body: dict = {"ok": True, "loaded": loaded}
            if loaded:
                cont = self._ensure_cont(rt)
                if cont is None or cont.drain_state != "serving":
                    body = {"ok": False,
                            "error": "destination cannot adopt (no slot "
                                     "engine, or draining itself)"}
                else:
                    chain = [
                        int(t)
                        for t in np.asarray(p.get("chain", [])).reshape(-1)
                    ]
                    body["resident_pages"] = cont.resident_prefix_pages(
                        chain, int(p.get("limit", 0))
                    )
            self._respond(p["peer"], proto.MIGRATE_RESP, p["rid"], body)
            return
        if op == "put":
            if self.faults is not None:
                # fault site "migrate.import": error refuses the staging
                # (source falls back), crash kills the destination mid-
                # migration — the chaos suite's kill-the-receiver case
                self.faults.inject("migrate.import", str(p.get("mig", "")))
            if rt is None or rt.engine is None:
                self._respond(
                    p["peer"], proto.MIGRATE_RESP, p["rid"],
                    {"ok": False, "error": "job not loaded"},
                )
                return
            cont = self._ensure_cont(rt)
            if cont is None:
                self._respond(
                    p["peer"], proto.MIGRATE_RESP, p["rid"],
                    {"ok": False, "error": "continuous unsupported"},
                )
                return
            ok = cont.stage_migration(str(p.get("mig", "")), p["blob"])
            self._respond(
                p["peer"], proto.MIGRATE_RESP, p["rid"],
                {"ok": bool(ok)} if ok else
                {"ok": False,
                 "error": "staging refused (mode mismatch, evicted "
                          "prefix, bad digest, or allocator dry)"},
            )
            return
        if op == "pull":
            # fleet prefix pull (docs/SERVING.md "Tiered prefix cache"):
            # a sibling replica on a local cache miss asks for our
            # resident pages covering its prompt's leading chain. READ-
            # ONLY on this side (gather, never alloc/scatter), so it is
            # deliberately outside the draining fence above — a draining
            # worker's cache is exactly the one worth raiding before its
            # pages die with the drain.
            if self.faults is not None:
                # fault site "kvtier.fetch": error refuses the export
                # (the puller degrades to re-prefill), crash kills this
                # SOURCE mid-pull — the chaos suite's tiered-cache case
                self.faults.inject(
                    "kvtier.fetch", f"pull-src:{p.get('job_id', '')}"
                )
            cont = self._ensure_cont(rt) if (
                rt is not None and rt.engine is not None
            ) else None
            if cont is None:
                self._respond(
                    p["peer"], proto.MIGRATE_RESP, p["rid"],
                    {"ok": False, "error": "job not loaded"},
                )
                return
            chain = [
                int(t) for t in np.asarray(p.get("chain", [])).reshape(-1)
            ]
            blob = cont.export_prefix_pages(
                chain, int(p.get("limit", 0)),
                n_skip=int(p.get("n_skip", 0)),
            )
            # blob=None (chain fell out of both tiers since the digest
            # was published) is ok:True with no blob — losing the race
            # to eviction is a degrade rung, never an error
            self._respond(
                p["peer"], proto.MIGRATE_RESP, p["rid"],
                {"ok": True, "blob": blob},
            )
            return
        if op == "expire":
            # a recovered source validator expiring stranded tickets
            # deterministically at journal replay — without this, a
            # validator restart mid-drain left staged pages pinned until
            # the destination's TTL GC happened to fire
            n = 0
            if rt is not None and rt.cont is not None:
                cont = rt.cont
                want = str(p.get("mig", "") or "")
                for mig_id in cont.staged_migrations():
                    if want and mig_id != want:
                        continue
                    cont.drop_staged_migration(mig_id)
                    n += 1
                cont.check_page_conservation()
            self._respond(
                p["peer"], proto.MIGRATE_RESP, p["rid"],
                {"ok": True, "expired": n},
            )
            return
        raise ValueError(f"unknown migrate op {op!r}")

    def _beam_step(self, job_id: str, rid: str) -> None:
        """Advance an in-flight beam session one bounded chunk. Unfinished
        sessions requeue a light marker on the worker's OWN work queue —
        FIFO, so every generate that arrived meanwhile runs before the
        next chunk (bounded occupancy instead of head-of-line blocking)."""
        rt = self._runtime(job_id)
        entry = rt.beam_sessions.get(rid)
        if entry is None:
            return  # job shut down / duplicate marker
        st, p, k = entry
        try:
            # advance via the engine the session STARTED on (st.engine):
            # a load_stage between chunks may swap rt.engine, and scoring
            # this session's KV under different weights would corrupt it
            done = st.engine.beam_advance(st, max_steps=_BEAM_CHUNK_STEPS)
        except BaseException:
            rt.beam_sessions.pop(rid, None)
            raise  # the run-loop error path responds on this rid
        if not done:
            self.bridge.q.work.put(
                ("beam_continue",
                 {"job_id": job_id, "rid": rid, "peer": p["peer"]})
            )
            return
        rt.beam_sessions.pop(rid, None)
        result = st.engine.beam_finish(st)
        stream_id = p.get("stream")
        if stream_id:
            # beams emit nothing until the search completes; close the
            # relay so a streaming caller never stalls on the drain
            self.bridge.request(
                "send_token",
                {"peer": p["peer"], "stream": stream_id, "tokens": [],
                 "done": True},
            )
        self._respond(
            p["peer"], proto.GENERATE_RESP, rid,
            {"sequences": [list(map(int, s)) for s in result.sequences],
             "finished": list(map(bool, result.finished)),
             "num_beams_used": k},
        )

    # -- parameters -----------------------------------------------------
    @staticmethod
    def _exact_params(rt: StageRuntime):
        """rt.params with int8-serving QTensor leaves dequantized — the
        wire/disk formats carry plain arrays."""
        from tensorlink_tpu.models.quant import QTensor, dequantize

        def fix(node):
            if isinstance(node, dict):
                return {k: fix(v) for k, v in node.items()}
            if isinstance(node, QTensor):
                return dequantize(node, rt.cfg.dtype)
            return node

        return fix(rt.params)

    def _params_req(self, p: dict) -> None:
        """Ship this stage's parameters back (reference parameter download,
        ml/worker.py:1394-1413 writes a file; here it is one bulk frame).
        Mirrored on merged co-slice stages: every member runs the gathers
        (collectives on a spanning mesh), only the primary ships bytes."""
        import jax

        rt = self._runtime(p["job_id"])
        host_params = jax.tree.map(
            lambda a: self._to_host(rt, a), self._exact_params(rt)
        )
        if p.get("mirror"):
            self._respond(
                p["peer"], proto.PARAMETERS, p["rid"], {"ok": True, "mirror": True}
            )
            return
        self._respond(p["peer"], proto.PARAMETERS, p["rid"], {"params": host_params})

    def _train_mode(self, p: dict) -> None:
        import jax

        from tensorlink_tpu.models.quant import QTensor

        rt = self._runtime(p["job_id"])
        quantized = any(
            isinstance(l, QTensor)
            for l in jax.tree.leaves(
                rt.params, is_leaf=lambda x: isinstance(x, QTensor)
            )
        )
        if bool(p.get("training", True)) and quantized:
            raise ValueError(
                "int8-quantized serving job cannot switch to training — "
                "request the job with quant=None for fine-tuning"
            )
        rt.training = bool(p.get("training", True))
        self._respond(
            p["peer"], proto.TRAIN_MODE_ACK, p["rid"],
            {"job_id": rt.job_id, "training": rt.training},
        )
