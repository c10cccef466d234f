"""Continuous-batching decode engine over the paged KV cache.

Replaces run-to-completion static batches (GenerationEngine.generate_* on
a window-coalesced request group) with **step-granularity admission and
eviction**: the engine decodes a fixed slot batch (B = max_slots) in
chunks, and every chunk boundary can admit queued prefills into free
slots and return finished slots' pages to the free-list. A request
therefore joins the running batch within at most one decode chunk, and a
finished row stops consuming decode steps immediately — the two failure
modes of the static batcher (queue-until-drain, dead ``done``-masked
rows) are structurally gone.

Admission prefills through the **automatic prefix cache** + the
**unified ragged step** (docs/SERVING.md): the longest cached chain of
full KV pages maps into the new slot's block table with zero prefill
compute, the first divergent page is copy-on-write, and the remaining
suffix rides the packed ``[slots, chunk]`` block of the one step
program — each mid-prefill slot's next prompt piece (its grant from
:func:`pack_prefill_budgets`) and each decode slot's next token in the
SAME ragged dispatch, so a long admission never stalls co-resident
decodes at all. The block is packed at the narrowest of at most two
widths that holds the chunk's longest grant (``block_widths``: a page or
two when nobody prefills, else ``prefill_chunk``), a ``prefill_chunk``-wide
block whose live rows fit ``flat_rows`` runs the flat rung (the ragged
pass computes the rows that carry a token, ``_block_width``; a patterned
model's one wide program instead tiles its row list by the chunk's live
rows, ``tiled``), and the step is compiled once a rung. (The legacy two-program schedule — ≤1 prefill chunk per
mid-prefill slot before a separate decode chunk — and the monolithic
dense-prefill admission were retired after their one-release fallback
window; ``prefill_chunk`` must be ≥ 1.) Finished slots promote their
prompt-region pages back into the cache (ref-counted, LRU-leaf eviction
under memory pressure), which also makes crash-recovery re-prefill
near-free while the prefix stays resident.

``kv_quant="int8"`` stores the KV pages int8 with per-(page, position,
head) scales (engine/paged.py): ~2× slots and ~2× prefix-cache residency
per HBM byte. ``kv_quant="int4"`` packs two values per byte at the same
scale granularity: ~4× at a byte-matched budget. Quantized streams keep
every determinism contract below among themselves (a quantized page +
scales IS the cache value, moved byte-exactly by
COW/promotion/eviction/recovery); only the fp-vs-quantized comparison
differs, bounded in tests/test_ops.py.

**Co-hosting** (docs/SERVING.md "Co-hosting multiple models"): several
engines — one per tenant model — may share ONE physical page pool
(engine/paged.py::SharedPagePool) under per-tenant page quotas. Each
tenant keeps its own slots, scheduler, and prefix cache; the shared
free list is the contended resource, reclaimed cross-tenant first from
cold resident prefixes and then by preempting strictly-lower-ranked
neighbors (the PR 4 rank rules applied across models). Page
conservation extends per-tenant and is checked globally.

Determinism contract (the parity tests' anchor): each slot samples with
its OWN stateless key chain — token n of a request draws from
``fold_in(PRNGKey(seed), n)`` — and a slot's logits depend only on its
own pages (attention masks by slot length). Cached KV is bitwise the KV
the slot would have computed (prefill chunk framing is invariant,
test-pinned; decode-written pages are never promoted). So a request
decodes token-for-token identically whether it runs alone, co-resident
with any mix of neighbors, admitted mid-flight, or resumed on a
replacement worker after a crash (the recovery path re-prefills prompt +
emitted and continues the chain at n = len(emitted)) — with the prefix
cache on or off.
"""

from __future__ import annotations

import itertools
import math
import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor, wait
from functools import partial
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import faults
from ..core.metrics import MetricsRegistry
from ..core.trace import (
    ENGINE_SPAN_PARENT,
    HOST,
    SPANS_NAMED_AHEAD,
    FlightRecorder,
    first_token_stamp,
    get_tracer,
)
from ..models.sala import check_sparse, is_sala
from ..models.transformer import tp_partition_specs, tp_shardable
from ..parallel.mesh import serving_mesh
from .generate import GenerationEngine
from .kvtier import HostPagePool
from .paged import (
    EOS_WIDTH,
    PageAllocator,
    LatentPagedCache,
    PagedKVCache,
    PrefixCache,
    SharedPagePool,
    copy_page,
    flat_rung_rows,
    gather_page,
    make_tp_ragged_step,
    pack_control,
    paged_decode_step,
    paged_ragged_step,
    pages_needed,
    row_tile,
    scatter_page,
    set_counts_row,
    tiled_rows,
    tp_cache_specs,
    tp_gather_costs,
    unpack_results,
)
from .latent import restore_window, take_window, window_snapshot_pool
from .sala import (
    held as held_arrays, restore_snapshot, snapshot_pool, take_snapshot,
    tree_bytes, zero_state,
)
from .sampling import SamplingParams, penalized, sample
from .spec import SpecController
from .scheduler import (
    DEFAULT_PRIORITY,
    PRIORITY_CLASSES,
    PRIORITY_RANK,
    RequestScheduler,
    SchedulerOverloaded,
    normalize_priority,
)


class PagedUnsupported(ValueError):
    """A declared refusal: this model or deployment is one the paged slot
    engine does not serve (sliding-window attention, a tensor-parallel
    degree the model or the host cannot shard to). The worker's hosting
    seam turns exactly this type into the static batcher; anything else a
    constructor or a compile raises — a bad knob, a lowering error, an
    out-of-memory — is a fault and propagates."""


def paged_unsupported(cfg) -> str | None:
    """Why the paged engine can't serve a model config — None when it
    can. THE hosting-time routing predicate (ml/validator.py): models it
    rejects get the windowed static batcher. An int8 KV cache is
    deliberately NOT a reason anymore — the paged cache stores int8
    pages natively (``kv_quant``), so ``quant="int8+kv"`` model specs
    serve continuous (regression-pinned in tests/test_quant.py)."""
    if getattr(cfg, "patterned", False):
        # layers of more than one kind: windows live in the page cache
        # there, per kind (engine/latent.py)
        from .latent import unsupported

        return unsupported(cfg)
    if getattr(cfg, "sliding_window", None) is not None:
        return "sliding-window attention"
    return None


# tlint: hot-path
def pack_prefill_budgets(
    remaining: "list[int]", chunk: int, budget: "int | None" = None,
    phase: int = 0,
) -> list[int]:
    """The unified ragged step's per-step token-budget assembly: how many
    prefill tokens each mid-prefill slot gets this step.

    Pure host-side and deterministic — given each mid-prefill slot's
    remaining prompt-token count (in slot order), grant up to ``chunk``
    tokens per slot (the packed block's row width), subject to an
    optional TOTAL ``budget`` shared across slots. Under a budget the
    split is round-robin one token at a time starting from slot index
    ``phase % n`` — the caller advances ``phase`` every step, so a
    budget smaller than the number of concurrent admissions rotates
    who gets this step's tokens instead of starving the tail slots
    forever. The split for a given (remaining, chunk, budget, phase) is
    a pure function of its inputs — which is what makes it unit-testable
    in isolation AND what the ragged framing-invariance contract
    quantifies over: ANY grant schedule that eventually covers the
    prompt yields bitwise the same KV (test-pinned in
    tests/test_ops.py)."""
    n = len(remaining)
    want = [min(int(chunk), max(int(r), 0)) for r in remaining]
    if budget is None or sum(want) <= int(budget):
        return want
    grants = [0] * n
    left = int(budget)
    # token-granular round-robin: bounds are small (budget < slots*chunk
    # here, else the fast path above returned) so the exact-fairness
    # loop stays trivial
    start = int(phase) % n if n else 0
    while left > 0:
        progressed = False
        for j in range(n):
            i = (start + j) % n
            if grants[i] < want[i] and left > 0:
                grants[i] += 1
                left -= 1
                progressed = True
        if not progressed:
            break
    return grants


# tlint: hot-path
@jax.jit
def _row_keys(seeds: jax.Array, steps: jax.Array) -> jax.Array:
    """Per-slot sampling keys: ``fold_in(PRNGKey(seed_s), step_s)``.
    Stateless in the step index — the property that makes crash recovery
    and mid-flight admission bit-exact (no split chain to replay)."""
    return jax.vmap(
        lambda s, n: jax.random.fold_in(jax.random.PRNGKey(s), n)
    )(seeds, steps)


# tlint: hot-path
@jax.jit
def _sample_rows(logits, keys, temp, top_k, top_p, pres, freq, counts):
    """Row-independent sampling: each slot draws from its own key over its
    own logits, so neighbors can never perturb a request's stream.

    The greedy/sampled choice is made ONCE over the slots, outside the
    per-row ``vmap``: under the ``vmap`` ``sample``'s own ``cond`` has a
    batched predicate and lowers to a select, so the vocabulary sort,
    softmax, cumsum and Gumbel draw ran for every row of every call
    whatever the slots asked for. A scalar predicate keeps the ``cond`` a
    branch: a block in which no slot samples (an idle or released slot's
    ``_temp`` is 0) applies the penalties and takes an argmax; a block
    with one sampled slot runs the per-row ``sample`` for all, which
    already gives a ``temp == 0`` row its argmax. Tokens are bitwise the
    same either way (tests/test_sampling_epilogue.py)."""

    def one(lg, key, t, k, p, pp, fp, cnt):
        sp = SamplingParams(
            temperature=t, top_k=k, top_p=p,
            presence_penalty=pp, frequency_penalty=fp,
        )
        return sample(lg[None], key, sp, cnt[None])[0]

    def sampled(_):
        return jax.vmap(one)(
            logits, keys, temp, top_k, top_p, pres, freq, counts
        )

    def greedy(_):  # ``sample``'s penalties and its ``greedy`` branch
        lg = penalized(
            logits.astype(jnp.float32), counts, pres[:, None], freq[:, None]
        )
        return lg.argmax(-1).astype(jnp.int32)

    with jax.named_scope("sample"):
        return jax.lax.cond((temp > 0.0).any(), sampled, greedy, None)


# host phases of one chunk, in the order step_chunk goes through them;
# "between" (the previous chunk's exit to this one's entry) comes first
CHUNK_PHASES = (
    "admit", "pack", "dispatch", "wait", "drain", "deliver", "post",
)
# parts of wait's stream stage and intake that lay behind the driver's first
# sight of the step's result ready (step_chunk): counted like the phases
LATE_PARTS = ("late_stream", "late_intake")

FLIGHT_CAPACITY = 1024  # chunks the flight recorder keeps
MIGRATION_TTL_S = 120.0  # an engine's ``migration_ttl_s`` starts here


# seconds an idle engine waits for its build thread at a time
# (``ContinuousEngine._join_build``): a request that arrives meanwhile
# waits behind it, and the server ends a stream after 30 s without an
# event. Fetches from the persistent cache are through in a second or
# two; this bounds a machine that has nothing cached and compiles a rung
# for a minute (its chunks run the full program meanwhile, so it waits
# as long as it safely can)
BUILD_JOIN_MAX_S = 20.0


def _timed(fn) -> float:
    """Run ``fn``; the seconds it took on this thread's wall clock."""
    t0 = time.monotonic()
    fn()
    return time.monotonic() - t0


class _Phase:
    """One host phase of a chunk, marked where the work happens: a
    ``tlink:<name>`` annotation on the profiler's host line (found by no
    session, it costs an object) and a ``time.monotonic()`` pair added to
    the chunk's phase table in seconds. No device sync: every phase
    starts and ends where the host already waits or never did."""

    __slots__ = ("acc", "name", "ann", "t0")

    def __init__(self, acc: dict, name: str, **args):
        self.acc = acc
        self.name = name
        self.ann = jax.profiler.TraceAnnotation(f"tlink:{name}", **args)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        self.acc[self.name] = t1 - self.t0
        self.acc["end"] = t1  # the last phase's end is the chunk's exit
        self.ann.__exit__(*exc)
        return False


# the engine's counter families: (legacy /stats key, prometheus name,
# help). The legacy keys are the test-pinned serving_snapshot() contract;
# the prometheus names are the /metrics exposition of the SAME cells.
_ENGINE_COUNTERS = (
    ("admitted", "tlink_engine_admitted_total",
     "requests admitted into a slot"),
    ("evicted", "tlink_engine_evicted_total",
     "finished slots evicted at a chunk boundary"),
    ("preemptions", "tlink_engine_preemptions_total",
     "slots preempted for a higher-ranked candidate"),
    ("decode_steps", "tlink_engine_decode_steps_total",
     "compiled decode steps executed"),
    ("slot_steps_live", "tlink_engine_slot_steps_live_total",
     "slot-steps that delivered a token"),
    ("slot_steps_total", "tlink_engine_slot_steps_total",
     "slot-steps executed including padding rows"),
    ("prefill_chunks", "tlink_engine_prefill_chunks_total",
     "prefill grants executed"),
    ("prefill_tokens", "tlink_engine_prefill_tokens_total",
     "prompt tokens prefilled on device"),
    ("prefill_tokens_skipped", "tlink_engine_prefill_tokens_skipped_total",
     "prompt tokens served from the prefix cache"),
    ("migrations_started", "tlink_engine_migrations_started_total",
     "slots frozen for export (source side)"),
    ("migrations_completed", "tlink_engine_migrations_completed_total",
     "migrations whose pages shipped and committed (source side)"),
    ("migrations_failed", "tlink_engine_migrations_failed_total",
     "migrations aborted or fallen back (source side)"),
    ("migrations_fell_back", "tlink_engine_migrations_fell_back_total",
     "streams redirected down the re-prefill rung"),
    ("migrations_adopted", "tlink_engine_migrations_adopted_total",
     "staged migrations adopted into a slot (destination side)"),
    # disaggregated prefill/decode pools (docs/SERVING.md "Disaggregated
    # prefill/decode"): prefill-pool slots frozen at the prefill→decode
    # boundary and shipped to a decode-pool worker at admission time —
    # migration promoted from a maintenance action to the steady-state
    # data path (started == completed + fell_back over any quiet window)
    ("handoffs_started", "tlink_engine_handoffs_started_total",
     "prefill-completed slots frozen for prefill→decode handoff"),
    ("handoffs_completed", "tlink_engine_handoffs_completed_total",
     "handoffs whose pages shipped and committed (source side)"),
    ("handoffs_fell_back", "tlink_engine_handoffs_fell_back_total",
     "handoffs that fell back (re-prefill redirect or local resume)"),
    # speculative decoding (docs/SERVING.md "Speculative decoding"):
    # draft tokens packed as extra ragged rows and verified in-program
    ("spec_drafted", "tlink_engine_spec_drafted_total",
     "draft tokens packed for in-program verification"),
    ("spec_accepted", "tlink_engine_spec_accepted_total",
     "draft tokens accepted by in-program verification"),
    ("spec_verify_passes", "tlink_engine_spec_verify_passes_total",
     "verify passes executed (one per speculating slot per step)"),
    ("spec_killed", "tlink_engine_spec_killed_total",
     "requests whose acceptance-rate kill switch fired"),
    # multi-tenant co-hosting (docs/SERVING.md "Co-hosting multiple
    # models"): this engine's slots torn down for ANOTHER tenant's
    # higher-ranked candidate on the shared page pool
    ("preempted_cross_tenant", "tlink_engine_preempted_cross_tenant_total",
     "slots preempted for another tenant's higher-ranked candidate"),
    # serve-and-train (docs/TRAINING.md "Serve-and-train"): live weight
    # publishes hot-swapped at the chunk boundary, and background train
    # steps executed between this engine's serving chunks
    ("weights_published", "tlink_engine_weights_published_total",
     "weight versions hot-swapped into the serving engine"),
    ("train_steps", "tlink_engine_train_steps_total",
     "background train steps run between serving chunks"),
    # tiered prefix cache (docs/SERVING.md "Tiered prefix cache"):
    # evicted pages demote to host RAM instead of dying, admission
    # promotes host-resident chains back, and a local miss may pull the
    # prefix from a sibling replica through the MIGRATE wire
    ("prefix_demotions", "tlink_engine_prefix_demotions_total",
     "refcount-0 prefix pages demoted to the host-RAM tier at eviction"),
    ("host_tier_hits", "tlink_engine_host_tier_hits_total",
     "pages promoted from the host tier back into HBM at admission"),
    ("fleet_pulls", "tlink_engine_fleet_pulls_total",
     "admissions that attempted a cross-replica prefix pull"),
    ("fleet_pull_fallbacks", "tlink_engine_fleet_pull_fallbacks_total",
     "fleet pulls that degraded to the next rung (local prefill)"),
    # flat token packing (ROADMAP S5): rows of the packed [S, C] block
    # that carried a token against rows the ragged pass computed
    ("ragged_rows_valid", "tlink_engine_ragged_rows_valid_total",
     "rows of the packed block that carried a token"),
    ("ragged_rows_computed", "tlink_engine_ragged_rows_computed_total",
     "rows the ragged pass computed position-wise (the flat rung's row "
     "count, the tiled pass's live tiles, else slots x the width that ran)"),
    # the width ladder (ROADMAP S5): a chunk whose longest grant fits the
    # narrow width packs a block that wide, every other one prefill_chunk
    ("ragged_blocks", "tlink_engine_ragged_blocks_total",
     "packed blocks dispatched (one a chunk)"),
    ("ragged_blocks_narrow", "tlink_engine_ragged_blocks_narrow_total",
     "of those, blocks packed at the narrow width of the ladder"),
    ("ragged_blocks_narrow_unbuilt",
     "tlink_engine_ragged_blocks_narrow_unbuilt_total",
     "blocks that fitted the narrow width and ran wide: its program was "
     "still being built"),
    # the flat rung (ROADMAP S5): a prefill_chunk-wide block whose live
    # rows fit flat_rows has the ragged pass compute that many rows
    ("ragged_blocks_flat", "tlink_engine_ragged_blocks_flat_total",
     "of those, blocks whose ragged pass ran over the flat row list"),
    ("ragged_blocks_flat_unbuilt",
     "tlink_engine_ragged_blocks_flat_unbuilt_total",
     "blocks whose live rows fitted the flat rung and ran the full "
     "program: the rung's was still being built"),
    # the paged kernels' live-span walk (ROADMAP S7): pages the walk
    # reads against the page slots a capacity-wide walk would visit
    ("attn_pages_live", "tlink_engine_attn_pages_live_total",
     "KV pages the attention passes of a chunk walk (contexts as packed)"),
    ("attn_pages_capacity", "tlink_engine_attn_pages_capacity_total",
     "page slots of those passes (slots x pages per slot)"),
    # the walk's two heights (ops/attention.py::_short_positions): a slot
    # with ONE row in the ragged pass walks a row block of one position
    ("ragged_slots_live", "tlink_engine_ragged_slots_live_total",
     "slots with a row in a chunk's ragged pass"),
    ("ragged_slots_single", "tlink_engine_ragged_slots_single_total",
     "of those, slots with exactly one row (the walk's short row block)"),
    # the tensor-parallel step's activation gathers (docs/SHARDING.md):
    # from the shapes the host packed, per dispatched chunk; 0 at tp=1
    ("tp_gather_bytes", "tlink_engine_tp_gather_bytes_total",
     "bytes each chip received in the tp step's all-gathers"),
    ("tp_gather_calls", "tlink_engine_tp_gather_calls_total",
     "all-gathers the tp step executed"),
    # a patterned model's step (engine/latent.py): what the routing and
    # the selection of a chunk came to, counted by the program itself
    # (the cache's ``stats``, read with the chunk's one sync), each summed
    # over the chunk's expert / full layer executions; 0 for other models
    ("moe_rows_routed_local", "tlink_engine_moe_rows_routed_local_total",
     "(row, expert) pairs that fell on an expert this program holds"),
    ("moe_rows_computed", "tlink_engine_moe_rows_computed_total",
     "rows the expert loop computed (whole tiles of one expert's rows)"),
    ("moe_rows_busiest_expert", "tlink_engine_moe_rows_busiest_expert_total",
     "rows of the held expert with most rows, a layer execution"),
    ("moe_experts_touched", "tlink_engine_moe_experts_touched_total",
     "held experts that got a row, a layer execution"),
    ("moe_experts_held", "tlink_engine_moe_experts_held_total",
     "held experts, a layer execution"),
    ("moe_rows_in_group", "tlink_engine_moe_rows_in_group_total",
     "rows whose routing groups reach an expert this program holds"),
    ("moe_rows_valid", "tlink_engine_moe_rows_valid_total",
     "rows that carry a token, an expert layer execution"),
    ("sparse_positions_kept", "tlink_engine_sparse_positions_kept_total",
     "cached positions the full layers attended after selection"),
    ("sparse_positions_scored", "tlink_engine_sparse_positions_scored_total",
     "cached positions in those queries' causal spans"),
    # from the contexts as packed, like attn_pages_live: pages a sliding
    # layer's pass reads against the pages the slot's context holds
    ("window_pages_walked", "tlink_engine_window_pages_walked_total",
     "pages the sliding layers' window spans reach"),
    ("window_pages_context", "tlink_engine_window_pages_context_total",
     "pages of context under those passes"),
    # ... and cached rows the walk of a full layer without a selector
    # reads against the rows the slots of those passes could hold
    ("latent_rows_read", "tlink_engine_latent_rows_read_total",
     "cached rows the full layers' walk reads (each slot's live span)"),
    ("latent_rows_capacity", "tlink_engine_latent_rows_capacity_total",
     "rows the slots of those passes could hold"),
    # block-sparse GQA and lightning layers (engine/sala.py): the step's
    # own counts, summed like the ones above over a chunk's layer
    # executions of the kind ...
    ("sparse_blocks_kept", "tlink_engine_sparse_blocks_kept_total",
     "blocks the sparse layers attended, a query row and kv group"),
    ("sparse_blocks_visible", "tlink_engine_sparse_blocks_visible_total",
     "blocks those queries could see (their causal spans)"),
    ("sparse_rows_dense", "tlink_engine_sparse_rows_dense_total",
     "query rows under dense_len (nothing selected), a sparse layer"),
    ("lightning_rows", "tlink_engine_lightning_rows_total",
     "rows the recurrence took, a lightning layer"),
    # ... and what admission did about the slots' states (host side)
    ("state_admissions", "tlink_engine_state_admissions_total",
     "admissions of a model with recurrent layers"),
    ("state_snapshots_taken", "tlink_engine_state_snapshots_taken_total",
     "state snapshots taken where a prefill chunk ended"),
    ("state_snapshots_restored",
     "tlink_engine_state_snapshots_restored_total",
     "admissions that restored a snapshot under their prefix hit"),
    ("state_snapshots_skipped", "tlink_engine_state_snapshots_skipped_total",
     "snapshot points passed with no place free in the snapshot pool"),
    ("state_rows_replayed", "tlink_engine_state_rows_replayed_total",
     "cached positions prefilled again between a snapshot and the match"),
    # ... and the same of a model whose window layers hold a ring a slot
    ("window_admissions", "tlink_engine_window_admissions_total",
     "admissions of a model with window layers held as rings"),
    ("window_snapshots_taken", "tlink_engine_window_snapshots_taken_total",
     "window snapshots taken where a prefill chunk ended"),
    ("window_snapshots_restored",
     "tlink_engine_window_snapshots_restored_total",
     "admissions that restored a window snapshot under their prefix hit"),
    ("window_snapshots_skipped",
     "tlink_engine_window_snapshots_skipped_total",
     "snapshot points passed with no place free in the snapshot pool"),
    ("window_rows_replayed", "tlink_engine_window_rows_replayed_total",
     "cached positions prefilled again between a snapshot and the match"),
    # ... and of a model whose short-convolution layers hold a tail a slot
    ("conv_admissions", "tlink_engine_conv_admissions_total",
     "admissions of a model with short-convolution layers"),
    ("conv_snapshots_taken", "tlink_engine_conv_snapshots_taken_total",
     "tail snapshots taken where a prefill chunk ended"),
    ("conv_snapshots_restored", "tlink_engine_conv_snapshots_restored_total",
     "admissions that restored a tail snapshot under their prefix hit"),
    ("conv_snapshots_skipped", "tlink_engine_conv_snapshots_skipped_total",
     "snapshot points passed with no place free in the snapshot pool"),
    ("conv_rows_replayed", "tlink_engine_conv_rows_replayed_total",
     "cached positions prefilled again between a snapshot and the match"),
    # the sampling epilogue (ROADMAP S1): what the packed slots asked of
    # it, per dispatched chunk from the host's own arrays. A sampler call
    # is one _sample_rows over [slots, vocabulary]: each verify row
    # walked and each continuation step
    ("sampler_calls", "tlink_engine_sampler_calls_total",
     "sampler calls the step program executed"),
    ("sampler_calls_sampled", "tlink_engine_sampler_calls_sampled_total",
     "sampler calls that took the sort/softmax branch (a slot samples)"),
    ("verify_rows_walked", "tlink_engine_verify_rows_walked_total",
     "verify rows walked (longest emitting draft + 1, 0 if none emits)"),
    ("verify_rows_capacity", "tlink_engine_verify_rows_capacity_total",
     "verify rows the program holds (spec_width a dispatched chunk)"),
) + tuple(
    # the anatomy of a chunk on the host (docs/SERVING.md "Observability"):
    # cumulative microseconds per phase of step_chunk, so a window reads
    # each as a difference whatever the flight recorder's length
    (f"chunk_us_{ph}", f"tlink_engine_chunk_us_{ph}_total",
     f"host microseconds in a chunk's {ph} phase")
    for ph in ("between",) + CHUNK_PHASES
) + (
    # the stream stage (docs/SERVING.md "Observability"): a chunk's tokens
    # leave for their callbacks behind the NEXT chunk's dispatch, so its
    # microseconds lie inside that chunk's wait; with no step in flight
    # they lie inside deliver (or wherever a flush was asked for)
    ("chunk_us_stream", "tlink_engine_chunk_us_stream_total",
     "host microseconds in the stream stage (a sub-span of wait or deliver)"),
    ("stream_tokens_overlapped", "tlink_engine_stream_tokens_overlapped_total",
     "tokens streamed while the device ran the next chunk"),
    ("stream_tokens_flushed", "tlink_engine_stream_tokens_flushed_total",
     "tokens streamed with no step in flight"),
    # the host-device boundary of a chunk (docs/SERVING.md "The anatomy of
    # a chunk"): the packed control buffer in, the packed results out
    ("chunk_host_arrays", "tlink_engine_chunk_host_arrays_total",
     "arrays step_chunk placed on or fetched from the device (2 a chunk)"),
    # what an admission or a retirement changes on the device rides the
    # next chunk's control buffer; what cannot ride stays a call
    ("slot_binds_packed", "tlink_engine_slot_binds_packed_total",
     "slot binds and clears handed to the next chunk's control buffer"),
    ("admit_device_calls", "tlink_engine_admit_device_calls_total",
     "device calls of the admission and retirement path (copy-on-write "
     "copies, state restores, penalty histograms, promoted pages)"),
    # intake ahead (docs/SERVING.md "The anatomy of a chunk"): what the
    # host did for the next chunk while the device ran this one
    ("submitted", "tlink_engine_submitted_total",
     "requests handed to submit"),
    ("submitted_ahead", "tlink_engine_submitted_ahead_total",
     "of those, requests submitted inside a chunk's wait (the intake)"),
    ("admitted_ahead", "tlink_engine_admitted_ahead_total",
     "of the admitted, requests an ahead round prepared during a chunk's "
     "wait and the next chunk's edge committed"),
    ("chunk_us_intake", "tlink_engine_chunk_us_intake_total",
     "host microseconds in the intake's frames and ahead rounds (a "
     "sub-span of wait)"),
    # the inside of a chunk's wait: the stream stage, then the intake, then
    # the fetch. What of the first two lay behind the moment the driver
    # first saw the step's result ready: the device was done, and the
    # driver's own serial work kept it from the fetch
    ("chunk_us_late_stream", "tlink_engine_chunk_us_late_stream_total",
     "host microseconds of the in-flight stream stage after the driver "
     "first saw the chunk's result ready"),
    ("chunk_us_late_intake", "tlink_engine_chunk_us_late_intake_total",
     "host microseconds of the intake (work and blocking) after the driver "
     "first saw the chunk's result ready"),
)


@dataclass
class ContinuousRequest:
    """One in-flight (or queued) request's host-side state."""

    rid: int
    prompt: list[int]  # original prompt + any previously-emitted prefix
    budget: int  # total tokens wanted THIS submission (incl. pre-preempt)
    sampling: SamplingParams  # scalar leaves
    eos: frozenset
    seed: int
    start_step: int = 0  # tokens emitted before admission (recovery)
    stream_cb: Callable[[int], bool | None] | None = None
    on_finish: Callable[["ContinuousRequest"], None] | None = None
    tokens: list[int] = field(default_factory=list)  # emitted THIS run
    finished: bool = False
    slot: int = -1
    pages: list[int] = field(default_factory=list)  # pages this slot OWNS
    shared_nodes: list = field(default_factory=list)  # prefix-cache hits
    prefill_pos: int = 0  # prefill tokens written so far (chunked prefill)
    # the token sequence the CURRENT admission prefilled (prompt plus any
    # tokens emitted before a preemption); prefill_target = its length —
    # the promotion cap (positions past it are decode-written, never
    # cached) and the key source for promoted pages
    prefill_tokens: list[int] = field(default_factory=list)
    prefill_target: int = 0
    error: BaseException | None = None
    # stream_cb asked for a stop while the next chunk was already running
    # with this slot in it: that chunk's settle evicts the slot and drops
    # its tokens (docs/SERVING.md "Continuous batching", the cancel)
    cancelled: bool = False
    done: threading.Event = field(default_factory=threading.Event)
    # -- live migration (docs/FAILURE_MODEL.md "Migration & drain") ------
    # staged-adoption ticket id: admission binds the shipped KV pages
    # instead of prefilling (engine._migrations); cleared on fallback
    adopt: str | None = None
    # -- disaggregated prefill/decode (docs/SERVING.md) ------------------
    # on a handoff-armed (prefill-pool) engine: this request's prefill
    # stops ONE token short of its prompt and the slot freezes for
    # shipment to a decode-pool worker instead of drawing its first
    # token here — the export then carries (chain=prompt, length=T-1,
    # last_tok=prompt[-1]), exactly the staged-adoption ticket shape, so
    # the DESTINATION makes the first draw: fold_in(seed, 0) over
    # position T-1's logits, bitwise the single-pool run's first token
    # by the ragged framing-invariance contract (tests/test_ops.py)
    handoff: bool = False
    # opaque client/transport context (peer, rid, stream id) the worker
    # layer attaches so a drain can redirect the stream mid-flight
    client_meta: dict | None = None
    # -- scheduling (engine/scheduler.py) -------------------------------
    priority: str = DEFAULT_PRIORITY
    sched_seq: int = 0  # arrival order; preserved across preemption
    admit_seq: int = 0  # admission order; fresh on every (re)admission
    enqueue_tick: int = 0  # aging clock origin; restarts on requeue
    enqueue_t: float = 0.0
    admit_rank: int = -1  # effective rank AT admission (preemption shield)
    submit_t: float = 0.0
    admit_t: float = 0.0
    # -- observability (core/trace.py) -----------------------------------
    # distributed-trace id minted by the API server (empty = untraced:
    # the engine skips every span-recording call for this request)
    trace_id: str = ""
    # name -> sid of the spans this request's other spans name as their
    # ``parent`` before they are recorded (a span is recorded at its end)
    span_sids: dict | None = None
    prefill_done_t: float = 0.0  # when the slot left the prefilling set
    # deepest cache tier that contributed to this admission's hit region
    # ("none" | "hbm" | "host" | "fleet") — rides the admission span so
    # a trace shows WHERE a prefix came from, not just how much it saved
    cache_tier: str = "none"
    # -- live weight publish (docs/TRAINING.md "Serve-and-train") --------
    # the engine weights version this request was ADMITTED under: its
    # prefill-written pages may promote into the prefix cache only while
    # this still equals the engine's version — KV computed under older
    # weights must never become a cache hit for a post-publish admission
    weights_version: int = 0
    # -- speculative decoding (engine/spec.py, docs/SERVING.md) ----------
    # the request opted in ({"speculative": true}); only effective on an
    # engine with MLConfig.spec_decode enabled
    speculative: bool = False
    # -- a model with recurrent layers (engine/sala.py) -------------------
    # snapshots of the slot's state this admission's prefill took, by
    # position (places in the engine's snapshot pool): they join the trie
    # with the pages at release. ``state_restored_at``: the position of
    # the snapshot the admission restored (-1: none, the state began at 0)
    snaps: dict = field(default_factory=dict)
    state_restored_at: int = -1
    # per-request drafting state machine (created lazily at the first
    # decode pack; survives preemption/requeue so the permanent kill
    # switch never re-probes; NOT shipped by migration — a migrated
    # stream re-probes fresh at the destination)
    spec_state: object = None


def device_bytes(tree) -> dict:
    """``{device: bytes}`` of ``tree``'s arrays resident on each device
    that holds any of them (a replicated leaf counts on every device it is
    on)."""
    held: dict = {}
    for leaf in jax.tree.leaves(tree):
        # from the sharding alone: reading a shard's ``data`` would leave a
        # one-device view of it alive beside the array
        n = math.prod(leaf.sharding.shard_shape(leaf.shape)) * leaf.dtype.itemsize
        for d in leaf.sharding.addressable_devices:
            held[d] = held.get(d, 0) + n
    return held


def tp_serving_refusal(
    cfg, tp: int, *, shared_pool: bool = False, weight_quant: bool = False
) -> str | None:
    """Why a slot engine cannot serve ``cfg`` sharded ``tp`` ways here, or
    None when it can. One rule for the engine (which refuses with
    :class:`PagedUnsupported`) and for the worker's load (which makes the
    weights in the layout of the engine that will serve them)."""
    tp = int(tp)
    if tp <= 1:
        return None
    if cfg.sliding_window is not None:
        return "sliding-window attention has no paged serving path"
    if len(jax.devices()) < tp:
        return (
            f"tensor_parallel={tp} needs as many devices, have "
            f"{len(jax.devices())}"
        )
    reason = tp_shardable(cfg, tp)
    if reason is not None:
        return f"tensor_parallel={tp}: {reason}"
    if shared_pool:
        return (
            "tensor parallelism does not compose with a shared page pool "
            "yet — the pool's page arrays are unsharded"
        )
    if weight_quant:
        return (
            "weight-quantized engines cannot shard over a tp axis — "
            "QTensor scale layouts have no partition specs yet"
        )
    return None


class ContinuousEngine:
    """Slot-batched continuous decode over one GenerationEngine's model.

    Single-driver discipline: ``submit``/``cancel`` are thread-safe;
    ``step_chunk`` must be called from one driver thread (the worker's
    work loop or a ContinuousBatcher's dispatcher).
    """

    def __init__(
        self,
        engine: GenerationEngine,
        *,
        max_slots: int = 8,
        page_size: int = 16,
        chunk_steps: int = 8,
        prefill_chunk: int = 128,
        prefix_cache: bool = True,
        host_tier_pages: int = 0,
        kv_quant: str = "none",
        prefill_budget: int = 0,
        spec_decode: bool = False,
        spec_draft: int = 8,
        spec_budget: int = 0,
        sched_queue_cap: int = 64,
        sched_aging_ticks: int = 32,
        sched_preemption: bool = True,
        sched_policy: str = "slo",
        sched_max_wait_s: float = 60.0,
        default_priority: str = DEFAULT_PRIORITY,
        handoff_after_prefill: bool = False,
        worker_role: str = "mixed",
        trace_site: str = "",
        metrics: MetricsRegistry | None = None,
        pool: SharedPagePool | None = None,
        model_id: str = "",
        page_quota: int = 0,
        tensor_parallel: int = 1,
        state_snapshot_stride: int = 0,
        state_snapshots: int = 0,
        intake: Callable | None = None,
    ):
        if engine.cfg.sliding_window is not None:
            raise PagedUnsupported(
                "continuous batching does not support sliding-window "
                "attention yet — serve through the static batcher"
            )
        # a patterned model's cache is latent pages per layer kind
        # (engine/latent.py): the parts of the system that move or share
        # K/V pages by name do not know them yet, and say so
        self._latent = bool(engine.cfg.patterned)
        # ... and a model with recurrent layers holds a state a slot that
        # no page chain describes: what reuses or moves a slot restores a
        # snapshot and replays, or refuses (``stateful_refusals``)
        # ... as does a model whose window layers hold a ring a slot
        # (engine/latent.py): the same rule, counted under ``window_*``
        # ... and a model whose short-convolution layers hold a tail a
        # slot: the same rule again, counted under ``conv_*``
        # ... and a model whose gated delta-rule layers hold a state AND a
        # tail a slot, two arrays in one snapshot, counted under
        # ``state_*``. One property says which (``ModelConfig.slot_state``)
        kind = engine.cfg.slot_state
        self._ring = kind == "gqa_window"
        self._tail = kind == "conv"
        self._delta = kind == "gated_delta"
        self._stateful = kind is not None
        self._snap_counts, what, states = {
            None: ("state", "latent pages", ""),
            "lightning": ("state", "pages and recurrent states",
                          "recurrent states"),
            "gqa_window": ("window", "pages and window rings",
                           "window rings"),
            "conv": ("conv", "pages and convolution tails",
                     "convolution tails"),
            "gated_delta": ("state", "pages, recurrent states and "
                            "convolution tails",
                            "recurrent states and convolution tails"),
        }[kind]
        self._held_what = what
        if self._latent:
            asked = str(kv_quant or "none")
            refusals = (
                (paged_unsupported(engine.cfg), None),
                (asked != "none" or engine.cache_quant,
                 f"{what} are stored in the model dtype (kv_quant "
                 f"{asked!r} asked)"),
                (pool is not None, f"{what} in a shared page pool"),
                (int(host_tier_pages) > 0, f"{what} in the host-RAM tier"),
                (handoff_after_prefill,
                 f"{what} do not hand off between workers"),
                (self._stateful and int(tensor_parallel or 1) > 1,
                 f"{states} have no partition specs "
                 f"(tensor_parallel={tensor_parallel} asked)"),
                ("sparse" in engine.cfg.layer_kinds and check_sparse(
                    engine.cfg.latent_of("sparse"), int(page_size)), None),
            )
            for hit, why in refusals:
                if hit:
                    raise PagedUnsupported(f"patterned model: {why or hit}")
        # requests that ask to draft are served without drafts: a rejected
        # draft row would have advanced the state (said in
        # ``serving_snapshot()["spec_refusal"]``)
        self.spec_refusal = ""
        if self._stateful and spec_decode:
            spec_decode = False
            self.spec_refusal = (
                "a model whose window layers hold a ring does not draft: a "
                "rejected draft row would have overwritten the ring's oldest "
                "page" if self._ring else
                "a model with short-convolution layers does not draft: a "
                "rejected draft row would have moved the slot's tail"
                if self._tail else
                "a model with gated delta-rule layers does not draft: a "
                "rejected draft row would have advanced the slot's state "
                "and moved its tail" if self._delta else
                "a model with recurrent layers does not draft: a rejected "
                "draft row would have advanced the slot's state")
        if int(prefill_chunk) <= 0:
            raise ValueError(
                "prefill_chunk must be >= 1 — the monolithic dense-prefill "
                "admission was retired with the legacy two-program step"
            )
        kv_quant = str(kv_quant or "none")
        if engine.cache_quant and kv_quant == "none":
            # the model spec asked for an int8 KV cache ("int8+kv"): the
            # paged engine serves it natively as int8 pages — this is what
            # used to (wrongly) route such models to the dense engine
            kv_quant = "int8"
        if kv_quant not in ("none", "int8", "int4"):
            raise ValueError(f"unknown kv_quant mode {kv_quant!r}")
        self.kv_quant = kv_quant
        self.engine = engine
        self.cfg = engine.cfg
        self.max_slots = int(max_slots)
        self.page_size = int(page_size)
        self.chunk_steps = max(int(chunk_steps), 1)
        self.max_seq_len = engine.max_seq_len
        # the Pallas kernel needs a real TPU; CPU (tests, fallback serving)
        # runs the pure-jnp reference path — same math, one compiled program
        self.use_kernel = jax.default_backend() == "tpu"
        # -- tensor parallelism (docs/SHARDING.md) -----------------------
        # tp > 1 serves this model sharded over a tp mesh axis: weights
        # as head-major column slices, KV pages by kv head, every
        # control-state array replicated — streams stay bit-identical to
        # tp=1 (tests/test_tp.py). PagedUnsupported here routes the worker's
        # hosting seam to its static fallback, same as any other refusal.
        self.tensor_parallel = max(int(tensor_parallel or 1), 1)
        self._tp_mesh = None
        self._tp_step = None
        self._tp_flat_step = None
        if self.tensor_parallel > 1:
            reason = tp_serving_refusal(
                self.cfg, self.tensor_parallel,
                shared_pool=pool is not None,
                weight_quant=bool(getattr(engine, "quant", None)),
            )
            if reason is not None:
                raise PagedUnsupported(reason)
            self._tp_mesh = serving_mesh(self.tensor_parallel)
        # -- co-hosting (docs/SERVING.md "Co-hosting multiple models") ---
        # with a shared pool the physical page arrays live in the pool
        # (one set for every tenant); this engine keeps only its OWN
        # block tables + lengths, and `self.cache` is a property view
        # stitching the two — every `self.cache = step(...)` writes the
        # donated arrays back so the next tenant's step reads them
        self.pool = pool
        self.model_id = str(model_id or "default")
        if pool is not None:
            n_pp = pages_needed(self.max_seq_len, self.page_size)
            self._bt = jnp.zeros((self.max_slots, n_pp), jnp.int32)
            self._lengths = jnp.zeros((self.max_slots,), jnp.int32)
            # the actual pool.attach is the LAST statement of __init__:
            # attaching here and then failing later (device OOM on the
            # per-slot buffers, a bad knob) would wedge the tenant id on
            # the pool — every rebuild for the job would refuse with
            # "already attached" and the empty pool could never GC
            self.alloc = None
        else:
            def new_cache():
                if self._latent:
                    return LatentPagedCache.init(
                        self.cfg, self.max_slots, page_size=self.page_size,
                        max_len=self.max_seq_len, dtype=engine.cache_dtype,
                        prefill_chunk=int(prefill_chunk),
                    )
                return PagedKVCache.init(
                    self.cfg, self.max_slots, page_size=self.page_size,
                    max_len=self.max_seq_len, dtype=engine.cache_dtype,
                    kv_quant=kv_quant,
                )

            if self._tp_mesh is None:
                self.cache = new_cache()
            else:
                # each chip zeroes its own kv heads' pages: the whole pool
                # never sits on device 0 beside a model sized to need the
                # mesh. Outputs carry the step's cache specs, so the
                # donated cache keeps its sharding from the first chunk on.
                self.cache = jax.jit(new_cache, out_shardings=jax.tree.map(
                    lambda s: NamedSharding(self._tp_mesh, s),
                    tp_cache_specs(kv_quant != "none"),
                ))()
            self.alloc = PageAllocator(self.cache.n_pages)
        # chunked prefill: the prompt suffix beyond any cache hit prefills
        # in fixed-shape grants of the packed [slots, chunk] block, so a
        # long admission never stalls running slots at all
        self.prefill_chunk = min(int(prefill_chunk), self.max_seq_len)
        self.prefix = PrefixCache(self.page_size) if prefix_cache else None
        # -- a model with recurrent layers: the snapshot pool -------------
        # A prefix hit is only as good as the nearest state snapshot at or
        # below it: the slot restores that and prefills the rest again. A
        # state exists only where a chunk of the ragged pass ended, so a
        # prefill's grants stop at every multiple of ``snap_stride`` (the
        # constructor's ``state_snapshot_stride``, sized from the context
        # when 0; ``state_snapshots`` places, 0 = one a stride plus two a
        # slot: both are sizes a test sets, not deployment options) and at
        # the last page edge under its prompt's end (what the trie inserts
        # at release), and a snapshot is taken at each stop. Snapshots
        # belong to trie nodes and leave with them; when the pool is full
        # the one whose NODE was matched longest ago goes: every admission
        # walks the shared document's nodes, so its snapshots stay while
        # those of sessions that ended go (by the snapshots' own last use
        # the document's went first, restored only by first turns: eight
        # new sessions then prefilled 32,768 tokens each, PERF.md PR 42).
        self.snap_stride = 0
        self._snaps = None
        self._snap_free: list[int] = []
        self._snap_nodes: dict[int, object] = {}  # place -> trie node
        if self._stateful and self.prefix is not None:
            # 32 prefill chunks, or an eighth of the context where that
            # is less (a short context still gets its eight)
            # ... a tail is 74 KB at the published sizes where a state is
            # 2 MB and a window 12.6: a snapshot wherever a chunk ends
            chunk = self.prefill_chunk
            stride = int(state_snapshot_stride) or (
                chunk if self._tail else min(
                    32 * chunk,
                    max(self.max_seq_len // 8 // chunk, 1) * chunk))
            self.snap_stride = -(-stride // self.page_size) * self.page_size
            # ... a window snapshot is 12.6 MB at the published sizes
            # where a state is 2: one a stride and three for two slots
            n = int(state_snapshots) or (
                self.max_seq_len // self.snap_stride + (
                    3 * self.max_slots // 2 if self._ring
                    else 2 * self.max_slots))
            if self._ring:
                self._snaps = window_snapshot_pool(
                    self.cache, n, self.cfg.ring_window)
            else:
                self._snaps = snapshot_pool(self.cache, n)
            self._snap_free = list(range(n))
            self.prefix.on_drop = self._drop_snapshot
        # -- tiered prefix cache (docs/SERVING.md "Tiered prefix cache") -
        # host_tier_pages > 0 arms the host-RAM tier: refcount-0 pages
        # the trie evicts DEMOTE there (PrefixCache.spill) instead of
        # being destroyed, and admission PROMOTES host-resident chains
        # back into HBM — one existing scatter_page dispatch per page,
        # zero new compiled programs
        self.host_tier = None
        if int(host_tier_pages) > 0 and self.prefix is not None:
            self.host_tier = HostPagePool(
                int(host_tier_pages), self.page_size
            )
            self.prefix.spill = self._demote_page
        # rung 3 of the admission ladder: an optional fleet-layer hook
        # ``(chain_tokens, limit, n_local_pages) -> blob | None`` that
        # fetches the prefix pages from a sibling replica (the prefix
        # map picks one by digest coverage, fleet/prefixmap.py); the
        # returned blob feeds stage_prefix. Any failure inside the hook
        # degrades to local prefill — never an admission error.
        self.fetch_prefix = None
        # device pages transiently pinned by an in-progress tier
        # transfer (allocated, being byte-filled, not yet trie-resident)
        # — the host_tier term of the page-conservation equation, so the
        # invariant stays checkable mid-promote/mid-pull
        self._tier_pinned: list[int] = []
        # host-tier analogue of _prefix_digest: driver-refreshed swap
        # copy of HostPagePool.digest() for the fleet prefix map
        self._host_digest: dict = {}
        self._host_digest_version = -1
        # fleet-router cache-affinity digest (docs/SERVING.md "Fleet
        # serving"): a compact {chain_hash: covered_tokens} view of the
        # resident trie, rebuilt by the DRIVER at chunk boundaries only
        # when trie membership changed (PrefixCache.version) — readers
        # (serving_snapshot, /stats, the GENERATE_RESP snapshot) see an
        # atomically-swapped plain dict, never the live trie
        self._prefix_digest: dict = {}
        self._digest_version = -1
        # optional TOTAL prefill tokens per unified step shared across
        # mid-prefill slots (0 = each slot gets a full chunk row): bounds
        # the per-step prefill compute on TPU where the kernel's cost is
        # ragged (follows n_valid), trading admission latency for an even
        # tighter inter-token bound
        self.prefill_budget = int(prefill_budget)
        # -- speculative decoding (docs/SERVING.md) ----------------------
        # spec_width is the step program's STATIC verify-row count: the
        # same compiled ragged_step (one a width of the ladder below)
        # whether speculation is on or off (per-slot draft lengths are
        # data — spec/non-spec request mixes never recompile). Draft
        # rows ride the packed block's
        # columns, so the width caps at the chunk row (prefill_chunk).
        self.spec_decode = bool(spec_decode)
        self.spec_draft = max(0, min(int(spec_draft), self.prefill_chunk - 1))
        self.spec_width = 1 + (self.spec_draft if self.spec_decode else 0)
        # the packed block's width follows the chunk's longest grant, from
        # a ladder of at most two fixed here: the smallest whole number of
        # pages that holds the verify rows (a chunk in which nobody
        # prefills: a row a slot and its drafts), and prefill_chunk. A
        # width is a shape of the step program: one program a width (and
        # the flat rung below: ``rungs`` is the whole compile set). It
        # collapses to one
        # where the narrow width would not be the smaller, and for a
        # patterned model: its step is a program of several layer bodies
        # a pass (dots3's: 1.9 + 1.8 + 5.5 s to trace, lower and fetch a
        # second one), and with the ladder the dots3 cell's set-up read
        # 4.8 s (10%) more where a tenth of its chunks packed narrow
        # and no end-to-end number moved (PERF.md section 6, PR 35)
        narrow = -(-self.spec_width // self.page_size) * self.page_size
        self.block_widths = (
            (narrow, self.prefill_chunk)
            if narrow < self.prefill_chunk and not self._latent
            else (self.prefill_chunk,)
        )
        # the prefill_chunk-wide pass has three shapes, and the engine
        # picks by what it sees of the model's layers (``flat_rows``):
        # - ladder and static rung (a pass of ONE layer body: dense,
        #   DeepSeek-V2): two programs, the full one computes the block's
        #   rows where they lie, the flat rung the chunk's live rows as
        #   one list of flat_rows (paged.FlatRows; a function of the
        #   shapes alone: a full grant beside every slot's decode row and
        #   drafts, or a quarter of the block; 0 where the block is no
        #   larger). A chunk takes the rung when its rows fit
        #   (``_block_width``);
        # - tiled (layer bodies of more than one kind, all of them
        #   engine/latent.py's own: dots3, Laguna, LFM2): ONE program,
        #   whose row list holds the whole block (``tiled_rows``) and
        #   whose position-wise work runs over row tiles as far as the
        #   chunk's live rows reach, a trip count that is data
        #   (``FlatRows.by_tile``). A second wide program of several
        #   bodies is a second set-up: built behind the first requests
        #   it cost dots3's set-up 7.5-10.9 s of 45 and laguna's 3.0-7.8
        #   of 43 (my chip runs, PR 45; PERF.md section 6);
        # - full (a model of sparse / lightning layers, engine/sala.py's
        #   loop over runs of one kind): one program over the block as it
        #   lies: its second program takes 22 s to lower and 1.4 GB
        #   beside 14.1, and its pass is given no bound yet (ROADMAP
        #   S5(e)).
        # The step serves the static rung for every kind of layer all
        # the same (tests/test_flat_rung.py), for when it costs less
        kinds = set(self.cfg.layer_kinds)
        if len(kinds) <= 1:
            self.flat_rows = flat_rung_rows(
                self.max_slots, self.prefill_chunk, self.spec_width)
        elif is_sala(self.cfg):
            self.flat_rows = 0
        else:
            self.flat_rows = tiled_rows(self.max_slots, self.prefill_chunk)
        # a width is packed only once its program is built. build_steps
        # (what a server calls before traffic) leaves the narrow program's
        # compile running on a thread, here, while the wide one serves;
        # an engine nobody called it on builds each width at its first
        # call, like any jitted function
        self._build: Future | None = None
        # the step programs' build, counted where it happens (the gauges
        # ``step_build_ms`` / ``step_build_waited_ms`` of
        # ``serving_snapshot``): seconds spent tracing, lowering and
        # compiling or fetching every step program this engine built, on
        # whatever thread, and seconds a serving path spent inside that
        # work or waiting for it. Written only where a program is built
        # or waited for (``build_steps``, ``_join_build``, the first call
        # at a width: ``_note_first_call``); ``_unbuilt`` empties there
        # and a chunk's path reads nothing else of this
        self._step_build_s = 0.0
        self._step_build_waited_s = 0.0
        self._unbuilt = set(self.rungs)
        # optional TOTAL draft tokens per step shared across speculating
        # slots (0 = each gets a full draft): bounds the extra verify
        # compute like prefill_budget bounds prefill compute — and since
        # draft rows live in DECODE slots' rows, drafting can never eat
        # a co-resident prefill's grant either way
        self.spec_budget = int(spec_budget)
        self._spec_phase = 0  # round-robin origin for a draft budget
        if self._tp_mesh is not None:
            # shard weights + KV pages onto the mesh and build THE
            # tensor-parallel chunk program. publish_weights re-places
            # staged trees onto these committed leaf shardings, so the
            # serve-and-train hot-swap keeps the layout with no extra
            # seam. Donated outputs mirror the input specs — the cache
            # keeps its sharding across chunks, steady-state.
            # (a worker that loads for this engine makes the weights in
            # this layout, ml/worker.py::_load_stage: the put is then no
            # copy, and the job keeps ONE copy a chip)
            engine.params = jax.tree.map(
                lambda x, s: jax.device_put(
                    x, NamedSharding(self._tp_mesh, s)
                ),
                engine.params, tp_partition_specs(self.cfg),
            )
            tp_step = partial(
                make_tp_ragged_step, self._tp_mesh, self.cfg,
                n_steps=self.chunk_steps, spec_width=self.spec_width,
                kernel=self.use_kernel,
                tp_quant=bool(self.cfg.collective_quant),
            )
            self._tp_step = tp_step()
            if self.flat_rows:  # the flat rung is a program of its own
                self._tp_flat_step = tp_step(flat_rows=self.flat_rows)
            self._tp_gather = tp_gather_costs(
                self.cfg, self.tensor_parallel,
                bool(self.cfg.collective_quant),
            )
        # weights resident per device, fullest and emptiest: what a
        # deployment sizes slots and context against (one copy a chip
        # under tp, tests/test_tp_load.py)
        self.weights_bytes_device = list(device_bytes(engine.params).values())
        self._prefilling: dict[int, ContinuousRequest] = {}
        # -- live slot migration (docs/FAILURE_MODEL.md) -----------------
        # slots frozen for export: excluded from stepping, their pages
        # counted IN TRANSIT by page_accounting until commit/abort
        self._frozen: set[int] = set()
        # staged inbound adoptions: mig_id -> {pages, nodes, chain,
        # length, last_tok, prefill_target, t}. Pages are allocated and
        # byte-filled at staging (MIGRATE put), so the later attach only
        # binds a slot; idempotent by mig_id (wire dups are no-ops).
        self._migrations: dict[str, dict] = {}
        # staged tickets whose client never attaches (it died mid-drain)
        # are garbage-collected after this many seconds so their pages
        # can't leak; close() frees the rest before the conservation check
        self.migration_ttl_s = MIGRATION_TTL_S
        self.drain_state = "serving"  # "serving" | "draining"
        # -- disaggregated prefill/decode (docs/SERVING.md) --------------
        # the drain fence GENERALIZED into steady-state handoff: a
        # handoff-armed (prefill-pool) engine is always "draining" its
        # completed prefills — each opted-in slot freezes at the
        # prefill→decode boundary and lands in _handoff_ready for the
        # driver to ship — but, unlike begin_drain, the admission path
        # stays OPEN the whole time: new requests keep admitting and
        # prefilling while earlier slots are frozen in transit
        self.handoff_after_prefill = bool(handoff_after_prefill)
        self.worker_role = str(worker_role or "mixed")
        self._handoff_ready: list[int] = []
        # rotates the budgeted packing's round-robin origin so a
        # prefill_budget smaller than the number of concurrent
        # admissions never starves the tail slots
        self._pack_phase = 0
        self._lock = threading.Lock()
        # the policy layer owning the queued side of the lifecycle:
        # priority classes, aging, preemption decisions, backpressure
        # (engine/scheduler.py) — replaces the old FIFO deque. Client
        # threads (submit/admission_check/serving_snapshot) race the
        # driver on it; every touch goes through the engine lock.
        self.default_priority = normalize_priority(default_priority)
        # -- observability (core/trace.py, core/metrics.py) --------------
        # spans are recorded host-side, ONLY at boundaries this engine
        # already synchronizes (admission, the per-chunk drain, the
        # migration verbs) and ONLY for requests carrying a trace id —
        # zero compiled programs, zero extra device syncs, near-zero cost
        # when tracing is off
        self.tracer = get_tracer()
        self.trace_site = str(trace_site)
        self.recorder = FlightRecorder(FLIGHT_CAPACITY)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._stat = {
            key: self.metrics.counter(name, help)
            for key, name, help in _ENGINE_COUNTERS
        }
        self.metrics.gauge(
            "tlink_engine_kv_pages_free", "free KV pages",
            fn=lambda: self.alloc.n_free,
        )
        self.metrics.gauge(
            "tlink_engine_live_slots", "slots decoding or mid-prefill",
            fn=lambda: self.live_slots,
        )
        self.metrics.gauge(
            "tlink_engine_pages_in_transit",
            "pages held by in-flight migrations (either side)",
            fn=lambda: self._pages_in_transit(),
        )
        # tiered prefix cache: host-tier occupancy + per-fetch latency.
        # DEFAULT_BUCKETS are seconds-scale; a promote is a host→device
        # put (sub-ms to a few ms on real pages) and a fleet pull adds a
        # wire round trip — hence the ms-scale bucket ladder
        self.metrics.gauge(
            "tlink_engine_host_tier_resident_pages",
            "prefix pages resident in the host-RAM tier",
            fn=lambda: (
                self.host_tier.n_resident if self.host_tier else 0
            ),
        )
        self._tier_hist = self.metrics.histogram(
            "tlink_engine_tier_fetch_ms",
            "host-tier promote / fleet prefix pull latency per page (ms)",
            buckets=(0.05, 0.2, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                     100.0, 250.0, 1000.0),
        )
        # throughput-mode discovery for operators/routers: which modes a
        # replica actually runs rides /metrics (and /healthz) alongside
        # kv_quant — see ml/validator.py::health_snapshot
        self.metrics.gauge(
            "tlink_engine_spec_decode",
            "1 when speculative decoding is enabled on this engine",
            fn=lambda: int(self.spec_decode),
        )
        # -- live weight publish / serve-and-train (docs/TRAINING.md) ----
        # the model version this engine serves: starts at 1 (the loaded
        # checkpoint) and bumps on every publish_weights — the fleet
        # router reads it off /healthz//metrics to see which replicas
        # have picked a new version up
        self.weights_version = 1
        self._train_step_ms = 0.0  # last background train step (gauge)
        self._train_mfu = 0.0
        self.metrics.gauge(
            "tlink_engine_weights_version",
            "model weights version this engine serves (bumps per publish)",
            fn=lambda: self.weights_version,
        )
        self.metrics.gauge(
            "tlink_engine_train_step_ms",
            "last background train step wall time (ms)",
            fn=lambda: self._train_step_ms,
        )
        self.metrics.gauge(
            "tlink_engine_train_mfu",
            "model FLOPs utilization of the last background train step",
            fn=lambda: self._train_mfu,
        )
        # monotonic stamp of the last dispatched chunk's exit while the
        # engine still had work: the next chunk's "between" starts there
        self._chunk_exit_t: float | None = None
        # the flight-recorder step of the chunk in progress
        self._chunk_step = 0
        # what the settle stage left for the stream stage: rid -> (request,
        # its last n tokens not yet handed to stream_cb, whether they hold
        # its first token ever, whether on_finish follows them, the chunk
        # that made them). At most one entry a request; driver-thread only
        self._unstreamed: dict[int, tuple] = {}
        # intake ahead (docs/SERVING.md "The anatomy of a chunk"): what a
        # chunk's wait does for the next chunk while the device runs this
        # one. ``intake(result)`` yields, one by one, the requests that
        # arrive before ``result`` is ready, each as a callable that
        # submits it (the worker's: ml/worker.py::_intake); it blocks
        # between them and ends when ``result`` is ready or earlier. None:
        # the wait is the fetch alone.
        self.intake = intake
        # admissions an ahead round prepared and the next ``_admit`` will
        # publish: slot -> (request, rows replayed), in the order prepared.
        # The request holds its pages and its hit chain, the host's table
        # the slot's row; ``_slots`` / ``_prefilling`` do not know it yet
        self._prepared: dict[int, tuple] = {}
        # inside a chunk's intake: a request submitted now arrived while
        # the chunk ``recorder.next_step`` names was in flight
        self.taking_in = False
        # inside a chunk's wait, until the driver has seen the step's
        # result ready: the result's ``is_ready`` (``_seen_ready`` asks it),
        # and the monotonic stamp of the first True
        self._ready_poll = None
        self._ready_t: float | None = None
        # ... and where the driver's work began that ended there: the last
        # "not yet" in the stream stage (at first the wait's start), a take's
        # start, the stamp itself where the driver had been blocked
        self._unready_t = 0.0
        if pool is not None:
            # per-tenant pool occupancy: these render under the model's
            # label at /metrics (the registry-per-model grouping), which
            # is what makes quota pressure visible PER TENANT
            self.metrics.gauge(
                "tlink_engine_pool_quota",
                "this tenant's page quota on the shared pool",
                fn=lambda: self.alloc.quota,
            )
            self.metrics.gauge(
                "tlink_engine_pool_pages_used",
                "pages this tenant holds (slots + cached + in transit)",
                fn=lambda: self.alloc.used,
            )
            self.metrics.gauge(
                "tlink_engine_pool_pages_free",
                "free pages on the shared pool (all tenants)",
                fn=lambda: self.pool.alloc.n_free,
            )
        self.sched = RequestScheduler(  #: guarded by self._lock
            max_slots=self.max_slots,
            queue_cap=sched_queue_cap,
            aging_ticks=sched_aging_ticks,
            preemption=sched_preemption,
            policy=sched_policy,
            max_wait_s=sched_max_wait_s,
            metrics=self.metrics,
        )
        self._rid = itertools.count(1)
        self._slots: list[ContinuousRequest | None] = [None] * self.max_slots
        # host mirrors of per-slot decode state (device arrays are rebuilt
        # from these on admission/eviction — small, [S]-shaped)
        self._tok = np.zeros(self.max_slots, np.int32)
        self._seeds = np.zeros(self.max_slots, np.int32)
        self._steps = np.zeros(self.max_slots, np.int32)
        self._active = np.zeros(self.max_slots, bool)
        self._temp = np.zeros(self.max_slots, np.float32)
        self._topk = np.zeros(self.max_slots, np.int32)
        self._topp = np.ones(self.max_slots, np.float32)
        self._pres = np.zeros(self.max_slots, np.float32)
        self._freq = np.zeros(self.max_slots, np.float32)
        self._counts = jnp.zeros(
            (self.max_slots, self.cfg.vocab_size), jnp.int32
        )
        if self._tp_mesh is not None:
            # commit the histograms to the mesh (replicated) so the TP
            # step's donation keeps ONE steady-state program from the
            # first chunk on — rank-expanded spelling, the canonical
            # cache key the step's own outputs carry (TL101)
            self._counts = jax.device_put(
                self._counts,
                NamedSharding(
                    self._tp_mesh, P(*([None] * self._counts.ndim))
                ),
            )
        # What an admission and a retirement change on the device: the
        # host's copy of the block table and each slot's start length, and
        # which slots' rows (``_bind``) and histograms (``_reset``) the
        # next dispatched step program takes from its control buffer
        # before its ragged pass (``paged.pack_control``'s bind columns).
        # Driver-thread only
        self._bt_host = np.zeros(
            (self.max_slots, self.cache.pages_per_slot), np.int32
        )
        self._len0 = np.zeros(self.max_slots, np.int32)
        self._bind = np.zeros(self.max_slots, bool)
        self._reset = np.zeros(self.max_slots, bool)
        if pool is not None:
            # nothing fallible may follow: a registered-but-dead tenant
            # is unrecoverable without a worker restart (see above)
            self.alloc = pool.attach(
                self.model_id, self, quota=int(page_quota)
            )

    @property
    def cache(self) -> PagedKVCache:
        """This tenant's paged-cache view. Solo engines own the whole
        cache; a pool tenant stitches the SHARED physical page arrays
        (engine/paged.py::SharedPagePool.kv) to its own block tables and
        lengths — so N co-hosted engines read and write ONE page pool,
        and a step's donated arrays flow back through the setter for the
        next tenant's step to pick up (single driver thread across
        tenants, the pool's contract)."""
        if self.pool is None:
            return self._cache
        kv = self.pool.kv
        ks, vs = (kv[2], kv[3]) if len(kv) == 4 else (None, None)
        return PagedKVCache(
            k=kv[0], v=kv[1], block_tables=self._bt,
            lengths=self._lengths, k_scale=ks, v_scale=vs,
        )

    @cache.setter
    def cache(self, value: PagedKVCache) -> None:
        if self.pool is None:
            self._cache = value
            return
        self.pool.kv = (
            (value.k, value.v) if value.k_scale is None
            else (value.k, value.v, value.k_scale, value.v_scale)
        )
        self._bt = value.block_tables
        self._lengths = value.lengths

    @property
    def stats(self) -> dict:
        """Legacy serving-telemetry view: the exact, test-pinned key set
        the old ad-hoc counter dict exposed, now DERIVED from the typed
        registry (core/metrics.py) — /stats consumers see byte-compatible
        keys while /metrics reads the same counters as Prometheus
        series."""
        return {k: int(c.value) for k, c in self._stat.items()}

    def _count(self, key: str, n: int = 1) -> None:
        """Driver-thread counter bump (single-writer discipline)."""
        self._stat[key].inc(n)

    def _trace(self, req, name: str, dur_s: float | None = None,
               t0: float | None = None, **attrs) -> str:
        """Record one span for a traced request (no-op when the request
        carries no trace id — the disabled-mode fast path). ``t0`` is
        its start on the monotonic clock (left out: it ends now);
        ``parent`` follows ``ENGINE_SPAN_PARENT``, by ids minted ahead
        of the spans that end after what they caused. Returns the sid."""
        if req is None or not req.trace_id:
            return ""
        sids = req.span_sids
        if sids is None:
            sids = req.span_sids = {}
        cause = ENGINE_SPAN_PARENT.get(name, "")
        if cause in SPANS_NAMED_AHEAD and cause not in sids:
            sids[cause] = self.tracer.new_sid()
        ahead = name in SPANS_NAMED_AHEAD
        sid = self.tracer.record(
            req.trace_id, name, site=self.trace_site, dur_s=dur_s, t0=t0,
            parent=sids.get(cause, ""),
            # an id named ahead is used once: a second round (a
            # preempted request's) names a new one
            sid=sids.pop(name, "") if ahead else "", **attrs,
        )
        if not ahead:
            sids[name] = sid  # what it causes ends later and finds it here
        return sid

    # -- client side -----------------------------------------------------
    def submit(
        self,
        prompt: list[int],
        *,
        max_new_tokens: int,
        sampling: SamplingParams | None = None,
        eos_ids=(),
        seed: int = 0,
        start_step: int = 0,
        priority: str | None = None,
        stream_cb: Callable[[int], bool | None] | None = None,
        on_finish: Callable[[ContinuousRequest], None] | None = None,
        adopt: str | None = None,
        trace_id: str | None = None,
        trace_parent: str = "",
        speculative: bool = False,
        handoff: bool = False,
    ) -> ContinuousRequest:
        """Queue a request; the scheduler decides when (and at whose
        expense) it joins the slot batch. ``start_step`` > 0 resumes a
        recovered request's key chain (prompt then carries the original
        prompt + tokens already delivered). ``priority`` is one of the
        scheduler's classes (None → the engine default); past the class
        queue cap the request fails immediately with
        :class:`SchedulerOverloaded` on ``req.error`` instead of queueing
        forever — the API layer's 429 backstop. ``adopt`` names a staged
        migration ticket (:meth:`stage_migration`): admission binds the
        shipped KV pages instead of prefilling, falling back to the
        normal (re-)prefill path when the ticket is missing or stale.
        ``speculative`` opts the request into draft/verify decoding when
        the engine runs with ``spec_decode`` on (a pure speed hint: the
        emitted stream is bit-identical either way). ``handoff`` marks
        the request for prefill→decode handoff on a handoff-armed
        engine: its prefill stops one token short, the slot freezes at
        the boundary, and the driver ships it to a decode-pool worker
        (no effect unless ``handoff_after_prefill`` is set; 1-token
        prompts are exempt — there is nothing to prefill ahead of the
        first draw, so shipping zero pages would cost more than it
        saves)."""
        req = ContinuousRequest(
            rid=next(self._rid),
            prompt=[int(t) for t in prompt],
            budget=int(max_new_tokens),
            sampling=sampling or SamplingParams.make(),
            eos=frozenset(int(e) for e in eos_ids),
            seed=int(seed),
            start_step=int(start_step),
            priority=normalize_priority(
                priority if priority else self.default_priority
            ),
            stream_cb=stream_cb,
            on_finish=on_finish,
            adopt=adopt,
            trace_id=str(trace_id or ""),
            span_sids={"submit": trace_parent} if trace_parent else None,
            speculative=bool(speculative) and self.spec_decode,
            handoff=(
                bool(handoff) and self.handoff_after_prefill
                and len(prompt) > 1
            ),
        )
        req.submit_t = time.monotonic()
        overload: SchedulerOverloaded | None = None
        with self._lock:
            # under the lock: a client thread may submit beside the driver
            self._count("submitted")
            if self.taking_in:
                self._count("submitted_ahead")
            try:
                self.sched.push(req)
            except SchedulerOverloaded as e:
                overload = e
        if overload is not None:
            self._trace(
                req, "rejected", priority=overload.priority,
                queue_depth=overload.queue_depth,
                retry_after=overload.retry_after,
            )
            # a rejected resume must release its staged-adoption ticket —
            # otherwise the shipped pages stay pinned in-transit for the
            # full TTL on exactly the engine absorbing a drain. submit()
            # may run on a client thread, so the pages are NOT freed here
            # (the allocator/trie are driver-thread state): the ticket is
            # expired in place and the driver's next GC sweep frees it.
            self._expire_ticket(req)
            req.error = overload
            self._finish(req, finished=False)
        return req

    def admission_check(self, priority: str | None = None, n: int = 1):
        """The batcher/API backpressure probe: None = would admit, else a
        rejection record (queue depth, cap, retry-after estimate)."""
        with self._lock:
            return self.sched.admission_check(
                priority if priority else self.default_priority, n
            )

    def router_snapshot(self) -> dict:
        """Placement-scoring view for the fleet router (docs/SERVING.md
        "Fleet serving"): headroom, per-class queue depth, service EWMA,
        role/drain state, and the driver-refreshed prefix digest. Cheap
        by contract — attribute reads plus one pass over the host queue
        under the engine lock; NO device work, NO trie walk."""
        with self._lock:
            depth = {c: self.sched.depth(c) for c in PRIORITY_CLASSES}
            ewma = self.sched._service_ewma
        return {
            "draining": self.drain_state != "serving",
            "worker_role": self.worker_role,
            "max_slots": self.max_slots,
            "slots_free": len(self._free_slots()),
            "kv_pages_free": self.alloc.n_free,
            "kv_pages_total": self.cache.n_pages - 1,
            "service_ewma_s": float(ewma),
            "queue_depth": depth,
            "prefix_digest": self._prefix_digest,
            # host-tier residency rides the same heartbeat: the router's
            # affinity scoring and the fleet prefix map both read it —
            # a replica whose HBM evicted a hot prefix but still holds
            # it in host RAM remains a (cheaper-than-prefill) target
            "host_tier_digest": self._host_digest,
        }

    def has_work(self) -> bool:
        with self._lock:
            return (
                len(self.sched) > 0
                or bool(self._active.any())
                or bool(self._prefilling)
                or bool(self._prepared)
            )

    def _prepared_requests(self) -> list:
        """The requests of the prepared admissions (a copy: other threads
        read snapshots while the driver prepares and commits)."""
        return [r for r, _ in list(self._prepared.values())]

    def _free_slots(self) -> list[int]:
        """Slots that hold no request and no prepared admission."""
        return [
            s for s, r in enumerate(self._slots)
            if r is None and s not in self._prepared
        ]

    @property
    def live_slots(self) -> int:
        """Slots holding a live request — decoding or mid-prefill."""
        return int(self._active.sum()) + len(self._prefilling)

    def jit_cache_sizes(self) -> dict:
        """Compiled-program counts of the slot-batched hot loop — the
        "no unbounded compile set" guarantee, asserted by the engine
        tests: these stay fixed no matter the request mix. The entire
        serving hot loop is ONE top-level step function (``ragged_step``;
        prompt length, cache-hit offset, prefill/decode mix, budget
        split AND the kv_quant storage mode are all DATA or trace-time
        constants to it), compiled once a rung of ``rungs`` (at
        most three: the packed block's shape and the flat rung's row count
        key the jit cache), plus the
        COW ``copy_page`` (a slot's bind and clear ride the step's
        control buffer and are no program). ``decode_step`` /
        ``sample_rows`` / ``row_keys`` are traced INSIDE the step
        program — never dispatched from the host loop. (The legacy
        two-program pair ``decode_chunk``/``prefill_chunk`` was retired
        with its fallback flag.)"""
        return {
            "decode_step": paged_decode_step._cache_size(),
            "sample_rows": _sample_rows._cache_size(),
            "row_keys": _row_keys._cache_size(),
            "ragged_step": paged_ragged_step._cache_size(),
            # the sharded analogue: one ragged program per shard degree
            # and width (the factory builds a plain/quant-cache pair,
            # only the arity matching this engine's cache ever compiles)
            "tp_ragged_step": (
                self._step_programs() if self._tp_step is not None else 0
            ),
            "copy_page": copy_page._cache_size(),
            # migration export/import move ONE page per dispatch (fixed
            # shape), so live slot migration adds exactly these two keys
            # and can never grow the serving-step program set
            "gather_page": gather_page._cache_size(),
            "scatter_page": scatter_page._cache_size(),
        }

    # -- admission / eviction -------------------------------------------
    def _finish(self, req: ContinuousRequest, *, finished: bool) -> None:
        req.finished = finished
        cb = req.on_finish
        req.done.set()
        if cb is not None:
            cb(req)

    # tlint: hot-path
    def flush_stream(self, req: ContinuousRequest | None = None, *,
                     in_flight: bool = False) -> None:
        """The stream stage: hand what the settle stage left pending to
        the requests' callbacks, in order: the ``first_token`` spans where
        a first token leaves, ``stream_cb`` a token, ``on_finish`` after a
        finished request's last one. Every first token goes first, then
        the next token of every other stream (one that ends with this
        chunk too), then the rest of the entries that go on in slot
        order, then the rest of the entries that end a request, each
        with its ``on_finish``. A first token does not queue behind
        tokens whose readers already have a stream going, and no
        reader's stall lasts while another reader's whole chunk is
        handed on ahead of it: handed on entry by entry, the gap that
        ends with a stream's next token was 4-5 ms longer a slot of slot
        order (eight ``stream_cb`` calls an entry), 27 to 50 ms behind a
        wide chunk on four chips and longest of all for the entry that
        ended a request; eight clients that each keep their slot then
        read a longest gap by their slot, and the median over requests
        fell between two slots' values, another way every run (PERF.md
        section 6, PR 43). Only ONE token of an entry goes ahead (an
        answer that ends with its first chunk leaves whole): the tokens
        that came with it keep their slot's place, because a whole entry
        that changes place between two stages moves every reader behind
        it by an entry and its own reader's next gap by the way back
        (the longest gap a reader sees; PERF.md section 6, PR 36).
        What is left of an entry that ends a request goes last: its
        ``on_finish`` is the one callback that blocks for long (on a
        worker the done-marker's round trip and ``GENERATE_RESP``, 15-20
        ms), so there it holds back nobody's tokens; and a client that
        waits for its answer to send the next request gets it where the
        engine goes on to its sync. Answered at the START of the stage,
        that client's next request came back about a narrow chunk later
        on four chips once such a chunk was 60 ms long, made the next
        admission or missed it by a millisecond, and eight clients
        asking equal answers drifted into step, a different way every
        run; answered at the end it misses that admission every time and
        meets the one after (PERF.md section 6, PR 40). A request's own
        tokens keep their order. ``step_chunk`` runs it behind its
        dispatch (``in_flight``: the device executes the next chunk
        meanwhile), or at once when no step follows. Anything else that
        answers for a request calls it first, for that request (``req``:
        its entry leaves whole) or for all: ``close``, ``begin_drain``,
        ``freeze_slot`` and every teardown that is not a finish
        (``_teardown_slot``: preemption, shed, handoff commit).
        Driver-thread only."""
        pend = self._unstreamed
        if not pend or (req is not None and req.rid not in pend):
            return
        t0 = time.monotonic()
        n = 0
        try:
            with jax.profiler.TraceAnnotation("tlink:stream"):
                order = (req.rid,) if req is not None else tuple(pend)
                if req is None:
                    led = set()
                    for rid in order:  # first tokens
                        entry = pend.get(rid)
                        if entry is None or not entry[2]:
                            continue
                        r, k, _first, finish, step = entry
                        head = k > 1 and not finish
                        if head:  # the rest stays pending, in its place
                            pend[rid] = (r, k - 1, False, False, step)
                        else:  # one token, or a whole answer: all of it
                            del pend[rid]
                        led.add(rid)
                        n += self._stream_one(
                            r, k, True, finish, step, in_flight, head=head,
                        )
                    for rid in order:  # every other stream's next token
                        entry = pend.get(rid)
                        if entry is None or entry[1] < 2 or rid in led:
                            continue
                        r, k, _first, finish, step = entry
                        pend[rid] = (r, k - 1, False, finish, step)
                        n += self._stream_one(
                            r, k, False, finish, step, in_flight, head=True,
                        )
                    for rid in order:  # what goes on, in slot order
                        entry = pend.get(rid)
                        if entry is not None and not entry[3]:
                            del pend[rid]
                            n += self._stream_one(*entry, in_flight)
                for rid in order:  # what ends a request
                    entry = pend.pop(rid, None)
                    if entry is not None:
                        n += self._stream_one(*entry, in_flight)
        finally:
            self._count(
                "chunk_us_stream", int((time.monotonic() - t0) * 1e6 + 0.5)
            )
            self._count(
                "stream_tokens_overlapped" if in_flight
                else "stream_tokens_flushed", n,
            )

    def _stream_one(self, req: ContinuousRequest, n: int, first: bool,
                    finish: bool, step: int, in_flight: bool, *,
                    head: bool = False) -> int:
        """One request's pending tokens (the last ``n`` of ``req.tokens``)
        to its callbacks, or with ``head`` the first of them alone (the
        caller keeps the rest pending); returns how many left. A truthy
        ``stream_cb`` return (a confirmed stop) ends the stream there:
        ``req.tokens`` is cut back to what was streamed, so at
        ``on_finish`` it is exactly the sequence the callback was given.
        ``finish`` with ``head``: the entry ends its request, which is
        finished here only if its reader stops at this token."""
        base = len(req.tokens) - n
        sent, cancel = 1 if head else n, False
        try:
            if first:
                # first token EVER for this request (a resumed-after-
                # preempt request already has tokens, so TTFT is recorded
                # once), stamped when it really leaves. Under the lock:
                # serving_snapshot() iterates the TTFT sample deque from
                # other threads (/stats), and a deque append racing that
                # iteration raises.
                now = time.monotonic()
                with self._lock:
                    self.sched.note_first_token(req, now - req.submit_t)
                if req.trace_id:
                    # the TTFT decomposition's last leg: prefill completed
                    # → first token delivered (contiguous with the
                    # queue_wait and prefill spans by construction, so the
                    # three parts sum to the first_token span's TTFT)
                    t_pf = req.prefill_done_t or req.admit_t or req.submit_t
                    self._trace(req, "first_decode", dur_s=now - t_pf, t0=t_pf)
                    sid = self._trace(
                        req, "first_token", dur_s=now - req.submit_t,
                        t0=req.submit_t, chunk=step,
                    )
                    # where first_token ends the way out starts: left on
                    # this thread for the stream callback, which sends it
                    # with the stream's first frame (``token_out``)
                    first_token_stamp.set(
                        {"t": now, "host": HOST, "parent": sid}
                    )
            cb = req.stream_cb
            try:
                if cb is not None:
                    for i in range(sent):
                        if cb(req.tokens[base + i]):
                            sent, cancel = i + 1, True
                            del req.tokens[base + sent:]
                            break
            finally:
                if first and req.trace_id:
                    first_token_stamp.set(None)
            if finish:
                # an ending entry's token sent ahead ends nothing, unless
                # its reader stops there: the rest goes with the cut
                if cancel and head:
                    self._unstreamed.pop(req.rid, None)
                if cancel or not head:
                    self._finish(req, finished=True)
            elif cancel:
                # what a first token left pending went with the cut
                self._unstreamed.pop(req.rid, None)
                req.cancelled = True
                if not in_flight:
                    self._evict(req.slot)
                # else the running chunk holds the slot: its settle evicts
        except BaseException as e:
            if head:
                # a callback that raises loses its own stream
                self._unstreamed.pop(req.rid, None)
            if finish and not req.done.is_set():
                # its slot is gone: nobody else would answer for it
                req.error = e
                self._finish(req, finished=False)
            raise
        if in_flight:
            # (entries follow one another: this one began at the last ask)
            self._seen_ready(self._unready_t)
        return sent

    def _admit_one(self, req: ContinuousRequest, slot: int, *,
                   ahead: bool = False) -> bool:
        """Place ``req`` into ``slot`` (``ahead``: prepare it for the slot,
        ``_admit_ahead``). Returns False when no pages are
        free (request stays queued). A preempted request re-admits here
        with ``req.tokens`` non-empty: the prefill sequence is prompt +
        emitted (the crash-recovery shape, so resumption is bit-exact)
        and the budget/step accounting stays cumulative."""
        seq = req.prompt + req.tokens
        if len(seq) > self.max_seq_len:
            # surface the same diagnosable error the static path raises
            # from prefill — never a mysterious empty completion
            req.error = ValueError(
                f"prompt length {len(seq)} exceeds max_seq_len "
                f"{self.max_seq_len}"
            )
            self._drop_ticket(req)
            self._finish(req, finished=False)
            return True
        room = self.max_seq_len - len(seq)
        remaining = req.budget - len(req.tokens)
        eff = min(remaining, room)
        if eff <= 0:
            # zero room: report finished with an empty completion, matching
            # the static paths' contract
            self._drop_ticket(req)
            self._finish(req, finished=True)
            return True
        req.budget = len(req.tokens) + eff
        total = min(len(seq) + eff, self.max_seq_len)
        if req.adopt is not None:
            ticket = self._migrations.get(req.adopt)
            if ticket is not None and self._ticket_matches(ticket, seq):
                return self._admit_adopted(req, slot, total, ticket)
            # missing / stale / mismatched ticket: the request already
            # carries the full resume shape (prompt + delivered,
            # start_step), so the fallback ladder's next rung is simply
            # the crash-recovery re-prefill below
            self._drop_ticket(req)
        req.prefill_tokens = seq
        req.prefill_target = len(seq)
        return self._admit_paged(req, slot, total, ahead=ahead)

    def _alloc_pages(self, n: int) -> list[int] | None:
        """All-or-nothing page grab with eviction-on-demand: when the
        free-list is short, unreferenced cached prefixes are evicted
        LRU-leaf-first — but ONLY when eviction can actually cover the
        deficit. A request too big to fit even after a full cache wipe
        stays queued WITHOUT destroying the resident prefixes the other
        requests keep hitting. On a shared pool a further rung follows:
        OTHER tenants' cold resident prefixes reclaim to the shared
        free list (pool.reclaim_cache) — but only when this tenant's
        QUOTA has room, because a quota-dry tenant must pay with its
        own pages, never a neighbor's. With a chunk in flight (an ahead
        round: ``taking_in``) it evicts unreferenced leaves of its own
        trie (the chunk reads only pages its slots reference) unless an
        evicted page would be demoted, which fetches it; the other rungs
        are the edge's."""
        pages = self.alloc.alloc(n)
        if self.taking_in and self.host_tier is not None:
            return pages
        if pages is None and self.prefix is not None:
            deficit = n - self.alloc.n_free
            if deficit > 0 and self.prefix.n_evictable() >= deficit:
                self.alloc.free(self.prefix.evict(deficit))
                pages = self.alloc.alloc(n)
        if pages is None and self.pool is not None and not self.taking_in:
            quota_room = self.alloc.quota - self.alloc.used
            deficit = n - self.pool.alloc.n_free
            if n <= quota_room and 0 < deficit <= self.pool.reclaim_cache(
                deficit, self
            ):
                pages = self.alloc.alloc(n)
        return pages

    def _admit_paged(self, req: ContinuousRequest, slot: int,
                     total: int, *, ahead: bool = False) -> bool:
        """Chunked-prefill admission down the tiered-cache ladder
        (docs/SERVING.md "Tiered prefix cache"): (1) walk the HBM trie
        for the longest resident chain of full pages (zero prefill
        compute for the hit region); (2) extend it with host-tier
        promotes — demoted pages scattered back into fresh HBM pages;
        (3) on a still-short chain, pull the prefix from a sibling
        replica through the fleet hook; (4) copy-on-write the first
        divergent page when a cached sibling shares a partial token
        prefix; then allocate private pages for the rest and queue the
        non-hit suffix for chunked prefill. Every rung fails safe to
        the next — a dry allocator, a lost eviction race or a dead
        sibling just means more tokens prefill locally."""
        seq = req.prefill_tokens
        T = len(seq)
        hit_nodes: list = []
        cow = None
        replayed = 0
        if self.prefix is not None:
            # at least ONE real token must prefill so the final chunk
            # yields the last prompt position's logits for the first draw
            limit = T - 1
            hit_nodes = self.prefix.match(seq, limit)
            # pin the hit chain FIRST — the tier rungs below allocate
            # pages, and eviction-on-demand must not free the chain
            # we are standing on
            self.prefix.acquire(hit_nodes)
            req.cache_tier = "hbm" if hit_nodes else "none"
            if self.host_tier is not None:
                n0 = len(hit_nodes)
                hit_nodes = self._promote_chain(seq, limit, hit_nodes)
                if len(hit_nodes) > n0:
                    req.cache_tier = "host"
            if (
                self.fetch_prefix is not None
                and limit - len(hit_nodes) * self.page_size
                >= self.page_size
            ):
                n0 = len(hit_nodes)
                hit_nodes = self._pull_chain(seq, limit, hit_nodes)
                if len(hit_nodes) > n0:
                    req.cache_tier = "fleet"
            if self._stateful:
                # the hit ends at the nearest snapshot at or below the
                # match; what lies between is prefilled again (through
                # every layer: a recurrent layer's input is the output of
                # the layers before it), into pages of the slot's own
                keep = max((i + 1 for i, n in enumerate(hit_nodes)
                            if n.snap is not None), default=0)
                replayed = (len(hit_nodes) - keep) * self.page_size
                self.prefix.release(hit_nodes[keep:])
                hit_nodes = hit_nodes[:keep]
            else:
                cow = self.prefix.partial_match(hit_nodes, seq, limit)
            if cow is not None:
                self.prefix.acquire([cow[0]])
        n_hit = len(hit_nodes)
        pages = None
        if not (ahead and self._hit_may_grow(
                seq, n_hit * self.page_size + (cow[1] if cow else 0))):
            pages = self._alloc_pages(
                pages_needed(total, self.page_size) - n_hit)
        if pages is None:
            if self.prefix is not None:
                self.prefix.release(hit_nodes)
                if cow is not None:
                    self.prefix.release([cow[0]])
            return False
        hit_len = n_hit * self.page_size
        cow_released = False
        try:
            bt_row = np.zeros(self.cache.pages_per_slot, np.int32)
            bt_row[:n_hit] = [n.page for n in hit_nodes]
            bt_row[n_hit : n_hit + len(pages)] = pages
            if cow is not None:
                # the divergent page: duplicate the cached page into the
                # slot's first private page and credit the matched
                # positions (the call places its own scalars)
                src, n_match = cow
                self.cache = copy_page(
                    self.cache, np.int32(src.page), np.int32(pages[0])
                )
                self._count("admit_device_calls")
                hit_len += n_match
                self.prefix.stats["cow_copies"] += 1
                self.prefix.release([src])
                cow_released = True
            if self._stateful:
                self._admit_state(req, slot, hit_nodes, hit_len)
            self._bind_slot(slot, bt_row, hit_len)
        except BaseException:
            # a failed admission must not leak: return the private pages
            # and drop the pinned refs so close()'s conservation check
            # still holds on the error-cleanup path
            self.alloc.free(pages)
            if self.prefix is not None:
                self.prefix.release(hit_nodes)
                if cow is not None and not cow_released:
                    self.prefix.release([cow[0]])
            raise
        req.pages = pages
        req.shared_nodes = hit_nodes
        req.prefill_pos = hit_len
        if ahead:
            # prepared: the chunk in flight was packed without this slot,
            # and what reads the residents until it has settled (the
            # settle stage, the flight recorder, the benchmark's taps)
            # reads them as they were packed; the next ``_admit``
            # publishes (``_commit_prepared``)
            self._prepared[slot] = (req, replayed)
        else:
            self._publish(req, slot, replayed)
        return True

    def _hit_may_grow(self, seq: list, hit_len: int) -> bool:
        """Whether the chunk in flight may leave ``seq`` a longer hit than
        the ``hit_len`` positions the trie gives it now. A resident that
        retires in this chunk promotes its prefill's pages when the chunk
        settles (``_release_pages``), before the edge walks the trie: where
        one's prefill goes on as ``seq`` does, past ``hit_len`` and as far
        as the next position a hit could end at (any, by copy-on-write; a
        stateful model's next snapshot point), an ahead round leaves the
        request to the edge, which knows. Which resident retires is the
        device's to say, so every one counts."""
        if self.prefix is None:
            return False
        page, limit = self.page_size, len(seq) - 1
        for r in self._slots:
            if r is None:
                continue
            own = r.prefill_tokens
            if not self._stateful:
                h = hit_len + 1
            elif self.snap_stride:
                h = (hit_len // self.snap_stride + 1) * self.snap_stride
                final = (len(own) - 1) // page * page
                if final > hit_len:
                    h = min(h, final)
            else:
                return False  # no snapshot, no hit
            if (h <= min(limit, r.prefill_target // page * page)
                    and own[h - 1] == seq[h - 1] and own[:h] == seq[:h]):
                return True
        return False

    def _publish(self, req: ContinuousRequest, slot: int, replayed: int,
                 *, ahead: bool = False) -> None:
        """The end of an admission: ``slot`` holds ``req`` for everything
        that enumerates residents from here on, and the counters say so."""
        req.slot = slot
        self._slots[slot] = req
        self._prefilling[slot] = req
        # the completing step samples the first token IN-program, so the
        # slot's sampling state must be armed before its first packed block
        self._arm_slot(req, slot)
        hit_len = req.prefill_pos
        self._count("admitted")
        if ahead:
            self._count("admitted_ahead")
        self._count("prefill_tokens_skipped", hit_len)
        if self._stateful:
            self._count(f"{self._snap_counts}_admissions")
            if self.prefix is not None:
                self._count(f"{self._snap_counts}_rows_replayed", replayed)
        if self.prefix is not None:
            # counted HERE, not in match(): one lookup per admission, so
            # head-of-line page-wait retries don't skew the hit rate
            self.prefix.stats["lookups"] += 1
            if hit_len > 0:
                self.prefix.stats["hits"] += 1
            self.prefix.stats["hit_tokens"] += hit_len

    def _commit_prepared(self) -> None:
        """Publish what the ahead rounds prepared, in the order prepared:
        the first thing an admission round does, and whatever else
        enumerates residents between two chunks (a drain, a weight
        publish)."""
        for slot in list(self._prepared):
            req, replayed = self._prepared.pop(slot)
            self._publish(req, slot, replayed, ahead=True)

    def _unprepare(self, slot: int) -> ContinuousRequest:
        """Take a prepared admission back (``close``): its pages and
        references return, the slot's row goes back to the scratch page,
        the request is nobody's. Host work alone."""
        req, _ = self._prepared.pop(slot)
        self._give_back(slot, req)
        return req

    # -- recurrent state: snapshots (engine/sala.py) ----------------------
    def _admit_state(self, req, slot: int, hit_nodes: list,
                     hit_len: int) -> None:
        """``slot``'s state as ``hit_len`` positions left it: the snapshot
        of the hit's last node, or zero where nothing was hit (a ring
        needs no zero: no query reads a position before its slot's
        first)."""
        req.snaps = {}
        req.state_restored_at = hit_len if hit_nodes else -1
        if hit_nodes:
            place = (np.int32(slot), np.int32(hit_nodes[-1].snap))
            if self._ring:
                self.cache = restore_window(
                    self.cache, self._snaps, *place, np.int32(hit_len))
            else:
                self.cache = restore_snapshot(self.cache, self._snaps, *place)
            self._count(f"{self._snap_counts}_snapshots_restored")
        elif self._ring or self._tail or self._delta:
            return  # ... and the ragged pass reads zeros before position 0
        else:
            self.cache = zero_state(self.cache, np.int32(slot))
        self._count("admit_device_calls")

    def _next_stop(self, req: ContinuousRequest) -> int:
        """Where ``req``'s next prefill grant has to end at the latest: the
        next multiple of ``snap_stride``, the last page edge under the
        prompt's last token (the length the trie inserts at release), or
        the prompt's end."""
        T, pos = len(req.prefill_tokens), req.prefill_pos
        if not self.snap_stride:
            return T
        final = (T - 1) // self.page_size * self.page_size
        stop = (pos // self.snap_stride + 1) * self.snap_stride
        if pos < final:
            stop = min(stop, final)
        return min(stop, T)

    def _snapshot_slot(self, req: ContinuousRequest, slot: int) -> None:
        """Keep ``slot``'s state at ``req.prefill_pos``, where its chunk
        just ended, if that is a snapshot point."""
        T, pos = len(req.prefill_tokens), req.prefill_pos
        final = (T - 1) // self.page_size * self.page_size
        if not self.snap_stride or not 0 < pos < T or pos in req.snaps or (
            pos % self.snap_stride and pos != final
        ):
            return
        if not self._snap_free and self._snap_nodes:
            # the pool is full: the snapshot of the node matched longest
            # ago goes (the node stays; a hit there restores further down)
            self._drop_snapshot(
                min(self._snap_nodes.values(), key=lambda n: n.tick))
        if not self._snap_free:
            self._count(f"{self._snap_counts}_snapshots_skipped")
            return
        idx = self._snap_free.pop()
        if self._ring:
            self._snaps = take_window(
                self._snaps, self.cache, np.int32(slot), np.int32(idx),
                np.int32(pos))
        else:
            self._snaps = take_snapshot(
                self._snaps, held_arrays(self.cache), np.int32(slot),
                np.int32(idx))
        req.snaps[pos] = idx
        self._count(f"{self._snap_counts}_snapshots_taken")

    def _drop_snapshot(self, node) -> None:
        """``node``'s snapshot, if it has one, back to the free places
        (``PrefixCache.on_drop``: the node leaves the trie)."""
        idx, node.snap = node.snap, None
        if idx is not None:
            del self._snap_nodes[idx]
            self._snap_free.append(idx)

    def _free_snapshots(self, req: ContinuousRequest) -> None:
        """The snapshots ``req`` took that no trie node took over."""
        self._snap_free.extend(req.snaps.values())
        req.snaps = {}

    # -- tiered prefix cache (docs/SERVING.md "Tiered prefix cache") -----
    # tlint: hot-path
    def _demote_page(self, node) -> None:
        """The demote seam (wired as ``PrefixCache.spill``): an evicted
        refcount-0 page's bytes move to the host-RAM tier instead of
        dying with the page id — the bytes are still intact in HBM when
        the trie calls this, so one ``gather_page`` dispatch reads them
        out. Best-effort by contract: an injected fault (or any torn
        gather) degrades to the seed behavior — that page is destroyed —
        and never blocks the eviction; an injected CRASH propagates (a
        dying process does not demote)."""
        if node.weights_version != self.prefix.weights_version:
            return  # publish-fenced: stale-weights KV must not survive
        try:
            if faults.ENABLED:
                faults.inject("kvtier.demote", "demote:" + node.key_hash)
            got = gather_page(self.cache, np.int32(node.page))
        except faults.FaultInjected:
            return  # destroyed instead — exactly the pre-tier behavior
        blocks: list[tuple] = []
        walk = node
        while walk is not None and walk.parent is not None:
            blocks.append(walk.block)
            walk = walk.parent
        blocks.reverse()
        self.host_tier.put(
            tuple(blocks), got[0], got[1],
            got[2] if len(got) == 4 else None,
            got[3] if len(got) == 4 else None,
            weights_version=node.weights_version,
        )
        self._count("prefix_demotions")

    # tlint: hot-path
    def _scatter_page(self, pid: int, k, v, k_scale=None,
                      v_scale=None) -> None:
        """A promoted, pulled or shipped page's bytes into page ``pid``:
        ONE call, which places the page id and the payload itself (a host
        value wrapped in ``jnp`` first is a placement of its own)."""
        if k_scale is None:
            self.cache = scatter_page(self.cache, np.int32(pid), k, v)
        else:
            self.cache = scatter_page(
                self.cache, np.int32(pid), k, v, k_scale, v_scale
            )

    # tlint: hot-path
    def _promote_chain(self, seq, limit: int, hit_nodes: list) -> list:
        """Rung 2 of the admission ladder: extend the HBM hit chain with
        host-tier residents. Each promoted page is a fresh allocation
        byte-filled by the SAME fixed-shape ``scatter_page`` dispatch
        migration staging uses (zero new compiled programs), inserted
        into the trie, and pinned like any other hit node — so the hit
        is bitwise what a cold re-prefill would compute, because the
        demoted payload is the prefill's exact output bytes. Any
        failure (allocator dry, injected fetch fault) stops the walk;
        the remaining suffix takes the next rung."""
        p = self.page_size
        node = hit_nodes[-1] if hit_nodes else None
        blocks = [
            tuple(int(t) for t in seq[i * p : (i + 1) * p])
            for i in range(limit // p)
        ]
        while len(hit_nodes) < len(blocks):
            depth = len(hit_nodes) + 1
            entry = self.host_tier.lookup(
                tuple(blocks[:depth]), self.prefix.weights_version
            )
            if entry is None:
                break
            t0 = time.monotonic()
            pages = self._alloc_pages(1)
            if pages is None:
                break  # allocator dry: the suffix prefills instead
            pid = pages[0]
            self._tier_pinned.append(pid)
            try:
                if faults.ENABLED:
                    faults.inject(
                        "kvtier.fetch", "promote:" + entry.key_hash
                    )
                self._scatter_page(
                    pid, entry.k, entry.v, entry.k_scale, entry.v_scale
                )
                self._count("admit_device_calls")
            except faults.FaultInjected:
                # failed promotion fails SAFE: the page returns to the
                # free list and the suffix takes the next rung
                self._tier_pinned.remove(pid)
                self.alloc.free([pid])
                break
            except BaseException:
                # even a crash path must not leak the pinned page —
                # conservation holds on every exit (chaos-pinned)
                self._tier_pinned.remove(pid)
                self.alloc.free([pid])
                raise
            self._tier_pinned.remove(pid)
            freed: list[int] = []
            new_node, adopted = self.prefix.insert(
                node, blocks[depth - 1], pid, freed=freed
            )
            self.alloc.free(freed)
            if not adopted:
                # an identical chain is already resident (it can appear
                # mid-walk via our own alloc's eviction cascade): keep
                # the resident page, return ours
                self.alloc.free([pid])
            self.prefix.acquire([new_node])
            hit_nodes.append(new_node)
            node = new_node
            self._count("host_tier_hits")
            self._tier_hist.observe((time.monotonic() - t0) * 1e3)
        return hit_nodes

    def _pull_chain(self, seq, limit: int, hit_nodes: list) -> list:
        """Rung 3 of the admission ladder: on a still-short chain, ask
        the fleet hook for the prefix pages of a sibling replica and
        stage them into our trie, then re-walk the match. Everything
        here degrades — a dead sibling, a mid-pull source eviction, a
        refused staging or an injected fault all just fall through to
        local prefill (fleet_pull_fallbacks counts them)."""
        p = self.page_size
        n_local = len(hit_nodes)
        chain = [int(t) for t in seq[: (limit // p) * p]]
        self._count("fleet_pulls")
        t0 = time.monotonic()
        staged = 0
        try:
            if faults.ENABLED:
                faults.inject("kvtier.fetch", f"pull:{len(chain)}")
            blob = self.fetch_prefix(chain, limit, n_local)
            if blob is not None:
                staged = self.stage_prefix(blob)
        except faults.FaultInjected:
            staged = 0
        except Exception as e:
            from ..core.logging import get_logger

            get_logger("engine.kvtier").debug(
                "fleet prefix pull failed (falling back to prefill): %s", e
            )
            staged = 0
        if staged > n_local * p:
            ext = self.prefix.match(seq, limit)
            if len(ext) > n_local and ext[:n_local] == hit_nodes:
                self.prefix.acquire(ext[n_local:])
                self._tier_hist.observe((time.monotonic() - t0) * 1e3)
                return ext
        self._count("fleet_pull_fallbacks")
        return hit_nodes

    def export_prefix_pages(
        self, chain, limit: int, *, n_skip: int = 0
    ) -> dict | None:
        """Source side of a fleet prefix pull: the resident prefix pages
        of ``chain`` past the first ``n_skip``, as a blob shaped like a
        migration export (same storage-mode triple, same sha256 payload
        digest, same per-page ``gather_page`` dispatch) so the MIGRATE
        wire carries it unchanged. READ-ONLY — the chain is pinned only
        for the gather, nothing moves or frees — so a puller can never
        corrupt the source. Returns None when nothing useful is
        resident (the prefix lost the race to eviction since the digest
        was published): the puller degrades to its next rung."""
        if self._latent:  # latent pages do not travel yet (ROADMAP R3)
            return None
        if self.prefix is None:
            return None
        chain = [int(t) for t in chain]
        limit = min(int(limit), (len(chain) // self.page_size)
                    * self.page_size)
        nodes = self.prefix.match(chain, limit)
        n_skip = max(0, int(n_skip))
        if len(nodes) <= n_skip:
            return None
        self.prefix.acquire(nodes)
        try:
            if faults.ENABLED:
                faults.inject("kvtier.fetch", f"export:{len(nodes)}")
            payload: dict[str, list] = {"k": [], "v": [], "ks": [], "vs": []}
            for n in nodes[n_skip:]:
                got = gather_page(self.cache, np.int32(n.page))
                payload["k"].append(np.asarray(got[0]))
                payload["v"].append(np.asarray(got[1]))
                if len(got) == 4:
                    payload["ks"].append(np.asarray(got[2]))
                    payload["vs"].append(np.asarray(got[3]))
        finally:
            self.prefix.release(nodes)
        blob = {
            "blob_v": 2,
            "chain": np.asarray(
                chain[: len(nodes) * self.page_size], np.int32
            ),
            "n_skip": int(n_skip),
            "page_size": int(self.page_size),
            "kv_quant": self.kv_quant,
            "dtype": str(np.dtype(self.cache.k.dtype)),
            # match() only returns current-version nodes, so the chain's
            # KV was computed under THIS version — the importer's
            # per-tier publish fence compares against it
            "weights_version": int(self.weights_version),
            "k": np.stack(payload["k"]),
            "v": np.stack(payload["v"]),
        }
        if payload["ks"]:
            blob["k_scale"] = np.stack(payload["ks"])
            blob["v_scale"] = np.stack(payload["vs"])
        from ..core.serialization import content_digest

        blob["digest"] = content_digest(
            {f: blob[f] for f in ("k", "v", "k_scale", "v_scale")
             if f in blob}
        )
        return blob

    def stage_prefix(self, blob: dict) -> int:
        """Destination side of a fleet prefix pull: verify a sibling's
        exported prefix blob (storage-mode triple, weights version,
        payload digest — the same gates migration staging runs) and
        adopt its pages directly into the trie as refcount-0 residents.
        The calling admission re-walks the match and pins them in the
        same driver turn. Returns the leading chain tokens now resident
        (0 = refused — the puller falls through to local prefill).
        Partial success is success: an allocator that dries up mid-blob
        keeps what it staged."""
        if self._latent:  # latent pages do not travel yet (ROADMAP R3)
            return 0
        if self.prefix is None:
            return 0
        ours = self.migration_mode()
        theirs = (
            str(blob.get("kv_quant", "none")),
            int(blob["page_size"]),
            str(blob.get("dtype") or ours[2]),
        )
        if theirs != ours:
            from ..core.logging import get_logger

            get_logger("engine.kvtier").warning(
                "refusing pulled prefix: storage mode %r does not match "
                "ours %r — falling back to prefill", theirs, ours,
            )
            return 0
        if int(blob.get("weights_version", 0)) != self.weights_version:
            # per-tier version fence (docs/TRAINING.md): a prefix
            # computed under any other weights version must not enter
            # this trie — mid-rolling-deploy pulls degrade to prefill
            return 0
        chain = [int(t) for t in np.asarray(blob["chain"]).reshape(-1)]
        p = self.page_size
        n_total = len(chain) // p
        n_skip = int(blob.get("n_skip", 0))
        k = np.asarray(blob["k"])
        v = np.asarray(blob["v"])
        n_ship = int(k.shape[0]) if k.ndim > 1 else 0
        if n_total == 0 or n_skip + n_ship != n_total:
            return 0
        if n_ship and k.dtype != np.dtype(self.cache.k.dtype):
            return 0
        if blob.get("digest"):
            from ..core.serialization import content_digest

            got = content_digest(
                {f: np.asarray(blob[f])
                 for f in ("k", "v", "k_scale", "v_scale") if f in blob}
            )
            if got != blob["digest"]:
                return 0  # corrupted transfer → prefill rung
        nodes = self.prefix.match(chain, n_total * p)
        if len(nodes) < n_skip:
            # the local prefix we promised the source has been evicted
            # mid-pull; the shipped payload starts past what we hold
            return 0
        node = nodes[-1] if nodes else None
        self.prefix.acquire(nodes)
        try:
            for i in range(len(nodes), n_total):
                pages = self._alloc_pages(1)
                if pages is None:
                    break  # keep what we staged; the rest prefills
                pid = pages[0]
                self._tier_pinned.append(pid)
                try:
                    j = i - n_skip  # index into the shipped payload
                    scales = (
                        (blob["k_scale"][j], blob["v_scale"][j])
                        if self.cache.quantized else ()
                    )
                    self._scatter_page(pid, k[j], v[j], *scales)
                    self._count("admit_device_calls")
                except BaseException:
                    # failed staging must not leak mid-pull: the pinned
                    # page returns before the error surfaces, so the
                    # conservation equation holds on BOTH sides of a
                    # pull killed anywhere (chaos-pinned)
                    self._tier_pinned.remove(pid)
                    self.alloc.free([pid])
                    raise
                self._tier_pinned.remove(pid)
                freed: list[int] = []
                block = tuple(chain[i * p : (i + 1) * p])
                new_node, adopted = self.prefix.insert(
                    node, block, pid, freed=freed
                )
                self.alloc.free(freed)
                if not adopted:
                    self.alloc.free([pid])
                # pin through OUR OWN later allocations in this loop —
                # a fresh refcount-0 node must not lose an eviction race
                # to the very pull that created it
                self.prefix.acquire([new_node])
                nodes.append(new_node)
                node = new_node
        finally:
            self.prefix.release(nodes)
        return len(nodes) * p

    # -- live slot migration (adopt side) --------------------------------
    def _drop_ticket(self, req: ContinuousRequest) -> None:
        """Release a request's staged-adoption ticket (fallback / early
        finish): the staged pages return to the free-list so they cannot
        leak past the conservation check. DRIVER THREAD ONLY — it mutates
        the allocator; client threads use :meth:`_expire_ticket`."""
        if req.adopt is not None:
            self.drop_staged_migration(req.adopt)
            req.adopt = None

    def _expire_ticket(self, req: ContinuousRequest) -> None:
        """Client-thread-safe ticket release: expire the staged ticket in
        place (one GIL-atomic float store) so the driver's next GC sweep
        frees its pages — never touch the allocator off the driver."""
        if req.adopt is None:
            return
        ticket = self._migrations.get(req.adopt)
        if ticket is not None:
            ticket["t"] = float("-inf")
        req.adopt = None

    @staticmethod
    def _ticket_matches(ticket: dict, seq: list[int]) -> bool:
        """A staged ticket is usable only when the resubmitted sequence is
        EXACTLY the chain whose KV was shipped — anything else (a retry
        that lost tokens, a stale ticket from an earlier drain) must take
        the re-prefill rung instead of adopting mismatched pages."""
        return (
            ticket["chain"] == seq
            and ticket["length"] == len(seq) - 1
            and ticket["last_tok"] == seq[-1]
        )

    def _admit_adopted(self, req: ContinuousRequest, slot: int,
                       total: int, ticket: dict) -> bool:
        """Bind a staged migration's pages into ``slot`` and resume
        decoding — the page-shipping fast path of a live migration. The
        shipped pages (byte-exact source KV) plus any locally-resident
        prefix chain become the slot's block table, growth pages cover
        the remaining budget, and the sampling state re-arms at
        ``fold_in(seed, start_step)`` — the same draw the source's next
        step would have made, so the migrated stream is bit-identical to
        an uninterrupted one BY CONSTRUCTION (identical KV bytes ⇒
        identical logits ⇒ identical draws). Returns False while the
        allocator can't cover the growth pages (request stays queued,
        ticket retained)."""
        seq = req.prompt + req.tokens
        length = int(ticket["length"])
        n_skip = len(ticket["nodes"])
        n_have = n_skip + len(ticket["pages"])
        grow = self._alloc_pages(
            max(pages_needed(total, self.page_size) - n_have, 0)
        )
        if grow is None:
            return False
        bt_row = np.zeros(self.cache.pages_per_slot, np.int32)
        bt_row[:n_skip] = [n.page for n in ticket["nodes"]]
        bt_row[n_skip:n_have] = ticket["pages"]
        bt_row[n_have : n_have + len(grow)] = grow
        self._bind_slot(slot, bt_row, length)
        req.slot = slot
        req.pages = list(ticket["pages"]) + grow
        req.shared_nodes = list(ticket["nodes"])
        # promotion semantics carry over from the source admission: only
        # the prefill-written region [0, prefill_target) may enter the
        # trie on a later teardown (shipped decode-written pages are
        # byte-exact for THIS stream but not bitwise a prefill recompute,
        # which is the cache's contract)
        req.prefill_target = int(ticket["prefill_target"])
        req.prefill_tokens = seq[: req.prefill_target]
        req.prefill_pos = length
        self._slots[slot] = req
        # decode-ready arming: the slot resumes mid-stream, so the next
        # draw index is start_step (= every token the stream has emitted,
        # across all prior submissions) and the context histogram covers
        # the WHOLE chain — exactly the uninterrupted run's state here
        self._arm_slot(req, slot, ctx=seq)
        # the adopted KV was computed under the SOURCE's weights: stamp
        # THAT version (overriding _arm_slot's local stamp) so the
        # promotion gate refuses these pages unless the source version
        # still equals this engine's at teardown — a mid-publish
        # migration can never seed the trie with old-weights KV
        req.weights_version = int(ticket.get("weights_version", 0))
        self._tok[slot] = int(ticket["last_tok"])
        self._active[slot] = True
        del self._migrations[req.adopt]
        req.adopt = None
        self._count("admitted")
        self._count("migrations_adopted")
        # adoption closes the migration arc: the shipped chain resumes
        # decoding here with zero prefill compute
        self._trace(
            req, "adopt", slot=slot, length=length,
            pages=len(req.pages), shared=n_skip,
        )
        return True

    def _set_knob_mirrors(self, slot: int, sp: SamplingParams) -> None:
        """Scalarize a request's sampling knobs into the per-slot host
        mirrors the compiled chunk consumes."""
        t = np.asarray(sp.temperature)
        self._temp[slot] = float(t.reshape(-1)[0])
        self._topk[slot] = int(np.asarray(sp.top_k).reshape(-1)[0])
        self._topp[slot] = float(np.asarray(sp.top_p).reshape(-1)[0])
        self._pres[slot] = float(np.asarray(sp.presence_penalty).reshape(-1)[0])
        self._freq[slot] = float(np.asarray(sp.frequency_penalty).reshape(-1)[0])

    def _arm_slot(self, req: ContinuousRequest, slot: int,
                  ctx=None) -> None:
        """Admission arming: the sampling state lands on the host at
        ADMISSION, before the slot's first packed block — so the step
        that completes its prefill draws the first token in-program with
        the request's own key chain (index ``start_step + len(tokens)``,
        counting recovery and pre-preemption tokens), the request's
        knobs, and the context histogram. ``ctx`` defaults to the prefill
        sequence (prompt + any pre-preemption tokens — exactly an
        uninterrupted run's context here); an adopted (migrated-in) slot
        passes its full chain instead."""
        self._seeds[slot] = req.seed
        self._steps[slot] = req.start_step + len(req.tokens)
        # stamp the weights version this admission prefills under: the
        # promotion path refuses pages from any OLDER version (a publish
        # between admission and eviction must not seed the trie with KV
        # the current weights would not have computed)
        req.weights_version = self.weights_version
        self._set_knob_mirrors(slot, req.sampling)
        if ctx is None:
            ctx = req.prefill_tokens or req.prompt
        hist = self._ctx_counts(req, ctx)
        # a histogram of zeros rides the next chunk's control buffer as
        # one flag; a context's own is written now, whole, and stands
        self._reset[slot] = hist is None
        if hist is not None:
            self._counts = set_counts_row(self._counts, np.int32(slot), hist)
            self._count("admit_device_calls")

    def _ctx_counts(self, req: ContinuousRequest, ctx) -> np.ndarray | None:
        """Histogram of ``ctx`` when the request's penalties need one
        (None otherwise: it starts at zero). An adopted (migrated-in)
        slot passes the full chain — prompt + every emitted token — which
        equals the uninterrupted run's integer counts at the same step."""
        if not (self._any(req.sampling.presence_penalty)
                or self._any(req.sampling.frequency_penalty)):
            return None
        c = np.zeros(self.cfg.vocab_size, np.int32)
        np.add.at(c, np.asarray(ctx, np.int64), 1)
        return c

    def _bind_slot(self, slot: int, bt_row, length: int) -> None:
        """Point ``slot`` at its pages, ``length`` positions in: on the
        host's table, for the next dispatched chunk's control buffer to
        carry (the step program replaces the row and the length before
        its ragged pass; ``_step_operands``)."""
        self._bt_host[slot] = bt_row
        self._len0[slot] = length
        self._bind[slot] = True
        self._count("slot_binds_packed")

    def _slot_length(self, slot: int) -> int:
        """``slot``'s length on the device, as the next step program will
        find it: what rides the next control buffer, where something
        does, stands for the device's own."""
        if self._bind[slot]:
            return int(self._len0[slot])
        return int(np.asarray(self.cache.lengths)[slot])

    @staticmethod
    def _any(v) -> bool:
        return bool(np.any(np.asarray(v)))

    def _evict(self, slot: int) -> None:
        """Retire a slot and answer for its request at once (``close``, a
        stop asked for with no step in flight); a chunk's settle stage
        retires and leaves ``on_finish`` to the stream stage."""
        req = self._retire(slot)
        if req is not None:
            self._finish(req, finished=True)

    def _retire(self, slot: int) -> ContinuousRequest | None:
        """Free a finished slot at a step boundary: shared prefix pages
        drop their refcount, promotable private pages move INTO the
        prefix cache, the rest return to the free-list; table row →
        scratch, slot → admission pool. No callback runs here."""
        req = self._teardown_slot(slot)
        if req is not None:
            self._count("evicted")
            # the decode span covers the DECODE phase only (prefill has
            # its own span — overlapping them would double-count TTFT
            # time in any span-layout view); adopted slots have no
            # prefill phase, so their base is the admission
            base = req.prefill_done_t or req.admit_t
            self._trace(
                req, "decode",
                dur_s=(time.monotonic() - base) if base else None,
                tokens=len(req.tokens),
            )
            st = req.spec_state
            if st is not None and st.verify_passes:
                # verify-pass amortization, attributed per request: how
                # much the draft/verify path multiplied this stream's
                # decode (tokens_per_pass 1.0 = speculation never paid)
                self._trace(
                    req, "spec", drafted=st.drafted, accepted=st.accepted,
                    passes=st.verify_passes,
                    tokens_per_pass=round(st.tokens_per_pass or 0.0, 3),
                    killed=st.dead,
                )
            if req.admit_t:
                # under the lock like every other scheduler touch: the
                # service EWMA this updates is read concurrently by
                # admission_check/serving_snapshot from client threads
                # (found by tlint TL001 — the only sched access that ran
                # outside the engine lock)
                with self._lock:
                    self.sched.note_finished(
                        req, time.monotonic() - req.admit_t
                    )
        return req

    def _teardown_slot(self, slot: int) -> ContinuousRequest | None:
        """Shared slot teardown for eviction AND preemption: device row →
        scratch, pages released (promotable prefill-written pages enter
        the prefix cache), host mirrors cleared. Returns the request that
        held the slot, its transient slot state reset. Tokens of its
        request that wait for the stream stage leave first: whoever tears
        a slot down answers for the request next (a requeue, a redirect
        to another worker), and a stop they bring frees the slot itself."""
        if self._slots[slot] is not None:
            self.flush_stream(self._slots[slot])
        req = self._slots[slot]
        self._slots[slot] = None
        self._prefilling.pop(slot, None)
        self._frozen.discard(slot)
        self._active[slot] = False
        self._tok[slot] = 0
        self._temp[slot] = 0.0
        self._give_back(slot, req)
        return req

    def _give_back(self, slot: int, req: ContinuousRequest | None) -> None:
        """``slot``'s row to the scratch page and ``req``'s pages to
        whoever keeps them next (the trie, the free list)."""
        # table row -> scratch page, length and histogram -> 0: the next
        # dispatched chunk carries it (no step reads a retired slot's row
        # before that, and a page freed here is written by nobody sooner)
        self._bind_slot(slot, 0, 0)
        self._reset[slot] = True
        if req is not None:
            if self.prefix is not None:
                self._release_pages(req)
            else:
                self.alloc.free(req.pages)
                self._free_snapshots(req)
            req.pages = []
            req.shared_nodes = []

    def _preempt(self, slot: int) -> None:
        """Preempt a running (or mid-prefill) slot at an admission
        boundary: tear the slot down through the normal release path —
        prefill-written pages PROMOTE into the prefix cache, so the
        resume's re-prefill walks them back with zero recompute while
        they stay resident — and re-queue the request with its arrival
        order intact (its aging clock restarts: ticks spent running are
        not ticks spent waiting). Tokens already emitted were
        already streamed; resumption re-prefills prompt + emitted and
        continues the per-token key chain at ``start_step +
        len(tokens)``, the exact crash-recovery contract, so the full
        stream is bit-identical to an uninterrupted run."""
        req = self._teardown_slot(slot)
        if req is None:
            return
        req.slot = -1
        req.prefill_pos = 0
        req.prefill_tokens = []
        req.prefill_target = 0
        req.prefill_done_t = 0.0
        self._count("preemptions")
        self._trace(req, "preempt", tokens=len(req.tokens))
        with self._lock:
            self.sched.requeue(req)

    def _release_pages(self, req: ContinuousRequest) -> None:
        """Return a released slot's pages, promoting what the cache can
        reuse. Promotable = full pages every position of which was
        PREFILL-written from this admission's prefill sequence
        (``prefill_pos`` caps a mid-prefill teardown, ``prefill_target``
        caps off the decoded region on eviction AND preemption). The
        decoded region is deliberately NOT cached: a decode step's KV is
        the same math as a prefill recompute but not bitwise identical
        to it (T=1 vs chunk-shaped programs), and the cache's contract
        is that a hit is bitwise the KV the slot would have computed —
        so only prefill-computed pages (themselves
        chunk-framing-invariant, test-pinned) may enter the trie."""
        self.prefix.release(req.shared_nodes)
        lim = min(req.prefill_target, req.prefill_pos)
        page = self.page_size
        n_hit = len(req.shared_nodes)
        node = req.shared_nodes[-1] if req.shared_nodes else None
        free_list: list[int] = []
        # version gate: KV prefilled under an older weights version must
        # never enter the (version-fenced) trie — see publish_weights
        promoting = (
            req.error is None
            and req.weights_version == self.weights_version
        )
        for j, pid in enumerate(req.pages):
            hi = (n_hit + j + 1) * page
            if promoting and hi <= lim:
                block = tuple(
                    int(t) for t in req.prefill_tokens[hi - page : hi]
                )
                node, adopted = self.prefix.insert(
                    node, block, pid, freed=free_list
                )
                if not adopted:
                    # an identical chain landed first (e.g. a co-batched
                    # twin finished earlier): keep theirs, free ours
                    free_list.append(pid)
                if hi in req.snaps and node.snap is None:
                    # the state after this page's last position goes
                    # where the page goes
                    node.snap = req.snaps.pop(hi)
                    self._snap_nodes[node.snap] = node
            else:
                # the chain must stay contiguous from position 0 — once a
                # page can't be promoted, nothing after it can attach
                promoting = False
                free_list.append(pid)
        self.alloc.free(free_list)
        self._free_snapshots(req)

    # -- live slot migration (export side) + drain -----------------------
    # Protocol (docs/FAILURE_MODEL.md "Migration & drain"): the DRIVER
    # freezes a decoding slot at a chunk boundary, exports its KV pages
    # byte-exactly, ships them to a destination engine that stages them
    # into freshly-allocated pages, and commits (teardown WITHOUT
    # finishing — the stream continues elsewhere). Every rung degrades to
    # the crash-recovery re-prefill: a failed export/wire/import just
    # means the resume request adopts nothing and prefills instead.

    def freeze_slot(self, slot: int) -> None:
        """Freeze a DECODING slot for export: it stops stepping (the
        packed block skips it) but keeps its pages and request — page
        accounting reports them in transit. Mid-prefill slots refuse
        (their cheap exit is the re-prefill fallback; they have no
        decode-written KV worth shipping). Driver-thread only, at a chunk
        boundary."""
        if self._slots[slot] is not None:
            # the export's chain is prompt + tokens and the destination
            # goes on from there: what this side made leaves this side
            self.flush_stream(self._slots[slot])
        req = self._slots[slot]
        if req is None or not self._active[slot] or slot in self._prefilling:
            raise ValueError(
                f"slot {slot} is not a steady decoding slot — only active "
                "decode slots freeze for migration (mid-prefill and idle "
                "slots take the re-prefill fallback)"
            )
        self._active[slot] = False
        self._frozen.add(slot)
        self._count("migrations_started")
        self._trace(req, "freeze", slot=slot, tokens=len(req.tokens))

    def migration_chain(self, slot: int) -> tuple[list[int], int]:
        """The frozen slot's token chain (prompt + emitted — the cache key
        of every valid position) and the prefix-probe limit: resident
        pages on the destination may substitute for shipped bytes only in
        the PREFILL-written region (cache hits are bitwise a prefill;
        decode-written positions are only byte-exact as shipped bytes)."""
        req = self._slots[slot]
        assert req is not None and slot in self._frozen
        length = self._slot_length(slot)
        return req.prompt + req.tokens, min(length, req.prefill_target)

    def export_slot(self, slot: int, *, n_skip: int = 0) -> dict:
        """Serialize a frozen slot into a TLTS-encodable migration blob:
        request/resume metadata plus the byte-exact KV of every valid
        page past the first ``n_skip`` (pages the destination's probe
        reported resident — the PR-3 trie short-circuit). The gather is
        one fixed-shape dispatch per page (``gather_page``), so exports
        never grow the compiled-program set."""
        if self._latent:
            raise PagedUnsupported(
                "a patterned model's " + self._held_what
                + " do not migrate yet: the stream falls back to "
                "re-prefill on its destination"
            )
        req = self._slots[slot]
        if req is None or slot not in self._frozen:
            raise ValueError(f"slot {slot} is not frozen for export")
        t_export = time.monotonic()
        length = self._slot_length(slot)
        chain, limit = self.migration_chain(slot)
        n_valid_pages = pages_needed(length, self.page_size)
        n_skip = max(0, min(int(n_skip), limit // self.page_size,
                            n_valid_pages))
        row = [n.page for n in req.shared_nodes] + list(req.pages)
        ship = row[n_skip:n_valid_pages]
        payload: dict[str, list] = {"k": [], "v": [], "ks": [], "vs": []}
        for pid in ship:
            got = gather_page(self.cache, np.int32(pid))
            payload["k"].append(np.asarray(got[0]))
            payload["v"].append(np.asarray(got[1]))
            if len(got) == 4:
                payload["ks"].append(np.asarray(got[2]))
                payload["vs"].append(np.asarray(got[3]))
        blob = {
            # wire-format version. NOT "v" — that key is the V-pages
            # payload below (the old "v": 1 entry was silently clobbered
            # by it, so blobs never actually carried a version)
            "blob_v": 2,
            "chain": np.asarray(chain, np.int32),
            "length": int(length),
            "last_tok": int(self._tok[slot]),
            "prefill_target": int(req.prefill_target),
            "n_skip": int(n_skip),
            "page_size": int(self.page_size),
            "kv_quant": self.kv_quant,
            # the storage-mode triple the importer must match exactly —
            # int4 and int8 pools share a numpy dtype (int8 bytes), so
            # dtype alone can NOT tell them apart; kv_quant in the triple
            # is what makes an int4<->int8 drain refuse loudly
            "dtype": str(np.dtype(self.cache.k.dtype)),
            # the model weights version this slot's KV was computed under
            # (docs/TRAINING.md): the destination stamps the adopted
            # request with IT, not with its own version, so mid-publish
            # migrations can never promote old-weights KV into a
            # newer-version trie
            "weights_version": int(req.weights_version),
            "k": np.stack(payload["k"]) if ship else np.zeros(0, np.int8),
            "v": np.stack(payload["v"]) if ship else np.zeros(0, np.int8),
        }
        if payload["ks"]:
            blob["k_scale"] = np.stack(payload["ks"])
            blob["v_scale"] = np.stack(payload["vs"])
        from ..core.serialization import content_digest

        # integrity tag over the KV payload: the importer recomputes it,
        # so corrupted bytes degrade into the re-prefill fallback instead
        # of silently decoding from garbage pages
        blob["digest"] = content_digest(
            {k: blob[k] for k in ("k", "v", "k_scale", "v_scale")
             if k in blob}
        )
        # the trace id rides the MIGRATE wire frame so the destination's
        # staging span stitches under the same trace as the source's
        blob["trace"] = req.trace_id
        self._trace(
            req, "export", dur_s=time.monotonic() - t_export,
            pages=len(ship), skipped=n_skip,
        )
        return blob

    def commit_migration(
        self, slot: int, *, fell_back: bool = False
    ) -> ContinuousRequest | None:
        """The frozen slot's stream now lives elsewhere (destination
        adopted its pages, or the caller redirected it down the
        re-prefill rung): tear the slot down through the normal release
        path — prefill-region pages PROMOTE into the prefix cache, the
        rest free — WITHOUT finishing the request (no on_finish, no done:
        the stream is not over, it just left this engine)."""
        if slot not in self._frozen:
            raise ValueError(f"slot {slot} is not frozen")
        req = self._teardown_slot(slot)
        if fell_back:
            self._count("migrations_failed")
            self._count("migrations_fell_back")
            self._trace(req, "migrate_fallback", slot=slot)
        else:
            self._count("migrations_completed")
            self._trace(req, "migrate_commit", slot=slot)
        return req

    def abort_migration(self, slot: int) -> None:
        """Un-freeze: the migration was abandoned and the slot resumes
        decoding HERE, exactly where it stopped (the freeze moved no
        bytes — export is read-only)."""
        if slot not in self._frozen:
            raise ValueError(f"slot {slot} is not frozen")
        self._frozen.discard(slot)
        self._count("migrations_failed")
        if self._slots[slot] is not None:
            self._active[slot] = True

    def shed_slot(self, slot: int) -> ContinuousRequest | None:
        """Drain fallback for slots that cannot page-ship (mid-prefill,
        or a failed freeze): release the slot without finishing the
        request — the caller redirects the stream down the re-prefill
        rung."""
        req = self._teardown_slot(slot)
        if req is not None:
            self._count("migrations_fell_back")
            self._trace(req, "migrate_fallback", slot=slot)
        return req

    def shed_queued(self) -> list[ContinuousRequest]:
        """Pop every queued (not-yet-admitted) request for redirection
        during a drain — they carry no KV, so their 'migration' is a pure
        resubmission at the destination."""
        with self._lock:
            pending = self.sched.pending()
            for r in pending:
                self.sched.remove(r)
        for r in pending:
            # a queued resume's staged ticket names THIS engine's pages —
            # dead the moment the stream redirects elsewhere (driver
            # thread: shed_queued runs from the drain loop)
            self._drop_ticket(r)
        self._count("migrations_fell_back", len(pending))
        return pending

    def fail_queued(self, req: ContinuousRequest, err: BaseException) -> None:
        """Fail a request popped by :meth:`shed_queued` that has nowhere
        to be redirected (no transport context) — loud, never stranded."""
        self._drop_ticket(req)
        req.error = err
        self._finish(req, finished=False)

    def begin_drain(self) -> None:
        """Admission fence: stop taking new work (submit fails fast,
        admission_check rejects) so the drain loop can shed every live
        slot without racing fresh arrivals. What the last chunk left for
        the stream stage leaves first, so the manifest the drain reads is
        of requests whose clients hold every token made here."""
        self.flush_stream()
        # a prepared admission is a resident to the drain: a mid-prefill
        # slot of its manifest, shed down the re-prefill rung
        self._commit_prepared()
        self.drain_state = "draining"
        with self._lock:
            self.sched.set_draining(True)

    def end_drain(self) -> None:
        """Lower the fence — a drain that aborted before shedding (e.g.
        the destination can't host the job) resumes serving in place."""
        self.drain_state = "serving"
        with self._lock:
            self.sched.set_draining(False)

    # -- live weight publish / serve-and-train (docs/TRAINING.md) --------
    def publish_weights(self, params, *, version: int | None = None) -> int:
        """Hot-swap the serving weights at the chunk boundary. DRIVER-
        THREAD ONLY (ContinuousBatcher.publish_weights routes here via
        run_on_driver; a background trainer is already on the driver).

        The published tree must match the serving tree leaf-for-leaf
        (structure, shapes, dtypes): params are DATA to the compiled
        ragged step, so a conforming publish adds ZERO compiled programs
        to the serving hot path (test-pinned) — anything else is refused
        loudly before the swap. Weight-only-quantized engines quantize
        the published tree through the same path the original load took.

        Contract (docs/TRAINING.md "Hot-swap contract"): live streams
        continue without a dropped token — their already-written KV is
        NOT recomputed, so tokens after the swap mix old-weight KV with
        new-weight QKV (the standard live-fine-tune approximation);
        admissions from here on prefill under the new weights. The
        prefix cache is version-fenced: chains cached under older
        versions stop matching immediately, their unreferenced pages are
        evicted now, and in-flight requests admitted under an older
        version never promote their pages (the bitwise cache contract
        survives every publish). Returns the new version."""
        new_version = (
            int(version) if version is not None else self.weights_version + 1
        )
        if new_version <= self.weights_version:
            raise ValueError(
                f"weights version must grow: {new_version} <= "
                f"{self.weights_version}"
            )
        eng = self.engine
        params_in = params
        if getattr(eng, "quant", None):
            from ..models.quant import quantize_params

            params_in = quantize_params(params_in)
        old = eng.params
        try:
            match = jax.tree.all(jax.tree.map(
                lambda a, b: tuple(jnp.shape(a)) == tuple(jnp.shape(b))
                and getattr(a, "dtype", None) == getattr(b, "dtype", None),
                old, params_in,
            ))
        except (ValueError, TypeError) as e:
            raise ValueError(
                f"published params tree does not match the serving tree: {e}"
            ) from e
        if not match:
            raise ValueError(
                "published params leaf shapes/dtypes do not match the "
                "serving model — a publish must never recompile the step"
            )

        # Placement normalization — the other half of "zero new compiled
        # programs": a leaf whose device COMMITMENT differs from the
        # serving tree's changes the step's jit cache key (measured), so
        # every entry point (batcher staging, the serve-train loop's
        # driver-side publish, fleet actions on sibling replicas) funnels
        # through this one fix-up. Committed serving leaves get the new
        # leaf device_put onto their own sharding; uncommitted serving
        # leaves keep the new leaf as-is unless IT arrived committed —
        # then it bounces through the host once (rare: only explicitly
        # device_put trees published into an uncommitted engine).
        def _place(x, c):
            c_committed = getattr(c, "_committed", False)
            x_committed = getattr(x, "_committed", False)
            if c_committed and getattr(c, "sharding", None) is not None:
                if x_committed and x.sharding == c.sharding:
                    return x
                return jax.device_put(x, c.sharding)
            if x_committed:
                return jnp.asarray(np.asarray(x))
            return x

        try:
            params_in = jax.tree.map(_place, params_in, old)
        # tlint: disable=TL005(leaves that aren't arrays — exotic QTensor layouts — can't be re-placed; structure was validated above, so swapping the tree as given is the correct degradation)
        except (ValueError, TypeError):
            pass
        # an admission prepared under the old weights is one of theirs
        # (its hit chain is, and the version it is stamped with)
        self._commit_prepared()
        eng.params = params_in
        self.weights_version = new_version
        if self.prefix is not None:
            # version-fence the trie: future inserts tag the new version,
            # stale chains stop matching, and whatever is unreferenced
            # frees right now (referenced pages free as their slots do)
            self.prefix.weights_version = new_version
            self.alloc.free(self.prefix.drop_all())
            if self.host_tier is not None:
                # the publish fence extends PER TIER: entries demoted
                # under older weights can never match again — reap them
                # now instead of letting them squat on host RAM (the
                # drop_all above ran with prefix.weights_version already
                # bumped, so none of ITS victims demoted either)
                self.host_tier.drop_stale(new_version)
            self._refresh_prefix_digest()
        self._count("weights_published")
        return new_version

    def note_train_step(self, step_ms: float, mfu: float = 0.0) -> None:
        """Record one background train step's telemetry (driver-thread
        only — the serve-and-train loop runs between this engine's
        chunks): rides serving_snapshot → /stats and the registry gauges
        → /metrics."""
        self._train_step_ms = float(step_ms)
        self._train_mfu = float(mfu)
        self._count("train_steps")

    def foreground_work(self, above: str = "best_effort") -> bool:
        """True when any live or queued request outranks ``above``
        (scheduler rank order: LOWER rank = higher class) — the
        background trainer's yield gate: train steps run at chunk
        granularity only while the engine serves nothing above the
        best_effort class, so an interactive arrival waits at most ONE
        train step (the chunk-boundary control the scheduler already
        gives preemption). Thread-safe."""
        bar = PRIORITY_RANK[normalize_priority(above)]
        with self._lock:
            if any(
                PRIORITY_RANK.get(r.priority, bar) < bar
                for r in self.sched.pending()
            ):
                return True
        for req in self._slots + self._prepared_requests():
            if req is not None and PRIORITY_RANK.get(req.priority, bar) < bar:
                return True
        return False

    def frozen_slots(self) -> list[int]:
        return sorted(self._frozen)

    def live_manifest(self) -> list[tuple[str, int, ContinuousRequest]]:
        """Snapshot of what a drain must move: ("decode"|"prefill", slot,
        request) for every live slot. Driver-thread only."""
        self._commit_prepared()
        out: list[tuple[str, int, ContinuousRequest]] = []
        for s in range(self.max_slots):
            req = self._slots[s]
            if req is None or s in self._frozen:
                continue
            kind = "prefill" if s in self._prefilling else "decode"
            out.append((kind, s, req))
        return out

    # -- disaggregated prefill/decode handoff (source side) --------------
    # The steady-state generalization of the drain: on a handoff-armed
    # engine every opted-in slot freezes at its prefill→decode boundary
    # (step_chunk, handoff_done) and waits here for the driver to ship it
    # through the SAME export/stage/adopt path a drain uses — while
    # admission stays open and co-resident slots keep stepping. Fallback
    # ladder per slot: page-ship → re-prefill redirect at the destination
    # (commit_handoff(fell_back=True)) → resume locally (abort_handoff,
    # the final prompt token simply prefills here and the slot decodes as
    # on a mixed worker) — never a dropped stream.

    def handoff_manifest(self) -> list[tuple[int, ContinuousRequest]]:
        """Pop the slots frozen at their prefill→decode boundary since
        the last call: (slot, request) pairs the driver must now ship,
        redirect, or abort back to local decoding. Driver-thread only."""
        ready, self._handoff_ready = self._handoff_ready, []
        return [
            (s, self._slots[s]) for s in ready
            if s in self._frozen and self._slots[s] is not None
        ]

    def commit_handoff(
        self, slot: int, *, fell_back: bool = False
    ) -> ContinuousRequest | None:
        """The handed-off stream now lives on the decode-pool worker
        (pages shipped and staged, or — ``fell_back`` — redirected for a
        fresh prefill there): tear the slot down through the normal
        release path without finishing the request, exactly like a
        drain's commit. Prefill-region pages promote into the trie, so a
        sibling request's admission (or this stream's own fallback
        re-prefill, should it bounce back) walks them for free."""
        if slot not in self._frozen:
            raise ValueError(f"slot {slot} is not frozen for handoff")
        req = self._slots[slot]
        dur = (
            time.monotonic() - req.prefill_done_t
            if req is not None and req.prefill_done_t else None
        )
        out = self._teardown_slot(slot)
        if fell_back:
            self._count("handoffs_fell_back")
            self._trace(out, "handoff_fallback", slot=slot)
        else:
            self._count("handoffs_completed")
            # the TTFT decomposition's handoff leg: prefill completed →
            # pages committed at the destination (contiguous with the
            # prefill span; the destination's first_token span covers
            # resubmit → first draw, closing the sum)
            self._trace(out, "handoff", dur_s=dur, slot=slot)
        return out

    def abort_handoff(self, slot: int) -> None:
        """No usable destination (pool empty, every probe refused, the
        worker is itself draining): un-freeze and finish the prefill
        HERE — the request drops its handoff mark, the next packed block
        grants its final prompt token, and the first draw happens
        in-program like any mixed-worker admission. The stream stays
        bit-identical (nothing was shipped; the grant schedule merely
        paused) and is never worse off than without disaggregation."""
        if slot not in self._frozen:
            raise ValueError(f"slot {slot} is not frozen for handoff")
        self._frozen.discard(slot)
        self._count("handoffs_fell_back")
        req = self._slots[slot]
        if req is not None:
            req.handoff = False
            self._prefilling[slot] = req
            self._trace(req, "handoff_fallback", slot=slot, local=True)

    # -- live slot migration (import side) -------------------------------
    def migration_mode(self) -> tuple[str, int, str]:
        """The (kv_quant, page_size, cache dtype) storage-mode triple a
        shipped page blob is portable within — ALL THREE must match for
        staged bytes to be meaningful on this engine (int4 and int8
        pools share the int8 byte dtype; page layouts differ per
        page_size; payload bytes differ per dtype)."""
        pools = self.cache.rows if self._latent else self.cache.k
        return (self.kv_quant, self.page_size, str(np.dtype(pools.dtype)))

    def resident_prefix_pages(self, chain, limit: int) -> int:
        """The probe: how many leading FULL pages of ``chain`` are
        resident in this engine's prefix cache — pages the exporter may
        skip shipping (bitwise-identical by the cache contract)."""
        if self.prefix is None:
            return 0
        return len(self.prefix.match(chain, int(limit)))

    def stage_migration(self, mig_id: str, blob: dict) -> bool:
        """Stage an inbound migration blob: pin the promised resident
        prefix, allocate pages for the shipped remainder, and write the
        bytes in (one fixed-shape ``scatter_page`` dispatch per page).
        Idempotent by ``mig_id`` — duplicated or reordered wire frames
        re-stage nothing. Returns False when this engine can't honor the
        blob (storage-mode mismatch, promised prefix evicted since the
        probe, allocator dry): the source then takes the re-prefill rung.
        Pages stay IN TRANSIT (conservation-tracked) until the stream's
        resume request adopts them, or the TTL/close GC frees them."""
        if self._latent:  # the source takes the re-prefill rung
            return False
        if mig_id in self._migrations:
            return True
        if self.drain_state != "serving":
            return False  # a draining engine must not adopt new streams
        t_stage = time.monotonic()
        ours = self.migration_mode()
        theirs = (
            str(blob.get("kv_quant", "none")),
            int(blob["page_size"]),
            # legacy blobs carry no dtype field: fall back to ours so the
            # per-array dtype check below stays the only dtype gate
            str(blob.get("dtype") or ours[2]),
        )
        if theirs != ours:
            # LOUD refusal on the full (kv_quant, page_size, dtype)
            # triple — an int4<->int8 drain shares the int8 byte dtype,
            # so a dtype-only check would silently adopt garbage pages;
            # the source descends the re-prefill ladder instead
            from ..core.logging import get_logger

            get_logger("engine.migrate").warning(
                "refusing inbound migration %s: storage mode "
                "(kv_quant, page_size, dtype) %r does not match ours %r "
                "— source takes the re-prefill rung",
                mig_id, theirs, ours,
            )
            return False
        chain = [int(t) for t in np.asarray(blob["chain"]).reshape(-1)]
        length = int(blob["length"])
        limit = min(length, int(blob["prefill_target"]))
        n_skip = int(blob["n_skip"])
        nodes: list = []
        if n_skip:
            if self.prefix is None:
                return False
            nodes = self.prefix.match(chain, limit)[:n_skip]
            if len(nodes) < n_skip:
                # the prefix the probe promised was evicted meanwhile —
                # the unshipped bytes are unrecoverable here
                return False
        k = np.asarray(blob["k"])
        v = np.asarray(blob["v"])
        n_ship = int(k.shape[0]) if k.ndim > 1 else 0
        if n_skip + n_ship != pages_needed(length, self.page_size):
            return False
        if n_ship and k.dtype != np.dtype(self.cache.k.dtype):
            return False  # cache dtype mismatch: bytes aren't portable
        if blob.get("digest"):
            from ..core.serialization import content_digest

            got = content_digest(
                {f: np.asarray(blob[f])
                 for f in ("k", "v", "k_scale", "v_scale") if f in blob}
            )
            if got != blob["digest"]:
                return False  # corrupted transfer → re-prefill rung
        pages = self._alloc_pages(n_ship)
        if pages is None:
            return False
        if self.prefix is not None:
            self.prefix.acquire(nodes)
        try:
            for i, pid in enumerate(pages):
                scales = (
                    (blob["k_scale"][i], blob["v_scale"][i])
                    if self.cache.quantized else ()
                )
                self._scatter_page(pid, k[i], v[i], *scales)
        except BaseException:
            # a failed staging must not leak: pages back to the free-list,
            # pinned refs dropped, so conservation holds on the error path
            self.alloc.free(pages)
            if self.prefix is not None:
                self.prefix.release(nodes)
            raise
        self._migrations[mig_id] = {
            "pages": pages,
            "nodes": nodes,
            "chain": chain,
            "length": length,
            "last_tok": int(blob["last_tok"]),
            "prefill_target": int(blob["prefill_target"]),
            # the SOURCE's weights version for the adopted request's
            # promotion gate; legacy blobs carry none → 0, which never
            # equals a live version, so their pages simply never promote
            "weights_version": int(blob.get("weights_version", 0)),
            "t": time.monotonic(),
        }
        tid = str(blob.get("trace") or "")
        if tid:
            # destination-side staging span under the SOURCE's trace id —
            # the cross-worker stitch the /trace endpoint serves
            self.tracer.record(
                tid, "stage", site=self.trace_site,
                dur_s=time.monotonic() - t_stage,
                pages=n_ship, shared=n_skip,
            )
        return True

    def drop_staged_migration(self, mig_id: str) -> None:
        """Free a staged migration's pages (fallback, TTL GC, close)."""
        ticket = self._migrations.pop(mig_id, None)
        if ticket is None:
            return
        self.alloc.free(ticket["pages"])
        if self.prefix is not None:
            self.prefix.release(ticket["nodes"])

    def staged_migrations(self) -> list[str]:
        """Ticket ids currently staged and awaiting adoption — the set a
        recovering source validator expires deterministically (MIGRATE
        op="expire") instead of leaving to the destination's TTL GC."""
        return list(self._migrations)

    def _gc_staged_migrations(self) -> None:
        """Free staged tickets whose resume request never arrived (the
        draining source or its client died mid-handoff) so abandoned
        migrations can't leak pages."""
        now = time.monotonic()
        for mig_id in [
            m for m, t in self._migrations.items()
            if now - t["t"] > self.migration_ttl_s
        ]:
            self.drop_staged_migration(mig_id)

    # -- page accounting -------------------------------------------------
    def page_accounting(self) -> dict:
        """Ownership snapshot over physical pages 1..P-1: the free-list,
        the cache-resident set, each live slot's private pages, and the
        IN-TRANSIT set — pages a migration currently holds (a frozen
        slot's pages awaiting commit on the source; a staged ticket's
        pages awaiting adoption on the destination)."""
        slot_pages: list[int] = []
        in_transit: list[int] = []
        for s in range(self.max_slots):
            req = self._slots[s]
            if req is not None:
                (in_transit if s in self._frozen else slot_pages).extend(
                    req.pages
                )
        for req in self._prepared_requests():
            slot_pages.extend(req.pages)  # a slot's, all but published
        for ticket in self._migrations.values():
            in_transit.extend(ticket["pages"])
        return {
            "free": set(self.alloc._free),
            "cached": self.prefix.resident_pages if self.prefix else set(),
            "slots": slot_pages,
            "in_transit": in_transit,
            # pages pinned by an in-progress tier transfer (allocated,
            # being byte-filled, not yet trie-resident) — empty at every
            # quiet boundary, non-empty exactly while a promote or a
            # fleet pull is staging a page
            "host_tier": list(self._tier_pinned),
        }

    def check_page_conservation(self) -> None:
        """The hardened free-list invariant: free + slot-owned +
        cache-resident + host-tier-pinned + in-transit == total usable
        pages, pairwise disjoint, scratch page 0 in none of them. Raises
        AssertionError on violation — asserted at engine teardown
        (close) and by the engine/chaos tests after recovery,
        mid-migration AND mid-pull (the in-transit and host-tier terms
        are what keep the invariant checkable while pages are between
        owners on either side). Every failure message carries the full
        per-term breakdown — a regression should name its numbers, not
        cost a debug round-trip to get them. On a shared pool the
        device-page invariant is GLOBAL — this delegates to the pool's
        per-tenant check (free + Σ tenants' (slots + cached +
        in-transit) == total, pairwise disjoint ACROSS tenants, quota
        counters honest). The host tier's own ledger (bounded residency,
        structural keys, paired scales) is checked alongside either
        way."""
        if self.pool is not None:
            self.pool.check_page_conservation()
            if self.host_tier is not None:
                self.host_tier.check_conservation()
            return
        acc = self.page_accounting()
        free, cached = acc["free"], acc["cached"]
        slots, transit = acc["slots"], acc["in_transit"]
        tier = acc["host_tier"]
        total = self.cache.n_pages - 1
        problems = []
        if len(slots) != len(set(slots)):
            problems.append("a page is owned by two slots")
        if len(transit) != len(set(transit)):
            problems.append("a page is in transit twice")
        if len(tier) != len(set(tier)):
            problems.append("a page is tier-pinned twice")
        if free & cached:
            problems.append("free-list and cache overlap")
        if set(slots) & (free | cached):
            problems.append("slot-owned page also free or cached")
        if set(transit) & (free | cached | set(slots)):
            problems.append("in-transit page also free, cached, or owned")
        if set(tier) & (free | cached | set(slots) | set(transit)):
            problems.append(
                "tier-pinned page also free, cached, owned, or in transit"
            )
        if 0 in (free | cached | set(slots) | set(transit) | set(tier)):
            problems.append("scratch page 0 entered an ownership set")
        if (
            len(free) + len(cached) + len(slots) + len(transit)
            + len(tier) != total
        ):
            problems.append("leak: the ownership terms do not sum to the pool")
        if problems:
            raise AssertionError(
                "page conservation violated: " + "; ".join(problems)
                + f" [free={len(free)} slots={len(slots)} "
                f"cached={len(cached)} host_tier={len(tier)} "
                f"in_transit={len(transit)} vs total={total}]"
            )
        if self.host_tier is not None:
            self.host_tier.check_conservation()
        if self._ring:
            # a slot's ring pages are its own by their number: the pools
            # hold the scratch page and ``ring_pages`` a slot, no more
            c = self.cache
            if c.wk.shape[1] != 1 + self.max_slots * c.ring_pages or (
                c.wv.shape != c.wk.shape
            ):
                raise AssertionError(
                    f"ring conservation violated: {c.wk.shape[1]} ring "
                    f"pages for {self.max_slots} slots of {c.ring_pages}")
        if self._tail:
            # one tail a conv layer and slot, no more
            c, sc = self.cache, self.cfg.latent_of("conv")
            want = (self.cfg.layer_kinds.count("conv"), self.max_slots,
                    sc.tail, sc.width)
            if c.state.shape != want:
                raise AssertionError(
                    f"tail conservation violated: {c.state.shape} for "
                    f"{want}")
        if self._delta:
            # one state and one tail a gated-delta layer and slot, and a
            # snapshot pool that holds both under every place
            c, gd = self.cache, self.cfg.latent_of("gated_delta")
            n = self.cfg.layer_kinds.count("gated_delta")
            want = {"state": (n, self.max_slots, gd.key_dim,
                              gd.n_heads * gd.value_dim),
                    "tail": (n, self.max_slots, gd.tail, gd.conv_width)}
            got = {k: a.shape for k, a in held_arrays(c).items()}
            # [places, layers, ...]: every array under the same places
            pool = {} if self._snaps is None else {
                k: a.shape for k, a in self._snaps.items()}
            places = {v[0] for v in pool.values()}
            if got != want or len(places) > 1 or any(
                    v[1:] != want[k][:1] + want[k][2:]
                    for k, v in pool.items()) or set(pool) - set(want):
                raise AssertionError(
                    f"state conservation violated: a slot holds {got} for "
                    f"{want}, the snapshot pool {pool}")
        if self._snaps is not None:
            self._check_snapshot_conservation()

    def _check_snapshot_conservation(self) -> None:
        """Every place of the snapshot pool is free, a resident trie
        node's, or a live request's, and only one of them."""
        held = [i for r in self._slots if r is not None
                for i in r.snaps.values()]
        owned = self._snap_free + list(self._snap_nodes) + held
        problems = []
        if len(owned) != len(set(owned)):
            problems.append("a snapshot place has two owners")
        n_places = jax.tree.leaves(self._snaps)[0].shape[0]
        if len(owned) != n_places:
            problems.append("leak: the owners do not sum to the pool")
        for idx, node in self._snap_nodes.items():
            if node.snap != idx or self.prefix._by_page.get(node.page) is not node:
                problems.append(f"place {idx} belongs to no resident node")
        if problems:
            raise AssertionError(
                "snapshot conservation violated: " + "; ".join(problems)
                + f" [free={len(self._snap_free)} "
                f"trie={len(self._snap_nodes)} slots={len(held)} vs "
                f"total={n_places}]"
            )

    def _pages_in_transit(self) -> int:
        """Pages currently held by an in-flight migration on either side:
        staged inbound tickets plus frozen outbound slots."""
        return (
            sum(len(t["pages"]) for t in self._migrations.values())
            + sum(
                len(self._slots[s].pages)
                for s in self._frozen
                if self._slots[s] is not None
            )
        )

    def serving_snapshot(self) -> dict:
        """Telemetry for the validator's /stats endpoint and the bench:
        engine counters, scheduler per-class stats (queue depth,
        queue-wait/TTFT percentiles, preemptions, rejections), plus
        prefix-cache occupancy. Keys are derived from the metrics
        registry but stay byte-compatible with the pre-registry dicts
        (test-pinned; see docs/SERVING.md "Telemetry")."""
        out = dict(self.stats)
        # the slots' states, or the tails of a model with conv layers
        # ... or the states AND tails of one with gated-delta layers
        held = self.cache.state_bytes if self._stateful else 0
        state_bytes, tail_bytes = (0, held) if self._tail else (held, 0)
        delta_tails = self.cache.tail_bytes if self._delta else 0
        snap_bytes = 0 if self._snaps is None else tree_bytes(self._snaps)
        ring_bytes = self.cache.ring_bytes if self._ring else 0
        # the snapshot pool is the states', the windows' or the tails':
        # (bytes, places held) under the name its counters carry
        of_states, of_windows, of_tails = (
            (snap_bytes, len(self._snap_nodes)) if kind == self._snap_counts
            else (0, 0) for kind in ("state", "window", "conv"))
        # KV storage mode + occupancy: the capacity math operators size
        # slots-per-chip with (kv_quant="int8" halves kv_page_bytes)
        c = self.cache
        if self._latent:
            page_bytes = c.pool_bytes // c.n_pages
        else:
            page_bytes = (c.k.nbytes + c.v.nbytes) // c.n_pages
        if c.quantized:
            page_bytes += (c.k_scale.nbytes + c.v_scale.nbytes) // c.n_pages
        # speculative decoding: enablement + the aggregate amortization
        # (tokens emitted per verify pass across every speculating slot;
        # 0.0 until the first verify pass ran)
        passes = out.get("spec_verify_passes", 0)
        out.update({
            "kv_quant": self.kv_quant,
            # weight storage mode of the wrapped engine ("int8"/"int8+kv"
            # = weight-only-quantized serving; operators size HBM with
            # kv_quant AND this)
            "weight_quant": getattr(self.engine, "quant", None) or "none",
            "kv_pages_total": c.n_pages - 1,
            "kv_pages_free": self.alloc.n_free,
            "kv_page_bytes": int(page_bytes),
            "spec_decode": self.spec_decode,
            "spec_tokens_per_pass": round(
                (out.get("spec_accepted", 0) + passes) / passes, 3
            ) if passes else 0.0,
            # live migration telemetry (migrations_* counters ride
            # self.stats above): drain fence state + pages currently held
            # by an in-flight migration on either side
            "drain_state": self.drain_state,
            "pages_in_transit": self._pages_in_transit(),
            # disaggregated prefill/decode (docs/SERVING.md): the pool
            # role this engine serves under (rides /stats → /metrics →
            # /healthz so a router can see the fleet's pool shape), and
            # the slot-owned page count — free + cached + slots +
            # in-transit == total is the conservation equation remote
            # observers (chaos e2e, operators) can audit per snapshot
            "worker_role": self.worker_role,
            "kv_pages_slots": sum(
                len(r.pages) for s, r in enumerate(self._slots)
                if r is not None and s not in self._frozen
            ) + sum(len(r.pages) for r in self._prepared_requests()),
            # fleet-router headroom (docs/SERVING.md "Fleet serving"):
            # slots no request holds — with kv_pages_free and the
            # per-class sched_classes depths below, the placement inputs
            # a router/LB needs without a second probe
            "slots_free": len(self._free_slots()),
            # serve-and-train (docs/TRAINING.md): which model version
            # this engine serves (bumps per weight publish — the fleet
            # view of a rolling model update), plus the background
            # trainer's last step telemetry (0.0 until one runs)
            "weights_version": self.weights_version,
            "train_step_ms": round(self._train_step_ms, 3),
            "train_mfu": round(self._train_mfu, 5),
            # tensor parallelism (docs/SHARDING.md): shard degree of the
            # hot path (1 = single device) — a router treats the whole
            # mesh as one placement unit
            "tensor_parallel": self.tensor_parallel,
            "latent_pool_bytes": (
                self.cache.pool_bytes if self._latent else 0
            ),
            # a model with recurrent layers: the slots' states, and the
            # states and snapshots together (0 for other models)
            "lightning_state_bytes": state_bytes,
            "state_snapshot_bytes": of_states[0],
            "state_pool_bytes": state_bytes + delta_tails + of_states[0],
            "state_snapshots_resident": of_states[1],
            # a model whose window layers hold a ring a slot: the rings,
            # and the rings and their snapshots together
            "window_ring_bytes": ring_bytes,
            "window_pool_bytes": ring_bytes + of_windows[0],
            "window_snapshots_resident": of_windows[1],
            # a model whose short-convolution layers hold a tail a slot:
            # the tails, and the tails and their snapshots together
            "conv_tail_bytes": tail_bytes,
            "conv_pool_bytes": tail_bytes + of_tails[0],
            "conv_snapshots_resident": of_tails[1],
            "spec_refusal": self.spec_refusal,
            "weights_bytes_device_max": max(self.weights_bytes_device),
            "weights_bytes_device_min": min(self.weights_bytes_device),
            # what building the step programs cost, and what of it a
            # serving path waited for (set at build time only)
            "step_build_ms": round(self._step_build_s * 1e3, 3),
            "step_build_waited_ms": round(self._step_build_waited_s * 1e3, 3),
        })
        if self.pool is not None:
            # co-hosting: the shared pool's occupancy plus THIS tenant's
            # quota view (docs/SERVING.md "Co-hosting multiple models")
            out.update(self.pool.snapshot())
            out["pool_quota"] = self.alloc.quota
            out["pool_pages_used"] = self.alloc.used
        with self._lock:
            out.update(self.sched.snapshot())
        if self.prefix is not None:
            ps = self.prefix.stats
            out.update({
                "prefix_lookups": ps["lookups"],
                "prefix_hits": ps["hits"],
                "prefix_hit_tokens": ps["hit_tokens"],
                "prefix_cow_copies": ps["cow_copies"],
                "prefix_evictions": ps["evictions"],
                "prefix_inserts": ps["inserts"],
                "prefix_resident_pages": self.prefix.n_resident,
                # compact resident-chain digest for fleet cache-affinity
                # scoring: the driver-refreshed swap copy, never the trie
                "prefix_digest": self._prefix_digest,
            })
        # tiered prefix cache (docs/SERVING.md "Tiered prefix cache"):
        # enablement + host-tier occupancy + per-fetch latency roll-up
        # (the tier counters themselves ride self.stats above)
        out["host_tier"] = self.host_tier is not None
        if self.host_tier is not None:
            out.update({
                "host_tier_capacity": self.host_tier.capacity,
                "host_tier_resident_pages": self.host_tier.n_resident,
                "host_tier_evictions": self.host_tier.stats["evictions"],
                # host-tier chain digest for the fleet prefix map — the
                # driver-refreshed swap copy, like prefix_digest (and
                # skipped by snapshot_gauges for the same unbounded-
                # metric-family reason)
                "host_tier_digest": self._host_digest,
                "tier_fetch_ms_count": self._tier_hist.count,
                "tier_fetch_ms_sum": round(self._tier_hist.sum, 3),
            })
        return out

    def _admit(self) -> None:
        """One admission round (one scheduler tick): commit what the ahead
        rounds of the chunk before prepared, then admit the scheduler's
        best queued request into a free slot, preempting strictly-lower-
        priority residents when the candidate would otherwise miss
        admission — no free slot, or the allocator dry even after
        prefix-cache eviction. The lock guards only the host-side queue
        state — the device-heavy prefill in _admit_one runs OUTSIDE it so
        client submit() calls never stack behind admission compute
        (single-driver discipline means nobody else pops the selection
        meanwhile)."""
        self._commit_prepared()
        if self._migrations:
            # abandoned staged adoptions (their resume never arrived)
            # must not hold pages forever
            self._gc_staged_migrations()
        with self._lock:
            self.sched.tick()
        self._admit_round()

    def _admit_ahead(self) -> None:
        """An ahead round, run from a chunk's wait after each request the
        intake took in: ``_admit``'s round for the slots that hold no
        request and no prepared admission, while the device runs the
        chunk that was packed without them. It prepares and does not
        publish (``_admit_paged``), never preempts, and leaves to the
        chunk's edge what only the edge can do: a request with no slot or
        no pages free, an adoption. Its device calls (a copy-on-write
        page, a restored state) take ``self.cache``, the step's result,
        and queue behind the step; none fetches."""
        self._admit_round(ahead=True)

    def _admit_round(self, *, ahead: bool = False) -> None:
        """The body ``_admit`` and ``_admit_ahead`` share."""
        while True:
            with self._lock:
                # a slot is free only when NO request holds it — active
                # decode or mid-prefill both count as occupied, and so
                # does an admission prepared for it
                free = self._free_slots()
                req = self.sched.select()
                victim = None
                if req is not None and not free and not ahead:
                    victim = self.sched.victim(self._preemptable(), req)
            if req is None:
                return
            if ahead and (not free or req.adopt is not None):
                return  # the edge's: a victim's slot, a staged adoption
            if not free:
                if victim is None:
                    return  # every resident outranks the best candidate
                self._preempt(victim.slot)
                continue  # the victim's slot is free now
            slot = free[0]
            t_adm = time.monotonic()
            while not self._admit_one(req, slot, ahead=ahead):
                if ahead:
                    return  # the edge finds the pages, or a victim
                # allocator pressure the prefix cache couldn't cover:
                # preempting a lower-priority resident frees its private
                # pages (and promotes its prefill region, so ITS resume
                # is near-free too); without a victim the candidate
                # waits head-of-line like before
                with self._lock:
                    victim = self.sched.victim(self._preemptable(), req)
                    cand_rank = self.sched.effective_rank(req)
                if victim is not None:
                    self._preempt(victim.slot)
                    continue
                if self.pool is not None and (
                    self.alloc.quota - self.alloc.used
                    >= pages_needed(
                        min(len(req.prompt) + req.budget, self.max_seq_len),
                        self.page_size,
                    )
                ):
                    # cross-tenant rung (docs/SERVING.md "Co-hosting"):
                    # no same-model victim, but the SHARED pool may hold a
                    # strictly-lower-ranked slot of another tenant — tear
                    # it down through ITS engine's normal preemption path
                    # (promotion + requeue + bit-identical resume all
                    # intact). Quota must have room: a quota-dry tenant
                    # never preempts a neighbor.
                    cross = self.pool.cross_model_victim(cand_rank, self)
                    if cross is not None:
                        owner, vreq = cross
                        owner._preempt(vreq.slot)
                        owner._count("preempted_cross_tenant")
                        continue
                return  # head-of-line waits for pages
            # in a slot, or prepared for one (else it ended at once: a
            # prompt too long, no room left)
            placed = req.slot >= 0 or slot in self._prepared
            with self._lock:
                self.sched.remove(req)
                if placed:
                    self.sched.note_admitted(req)
                    req.admit_t = time.monotonic()
            if placed and req.trace_id:
                # contiguous TTFT decomposition, part 1 and 2: time spent
                # queued, then the admission work itself (page grab,
                # prefix-cache walk, COW, any preemption teardown). An
                # ahead round's end here: the request waits for the edge
                # inside its ``prefill``
                self._trace(
                    req, "queue_wait", dur_s=req.admit_t - req.submit_t,
                    t0=req.submit_t, priority=req.priority,
                )
                self._trace(
                    req, "admission", dur_s=req.admit_t - t_adm,
                    t0=t_adm, slot=slot, cache_hit_tokens=req.prefill_pos,
                    **({f"{self._snap_counts}_restored_at":
                        req.state_restored_at} if self._stateful else {}),
                    # deepest tier that fed the hit region — "hbm",
                    # "host", "fleet", or "none" (adopted migrations
                    # keep their own "adopt" span instead)
                    tier=req.cache_tier,
                    **({"ahead": True} if ahead else {}),
                )

    def _preemptable(self) -> list:
        """Resident requests a preemption may consider: a slot frozen for
        migration is mid-handoff — tearing it down would corrupt the
        export — so it is invisible to the victim search."""
        return [
            r if s not in self._frozen else None
            for s, r in enumerate(self._slots)
        ]

    # -- the decode loop -------------------------------------------------
    # per-slot EOS ids carried INTO the compiled chunk (freeze
    # optimization); the host's delivery loop checks the full set, so an
    # overflowing set only costs wasted in-chunk steps, never correctness
    _EOS_WIDTH = EOS_WIDTH

    # tlint: hot-path
    def _pack_ragged(self):
        """Assemble the unified step's packed ``[S, C]`` token block — the
        pure host side of the zero-seam schedule: each mid-prefill slot's
        next prompt piece (its grant from :func:`pack_prefill_budgets`)
        and each decoding slot's current token ride ONE block, with
        per-slot ``(start, n_valid)`` as data. ``emit`` marks the slots
        that sample this step (decoders, and prefills whose prompt
        completes in this block). The block is cut to the narrowest width
        of ``block_widths`` that holds the chunk's longest grant: a chunk
        in which nobody prefills (or only a short prompt tail does) has
        the dense layers compute a page of rows a slot, not
        ``prefill_chunk``. A ``prefill_chunk``-wide block goes out as it
        is, per-slot ``starts`` / ``n_valid`` / ``n_spec`` beside it: the
        flat rung's row map is computed IN the program from ``n_valid``
        (``paged.FlatRows``), so the control buffer carries nothing more;
        ``flat`` (the tuple's eighth) says whether the chunk takes that
        rung (its row count, else 0: ``_block_width``). Returns None when
        nothing is live."""
        if not self._prefilling and not self._active.any():
            return None
        S, C = self.max_slots, self.prefill_chunk
        blk = np.zeros((S, C), np.int32)
        starts = np.zeros(S, np.int32)
        n_valid = np.zeros(S, np.int32)
        emit = np.zeros(S, bool)
        remaining = np.zeros(S, np.int32)
        eos_arr = np.full((S, self._EOS_WIDTH), -1, np.int32)
        completing: list[int] = []
        handoff_done: list[int] = []
        grants: dict[int, int] = {}
        pf_slots = sorted(self._prefilling)
        # a handoff-marked slot prefills only to T-1: the final prompt
        # token is deliberately NOT granted here — the DESTINATION feeds
        # it as its first decode row, recomputing position T-1's KV
        # bitwise (framing invariance) and making the first draw, so the
        # shipped state matches the staged-adoption ticket contract with
        # zero tokens emitted on this (prefill-pool) side
        pf_rem = [
            len(self._prefilling[s].prefill_tokens)
            - self._prefilling[s].prefill_pos
            - (1 if self._prefilling[s].handoff else 0)
            for s in pf_slots
        ]
        if self._stateful:
            # a grant ends where a snapshot is due (``_next_stop``)
            pf_rem = [
                min(r, self._next_stop(self._prefilling[s])
                    - self._prefilling[s].prefill_pos)
                for s, r in zip(pf_slots, pf_rem)
            ]
        budgets = pack_prefill_budgets(
            pf_rem, C,
            self.prefill_budget if self.prefill_budget > 0 else None,
            phase=self._pack_phase,
        )
        self._pack_phase += 1
        for s, g, rem in zip(pf_slots, budgets, pf_rem):
            req = self._prefilling[s]
            if req.handoff and rem <= 0:
                # already at T-1 (a prefix-cache hit covered everything
                # shippable at admission): freeze at this boundary with
                # no grant at all — the maximal prefix short-circuit
                handoff_done.append(s)
                continue
            if g <= 0:
                continue  # budget exhausted: the slot idles this step
            blk[s, :g] = req.prefill_tokens[
                req.prefill_pos : req.prefill_pos + g
            ]
            starts[s] = req.prefill_pos
            n_valid[s] = g
            grants[s] = g
            if req.handoff:
                if req.prefill_pos + g >= len(req.prefill_tokens) - 1:
                    handoff_done.append(s)  # freeze — no first draw here
            elif req.prefill_pos + g >= len(req.prefill_tokens):
                completing.append(s)
                emit[s] = True
        for s in range(S):
            req = self._slots[s]
            if req is None:
                continue
            if self._active[s]:
                blk[s, 0] = self._tok[s]
                # the slot's current length: every emitted token except
                # the last has been written — the last rides this block
                starts[s] = len(req.prompt) + len(req.tokens) - 1
                n_valid[s] = 1
                emit[s] = True
            if emit[s]:
                remaining[s] = req.budget - len(req.tokens)
                ids = sorted(req.eos)[: self._EOS_WIDTH]
                eos_arr[s, : len(ids)] = ids
        n_spec = self._pack_drafts(blk, n_valid, remaining)
        width, flat = self._block_width(n_valid)
        blk = np.ascontiguousarray(blk[:, :width])
        return (blk, starts, n_valid, n_spec, emit, remaining, eos_arr,
                flat, completing, handoff_done, grants)

    @property
    def rungs(self) -> tuple:
        """The step programs this engine runs, ``(width, flat_rows)``
        each, by the rows their ragged pass computes: the narrow width
        (where there is one), the flat rung of the ``prefill_chunk``-wide
        geometry (where it is smaller than the block), the full program."""
        C = self.block_widths[-1]
        return (
            tuple((w, 0) for w in self.block_widths[:-1])
            + (((C, self.flat_rows),) if self.flat_rows else ())
            + (() if self.tiled else ((C, 0),))
        )

    @property
    def tiled(self) -> bool:
        """The wide pass is the tiled one: its row list holds the whole
        block, so it is the one wide program and every chunk's."""
        return self.flat_rows >= self.max_slots * self.prefill_chunk

    # tlint: hot-path
    def _block_width(self, n_valid) -> tuple[int, int]:
        """The chunk's rung ``(width, flat_rows)``, from its ``n_valid``
        alone: the narrowest width of ``block_widths`` that holds the
        longest grant; at ``prefill_chunk`` the flat rung when the chunk's
        live rows fit it, else the full program. Only what is built is
        picked: while ``build_steps``' thread is at work every block goes
        out ``prefill_chunk`` wide to the full program (the same rows
        carry the same tokens; nothing waits)."""
        C = self.block_widths[-1]
        if self._build is not None:
            if not self._build.done():
                return C, 0
            self._join_build()  # what the build raised is raised here
        longest = int(n_valid.max())
        width = next(w for w in self.block_widths if longest <= w)
        if width == C and (
            self.tiled or 0 < int(n_valid.sum()) <= self.flat_rows
        ):
            return C, self.flat_rows
        return width, 0

    def _join_build(self, timeout: float | None = None) -> None:
        """Wait for ``build_steps``' thread (fetches from the persistent
        cache: seconds; compiles where nothing is cached: a minute) and
        raise what it raised; from there on every rung is packed. An idle
        engine waits ``timeout`` seconds and no longer (``step_chunk``):
        the request that arrives meanwhile waits behind this, and the
        server ends a stream after 30 s without an event. A thread that is
        not through by then goes on behind the requests; the next idle
        moment waits again."""
        build = self._build
        if build is None:
            return
        t0 = time.monotonic()
        done, _ = wait((build,), timeout)
        self._step_build_waited_s += time.monotonic() - t0
        if done:
            self._build = None  # what it raised is raised once
            self._step_build_s += build.result()  # the thread's own seconds

    def _pack_drafts(self, blk, n_valid, remaining):
        """Draft-budget packing, the speculative half of the packed
        block: each opted-in DECODING slot proposes a prompt-lookup draft
        (engine/spec.py — host-side, zero model cost) and packs it as
        extra valid rows after its current token; the unified step
        verifies all of them in-program. Grants ride the same
        round-robin fairness helper as prefill budgets
        (:func:`pack_prefill_budgets` under ``spec_budget``) — and
        because draft rows live in decode slots' OWN rows, speculation
        never shrinks a co-resident prefill's grant regardless of
        budget. Returns the per-slot draft counts ``n_spec`` (mutating
        ``blk``/``n_valid`` in place for granted drafts)."""
        S = self.max_slots
        n_spec = np.zeros(S, np.int32)
        if self.spec_width <= 1:
            return n_spec
        cands: list[tuple[int, list[int]]] = []
        for s in range(S):
            req = self._slots[s]
            if req is None or not self._active[s] or not req.speculative:
                continue
            if req.spec_state is None:
                # lazy arming: prescan the history once (prompt + any
                # recovered/pre-preempt tokens); the controller then
                # lives with the REQUEST, so preemption/requeue keeps
                # the permanent kill switch — it never re-probes
                req.spec_state = SpecController(self.spec_draft, rearm=True)
                req.spec_state.prescan(req.prompt + req.tokens)
            ctl = req.spec_state
            if not ctl.active:
                continue
            # cap: the draft must fit the block row, the budget (at most
            # remaining tokens can emit this pass, k drafts + 1 bonus),
            # and the slot's allocated pages (budget implies allocation)
            cap = min(self.spec_draft, int(remaining[s]) - 1)
            if cap < 1:
                continue
            draft = ctl.draft(req.prompt + req.tokens, cap=cap)
            if draft:
                cands.append((s, draft))
        if not cands:
            return n_spec
        grants = pack_prefill_budgets(
            [len(d) for _, d in cands], self.spec_draft,
            self.spec_budget if self.spec_budget > 0 else None,
            phase=self._spec_phase,
        )
        self._spec_phase += 1
        for (s, draft), g in zip(cands, grants):
            if g <= 0:
                continue
            d = draft[:g]
            blk[s, 1 : 1 + len(d)] = d
            n_valid[s] = 1 + len(d)
            n_spec[s] = len(d)
            # credit the GRANTED length, not the proposal — the trace
            # span's per-request drafted count must match what the
            # engine's spec_drafted counter saw under a draft budget
            self._slots[s].spec_state.drafted += len(d)
        return n_spec

    # tlint: hot-path
    def _step_operands(self, blk, starts, n_valid, n_spec, emit, remaining,
                       eos_arr) -> tuple:
        """The step program's positional operands: this chunk's packed
        block, per-slot control rows and sampling knobs in ONE host
        buffer (``paged.pack_control``: the call places it, nothing is
        placed here) beside the engine's resident state (weights, page
        cache, histograms)."""
        ctl = pack_control(
            blk, starts, n_valid, n_spec, emit, self._seeds, self._steps,
            self._temp, self._topk, self._topp, self._pres, self._freq,
            remaining, eos_arr, self._bind, self._len0, self._reset,
            self._bt_host,
        )
        return (self.engine.params, ctl, self.cache, self._counts)

    def _count_latent(self, step_stats, starts, n_valid, emit, n_exec):
        """A patterned model's counters of one chunk: the step's own
        (``STEP_STATS``, already summed over its layers and steps) and,
        from the contexts as packed (every slot with a row in the ragged
        pass, the emitting ones each further step): the pages the sliding
        layers' window spans reach against the pages of context under
        them, and the rows the walk of the full layers that select
        nothing reads against the rows those slots could hold."""
        from ..models.latent import kind_counts
        from ..models.sala import step_stats as names_of

        for name, v in zip(names_of(self.cfg), step_stats):
            self._count(name, int(v))
        ctx = starts + n_valid
        rows = n_valid > 0
        layers = kind_counts(self.cfg)
        sizes = dict(self.cfg.latent)

        def over_passes(x):  # summed over the slots of each pass
            return int(x[rows].sum() + (n_exec - 1) * x[emit].sum())

        for kind in ("sliding", "gqa_window"):
            if not layers.get(kind):
                continue
            pages = -(-ctx // self.page_size)
            first = np.maximum(
                starts - (sizes[kind].window - 1), 0) // self.page_size
            self._count("window_pages_walked",
                        layers[kind] * over_passes(pages - first))
            self._count("window_pages_context",
                        layers[kind] * over_passes(pages))
        if layers.get("full") and not sizes["full"].index_heads:
            self._count("latent_rows_read", layers["full"] * over_passes(ctx))
            self._count("latent_rows_capacity", layers["full"] * over_passes(
                np.full_like(ctx, self.cache.pages_per_slot * self.page_size)
            ))

    def lower_step(self, width: int | None = None, *,
                   flat: bool | None = None):
        """The step program lowered at this engine's own shapes and
        placement, not run: what ``chip_smoke.py`` reads to prove the
        Pallas kernel (``tpu_custom_call``) and, sharded, the collectives
        are in the program that serves. One program a rung of ``rungs``:
        ``width`` names the block's (the widest when not given) and
        ``flat`` the flat rung of the widest (not given: what serves a
        block with every row live, the tiled pass where the engine has
        it, else the full program; False is the full program, which a
        tiled engine never runs). Call it on an idle engine."""
        S = self.max_slots
        C = self.block_widths[-1] if width is None else int(width)
        if flat is None:
            flat = self.tiled and C == self.block_widths[-1]
        if C not in self.block_widths:
            raise ValueError(
                f"width {C} is not one of this engine's {self.block_widths}"
            )
        if flat and (not self.flat_rows or C != self.block_widths[-1]):
            raise ValueError(f"no flat rung at width {C} ({self.rungs})")
        zi = np.zeros(S, np.int32)
        ops = self._step_operands(
            np.zeros((S, C), np.int32), zi, zi, zi, np.zeros(S, bool),
            zi, np.full((S, self._EOS_WIDTH), -1, np.int32),
        )
        if self._tp_step is not None:
            return (self._tp_flat_step if flat else self._tp_step).lower(*ops)
        return paged_ragged_step.lower(  # spelled as step_chunk's call
            *ops, cfg=self.cfg, n_steps=self.chunk_steps,
            spec_width=self.spec_width, kernel=self.use_kernel,
            flat_rows=self.flat_rows if flat else 0,
        )

    def build_steps(self) -> None:
        """Build the step program of every rung of ``rungs`` (compiled,
        or fetched from the persistent cache) without running one: what a
        server calls once, before traffic
        (``ml/worker.py::_ensure_cont``). It returns when the FULL
        program is built, which serves every chunk; the other rungs'
        compiles go on behind the first requests, on a thread. Until they
        are through, ``_pack_ragged`` sends every block ``prefill_chunk``
        wide to the full program (``_block_width``; counted
        ``ragged_blocks_narrow_unbuilt`` / ``ragged_blocks_flat_unbuilt``),
        and each time the engine runs out of work it waits for the thread,
        ``BUILD_JOIN_MAX_S`` at a time (``step_chunk``), so no request
        waits for a rung while the full program can serve it, and fetches
        from the persistent cache are through by an engine's first or
        second idle moment. What the thread raised is raised there, on
        the serving path. The call at a rung
        then finds its program built: the jitted function's own lowering
        is what is compiled here.

        The order (cached, qwen3-4b on a v5e; PERF.md section 7): the
        full program is traced and lowered (1.4-1.6 + 1.45 s) and handed
        to a thread (a fetch of 1.75 s, 2.1 beside this thread's work)
        while this thread traces and lowers the others, each handed to
        the same thread as it is lowered (one worker: on a thread of its
        own a fetch started 0.2 s sooner and took 1.7-1.8 s for 1.0).
        DeepSeek-V2's engine (one width, two programs: 4.2 s to trace and
        lower the full one, 2.5 to fetch it) lowers the flat rung beside
        the full program's fetch: `warm requests` 6.9 -> 10.2 s, and the
        document then fills in 4.7 s for 8.4 on the rung (my chip runs,
        PR 45).

        An engine of one program has nothing to build ahead: its first
        chunk builds it, as ever. Call it on an idle engine.

        What this costs is counted here and where the thread is joined:
        ``step_build_ms`` takes every lowering and each fetch's own
        seconds (what overlaps counts twice), ``step_build_waited_ms``
        this call from entry to return, then the join's wait."""
        if len(self.rungs) == 1 or self._build is not None:
            return
        t0 = time.monotonic()
        pool = ThreadPoolExecutor(1, thread_name_prefix="build-step")
        lowered_rungs: queue.SimpleQueue = queue.SimpleQueue()

        def behind() -> float:
            """Each rung as this thread hands it over, lowered; the
            thread's own seconds. One job, so what a rung's compile raises
            ends it and is read where the thread is joined."""
            return sum(
                _timed(low.compile) for low in iter(lowered_rungs.get, None)
            )

        try:
            full = pool.submit(_timed, self.lower_step().compile)
            # one worker: a rung's fetch follows the one before it
            self._build = pool.submit(behind)
            for width, flat_rows in self.rungs[:-1]:
                lowered_rungs.put(
                    self.lower_step(width, flat=True) if flat_rows
                    else self.lower_step(width)
                )
            lowered = time.monotonic() - t0  # all, traced too: this thread
            built = full.result()  # what it raised is raised here
        finally:
            lowered_rungs.put(None)  # the thread ends behind the last rung
            pool.shutdown(wait=False)
        # the other rungs' seconds follow where the thread is joined
        self._step_build_s += lowered + built
        self._step_build_waited_s += time.monotonic() - t0
        self._unbuilt.clear()  # no call of this engine builds a program

    def _step_programs(self) -> int:
        """Step programs in the jit cache this engine's calls fill."""
        if self._tp_step is None:
            return paged_ragged_step._cache_size()
        return self._tp_step._cache_size() + (
            self._tp_flat_step._cache_size() if self._tp_flat_step else 0
        )

    def _note_first_call(self, rung: tuple, programs: int, dur_s: float):
        """After this engine's first call at ``rung`` (no
        ``build_steps`` came before it): where the call grew the jit
        cache from ``programs`` it built its program, and the dispatch
        phase's ``dur_s`` is build time that a serving path waited for."""
        self._unbuilt.discard(rung)
        if self._step_programs() > programs:
            self._step_build_s += dur_s
            self._step_build_waited_s += dur_s

    # tlint: hot-path
    def step_chunk(self, *, admit_only: bool = False) -> bool:
        """Admit queued requests, then run ONE compiled step program
        (the step at the rung this chunk's rows picked: one program a
        rung of ``rungs``, at most three).

        The packed ragged block — every mid-prefill slot's next prompt
        piece AND every decode slot's next token in one dispatch —
        followed by the decode continuation loop, all inside the single
        ``ragged_step`` program: a decode slot's inter-token latency is
        one step whether or not a co-resident admission is prefilling
        (no separate prefill dispatches to wait behind), and a
        completing prefill samples its first token in the same dispatch
        that finishes its prompt. Runs ``chunk_steps`` fixed-shape slot
        steps per host round trip, settles each slot's tokens up to its
        own done-point, and retires finished slots at the boundary. The
        tokens leave for their callbacks behind the NEXT call's dispatch,
        while the device runs that chunk, or at once when no step follows
        (nothing dispatched, or no work left). Returns True while any
        work (live slots or queued requests) remains — the driver's
        requeue signal."""
        # the anatomy of a chunk (docs/SERVING.md "Observability"): the
        # phases below are marked where the work happens, on the
        # profiler's host line (tlink:<phase> inside one tlink:chunk that
        # carries this chunk's flight-recorder step) and as monotonic
        # pairs in the chunk's record and the chunk_us_* counters. No
        # sync is added: the device is waited for at np.asarray(out),
        # the chunk's one fetch.
        t0 = time.monotonic()
        between = (
            t0 - self._chunk_exit_t if self._chunk_exit_t is not None else 0.0
        )
        step = self._chunk_step = self.recorder.next_step
        stream_us0 = self._stat["chunk_us_stream"].value
        intake_us0 = self._stat["chunk_us_intake"].value
        ph: dict = {}
        fields = None
        with jax.profiler.TraceAnnotation("tlink:chunk", chunk=step):
            with _Phase(ph, "admit"):
                self._admit()
            pack = None
            if not admit_only:
                with _Phase(ph, "pack"):
                    pack = self._pack_ragged()
            if pack is not None:
                blk, starts, n_valid, n_spec, emit, remaining, eos_arr, \
                    flat, completing, handoff_done, grants = pack
                programs = self._step_programs() if self._unbuilt else 0
                with _Phase(ph, "dispatch"):
                    # read before delivery releases a finished slot
                    any_sampled = bool((self._temp > 0).any())
                    ops = self._step_operands(
                        blk, starts, n_valid, n_spec, emit, remaining,
                        eos_arr,
                    )
                    if self._tp_step is not None:
                        # sharded hot path: same program semantics,
                        # weights/KV are device-local shards; the control
                        # buffer is replicated by the call
                        step = self._tp_flat_step if flat else self._tp_step
                        out, self.cache, self._counts = step(*ops)
                    else:
                        out, self.cache, self._counts = paged_ragged_step(
                            *ops, cfg=self.cfg, n_steps=self.chunk_steps,
                            spec_width=self.spec_width,
                            kernel=self.use_kernel, flat_rows=flat,
                        )
                    # the program took the binds and resets it carried
                    self._bind[:] = False
                    self._reset[:] = False
                    # host arrays the call placed on the device(s)
                    placed = sum(isinstance(x, np.ndarray) for x in ops)
                if self._unbuilt:
                    self._note_first_call(
                        (blk.shape[1], flat), programs, ph["dispatch"]
                    )
                with _Phase(ph, "wait") as wait:
                    # the wait laid out end to end: the stream stage, the
                    # intake, the fetch. The driver asks the result whether
                    # it is ready at the boundaries it passes on the way
                    # (``_seen_ready``: no sync) up to the first True
                    self._ready_poll = getattr(out, "is_ready", None)
                    self._ready_t = None
                    self._unready_t = wait.t0
                    try:
                        # the device runs this chunk: the one before it
                        # leaves for its callbacks meanwhile, nothing below
                        # reads what they return but a stop (settled next)
                        self.flush_stream(in_flight=True)
                        streamed = time.monotonic()
                        if self.intake is not None:
                            # ... and what arrives meanwhile is taken in and
                            # prepared for the chunk after this one
                            self._take_in(out)
                        # where the intake ends (on CHUNK_DONE: ready) and
                        # the driver comes to the fetch
                        self._seen_ready()
                    finally:
                        # (a callback that raised leaves no hold on the
                        # result in flight)
                        self._ready_poll = None
                    with _Phase(ph, "fetch") as fetch:
                        # the chunk's one fetch, and the one value that
                        # blocks: the device is done here
                        out = np.asarray(out)
                # what of the wait lay behind the first sight of a ready
                # result: the driver's own work kept it from the fetch
                ready = self._ready_t
                if ready is None:  # it blocked in the fetch, or cannot say
                    ph.update(late_stream=0.0, late_intake=0.0, late_max=0.0)
                else:
                    ph["late_stream"] = max(0.0, streamed - ready)
                    ph["late_intake"] = fetch.t0 - max(ready, streamed)
                    # ... and at most that with the entry or take whose end
                    # first saw it: the result turned ready inside it
                    ph["late_max"] = fetch.t0 - self._unready_t
                with _Phase(ph, "drain"):
                    # the host's copy split (the step's own counts of a
                    # patterned model rode it too)
                    toks_host, n_tok_host, spec_m_host, n_exec, \
                        step_stats = unpack_results(
                            out, self.chunk_steps, self.spec_width
                        )
                # the chunk's host-visible wall time — measured at the
                # ONE existing boundary sync, so span recording adds no
                # device round trips of its own
                chunk_dur = ph["dispatch"] + ph["wait"] + ph["drain"]
                with _Phase(ph, "deliver"):
                    delivered_total = self._settle(
                        grants, completing, handoff_done, emit, n_spec,
                        n_exec, toks_host, n_tok_host, spec_m_host,
                        chunk_dur,
                    )
                    if not self.has_work():
                        self.flush_stream()  # no step follows to hide it
                with _Phase(ph, "post"):
                    n_rows = int(n_valid.sum())
                    # what the pass computed position-wise (the tiled
                    # pass: the tiles its live rows reach into)
                    rows_computed = flat or blk.size
                    if flat and self.tiled:
                        tile = row_tile(*blk.shape)
                        rows_computed = -(-n_rows // tile) * tile
                    self._count("ragged_rows_valid", n_rows)
                    self._count("ragged_rows_computed", rows_computed)
                    self._count("ragged_blocks")
                    self._count("chunk_host_arrays", placed + 1)  # + out
                    if blk.shape[1] < self.prefill_chunk:
                        self._count("ragged_blocks_narrow")
                    elif flat:
                        self._count("ragged_blocks_flat")
                    elif int(n_valid.max()) <= self.block_widths[0] < (
                        blk.shape[1]
                    ):
                        # it fitted the narrow width, whose program was
                        # still being built
                        self._count("ragged_blocks_narrow_unbuilt")
                    elif n_rows <= self.flat_rows:
                        # it fitted the flat rung, whose program was
                        # still being built
                        self._count("ragged_blocks_flat_unbuilt")
                    # what the kernels' walk follows, from the contexts
                    # as packed: every slot with a row rides the ragged
                    # pass, the emitting ones each further step (at the
                    # packed context: off by at most a page a slot)
                    pages = -(-(starts + n_valid) // self.page_size)
                    self._count("attn_pages_live", int(
                        pages[n_valid > 0].sum()
                        + (n_exec - 1) * pages[emit].sum()
                    ))
                    self._count(
                        "attn_pages_capacity",
                        n_exec * blk.shape[0] * self.cache.pages_per_slot,
                    )
                    self._count("ragged_slots_live", int((n_valid > 0).sum()))
                    self._count(
                        "ragged_slots_single", int((n_valid == 1).sum()))
                    # the epilogue's work follows the slots: the walk is
                    # as long as the longest emitting draft, and every
                    # call sorts only if some packed slot samples
                    walked = int(np.where(emit, n_spec + 1, 0).max())
                    n_calls = walked + n_exec - 1
                    self._count("sampler_calls", n_calls)
                    if any_sampled:
                        self._count("sampler_calls_sampled", n_calls)
                    self._count("verify_rows_walked", walked)
                    self._count("verify_rows_capacity", self.spec_width)
                    if self._latent:
                        self._count_latent(
                            step_stats, starts, n_valid, emit, n_exec
                        )
                    if self._tp_step is not None:
                        # the ragged pass gathers every block row through
                        # the layers and the verify rows through the head,
                        # each continuation step one row a slot
                        S = blk.shape[0]
                        rows, head, calls = self._tp_gather
                        self._count("tp_gather_bytes", int(
                            rows * (rows_computed + (n_exec - 1) * S)
                            + head * S * (self.spec_width + n_exec - 1)
                        ))
                        self._count("tp_gather_calls", calls * n_exec)
                    # flight recorder (core/trace.py): the postmortem's
                    # per-step state; the append follows the phase's end
                    # because the record holds post_ms
                    fields = dict(
                        live_slots=(
                            int(self._active.sum()) + len(self._prefilling)
                        ),
                        prefilling=len(self._prefilling),
                        decode_steps=n_exec if bool(emit.any()) else 0,
                        prefill_granted=int(sum(grants.values())),
                        block_rows=blk.shape[1],  # the width that ran
                        rows_computed=rows_computed,  # ... and the rung
                        spec_drafted=int(n_spec.sum()),
                        tokens_emitted=delivered_total,
                        pages_free=self.alloc.n_free,
                        pages_in_transit=self._pages_in_transit(),
                        preemptions=int(self._stat["preemptions"].value),
                    )
                    self._refresh_prefix_digest()
            else:
                with _Phase(ph, "deliver"):
                    self.flush_stream()  # nothing was dispatched
        more = self.has_work()
        if not more:
            # out of work for now: nobody waits for this engine, so the
            # build behind (build_steps) is waited for here
            self._join_build(BUILD_JOIN_MAX_S)
        if fields is None:
            # nothing was dispatched (admission only, or nothing live):
            # no record; the round stays part of what lies between two
            # chunks unless the engine ran out of work
            if not more:
                self._chunk_exit_t = None
            return more
        ph["between"] = between
        counted = ("between",) + CHUNK_PHASES + LATE_PARTS
        us = {
            # (fetch: the end of wait, what the driver blocked for the
            # device; late_max: the lateness's upper bound; record alone)
            k: int(ph[k] * 1e6 + 0.5) for k in counted + ("fetch", "late_max")
        }
        for k in counted:
            self._count(f"chunk_us_{k}", us[k])
        # host_ms and chunk_ms keep their meaning: step_chunk's entry to
        # the dispatch, and the dispatch to the end of the drain
        self.recorder.record(
            **fields,
            chunk_ms=(us["dispatch"] + us["wait"] + us["drain"]) / 1e3,
            host_ms=(us["admit"] + us["pack"]) / 1e3,
            t0=t0,
            **{f"{k}_ms": v / 1e3 for k, v in us.items()},
            # a sub-span (of wait, or of deliver with no step in flight)
            stream_ms=(self._stat["chunk_us_stream"].value - stream_us0) / 1e3,
            # ... and one of wait alone
            intake_ms=(self._stat["chunk_us_intake"].value - intake_us0) / 1e3,
        )
        self._chunk_exit_t = ph["end"] if more else None
        return more

    # tlint: hot-path
    def _take_in(self, result) -> None:
        """The intake of a chunk's wait: every request that arrives
        before ``result`` (the step's, in flight) is ready is submitted
        where it arrives, and an ahead round follows each, so that the
        edge finds its admission prepared. ``self.intake`` blocks between
        the requests and says when to stop; the time counted is the
        host's work, not its waiting."""
        busy = 0.0
        # the fetch that follows finds the host's copy made: it is queued
        # behind the step now and not when the intake has come back
        result.copy_to_host_async()
        self.taking_in = True
        try:
            for take in self.intake(result):
                t0 = time.monotonic()
                with jax.profiler.TraceAnnotation("tlink:intake"):
                    take()
                    self._admit_ahead()
                busy += time.monotonic() - t0
                self._seen_ready(t0)
        finally:
            self.taking_in = False
            self._count("chunk_us_intake", int(busy * 1e6 + 0.5))

    # tlint: hot-path
    def _seen_ready(self, since: float | None = None) -> None:
        """Inside a chunk's wait, at a boundary the driver passes anyway
        (a stream callback returned, an intake take and its ahead round
        are through, the intake ended): ask the step's result whether it
        is ready, and stamp the first True. ``is_ready`` blocks for
        nothing and syncs nothing; a result over a mesh is ready when
        every shard is. Not asked again after the first True, and never
        of a result that has no ``is_ready``. The stamp is an upper bound
        of the moment the device was done, late by at most the entry or
        take before it: the lateness read from it is a lower bound.
        ``since`` is where that entry or take began (None: the driver had
        been blocked, or had just asked): ``_unready_t`` keeps it, and the
        lateness of the driver's own work is at most what lay behind it
        (the record's ``late_max_ms``)."""
        poll = self._ready_poll
        if poll is None:
            return
        ready = poll()
        now = time.monotonic()
        if ready:
            self._ready_t = now
            self._ready_poll = None
            self._unready_t = now if since is None else since
        else:
            self._unready_t = now

    # tlint: hot-path
    def _settle(self, grants, completing, handoff_done, emit, n_spec,
                n_exec, toks_host, n_tok_host, spec_m_host,
                chunk_dur) -> int:
        """The settle stage of a chunk, after the drain: everything the
        next admission and pack read. Prefill bookkeeping, each emitting
        slot's tokens onto ``req.tokens`` up to its own done-point (EOS,
        budget: decided from the token array itself) and the teardown of
        finished slots. It makes no callback: what it settled waits in
        ``_unstreamed`` for the stream stage. Returns the tokens settled."""
        S = self.max_slots
        step = self._chunk_step
        # prefill bookkeeping: the grants landed on device; completed
        # prompts switch to decode mode before delivery (their first
        # token is column 0 of this very chunk)
        for s, g in grants.items():
            req = self._prefilling[s]
            req.prefill_pos += g
            if self._stateful:
                self._snapshot_slot(req, s)
            self._count("prefill_chunks")
            self._count("prefill_tokens", g)
            self._trace(
                req, "prefill_chunk", dur_s=chunk_dur, tokens=g,
                pos=req.prefill_pos, chunk=step,
            )
        now = time.monotonic()
        for s in completing:
            req = self._prefilling[s]
            # a locally-resumed handoff (abort_handoff) already recorded
            # its prefill span at the freeze — completing the final
            # token must not emit a second one (the TTFT decomposition
            # would double-count the prefill leg)
            already_traced = bool(req.prefill_done_t)
            req.prefill_done_t = now
            if not already_traced:
                self._trace(
                    req, "prefill",
                    dur_s=(now - req.admit_t) if req.admit_t else None,
                    t0=req.admit_t or None,
                    tokens=req.prefill_pos, chunk=step,
                )
            del self._prefilling[s]
            self._active[s] = True
        for s in handoff_done:
            # the prefill→decode boundary, frozen WITHOUT a first draw
            # (grants stopped at T-1): the slot leaves the prefilling set
            # straight into the frozen (in-transit) state — _tok carries
            # the final prompt token so the export's last_tok is exactly
            # what the destination's first decode row must feed. Unlike
            # begin_drain, nothing fences admission: co-resident slots
            # keep stepping and new requests keep admitting while this
            # one waits for the driver to ship it.
            req = self._prefilling.pop(s)
            req.prefill_done_t = now
            self._trace(
                req, "prefill",
                dur_s=(now - req.admit_t) if req.admit_t else None,
                t0=req.admit_t or None,
                tokens=req.prefill_pos, chunk=step,
            )
            self._tok[s] = int(req.prefill_tokens[-1])
            self._frozen.add(s)
            self._handoff_ready.append(s)
            self._count("handoffs_started")
            self._trace(req, "freeze", slot=s, tokens=0)
        if emit.any():
            # prefill-only steps decode nothing — don't count them
            self._count("decode_steps", n_exec)
            self._count("slot_steps_total", n_exec * S)
        delivered_total = 0
        for s in range(S):
            if not emit[s]:
                continue
            req = self._slots[s]
            if req.cancelled:
                # its stream stopped while this chunk ran: the chunk's
                # tokens of this slot are dropped, none went anywhere
                self._retire(s)
                self._unstreamed[req.rid] = (req, 0, False, True, step)
                continue
            if n_spec[s] > 0 and req.spec_state is not None:
                # verify-pass accounting feeds the per-request kill
                # switch (engine/spec.py): spec_m is the pass's emitted
                # count — accepted drafts + the one bonus/correction
                m = int(spec_m_host[s])
                self._count("spec_drafted", int(n_spec[s]))
                self._count("spec_accepted", max(m - 1, 0))
                self._count("spec_verify_passes")
                if req.spec_state.note_verify(m):
                    self._count("spec_killed")
            finished = False
            emitted = 0
            first = not req.tokens
            for i in range(int(n_tok_host[s])):
                tok = int(toks_host[s, i])
                if req.spec_state is not None:
                    # keep the re-arm pair set current (a stream whose
                    # text turns repetitive re-arms on the first
                    # recurring pair — unless the kill switch fired)
                    prev = req.tokens[-1] if req.tokens else (
                        req.prompt[-1] if req.prompt else tok
                    )
                    req.spec_state.note_pair(prev, tok)
                self._tok[s] = tok
                emitted += 1
                req.tokens.append(tok)
                if tok in req.eos or len(req.tokens) >= req.budget:
                    finished = True
                    break
            # the chunk's frozen slots stopped their key chain exactly
            # where the host delivery stops, so the emitted count IS the
            # step advance (authoritative over the device mirror when an
            # EOS id overflowed _EOS_WIDTH)
            self._steps[s] += emitted
            self._count("slot_steps_live", emitted)
            delivered_total += emitted
            if finished:
                self._retire(s)
            if emitted:
                self._unstreamed[req.rid] = (
                    req, emitted, first, finished, step,
                )
        return delivered_total

    def _refresh_prefix_digest(self) -> None:
        """Rebuild the fleet digests (both tiers) when membership
        changed since the last chunk. Driver-thread only (the trie and
        host pool are driver state); each swap is atomic so snapshot
        readers never see a torn dict."""
        if self.prefix is None:
            return
        if self.prefix.version != self._digest_version:
            self._digest_version = self.prefix.version
            self._prefix_digest = self.prefix.digest()
        if self.host_tier is not None and (
            self.host_tier.version != self._host_digest_version
        ):
            self._host_digest_version = self.host_tier.version
            self._host_digest = self.host_tier.digest()

    def run_until_idle(self) -> None:
        """Drive the loop to quiescence (tests, bench, local serving)."""
        while self.step_chunk():
            pass

    def close(self, error: BaseException | None = None) -> None:
        """Fail everything still queued or in flight (model unhosting /
        engine teardown). A real error dumps the flight recorder — the
        last N chunks of slot/page state ride ``recorder.last_dump`` so a
        chaos postmortem reads data, not prints."""
        from ..core.logging import get_logger

        err = error or RuntimeError("continuous engine closed")
        build, self._build = self._build, None
        if build is not None:
            # a closed engine packs no block: a compile still queued is
            # dropped, one under way ends on its thread, unread
            build.cancel()
        if error is not None:
            dump = self.recorder.dump(error)
            get_logger("engine.flight").warning(
                "engine error — flight recorder dumped %d step records "
                "(last: %s)",
                dump["n_records"],
                dump["records"][-1] if dump["records"] else None,
            )
        while self._unstreamed:
            # what was settled leaves before anything is failed; a
            # callback that raises loses its own stream and no other
            try:
                self.flush_stream()
            except Exception:
                get_logger("engine.flight").exception(
                    "a stream callback raised while the engine closed"
                )
        with self._lock:
            pending = self.sched.pending()
            for req in pending:
                self.sched.remove(req)
        # an admission prepared and never committed (the chunk it waited
        # behind failed, or the engine goes before the next one): its
        # pages and references return, its request fails like a queued one
        pending += [self._unprepare(s) for s in list(self._prepared)]
        for s in range(self.max_slots):
            req = self._slots[s]
            if req is not None:
                req.error = err
                self._evict(s)
        for req in pending:
            req.error = err
            self._finish(req, finished=False)
        # staged adoptions whose resume never arrived die with the engine
        for mig_id in list(self._migrations):
            self.drop_staged_migration(mig_id)
        if self.pool is not None and self.prefix is not None:
            # a pool tenant's resident prefixes die with its engine (the
            # trie's pages belong to the shared pool — leaving them
            # parked would leak them past this tenant's detach)
            self.alloc.free(self.prefix.drop_all())
        # teardown invariant: with every slot evicted and every staged
        # migration released, the free-list plus the cache-resident set
        # must account for every usable page — a violation here means a
        # leak or a double-ownership upstream
        self.check_page_conservation()
        if self.pool is not None:
            # detach so the pool stops walking this tenant (and the model
            # id frees up for a rebuilt engine); keep a frozen cache view
            # so post-close telemetry reads don't dangle
            frozen = self.cache
            self.pool.detach(self.model_id)
            self.pool = None
            self._cache = frozen


__all__ = [
    "ContinuousEngine", "ContinuousRequest", "PagedUnsupported",
    "pack_prefill_budgets", "paged_unsupported",
]
