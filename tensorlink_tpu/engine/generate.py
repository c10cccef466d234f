"""Compiled generation engine: prefill/decode program pair with bucketing.

TPU-native replacement for the reference's eager ``model.generate()`` on the
worker (ml/worker.py:359-430 + streaming TensorlinkWorkerStreamer):

- **prefill** and **decode** are separate jit programs; the KV cache is a
  donated pytree so decode updates it in place (zero realloc per token).
- Shapes are **bucketed** (batch, prompt length) so a serving worker compiles
  a small, bounded set of programs instead of thrashing XLA on every request
  shape (SURVEY §7.3.5 recompilation management).
- The inner token loop can run fully on device (``lax.while_loop`` with
  early-exit on EOS) for throughput, or host-driven step-by-step for SSE
  streaming (tokens stream through the TOKEN relay like the reference's
  streamer, 4-hop path SURVEY §3.4).
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass
from functools import partial
from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import KVCache, ModelConfig
from ..models.transformer import forward
from .sampling import SamplingParams, sample

DEFAULT_SEQ_BUCKETS = (128, 256, 512, 1024, 2048, 4096)
DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8)


def _bucket(value: int, buckets: Sequence[int]) -> int:
    i = bisect.bisect_left(buckets, value)
    if i == len(buckets):
        raise ValueError(f"{value} exceeds largest bucket {buckets[-1]}")
    return buckets[i]


@partial(
    jax.jit, static_argnames=("cfg", "fmesh"), donate_argnames=("cache",)
)
def _prefill(params, tokens, attn_mask, cache, cfg: ModelConfig, fmesh=None):
    # flash_prefill is safe here and only here: the engine always prefills
    # a FRESH cache (offset 0, right-padded buckets); fmesh routes the
    # kernel through shard_map on sharded engines
    logits, cache = forward(
        params, tokens, cfg, cache=cache, attn_mask=attn_mask,
        flash_prefill=cfg.flash_attention, flash_mesh=fmesh,
    )
    # logits of the last *real* token per row
    last = jnp.maximum(attn_mask.sum(-1) - 1, 0)
    return jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0], cache


@partial(
    jax.jit,
    static_argnames=("cfg", "first", "fmesh"),
    donate_argnames=("cache",),
)
def _prefill_chunk(
    params, tokens, attn_mask, cache, cfg: ModelConfig, first, fmesh=None
):
    """One chunk of a long-prompt prefill: returns the final-norm hidden
    states (the vocab head runs ONCE at the end of chunking, not per
    chunk) and the grown cache. Flash only on the first chunk (offset 0)."""
    hidden, cache = forward(
        params, tokens, cfg, cache=cache, attn_mask=attn_mask,
        return_hidden=True,
        flash_prefill=cfg.flash_attention and first,
        flash_mesh=fmesh,
    )
    return hidden, cache


@partial(jax.jit, static_argnames=("cfg",))
def _head_from_hidden(params, hidden, cfg: ModelConfig):
    from ..models.transformer import _logits

    # hidden is already final-normed (forward(return_hidden=True))
    return _logits(params, hidden[:, None], cfg)[:, 0]


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def _decode_step(params, tok, cache, cfg: ModelConfig):
    logits, cache = forward(params, tok[:, None], cfg, cache=cache)
    return logits[:, 0], cache


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def _verify_step(params, toks, cache, cfg: ModelConfig):
    """Speculative verification: one forward over [tok, draft...] returns
    greedy targets at every position. The cache absorbs all positions;
    rejected ones are rolled back by resetting ``length`` — attention masks
    by length, so stale writes are invisible and simply overwritten later
    (no copy, the reason speculation is cheap in this engine)."""
    logits, cache = forward(params, toks, cfg, cache=cache)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


@partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "penalize"),
    donate_argnames=("cache",),
)
def _decode_loop(
    params,
    first_tok,  # [B] int32 — token sampled from prefill logits
    cache: KVCache,
    key,
    sampling: SamplingParams,
    eos_ids,  # int32 [n_eos] (pad with -1)
    limits,  # int32 [B] — loop tokens allowed per row (after first_tok)
    counts,  # int32 [B, V] context token counts (dummy when not penalize)
    cfg: ModelConfig,
    n_steps: int,
    penalize: bool = False,
):
    """Fully on-device decode: while_loop with EOS early exit.

    Emits ``tokens [B, n_steps]`` (first_tok included at index 0's successor
    position; i.e. tokens holds the *newly generated* tokens after
    first_tok). ``limits`` freezes rows individually — batched requests mix
    different budgets and different cache rooms without a host round-trip
    per step. ``penalize`` (static) threads per-token context counts
    through the loop for presence/frequency penalties — a separate program
    so the penalty-free path never pays the [B, V] carry.
    """
    B = first_tok.shape[0]
    tokens = jnp.zeros((B, n_steps), jnp.int32)
    done0 = jnp.isin(first_tok, eos_ids) | (limits <= 0)

    def cond(state):
        return (state[0] < n_steps) & ~state[3].all()

    def body(state):
        if penalize:
            i, tok, cache, done, key, tokens, counts = state
        else:
            i, tok, cache, done, key, tokens = state
            counts = None
        prev_len = cache.length
        logits, cache = forward(params, tok[:, None], cfg, cache=cache)
        # freeze the per-row write offset for finished rows: their re-fed
        # token writes one scratch KV slot at prev_len (invisible — attention
        # masks by length) instead of marching toward the cache end and
        # clamping over real entries. Residual: a row frozen exactly at full
        # room (length == max_len) still clamp-writes its last slot, so the
        # post-loop cache is only valid for rows with room left — every
        # caller deletes the cache after the loop.
        cache = KVCache(
            k=cache.k, v=cache.v,
            length=jnp.where(done, prev_len, cache.length),
            k_scale=cache.k_scale, v_scale=cache.v_scale,
        )
        key, sub = jax.random.split(key)
        nxt = sample(logits[:, 0], sub, sampling, counts)
        nxt = jnp.where(done, tok, nxt)  # freeze finished rows
        out = (i + 1, nxt, cache,
               done | jnp.isin(nxt, eos_ids) | (i + 1 >= limits),
               key, tokens.at[:, i].set(nxt))
        if penalize:
            # frozen rows re-feed the same token — don't recount it
            counts = counts.at[jnp.arange(B), nxt].add(
                jnp.where(done, 0, 1)
            )
            out = out + (counts,)
        return out

    init = (jnp.int32(0), first_tok, cache, done0, key, tokens)
    if penalize:
        init = init + (counts,)
    final = jax.lax.while_loop(cond, body, init)
    n_exec, _, cache, done, key, tokens = final[:6]
    # the advanced key lets chunked callers continue the EXACT per-step
    # split chain across chunk boundaries (sampled parity with a single
    # long loop)
    return tokens, cache, done, n_exec, key


@partial(jax.jit, static_argnames=("k",))
def _beam_topk(logits, k: int):
    """Per-row top-k of the log-softmax — the beam search's candidate
    selection, on device. Ships [rows, k] (score, id) pairs to the host
    instead of [rows, V] logits; ties resolve to the lowest index, matching
    a stable argsort over the negated row."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jax.lax.top_k(logp, k)


def beam_frontier_step(
    beams: list, scores, alive: list, done_pool: list,
    vals, idx, K: int, eos_set: set, room: int, length_penalty: float,
):
    """Pure host-side frontier advance shared by the engine's beam session
    and the pipelined (multi-stage) beam driver (ml/module.py): fold the
    per-beam device top-k candidates ``vals/idx [K, kk]`` into the next
    frontier. Returns ``(beams, scores, alive, src)`` — ``src`` names each
    surviving beam's source row for the KV-cache reorder — or ``None``
    when no live candidates remain. ``done_pool`` is appended in place."""
    kk = vals.shape[1]
    cand: list[tuple[float, int, int]] = []  # (score, beam, token)
    for k in range(K):
        if not alive[k]:
            continue
        for j in range(kk):
            cand.append((scores[k] + float(vals[k, j]), k, int(idx[k, j])))
    cand.sort(key=lambda c: -c[0])
    new_beams, new_scores, new_alive, src = [], [], [], []
    for sc, k, t in cand:
        if len(new_beams) >= K:
            break
        seq = beams[k] + [t]
        if t in eos_set or len(seq) >= room:
            done_pool.append((sc / (len(seq) ** length_penalty), seq))
            if t in eos_set:
                continue  # finished beams leave the frontier
        new_beams.append(seq)
        new_scores.append(sc)
        new_alive.append(t not in eos_set and len(seq) < room)
        src.append(k)
    if not new_beams:
        return None
    # pad the frontier back to K rows (duplicates of row 0 — masked out by
    # alive=False)
    while len(new_beams) < K:
        new_beams.append(new_beams[0])
        new_scores.append(-np.inf)
        new_alive.append(False)
        src.append(src[0])
    return new_beams, np.asarray(new_scores), new_alive, src


@dataclass
class BeamState:
    """Resumable beam-search session (engine.beam_start/advance/finish).

    Host-side frontier bookkeeping (beams/scores/alive/done_pool) plus the
    device-resident tiled KV cache. The serving worker keeps one of these
    per in-flight beam request and advances it a bounded chunk of steps at
    a time, so a long beam decode cannot head-of-line-block co-batched
    traffic on the worker's serial loop."""

    engine: "GenerationEngine"
    K: int
    B: int
    room: int
    prompt_len: int
    eos_set: set
    length_penalty: float
    beams: list = None  # type: ignore[assignment]
    scores: "np.ndarray" = None  # type: ignore[assignment]
    alive: list = None  # type: ignore[assignment]
    done_pool: list = None  # type: ignore[assignment]
    cache: KVCache | None = None
    tok: jax.Array | None = None
    step: int = 0

    def __post_init__(self):
        if self.beams is None:
            self.beams = []
        if self.alive is None:
            self.alive = []
        if self.done_pool is None:
            self.done_pool = []


@dataclass
class GenerationResult:
    sequences: list[list[int]]  # newly generated tokens per row (EOS included)
    prompt_lens: list[int]
    finished: list[bool]


class GenerationEngine:
    """Owns compiled programs + cache for one loaded model on one mesh."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        mesh: jax.sharding.Mesh | None = None,
        cache_specs=None,
        max_seq_len: int | None = None,
        seq_buckets: Sequence[int] = DEFAULT_SEQ_BUCKETS,
        batch_buckets: Sequence[int] = DEFAULT_BATCH_BUCKETS,
        cache_dtype=None,
        quant: str | None = None,
    ):
        self.cfg = cfg
        self.cache_quant = False
        if quant in ("int8", "int8+kv"):
            # weight-only int8 serving: halves the per-token HBM parameter
            # traffic that bounds B=1 decode (models/quant.py). "+kv" also
            # stores the KV cache int8 (halves the per-token cache stream
            # that grows with context, and doubles servable context per
            # HBM byte). Composes with a mesh: quantization is elementwise
            # per weight, so quantizing an ALREADY-SHARDED tree yields
            # QTensors whose q/scale inherit the weight's GSPMD sharding —
            # no explicit QTensor partition specs needed.
            from ..models.quant import quantize_params

            params = quantize_params(params)
            self.cache_quant = quant == "int8+kv"
        elif quant:
            raise ValueError(f"unknown quant mode {quant!r}")
        if self.cache_quant and cache_specs is not None and getattr(
            cache_specs, "k_scale", None
        ) is None:
            # widen plain KV specs to the int8 cache layout: per-position
            # scales shard exactly like their payload (trailing size-1 axis
            # is unsharded either way)
            cache_specs = KVCache(
                k=cache_specs.k, v=cache_specs.v, length=cache_specs.length,
                k_scale=cache_specs.k, v_scale=cache_specs.v,
            )
        self.quant = quant
        self.params = params
        self.mesh = mesh
        # mesh handle for the Pallas flash prefill: GSPMD cannot partition
        # a pallas_call, so sharded engines route it through shard_map
        # (models/transformer.py flash gate)
        self._fmesh = mesh if cfg.flash_attention else None
        self.cache_specs = cache_specs
        self.max_seq_len = max_seq_len or min(cfg.max_seq_len, seq_buckets[-1])
        self.seq_buckets = tuple(b for b in seq_buckets if b <= self.max_seq_len)
        if not self.seq_buckets:
            # every configured bucket exceeds max_seq_len — fall back to the
            # single bucket that exactly covers it
            self.seq_buckets = (self.max_seq_len,)
        self.batch_buckets = tuple(batch_buckets)
        self.cache_dtype = cache_dtype or cfg.dtype
        # prompt-prefix cache (reuse_prefix=True): host-side LRU of
        # (token-tuple -> per-position cache arrays), so conversation turns
        # re-prefill only the suffix beyond the previous turn
        from collections import OrderedDict

        self._prefix_lru: OrderedDict[tuple, dict] = OrderedDict()
        self.prefix_lru_size = 4
        # byte budget for the host-side prefix store: a 4k-token prompt on
        # an 8B model is 100s of MB of KV per entry, so eviction must be by
        # bytes, not count — and an entry above the whole budget is never
        # worth the device_get that storing it would cost
        self.prefix_lru_bytes = 512 << 20

    # -- batch bucketing --------------------------------------------------
    def batch_bucket(self, n_live: int) -> int:
        """The batch shape ``n_live`` concurrent rows decode at: the
        SMALLEST compiled bucket that fits them. This is the serving
        batcher's sizing contract (regression-pinned in
        tests/test_batching.py) — 2 live requests must run the B=2
        program, never pad out to B=8 and pay 4× the decode FLOPs for
        dead rows."""
        return _bucket(max(int(n_live), 1), self.batch_buckets)

    # -- cache ------------------------------------------------------------
    def new_cache(self, batch: int) -> KVCache:
        cache = KVCache.init(
            self.cfg, batch, max_len=self.max_seq_len, dtype=self.cache_dtype,
            quantized=self.cache_quant,
        )
        if self.mesh is not None and self.cache_specs is not None:
            cache = jax.tree.map(
                lambda x, s: jax.device_put(
                    x, jax.sharding.NamedSharding(self.mesh, s)
                ),
                cache,
                self.cache_specs,
            )
        return cache

    def _chunk_shape(self, span: int, room: int) -> int:
        """Padded shape for a prefill piece of ``span`` tokens with ``room``
        cache slots left: always a bucket value (bounded compile set) except
        when room is below the smallest bucket (≤ smallest-bucket distinct
        shapes, ever)."""
        usable = [b for b in self.seq_buckets if b <= room]
        if not usable:
            return room
        if span >= usable[-1]:
            return usable[-1]
        return next(b for b in usable if b >= span)

    # -- prompt-prefix cache ---------------------------------------------
    def _prefix_store(
        self,
        prompt: list[int],
        cache: KVCache,
        base_entry: dict | None = None,
        base_len: int = 0,
    ) -> None:
        """Keep this prompt's per-position cache rows (host copies — HBM
        stays free) as a reusable prefix for a later turn extending it. On
        a hit, only the NEW rows transfer device→host; the matched entry's
        arrays are reused for the shared prefix (per-turn cost stays
        O(delta), which is the point of the feature)."""
        L = len(prompt)
        if self._entry_nbytes_for(L) > self.prefix_lru_bytes:
            return  # larger than the whole budget: skip the device_get

        def rows(arr, base):
            new = np.asarray(arr[:, 0, base_len:L])
            return np.concatenate([base[:, :base_len], new], axis=1) \
                if base is not None else np.asarray(arr[:, 0, :L])

        b = base_entry or {}
        entry = {"k": rows(cache.k, b.get("k")),
                 "v": rows(cache.v, b.get("v"))}
        if cache.quantized:
            entry["k_scale"] = rows(cache.k_scale, b.get("k_scale"))
            entry["v_scale"] = rows(cache.v_scale, b.get("v_scale"))
        key = tuple(prompt)
        self._prefix_lru[key] = entry
        self._prefix_lru.move_to_end(key)
        while len(self._prefix_lru) > self.prefix_lru_size or (
            len(self._prefix_lru) > 1
            and self._prefix_total_bytes() > self.prefix_lru_bytes
        ):
            self._prefix_lru.popitem(last=False)

    @staticmethod
    def _entry_nbytes(entry: dict) -> int:
        return sum(a.nbytes for a in entry.values())

    def _entry_nbytes_for(self, n_tokens: int) -> int:
        """Bytes a stored prefix of ``n_tokens`` positions would occupy,
        computed WITHOUT the device transfer (the whole point of the
        pre-check): layers × positions × kv-heads × head-dim × 2 (k+v)."""
        c = self.cfg
        per_pos = c.n_layers * c.n_kv_heads * c.head_dim * 2
        if self.cache_quant:
            # int8 payload + f32 per-(pos, head) scales
            per_pos_bytes = per_pos + c.n_layers * c.n_kv_heads * 2 * 4
        else:
            per_pos_bytes = per_pos * jnp.dtype(self.cache_dtype).itemsize
        return n_tokens * per_pos_bytes

    def _prefix_total_bytes(self) -> int:
        return sum(self._entry_nbytes(e) for e in self._prefix_lru.values())

    def _prefix_match(self, prompt: list[int]) -> tuple[int, dict] | None:
        """Longest stored key that is a prefix of ``prompt``, used up to
        len(prompt)-1 positions (a repeated prompt still needs one real
        token prefilled to produce logits). A hit refreshes the entry's
        LRU recency — a hot shared prefix must not be evicted by colder
        stores."""
        best = None
        best_key = None
        p = tuple(prompt)
        for key, entry in self._prefix_lru.items():
            if p[: len(key)] == key:
                L_use = min(len(key), len(prompt) - 1)
                if L_use > 0 and (best is None or L_use > best[0]):
                    best = (L_use, entry)
                    best_key = key
        if best_key is not None:
            self._prefix_lru.move_to_end(best_key)
        return best

    def _prefill_with_prefix(self, prompt: list[int], L: int, entry: dict):
        """Seed a fresh B=1-bucket cache with the stored prefix rows, then
        prefill only the suffix (cache offsets handle positions), chunked
        like the cold path so any suffix length works."""
        B = _bucket(1, self.batch_buckets)
        cache = self.new_cache(B)
        k = cache.k.at[:, 0, :L].set(jnp.asarray(entry["k"][:, :L]))
        v = cache.v.at[:, 0, :L].set(jnp.asarray(entry["v"][:, :L]))
        ks = vs = None
        if cache.quantized:
            ks = cache.k_scale.at[:, 0, :L].set(
                jnp.asarray(entry["k_scale"][:, :L])
            )
            vs = cache.v_scale.at[:, 0, :L].set(
                jnp.asarray(entry["v_scale"][:, :L])
            )
        length = jnp.zeros((B,), jnp.int32).at[0].set(L)
        cache = KVCache(k=k, v=v, length=length, k_scale=ks, v_scale=vs)

        rest = prompt[L:]
        off = 0
        hidden_last = None
        while off < len(rest):
            span = min(len(rest) - off, self.seq_buckets[-1])
            Tc = self._chunk_shape(span, self.max_seq_len - L - off)
            span = min(span, Tc)
            toks = np.zeros((B, Tc), np.int32)
            mask = np.zeros((B, Tc), bool)
            toks[0, :span] = rest[off : off + span]
            mask[0, :span] = True
            hid, cache = _prefill_chunk(
                self.params, jnp.asarray(toks), jnp.asarray(mask), cache,
                self.cfg, False,  # offset != 0 — never flash
            )
            if off + span >= len(rest):
                hidden_last = hid[:, span - 1]
            off += span
        logits = _head_from_hidden(self.params, hidden_last, self.cfg)
        return logits, cache, [len(prompt)], B

    def warmup(self, *, max_new_tokens: int = 128) -> float:
        """Pre-compile the hot serving programs — for EVERY batch bucket
        (the batcher coalesces a first burst straight into B>1), the
        smallest-seq-bucket prefill + the decode loop at
        ``max_new_tokens``'s n_steps bucket. Hosting calls this when
        ``MLConfig.warmup_tokens`` is set. A request whose budget maps to a
        different pow2 n_steps bucket (or a longer prompt bucket) still
        compiles on first use. Returns elapsed seconds.

        Sampling leaves are warmed in the SERVING shape: the worker always
        ships stacked ``[B, 1]`` knobs (ml/worker.py::_generate), and leaf
        shapes are part of the jit cache key — warming with scalar leaves
        would compile a program no API request ever hits and leave the
        first real request paying the full decode-loop compile anyway."""
        import time as _t

        t0 = _t.perf_counter()
        span = max(self.seq_buckets[0] // 2, 1)
        for b in self.batch_buckets:
            self.generate_compiled(
                [[1] * span] * b, max_new_tokens=max_new_tokens,
                sampling=SamplingParams.stack(
                    [SamplingParams.make()] * b, pad_to=b
                ),
            )
        return _t.perf_counter() - t0

    # -- host-driven API --------------------------------------------------
    def prefill(
        self, prompts: Iterable[Sequence[int]], *, reuse_prefix: bool = False
    ):
        """Pad prompts into (batch, seq) buckets; returns
        (last_logits [B,V], cache, prompt_lens, batch_pad).

        Prompts longer than the largest seq bucket prefill in bucket-sized
        CHUNKS through the cache (each chunk attends everything before it),
        with the vocab head applied once to each row's last-token hidden —
        so long-prompt cost is chunks·(layers) plus ONE head, and the
        compiled-program set stays bounded.

        ``reuse_prefix`` (B=1 only): seed the cache from the longest stored
        prompt prefix and prefill only the suffix — a conversation turn
        extending the previous one re-pays just the delta; the full prompt's
        cache rows are stored back for the next turn."""
        prompts = [list(p) for p in prompts]
        if reuse_prefix and len(prompts) == 1:
            prompt = prompts[0]
            if len(prompt) > self.max_seq_len:
                raise ValueError(
                    f"prompt length {len(prompt)} exceeds max_seq_len "
                    f"{self.max_seq_len}"
                )
            hit = self._prefix_match(prompt)
            if hit is not None:
                L_use, entry = hit
                out = self._prefill_with_prefix(prompt, L_use, entry)
                self._prefix_store(
                    prompt, out[1], base_entry=entry, base_len=L_use
                )
                return out
            out = self.prefill(prompts)
            self._prefix_store(prompt, out[1])
            return out
        B = self.batch_bucket(len(prompts))
        lens = [len(p) for p in prompts]
        T_max = max(lens)
        if T_max > self.max_seq_len:
            raise ValueError(
                f"prompt length {T_max} exceeds max_seq_len {self.max_seq_len}"
            )
        if T_max <= self.seq_buckets[-1]:
            T = _bucket(T_max, self.seq_buckets)
            toks = np.zeros((B, T), np.int32)
            mask = np.zeros((B, T), bool)
            for i, p in enumerate(prompts):
                toks[i, : len(p)] = p
                mask[i, : len(p)] = True
            cache = self.new_cache(B)
            logits, cache = _prefill(
                self.params, jnp.asarray(toks), jnp.asarray(mask), cache,
                self.cfg, self._fmesh,
            )
            return logits, cache, lens, B
        return self._prefill_chunked(prompts, lens, B)

    def _prefill_chunked(self, prompts, lens, B):
        C = self.seq_buckets[-1]
        T_max = max(lens)
        cache = self.new_cache(B)
        lens_a = np.asarray(lens + [0] * (B - len(lens)))
        hidden_last = None
        off = 0
        while off < T_max:
            span = min(C, T_max - off)
            # the chunk may not overrun the cache (a clamped
            # dynamic_update_slice would shift the write backward over
            # already-written real keys), and its padded shape comes from
            # the bucket set so the compile set stays bounded
            Tc = self._chunk_shape(span, self.max_seq_len - off)
            toks = np.zeros((B, Tc), np.int32)
            mask = np.zeros((B, Tc), bool)
            for i, p in enumerate(prompts):
                part = p[off : off + Tc]
                toks[i, : len(part)] = part
                mask[i, : len(part)] = True
            hid, cache = _prefill_chunk(
                self.params, jnp.asarray(toks), jnp.asarray(mask), cache,
                self.cfg, off == 0, self._fmesh,
            )
            if hidden_last is None:
                hidden_last = jnp.zeros((B, hid.shape[-1]), hid.dtype)
            # rows whose last real token falls inside this chunk grab its
            # (already final-normed) hidden state
            last_idx = lens_a - 1
            in_chunk = (last_idx >= off) & (last_idx < off + Tc)
            local = np.clip(last_idx - off, 0, Tc - 1)
            gathered = hid[jnp.arange(B), jnp.asarray(local)]
            hidden_last = jnp.where(
                jnp.asarray(in_chunk)[:, None], gathered, hidden_last
            )
            off += Tc
        logits = _head_from_hidden(self.params, hidden_last, self.cfg)
        return logits, cache, lens, B

    def generate(
        self,
        prompts: Iterable[Sequence[int]],
        *,
        max_new_tokens: int = 128,
        sampling: SamplingParams | None = None,
        eos_ids: Sequence[int] = (),
        seed: int = 0,
        stream_cb: Callable[[list[int | None]], None] | None = None,
        budgets: Sequence[int] | None = None,
        reuse_prefix: bool = False,
    ) -> GenerationResult:
        """Host-driven loop (supports per-token streaming callbacks).

        ``stream_cb`` receives, per step, one new token id per live row
        (None for rows already finished); it MAY return a collection of
        row indices to CANCEL (e.g. a confirmed stop-sequence match
        downstream) — those rows freeze immediately instead of decoding
        to their budget. ``budgets`` caps rows individually (the serving
        batcher mixes requests with different max_new_tokens); each row
        is limited by its OWN budget and cache room, so a long-prompt
        neighbor never truncates a short one."""
        sampling = sampling or SamplingParams.make()
        prompts = [list(p) for p in prompts]  # materialize: iterated again
        # below for the penalty counts, and a generator would be spent
        logits, cache, lens, B = self.prefill(prompts, reuse_prefix=reuse_prefix)
        sampling = sampling.pad_rows(B)  # per-row knobs -> bucketed batch
        n_rows = len(lens)
        eff = self._row_limits(lens, B, max_new_tokens, budgets)
        steps = max(eff)
        eos = np.asarray(list(eos_ids) or [-1], np.int32)

        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        pen = self._penalized(sampling)
        counts = self._prompt_counts(prompts, B) if pen else None
        tok = sample(logits, sub, sampling, counts)
        seqs: list[list[int]] = [[] for _ in range(n_rows)]
        done = np.zeros(B, bool)
        for i in range(B):
            if eff[i] <= 0:
                done[i] = True
        for step in range(steps):
            tok_host = np.asarray(tok)
            emitted: list[int | None] = []
            for i in range(n_rows):
                if not done[i]:
                    seqs[i].append(int(tok_host[i]))
                    emitted.append(int(tok_host[i]))
                else:
                    emitted.append(None)
            if pen:
                # fold the just-emitted token into the context counts (rows
                # that emitted nothing this step add nothing)
                live = np.array(
                    [i < n_rows and emitted[i] is not None for i in range(B)]
                )
                counts = counts.at[jnp.arange(B), tok].add(
                    jnp.asarray(live.astype(np.int32))
                )
            done |= np.isin(tok_host, eos)
            for i in range(n_rows):
                if len(seqs[i]) >= eff[i]:
                    done[i] = True
            if stream_cb is not None:
                cancel = stream_cb(emitted)
                for i in cancel or ():
                    if 0 <= int(i) < B:
                        done[int(i)] = True
            if done[:n_rows].all() or step == steps - 1:
                break
            key, sub = jax.random.split(key)
            logits, cache = _decode_step(self.params, tok, cache, self.cfg)
            nxt = sample(logits, sub, sampling, counts)
            tok = jnp.where(jnp.asarray(done), tok, nxt)
        del cache
        return GenerationResult(
            sequences=seqs, prompt_lens=lens, finished=list(done[:n_rows])
        )

    def generate_chunked(
        self,
        prompts: Iterable[Sequence[int]],
        *,
        max_new_tokens: int = 128,
        sampling: SamplingParams | None = None,
        eos_ids: Sequence[int] = (),
        seed: int = 0,
        stream_cb: Callable[[list[int | None]], None] | None = None,
        budgets: Sequence[int] | None = None,
        reuse_prefix: bool = False,
        chunk_steps: int = 32,
        shrink_on_eviction: bool = True,
    ) -> GenerationResult:
        """Streaming at COMPILED-loop speed: the decode runs as a sequence
        of fully-on-device while_loop chunks (one program — ``chunk_steps``
        is its static n_steps), with the host touched once per chunk
        instead of once per token. The per-token host loop pays a host
        round trip per token; this bounds it to one round trip per
        ``chunk_steps`` tokens while keeping the stream
        callback's PER-STEP contract (tokens are just delivered in chunk
        batches). A cancel return from the callback stops that row's
        emission IMMEDIATELY (the already-decoded remainder of the chunk
        is discarded; only device compute runs to the chunk end).
        Penalized requests fall back to the per-token host loop — context
        counts don't ride across chunk calls.

        ``shrink_on_eviction``: when rows finish (EOS / budget / cancel)
        mid-batch, the next chunk re-buckets the SURVIVORS — live cache
        rows gather into the smallest bucket ≥ live count instead of
        dead-stepping the original batch shape to drain (the r5 co-batch
        regression: 2 live rows decoding at B=8 pay 4× the FLOPs per
        token). Greedy-only: argmax is shape-independent, but a sampled
        row's draw depends on the batch's shared key walk, so sampled
        mixes keep their shape to preserve seed parity with the one-shot
        compiled loop. ``self.last_chunk_batches`` records each chunk's
        batch shape for telemetry/tests.

        (Prologue is deliberately parallel to ``generate`` /
        ``generate_compiled`` — a semantic change to row limits, EOS
        handling, or first-token sampling must be applied to all three.)"""
        sampling = sampling or SamplingParams.make()
        if self._penalized(sampling):
            return self.generate(
                prompts, max_new_tokens=max_new_tokens, sampling=sampling,
                eos_ids=eos_ids, seed=seed, stream_cb=stream_cb,
                budgets=budgets, reuse_prefix=reuse_prefix,
            )
        prompts = [list(p) for p in prompts]
        logits, cache, lens, B = self.prefill(prompts, reuse_prefix=reuse_prefix)
        sampling = sampling.pad_rows(B)
        n_rows = len(lens)
        eff = self._row_limits(lens, B, max_new_tokens, budgets)
        eos_set = set(int(e) for e in eos_ids)
        eos = jnp.asarray(list(eos_ids) or [-1], np.int32)
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        tok = sample(logits, sub, sampling, None)
        dummy = jnp.zeros((1, 1), jnp.int32)
        chunk_steps = max(int(chunk_steps), 1)

        seqs: list[list[int]] = [[] for _ in range(n_rows)]
        done = np.zeros(n_rows, bool)
        remaining = np.asarray(eff[:n_rows], np.int64)
        done |= remaining <= 0
        # batch row -> request index (None for bucket padding); compaction
        # rewrites this map when survivors re-bucket
        rowmap: list[int | None] = list(range(n_rows)) + [None] * (B - n_rows)
        # all-greedy mixes may re-bucket: argmax is batch-shape-independent,
        # a sampled draw is not (the loop key is shared per step)
        shrinkable = shrink_on_eviction and not bool(
            np.any(np.asarray(sampling.temperature) > 0)
        )
        self.last_chunk_batches: list[int] = []

        def emit(step_tokens: np.ndarray) -> None:
            """Deliver one decode step's tokens (engine stream contract:
            one entry per REQUEST, None for finished rows) and fold them
            into the per-request sequences / done flags."""
            emitted: list[int | None] = [None] * n_rows
            for r, i in enumerate(rowmap):
                if i is None or done[i]:
                    continue
                t = int(step_tokens[r])
                seqs[i].append(t)
                emitted[i] = t
                remaining[i] -= 1
                if t in eos_set or remaining[i] <= 0:
                    done[i] = True
            if stream_cb is not None:
                cancel = stream_cb(emitted)
                for i in cancel or ():
                    if 0 <= int(i) < n_rows:
                        done[int(i)] = True

        emit(np.asarray(tok))
        while not done.all():
            if shrinkable:
                live = [i for i in range(n_rows) if not done[i]]
                newB = self.batch_bucket(len(live))
                if newB < len(rowmap):
                    # eviction: gather the survivors' cache rows into the
                    # smallest bucket that holds them and decode on
                    rows = [rowmap.index(i) for i in live]
                    gidx = jnp.asarray(
                        rows + [rows[0]] * (newB - len(rows)), jnp.int32
                    )
                    cache = KVCache(
                        k=cache.k[:, gidx], v=cache.v[:, gidx],
                        length=cache.length[gidx],
                        k_scale=None if cache.k_scale is None
                        else cache.k_scale[:, gidx],
                        v_scale=None if cache.v_scale is None
                        else cache.v_scale[:, gidx],
                    )
                    tok = tok[gidx]
                    sampling = jax.tree.map(
                        lambda l: l[gidx] if jnp.ndim(l) else l, sampling
                    )
                    rowmap = list(live) + [None] * (newB - len(live))
            self.last_chunk_batches.append(len(rowmap))
            # freeze finished rows for the whole chunk (limits <= 0 →
            # done0 inside the loop); live rows run up to their remaining
            # budget, capped by the chunk. The loop returns its ADVANCED
            # key, so the per-step split chain continues across chunks —
            # a chunked sampled decode emits exactly what one long
            # compiled loop (or the per-token host loop, which walks the
            # same chain) would emit for the same seed.
            lims = jnp.asarray(
                [
                    0 if (i is None or done[i]) else int(remaining[i])
                    for i in rowmap
                ],
                jnp.int32,
            )
            tokens, cache, _dd, n_exec, key = _decode_loop(
                self.params, tok, cache, key, sampling, eos, lims,
                dummy, self.cfg, chunk_steps, penalize=False,
            )
            n_exec = int(n_exec)
            if n_exec <= 0:
                break
            toks_host = np.asarray(tokens)[:, :n_exec]
            for s in range(n_exec):
                emit(toks_host[:, s])
                if done.all():
                    break
            # next chunk resumes from each row's LAST token (frozen rows
            # re-fed their own token inside the loop, so column n_exec-1
            # is correct for them too)
            tok = jnp.asarray(toks_host[:, n_exec - 1].astype(np.int32))
        del cache
        return GenerationResult(
            sequences=seqs, prompt_lens=lens, finished=list(done[:n_rows])
        )

    # -- beam search ------------------------------------------------------
    def beam_start(
        self,
        prompts: Iterable[Sequence[int]],
        *,
        num_beams: int = 4,
        max_new_tokens: int = 128,
        eos_ids: Sequence[int] = (),
        length_penalty: float = 1.0,
    ) -> "BeamState":
        """Prefill + first-token expansion of a RESUMABLE beam session.

        Beams ride the engine's BATCH axis, so each step is one batched
        decode (same parameter stream as B=1) plus a per-step cache
        reorder. Per-step candidate selection runs ON DEVICE via
        ``lax.top_k`` — K·(K+n_eos) ids+scores cross to the host, not
        [K, V] logits (VERDICT r4 weak #4: np.argsort over a 151k vocab
        per beam per token). The session shape lets the serving worker
        advance a bounded chunk of steps at a time instead of occupying
        its serial loop for the whole decode."""
        prompts = [list(p) for p in prompts]
        if len(prompts) != 1:
            raise ValueError("beam search is B=1")
        K = int(num_beams)
        if K < 1:
            raise ValueError("num_beams must be >= 1")
        if K > max(self.batch_buckets):
            raise ValueError(
                f"num_beams {K} exceeds the largest batch bucket "
                f"{max(self.batch_buckets)}"
            )
        prompt = prompts[0]
        eos_set = set(int(e) for e in eos_ids)
        room = min(max_new_tokens, self.max_seq_len - len(prompt))
        if room <= 0:
            return BeamState(
                engine=self, K=K, B=0, room=0, prompt_len=len(prompt),
                eos_set=eos_set, length_penalty=float(length_penalty),
            )
        # prefill ONCE at B=1 and tile the cache rows to K — the same
        # [:, idx] gather the per-step reorder uses, instead of paying the
        # prompt forward K times for byte-identical caches
        logits1, cache1, lens, _ = self.prefill([prompt])
        B = _bucket(K, self.batch_buckets)
        tile = jnp.zeros((B,), jnp.int32)  # every row copies row 0
        cache = KVCache(
            k=cache1.k[:, tile], v=cache1.v[:, tile],
            length=cache1.length[tile],
            k_scale=None if cache1.k_scale is None else cache1.k_scale[:, tile],
            v_scale=None if cache1.v_scale is None else cache1.v_scale[:, tile],
        )
        del cache1
        st = BeamState(
            engine=self, K=K, B=B, room=room, prompt_len=len(prompt),
            eos_set=eos_set, length_penalty=float(length_penalty),
        )
        vals, idx = _beam_topk(logits1[:1], K)
        row_v = np.asarray(vals)[0]
        row_i = np.asarray(idx)[0]
        st.scores = row_v.astype(np.float64)
        st.beams = [[int(t)] for t in row_i]
        st.alive = [int(t) not in eos_set for t in row_i]
        for k, b in enumerate(st.beams):
            if not st.alive[k]:
                st.done_pool.append((st.scores[k] / 1.0, b))
        st.cache = cache
        st.tok = jnp.asarray(np.resize(row_i.astype(np.int32), (B,)))
        st.step = 1
        return st

    def beam_advance(self, st: "BeamState", max_steps: int | None = None) -> bool:
        """Run up to ``max_steps`` beam steps (all remaining when None).
        Returns True when the session is finished."""
        if st.room <= 0:
            return True
        n = 0
        K = st.K
        kk = K + len(st.eos_set)
        while st.step < st.room and any(st.alive):
            if max_steps is not None and n >= max_steps:
                return False
            n += 1
            st.step += 1
            logits, st.cache = _decode_step(
                self.params, st.tok, st.cache, self.cfg
            )
            # [K, kk] scores+ids — the ONLY device->host transfer per step
            vals, idx = _beam_topk(logits[:K], kk)
            nxt = beam_frontier_step(
                st.beams, st.scores, st.alive, st.done_pool,
                np.asarray(vals), np.asarray(idx), K,
                st.eos_set, st.room, st.length_penalty,
            )
            if nxt is None:
                break
            st.beams, st.scores, st.alive, src = nxt
            # reorder every beam's cache row to follow its source beam
            gidx = jnp.asarray(np.resize(np.asarray(src, np.int32), (st.B,)))
            st.cache = KVCache(
                k=st.cache.k[:, gidx], v=st.cache.v[:, gidx],
                length=st.cache.length[gidx],
                k_scale=None if st.cache.k_scale is None
                else st.cache.k_scale[:, gidx],
                v_scale=None if st.cache.v_scale is None
                else st.cache.v_scale[:, gidx],
            )
            st.tok = jnp.asarray(
                np.resize(
                    np.asarray([b[-1] for b in st.beams], np.int32), (st.B,)
                )
            )
        return True

    def beam_finish(self, st: "BeamState") -> GenerationResult:
        """Close the session: fold surviving beams into the pool and pick
        the best by GNMT length-normalized log-probability."""
        if st.room <= 0:
            return GenerationResult(
                sequences=[[]], prompt_lens=[st.prompt_len], finished=[True]
            )
        st.cache = None  # free the tiled KV
        for k in range(st.K):
            if st.alive[k]:
                st.done_pool.append(
                    (
                        st.scores[k] / (len(st.beams[k]) ** st.length_penalty),
                        st.beams[k],
                    )
                )
        _best_score, best = max(st.done_pool, key=lambda d: d[0])
        fin = bool(best and best[-1] in st.eos_set)
        return GenerationResult(
            sequences=[best], prompt_lens=[st.prompt_len], finished=[fin]
        )

    def generate_beam(
        self,
        prompts: Iterable[Sequence[int]],
        *,
        num_beams: int = 4,
        max_new_tokens: int = 128,
        eos_ids: Sequence[int] = (),
        length_penalty: float = 1.0,
    ) -> GenerationResult:
        """One-shot beam-search decode (B=1): start + advance + finish.
        The reference exposes ``num_beams`` through HF ``generate``
        (ml/formatter.py:88-92); here it is a first-class engine path.
        Returns the best finished beam by length-normalized
        log-probability (GNMT ``len**length_penalty``)."""
        st = self.beam_start(
            prompts, num_beams=num_beams, max_new_tokens=max_new_tokens,
            eos_ids=eos_ids, length_penalty=length_penalty,
        )
        self.beam_advance(st)
        return self.beam_finish(st)

    # -- speculative decode (prompt-lookup) -------------------------------
    # The drafting + acceptance policy lives in engine/spec.py — ONE
    # implementation shared with the continuous engine's ragged verify
    # slots, so the two paths cannot drift. These staticmethods remain
    # the engine-level override points (tests patch them).
    @staticmethod
    def _lookup_draft(
        history: list[int], n_draft: int, ngram: int = 8, min_ngram: int = 2,
    ) -> list[int]:
        """Prompt-lookup drafting (see engine/spec.py::lookup_draft):
        if the trailing n-gram occurred earlier in the token history,
        propose the tokens that followed it — free, no draft model."""
        from .spec import lookup_draft

        return lookup_draft(history, n_draft, ngram=ngram, min_ngram=min_ngram)

    @staticmethod
    def _spec_worthwhile(tokens_per_pass: float, t_verify: float,
                         t_decode: float) -> bool:
        """Speculation continues only while its measured throughput beats
        vanilla (engine/spec.py::spec_worthwhile). Pure so the break-even
        rule is unit-testable without wall-clock flakiness."""
        from .spec import spec_worthwhile

        return spec_worthwhile(tokens_per_pass, t_verify, t_decode)

    def generate_lookahead(
        self,
        prompts: Iterable[Sequence[int]],
        *,
        max_new_tokens: int = 128,
        eos_ids: Sequence[int] = (),
        n_draft: int = 8,
        reuse_prefix: bool = False,
        stream_cb: Callable[[list[int | None]], None] | None = None,
        compiled_fallback: bool = True,
    ) -> GenerationResult:
        """Greedy decode with prompt-lookup speculation (B=1): draft up to
        ``n_draft`` tokens from the prompt's own n-grams, verify them in ONE
        forward, keep the matched prefix plus the model's correction token.
        Emits EXACTLY the vanilla greedy sequence — speculation only changes
        how many decode steps it takes.

        Adaptive (VERDICT r4 weak #3 — a bad draft mix must never make
        ``{"lookahead": true}`` a slowdown): steps with NO n-gram hit run a
        plain decode step instead of a padded verify pass, and both program
        kinds are wall-clock-tracked (EMA, first-call compile excluded);
        once the measured speculative throughput drops below vanilla's the
        request falls back to plain decode for its remainder —
        host-driven when streaming, or (``compiled_fallback``, non-stream
        only) the fully-compiled ``_decode_loop``, so a losing speculation
        costs a few early passes and then decodes at the engine's best
        rate."""
        from .spec import SpecController

        prompts = [list(p) for p in prompts]
        if len(prompts) != 1:
            raise ValueError("lookahead decode is B=1 (serving conversations)")
        import time as _time

        logits, cache, lens, B = self.prefill(
            prompts, reuse_prefix=reuse_prefix
        )
        n_passes = 1  # the prefill pass produced the first token
        n_verify = 0
        n_decode = 0
        eos_set = set(int(e) for e in eos_ids)
        history = list(prompts[0])
        tok = int(np.asarray(logits)[0].argmax())
        seq: list[int] = [tok]
        history.append(tok)
        if stream_cb is not None:
            stream_cb([tok])
        room = self.max_seq_len - lens[0]
        limit = min(max_new_tokens, room)

        # EMAs over SYNCED wall time (np.asarray below blocks on the
        # device); None until the program kind has a post-compile sample
        ema_tv: float | None = None
        ema_td: float | None = None
        seen_tv = seen_td = 0
        # the shared drafting/acceptance policy (engine/spec.py): prompt
        # prescan (a prompt with zero recurring adjacent pairs starts with
        # speculation off — a non-stream request then rides the compiled
        # tail from its first token), miss-run disarm, pair-recurrence
        # re-arm (STREAM requests only: a non-stream request's compiled
        # tail is already the fastest remainder), and the acceptance-rate
        # kill switch (VERDICT r5: a verify pass emitting < 1.5 tokens on
        # average cannot beat plain decode even if the padded pass were
        # free — after the probe window that measured acceptance disables
        # speculation PERMANENTLY, no timing signal required; the timing
        # break-even rule below also kills permanently, since re-arming
        # after a measured loss would reinstate the slowdown it stopped).
        # draft_fn = the engine staticmethod, the test-patchable override.
        ctrl = SpecController(
            n_draft=n_draft, rearm=stream_cb is not None,
            draft_fn=self._lookup_draft,
        )
        ctrl.prescan(history)

        def note_pair() -> None:
            ctrl.note_pair(history[-2], history[-1])

        compiled_tail = 0
        while len(seq) < limit and tok not in eos_set:
            remaining = limit - len(seq)
            if not ctrl.on and compiled_fallback and stream_cb is None:
                # speculation measured itself out — decode the remainder in
                # ONE on-device while_loop (the same program the serving
                # warmup compiles) instead of a host round-trip per token
                n_steps = 1
                while n_steps < remaining:
                    n_steps <<= 1
                n_steps = max(min(n_steps, self.max_seq_len), 1)
                sp = SamplingParams.stack([SamplingParams.make()], pad_to=B)
                eos_arr = jnp.asarray(
                    sorted(eos_set) or [-1], jnp.int32
                )
                lims = jnp.asarray(
                    [remaining] + [0] * (B - 1), jnp.int32
                )
                tokens, cache, _done, n_exec, _key = _decode_loop(
                    self.params, jnp.full((B,), tok, jnp.int32), cache,
                    jax.random.PRNGKey(0), sp, eos_arr, lims,
                    jnp.zeros((1, 1), jnp.int32), self.cfg, n_steps,
                    penalize=False,
                )
                compiled_tail = int(n_exec)
                n_passes += compiled_tail
                row = np.asarray(tokens)[0]
                for t in row[: min(compiled_tail, remaining)]:
                    t = int(t)
                    seq.append(t)
                    tok = t
                    if t in eos_set:
                        break
                break
            k = min(n_draft, remaining - 1, self.max_seq_len - lens[0] - len(seq))
            was_on = ctrl.active
            draft = ctrl.draft(history, cap=k) if k > 0 else []
            ctrl.drafted += len(draft)  # no budget here: granted = proposed
            if not draft:
                if was_on and not ctrl.on:
                    # the miss-run disarm just fired (engine/spec.py):
                    # non-stream hands the remainder to the compiled tail
                    continue
                # no hit (or speculation disabled): one plain decode step —
                # cheaper than a padded verify pass, and its timing seeds
                # the vanilla side of the break-even rule
                t0 = _time.perf_counter()
                logits, cache = _decode_step(
                    self.params, jnp.full((B,), tok, jnp.int32), cache, self.cfg
                )
                tok = int(np.asarray(logits)[0].argmax())
                dt = _time.perf_counter() - t0
                seen_td += 1
                if seen_td > 1:  # first call includes the XLA compile
                    ema_td = dt if ema_td is None else (
                        0.5 * dt + 0.5 * ema_td
                    )
                n_passes += 1
                n_decode += 1
                seq.append(tok)
                history.append(tok)
                note_pair()
                if stream_cb is not None:
                    stream_cb([tok])
                continue
            base_len = int(np.asarray(cache.length)[0])
            # pad the verify call to a FIXED [1, 1+n_draft] shape whenever
            # the cache has room: variable draft lengths would compile one
            # XLA program per length.
            # Padded positions write garbage KV that the same length-reset
            # rollback below discards, and acceptance only reads the real
            # draft prefix.
            pad_to = len(draft)
            if base_len + 1 + n_draft <= self.max_seq_len:
                pad_to = n_draft
            toks = np.zeros((B, 1 + pad_to), np.int32)
            toks[0, 0] = tok
            toks[0, 1 : 1 + len(draft)] = draft
            t0 = _time.perf_counter()
            targets, cache = _verify_step(
                self.params, jnp.asarray(toks), cache, self.cfg
            )
            t_host = np.asarray(targets)[0]
            dt = _time.perf_counter() - t0
            n_passes += 1
            n_verify += 1
            accepted = 0
            while accepted < len(draft) and draft[accepted] == int(t_host[accepted]):
                if draft[accepted] in eos_set:
                    break
                accepted += 1
            emitted = list(draft[:accepted]) + [int(t_host[accepted])]
            # shared acceptance accounting + the permanent kill switch
            # (engine/spec.py — same rule, same constants as the ragged
            # path, so the two implementations cannot drift)
            ctrl.note_verify(accepted + 1)
            seen_tv += 1
            if seen_tv > 1:  # first call includes the XLA compile
                ema_tv = dt if ema_tv is None else (
                    0.5 * dt + 0.5 * ema_tv
                )
                if ema_td is not None and seen_tv > 3 and not ctrl.dead:
                    # the measured break-even rule: a losing speculation
                    # kills permanently, like the acceptance rule
                    if not self._spec_worthwhile(ctrl.ema_acc, ema_tv, ema_td):
                        ctrl.kill()
            # roll back rejected cache positions by resetting length only
            new_len = base_len + 1 + accepted
            cache = KVCache(
                k=cache.k, v=cache.v,
                length=jnp.full_like(cache.length, new_len),
                k_scale=cache.k_scale, v_scale=cache.v_scale,
            )
            taken: list[int] = []
            for t in emitted:
                seq.append(t)
                history.append(t)
                note_pair()
                taken.append(t)
                tok = t
                if t in eos_set or len(seq) >= limit:
                    break
            if stream_cb is not None and taken:
                for t in taken:  # per-token, matching the host-loop contract
                    stream_cb([t])
            if tok in eos_set:
                break
        del cache
        seq = seq[:limit]
        # acceptance telemetry for the bench / serving metrics: mean tokens
        # emitted per model pass (1.0 = vanilla decode, >1 = speculation won)
        self.last_lookahead_stats = {
            "tokens": len(seq),
            "passes": n_passes,
            "verify_passes": n_verify,
            "decode_steps": n_decode,
            "tokens_per_pass": round(len(seq) / max(n_passes, 1), 3),
            "tokens_per_verify_pass": round(ctrl.tokens_per_pass, 3)
            if n_verify else None,
            "spec_disabled": not ctrl.on,
            "compiled_tail": compiled_tail,
        }
        fin = bool(seq and seq[-1] in eos_set)
        return GenerationResult(sequences=[seq], prompt_lens=lens, finished=[fin])

    # -- repetition penalties --------------------------------------------
    @staticmethod
    def _penalized(sampling: SamplingParams) -> bool:
        return bool(
            np.any(np.asarray(sampling.presence_penalty))
            or np.any(np.asarray(sampling.frequency_penalty))
        )

    def _prompt_counts(self, prompts, B: int) -> jax.Array:
        """Per-row token counts over the prompt — the context the OpenAI
        presence/frequency penalties score against (generated tokens are
        folded in as they decode)."""
        c = np.zeros((B, self.cfg.vocab_size), np.int32)
        for i, p in enumerate(prompts):
            np.add.at(c[i], np.asarray(list(p), np.int64), 1)
        return jnp.asarray(c)

    # -- fully-compiled API (throughput / bench) --------------------------
    def _row_limits(
        self,
        lens: list[int],
        B: int,
        max_new_tokens: int,
        budgets: Sequence[int] | None,
    ) -> list[int]:
        """Per-row total-token limits: each row is capped by its OWN budget
        and its OWN cache room — co-batching a long-prompt request must not
        truncate a short-prompt neighbor (and a row at its room must freeze
        so neighbors can continue without overrunning its cache slots)."""
        eff = []
        for i in range(len(lens)):
            want = int(budgets[i]) if budgets else max_new_tokens
            eff.append(max(min(want, self.max_seq_len - lens[i]), 0))
        eff += [0] * (B - len(lens))  # bucket-pad rows freeze immediately
        return eff

    def generate_compiled(
        self,
        prompts: Iterable[Sequence[int]],
        *,
        max_new_tokens: int = 128,
        sampling: SamplingParams | None = None,
        eos_ids: Sequence[int] = (),
        seed: int = 0,
        budgets: Sequence[int] | None = None,
        reuse_prefix: bool = False,
    ) -> GenerationResult:
        """Entire token loop on device (lax.while_loop, EOS early-exit).
        ``budgets`` caps rows individually (batched request mixes) with no
        host round-trips — limits ride the compiled loop."""
        sampling = sampling or SamplingParams.make()
        prompts = [list(p) for p in prompts]  # materialize: iterated again
        # below for the penalty counts, and a generator would be spent
        logits, cache, lens, B = self.prefill(prompts, reuse_prefix=reuse_prefix)
        sampling = sampling.pad_rows(B)  # per-row knobs -> bucketed batch
        eff = self._row_limits(lens, B, max_new_tokens, budgets)
        total = max(eff)
        if total <= 0:
            del cache
            return GenerationResult(
                sequences=[[] for _ in lens],
                prompt_lens=lens,
                finished=[True] * len(lens),  # zero room = nothing left
            )
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        pen = self._penalized(sampling)
        counts = (
            self._prompt_counts(prompts, B) if pen
            else jnp.zeros((1, 1), jnp.int32)  # dummy; static penalize=False
        )
        first = sample(logits, sub, sampling, counts if pen else None)
        eos = jnp.asarray(list(eos_ids) or [-1], np.int32)
        limits = jnp.asarray([e - 1 for e in eff], jnp.int32)  # after first
        if pen:
            live = jnp.asarray([e > 0 for e in eff])
            counts = counts.at[jnp.arange(B), first].add(
                live.astype(jnp.int32)
            )
        # n_steps is a STATIC arg of the compiled loop — bucket it to powers
        # of two so a serving batcher's varying budget mixes reuse a handful
        # of programs instead of compiling per distinct max(eff) (the loop
        # exits early once every row hits its limit, so the padding is free)
        n_steps = 1
        while n_steps < total - 1:
            n_steps <<= 1
        n_steps = max(min(n_steps, self.max_seq_len), 1)
        tokens, cache, done, n_exec, _key = _decode_loop(
            self.params, first, cache, key, sampling, eos, limits, counts,
            self.cfg, n_steps, penalize=pen,
        )
        del cache
        toks = np.asarray(tokens)
        first_host = np.asarray(first)
        n_exec = int(n_exec)  # steps the while_loop actually ran
        out: list[list[int]] = []
        fin: list[bool] = []
        done_host = np.asarray(done)
        eos_set = set(int(e) for e in np.asarray(eos))
        for i in range(len(lens)):
            if eff[i] <= 0:
                out.append([])
                fin.append(True)  # matches generate(): zero-room rows are done
                continue
            row = [int(first_host[i])]
            if row[0] not in eos_set:
                for t in toks[i, : min(n_exec, eff[i] - 1)]:
                    t = int(t)
                    row.append(t)
                    if t in eos_set:
                        break
            out.append(row)
            fin.append(bool(done_host[i]))
        return GenerationResult(sequences=out, prompt_lens=lens, finished=fin)
