"""Jittable token sampling.

The reference delegates sampling to HF ``generate()`` kwargs
(temperature/top-p/top-k normalized in ml/formatter.py:7-117); here sampling
is a pure function compiled into the decode program so the token loop never
leaves the device.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclass
class SamplingParams:
    """Dynamic sampling knobs — pytree leaves so one compiled program serves
    every request (no recompile per temperature change).

    Leaves are scalars for a single request, or ``[B, 1]`` for a batched
    mix of requests with different knobs (the serving batcher,
    ml/batching.py) — :func:`sample` broadcasts either shape."""

    temperature: jax.Array  # f32; <=0 → greedy
    top_k: jax.Array  # int32; 0 → disabled
    top_p: jax.Array  # f32; >=1 → disabled
    # OpenAI-style repetition control (0 → disabled): logits of tokens seen
    # in the context so far are shifted by
    #   -presence·1[count>0] - frequency·count
    # (applied in :func:`sample` when the caller supplies token counts —
    # the reference declares these fields, api/models.py:73-74, but never
    # applies them)
    presence_penalty: jax.Array = None  # type: ignore[assignment]
    frequency_penalty: jax.Array = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.presence_penalty is None:
            object.__setattr__(self, "presence_penalty", np.float32(0.0))
        if self.frequency_penalty is None:
            object.__setattr__(self, "frequency_penalty", np.float32(0.0))

    @classmethod
    def make(
        cls, temperature=0.0, top_k=0, top_p=1.0,
        presence_penalty=0.0, frequency_penalty=0.0,
    ) -> "SamplingParams":
        """One request's knobs as HOST scalars of the leaves' dtypes: a
        jitted call places them like any array (same program), and the
        slot engine, which reads them back at admission, reads host
        memory (as ``jnp`` scalars each was a placement here and a fetch
        there, with the device idle: PERF.md section 5, PR 43)."""
        return cls(
            temperature=np.float32(temperature),
            top_k=np.int32(top_k),
            top_p=np.float32(top_p),
            presence_penalty=np.float32(presence_penalty),
            frequency_penalty=np.float32(frequency_penalty),
        )

    def pad_rows(self, batch: int) -> "SamplingParams":
        """Pad per-row leaves to the engine's bucketed batch size (extra
        rows decode greedily); scalar leaves pass through untouched."""
        if jnp.asarray(self.temperature).ndim == 0:
            return self
        n = jnp.asarray(self.temperature).reshape(-1).shape[0]
        if n == batch:
            return self

        def pad(leaf, fill, dtype):
            flat = jnp.asarray(leaf, dtype).reshape(-1)
            return jnp.concatenate(
                [flat, jnp.full((batch - n,), fill, dtype)]
            )[:, None]

        return SamplingParams(
            temperature=pad(self.temperature, 0.0, jnp.float32),
            top_k=pad(self.top_k, 0, jnp.int32),
            top_p=pad(self.top_p, 1.0, jnp.float32),
            presence_penalty=pad(self.presence_penalty, 0.0, jnp.float32),
            frequency_penalty=pad(self.frequency_penalty, 0.0, jnp.float32),
        )

    @classmethod
    def stack(cls, params: "list[SamplingParams]", pad_to: int) -> "SamplingParams":
        """Per-row knobs for a batched generate; rows past ``len(params)``
        (bucket padding) decode greedily."""
        def col(attr, fill, dtype):
            vals = [float(jnp.asarray(getattr(p, attr))) for p in params]
            vals += [fill] * (pad_to - len(vals))
            return jnp.asarray(vals, dtype)[:, None]  # [B, 1]

        return cls(
            temperature=col("temperature", 0.0, jnp.float32),
            top_k=col("top_k", 0, jnp.int32),
            top_p=col("top_p", 1.0, jnp.float32),
            presence_penalty=col("presence_penalty", 0.0, jnp.float32),
            frequency_penalty=col("frequency_penalty", 0.0, jnp.float32),
        )


def penalized(logits, counts, pres, freq):
    """OpenAI-style repetition control over the context so far: float32
    ``logits [B, V]`` less ``pres`` where a token occurred and ``freq`` per
    occurrence (``pres`` / ``freq`` broadcast against ``[B, V]``)."""
    cf = counts.astype(jnp.float32)
    return logits - pres * (cf > 0) - freq * cf


@jax.jit
def sample(
    logits: jax.Array,  # [B, V] float
    key: jax.Array,
    p: SamplingParams,
    counts: jax.Array | None = None,  # int32 [B, V] context token counts
) -> jax.Array:
    """Temperature / top-k / top-p sampling, greedy when temperature<=0.

    Fully vectorized: filters are masks over the sorted distribution, so the
    same program handles any (k, p) at runtime.

    jit at the definition is load-bearing: the ``lax.cond`` below builds
    fresh branch closures per call, so an EAGER call can never hit jax's
    trace cache and pays a full XLA compile of the sampled branch (argsort
    over the vocab) every time — ~0.5 s on CPU, seconds on TPU. That
    exact miss sat on every ``generate_compiled`` call (the prefill-token
    sample) and every host-driven decode step, and was the dominant term in
    the round-2 decode benchmark (25 tok/s vs 101 roofline). Inside an
    enclosing jit the wrapper inlines and changes nothing.

    Scalar knobs apply to every row; ``[B, 1]`` knobs mix per-row
    settings in one batch and select greedy/sampled per row. The
    ``lax.cond`` at the end skips the vocab argsort when no row samples,
    for a caller that is NOT under a ``vmap`` (the legacy engine's
    callers). Under a ``vmap`` over rows the predicate is batched and the
    ``cond`` lowers to a select: both branches run for every row. That is
    how the continuous engine's step paid a sort, a softmax and a cumsum
    over the vocabulary per greedy row (``verify_emit_ms`` 106 ms a chunk
    on a v5e, PERF.md section 6, PR 27); its ``_sample_rows`` now makes
    the choice once over the slots, outside the ``vmap``, and calls this
    function only when some slot samples.
    """
    logits = logits.astype(jnp.float32)
    B, V = logits.shape
    if counts is not None:
        pres = jnp.broadcast_to(
            jnp.atleast_1d(p.presence_penalty).reshape(-1, 1), (B, 1)
        )
        freq = jnp.broadcast_to(
            jnp.atleast_1d(p.frequency_penalty).reshape(-1, 1), (B, 1)
        )
        logits = penalized(logits, counts, pres, freq)
    temp = jnp.broadcast_to(jnp.atleast_1d(p.temperature).reshape(-1, 1), (B, 1))
    top_k = jnp.broadcast_to(jnp.atleast_1d(p.top_k).reshape(-1, 1), (B, 1))
    top_p = jnp.broadcast_to(jnp.atleast_1d(p.top_p).reshape(-1, 1), (B, 1))

    def sampled(_):
        scaled = logits / jnp.maximum(temp, 1e-6)
        sort_idx = jnp.argsort(-scaled, axis=-1)
        sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
        ranks = jnp.arange(V)[None, :]
        # top-k: keep ranks < k (k==0 → keep all)
        k = jnp.where(top_k > 0, top_k, V)
        keep = ranks < k
        # top-p: keep the smallest prefix with cumulative prob >= p
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep &= (cum - probs) < top_p
        masked = jnp.where(keep, sorted_logits, -jnp.inf)
        choice = jax.random.categorical(key, masked, axis=-1)  # [B]
        picks = jnp.take_along_axis(sort_idx, choice[:, None], axis=-1)[:, 0]
        # per-row greedy/sampled selection for mixed batches
        return jnp.where(temp[:, 0] > 0.0, picks, logits.argmax(-1))

    def greedy(_):
        return logits.argmax(-1)

    return jax.lax.cond(temp.max() > 0.0, sampled, greedy, None).astype(
        jnp.int32
    )
