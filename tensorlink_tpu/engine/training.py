"""Compiled training: loss, train-step factory, micro-batch accumulation.

The reference's training path replays torch autograd per offloaded module and
fans out optimizer RPCs (ml/module.py:414-524, ml/optim.py:81-205). Here a
training job inside one mesh is ONE compiled program: forward + backward +
optax update, parameters/grads/optimizer state all sharded by GSPMD, gradient
all-reduce riding ICI (psum over data/fsdp axes inserted by the compiler).
Micro-batching is a ``lax.scan`` gradient accumulation inside the program —
the compiled analogue of the reference's micro-batch threads
(module.py:374-399).

``make_train_step(zero1=True)`` is the ZeRO-1 data-parallel variant
(docs/TRAINING.md): gradients reduce cross-replica in a FIXED gather
order (the same trick that makes ``quantized_psum`` bitwise,
parallel/ring.py), the optax update runs on optimizer state that LIVES
1/dp per replica — declared to GSPMD through ``PartitionSpec`` rather
than hand-rolled RPC — and the updated params re-replicate through the
compiler's all-gather. With ``n_micro == dp`` the sharded step is
bit-identical to the unsharded microbatched step (test-pinned in
tests/test_zero1.py) while per-replica optimizer-state bytes drop to
~1/dp.

``make_train_step(zero1=True, tp_axis="tp")`` composes that with the
serving path's explicit tensor parallelism (docs/SHARDING.md) on a 2-D
``(dp, tp)`` mesh: params enter and leave the step as the SAME
head/column shards the paged engine serves (transformer.py
``tp_partition_specs``), each device gathers them whole for
forward/backward (grads land replicated over tp), the gradient
reduction runs over the dp axis only, and the optimizer update is
sliced over the FLATTENED ``dp·tp`` device grid — so resident
optimizer+weight bytes drop to ~1/(dp·tp) while the step stays
bit-identical to the unsharded reference (tests/test_tp.py). This is
what lets ``ServeTrainLoop`` train and hot-swap the very tensors a
tensor-parallel engine is serving without a relayout on either side.
"""

from __future__ import annotations

import typing
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from ..models.base import ModelConfig
from ..models.transformer import forward


def causal_lm_loss(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, T]
    loss_mask: jax.Array | None = None,  # [B, T] — True where next-token counts
    remat: bool = True,
):
    """Next-token cross-entropy in fp32. Returns (loss, aux)."""
    logits, _ = forward(params, tokens, cfg, remat=remat)
    logits = logits[:, :-1].astype(jnp.float32)
    targets = tokens[:, 1:]
    mask = (
        loss_mask[:, 1:]
        if loss_mask is not None
        else jnp.ones_like(targets, dtype=bool)
    )
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    n = jnp.maximum(mask.sum(), 1)
    loss = nll.sum() / n
    return loss, {"loss": loss, "n_tokens": n}


class ChainedOptimizer(typing.NamedTuple):
    """A ``make_optimizer`` result: duck-types optax.GradientTransformation
    (``init``/``update`` are the full chain's) while keeping the chain
    STRUCTURE visible — ``grad_clip`` + ``inner`` (the post-clip stages).
    The zero1 step needs the split: the global-norm clip must see the FULL
    gradient (a shard's norm is not the global norm), while the inner
    elementwise stages run on each replica's 1/dp shard."""

    init: Callable
    update: Callable
    grad_clip: float | None
    inner: "optax.GradientTransformation"
    name: str


def make_optimizer(
    name: str = "adamw",
    lr: float | optax.Schedule = 1e-4,
    *,
    weight_decay: float = 0.0,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float | None = 1.0,
    **kw,
) -> ChainedOptimizer:
    """optax chain mirroring the reference's optimizer spec ser/de surface
    (ml/utils.py:870-887 maps a name + kwargs)."""
    if name in ("adamw", "adam"):
        opt = optax.adamw(lr, b1=b1, b2=b2, weight_decay=weight_decay, **kw)
    elif name == "sgd":
        opt = optax.sgd(lr, **kw)
    elif name == "adafactor":
        opt = optax.adafactor(lr, **kw)
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    inner = opt
    if grad_clip:
        opt = optax.chain(optax.clip_by_global_norm(grad_clip), opt)
    return ChainedOptimizer(
        init=opt.init, update=opt.update,
        grad_clip=float(grad_clip) if grad_clip else None,
        inner=inner, name=str(name),
    )


@dataclass
class TrainStep:
    """Bundle of compiled step + optimizer for a model on a mesh."""

    step_fn: Callable  # (params, opt_state, batch) -> (params, opt_state, metrics)
    optimizer: optax.GradientTransformation
    mode: str = "unsharded"  # "unsharded" | "zero1"
    mesh: Any = None  # zero1 only: the mesh carrying the dp axis
    dp_axis: str = "data"
    tp_axis: str | None = None  # zero1 × TP: the mesh's tensor axis

    def init_state(self, params):
        state = self.optimizer.init(params)
        if self.mode != "zero1":
            return state
        # ZeRO-1: the PERSISTENT optimizer state lives 1/dp per replica —
        # device_put with the dp-extended specs here, and every step's
        # output constraint keeps it there (the donated buffers round-trip
        # sharded, so full state never materializes after this point).
        # Composed with TP the slice axis is the FLATTENED (dp, tp) grid:
        # 1/(dp·tp) resident state per device.
        from jax.sharding import NamedSharding, PartitionSpec as P

        dp = int(self.mesh.shape[self.dp_axis])
        if self.tp_axis:
            axes: Any = (self.dp_axis, self.tp_axis)
            size = dp * int(self.mesh.shape[self.tp_axis])
        else:
            axes, size = self.dp_axis, dp
        sspecs = optimizer_state_specs(
            self.optimizer, params,
            jax.tree.map(lambda _: P(), params),
            dp_axis=axes, dp_size=size,
        )
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            state, sspecs,
        )

    def n_programs(self) -> int:
        """Compiled-program count of the step — the zero1 compile guard's
        probe: at most TWO programs per train config (the cold-entry
        layout whose params/state arrive freshly placed, and the
        steady-state layout whose inputs are the previous step's
        donated outputs), and further steps add ZERO (test-pinned)."""
        cache_size = getattr(self.step_fn, "_cache_size", None)
        return int(cache_size()) if cache_size is not None else -1


def _accum_micro_grads(sum_grads, params, toks, lm):
    """Token-weighted gradient accumulation over the leading micro axis
    of ``toks``/``lm`` — ONE implementation shared by the unsharded scan
    and each zero1 replica's local scan, which is what makes the zero1
    fixed-order cross-replica reduction bitwise against the unsharded
    carry (the two paths cannot drift). ``sum_grads`` returns SUM-form
    gradients (the backward is seeded with the micro's token count, see
    make_train_step), so the carry is a plain add per micro and the
    caller divides once by the total token count — matching the
    n_micro=1 step even when loss masks populate micro-batches unevenly.

    The carry accumulates in fp32 regardless of param dtype: bf16 params
    would otherwise mix a bf16 gradient into an accumulator whose dtype
    must not degrade — a ``lax.scan`` carry dtype mismatch (the r02
    train_error) — and fp32 is the numerically right accumulator
    (test-pinned: tests/test_engine.py::test_bf16_scan_carry_stays_fp32).

    Bitwise invariance (what the zero1 == unsharded pin is built on):
    the ``optimization_barrier`` fences pin the accumulation arithmetic
    to exactly "materialized grads, one add" per micro — without them
    XLA fuses the accumulate into the backward's epilogue differently
    per scan length, and a replica's length-1 scan would not be bitwise
    a prefix of the unsharded length-N scan (measured; so is the
    sum-FORM requirement itself — a mean-form backward followed by a
    ``* n_tok`` rescale cancels against the loss's ``/ n`` differently
    per program). Returns ``(grad_sums_fp32, nll_sum, tok_sum)``."""
    from jax import lax

    def scan_fn(acc, xs):
        t = xs[0]
        m = xs[1] if len(xs) > 1 else None
        nll_sum, n_tok, grads = sum_grads(params, t, m)
        grads = lax.optimization_barrier(grads)
        acc_grads, acc_nll, acc_tok = acc
        acc_grads = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32),
            acc_grads, grads,
        )
        return lax.optimization_barrier(
            (acc_grads, acc_nll + nll_sum, acc_tok + n_tok)
        ), None

    zero = jax.tree.map(
        lambda p: jnp.zeros_like(p, dtype=jnp.float32), params
    )
    xs = (toks, lm) if lm is not None else (toks,)
    (grads, nll_sum, tok_sum), _ = jax.lax.scan(
        scan_fn, (zero, jnp.float32(0.0), jnp.float32(0.0)), xs
    )
    return grads, nll_sum, tok_sum


def make_train_step(
    cfg: ModelConfig,
    optimizer: optax.GradientTransformation,
    *,
    n_micro: int = 1,
    remat: bool = True,
    loss_fn: Callable | None = None,
    donate: bool = True,
    zero1: bool = False,
    mesh: Any = None,
    dp_axis: str = "data",
    tp_axis: str | None = None,
) -> TrainStep:
    """Build the compiled train step.

    ``n_micro > 1`` splits the batch inside the program and accumulates
    gradients with ``lax.scan`` (sequential — bounds activation memory the
    same way the reference's micro-batch pipeline does, without threads).

    ``zero1=True`` (docs/TRAINING.md) shards the WEIGHT UPDATE across the
    ``dp_axis`` of ``mesh``: each replica scans its contiguous block of
    the global micro-batches, partial gradient sums reduce cross-replica
    in a fixed gather order (bitwise-deterministic, the quantized_psum
    trick), and the optax update runs over optimizer state stored 1/dp
    per replica — declared through ``PartitionSpec``/sharding constraints
    so GSPMD shards the elementwise update math and re-replicates the
    params with one all-gather. Forward/backward and ``lax.scan``
    microbatching are byte-for-byte the unsharded path's (shared helper);
    with ``n_micro == dp`` the whole step is bit-identical to
    ``zero1=False`` (test-pinned). Requires ``n_micro % dp == 0`` so each
    replica scans whole micro-batches; buffer donation is preserved.

    ``tp_axis`` (with ``zero1=True``) composes the update sharding with
    the serving path's tensor parallelism: params flow through the step
    AS the serving shards (``tp_partition_specs``), gathered whole
    per-device for forward/backward, and the optimizer slice axis
    becomes the flattened ``dp·tp`` grid — see the module docstring and
    docs/SHARDING.md. The batch still shards over ``dp_axis`` only.
    """
    loss_fn = loss_fn or causal_lm_loss

    def compute_grads(params, tokens, loss_mask):
        grad_fn = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, tokens, loss_mask, remat=remat),
            has_aux=True,
        )
        (loss, aux), grads = grad_fn(params)
        return loss, aux, grads

    def sum_grads(params, tokens, loss_mask):
        # token-SUM objective for the micro accumulation: seeding the
        # backward with the micro's token count yields sum-form grads
        # directly, so the scan carry is a plain add — a mean-form
        # backward rescaled by n_tok after the fact is NOT bitwise
        # stable across scan lengths (see _accum_micro_grads)
        def objective(p):
            loss, aux = loss_fn(p, cfg, tokens, loss_mask, remat=remat)
            return loss * aux["n_tokens"].astype(jnp.float32), aux

        (nll_sum, aux), grads = jax.value_and_grad(
            objective, has_aux=True
        )(params)
        return nll_sum, aux["n_tokens"].astype(jnp.float32), grads

    if zero1:
        tp_pspecs = None
        if tp_axis is not None:
            from ..models.transformer import tp_partition_specs, tp_shardable

            if mesh is None:
                raise ValueError("tp_axis requires a mesh")
            if tp_axis not in dict(mesh.shape):
                raise ValueError(
                    f"mesh has no {tp_axis!r} axis: {dict(mesh.shape)}"
                )
            reason = tp_shardable(cfg, int(mesh.shape[tp_axis]))
            if reason is not None:
                raise ValueError(f"tp_axis={tp_axis!r}: {reason}")
            tp_pspecs = tp_partition_specs(cfg, axis=tp_axis)
        return _make_zero1_step(
            optimizer, sum_grads, mesh=mesh, dp_axis=dp_axis,
            n_micro=n_micro, donate=donate,
            tp_axis=tp_axis, tp_pspecs=tp_pspecs,
        )
    if tp_axis is not None:
        raise ValueError("tp_axis requires zero1=True (the sharded step)")

    def step(params, opt_state, batch):
        tokens = batch["tokens"]
        loss_mask = batch.get("loss_mask")
        if n_micro > 1:
            B = tokens.shape[0]
            if B % n_micro != 0:
                raise ValueError(
                    f"batch {B} not divisible by n_micro={n_micro}"
                )
            mb = B // n_micro
            toks = tokens[: mb * n_micro].reshape(n_micro, mb, -1)
            lm = (
                loss_mask[: mb * n_micro].reshape(n_micro, mb, -1)
                if loss_mask is not None
                else None
            )
            grads, nll_sum, tok_sum = _accum_micro_grads(
                sum_grads, params, toks, lm
            )
            tok_sum = jnp.maximum(tok_sum, 1.0)
            # hand the optimizer grads in param dtype, matching n_micro=1
            # (keeps opt_state dtypes stable across both paths)
            grads = jax.tree.map(
                lambda g, p: (g / tok_sum).astype(p.dtype), grads, params
            )
            loss = nll_sum / tok_sum
        else:
            loss, _aux, grads = compute_grads(params, tokens, loss_mask)

        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grads)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    donate_args = (0, 1) if donate else ()
    return TrainStep(
        step_fn=jax.jit(step, donate_argnums=donate_args), optimizer=optimizer
    )


def _dp_shardable(shape, dp: int) -> bool:
    """THE zero1 sharding predicate — shared by the spec derivation and
    the step's in-region slicing so a state leaf can never shard
    differently from the param/grad slice it updates."""
    return bool(shape) and shape[0] >= dp and shape[0] % dp == 0


def _make_zero1_step(
    optimizer, sum_grads, *, mesh, dp_axis, n_micro, donate,
    tp_axis=None, tp_pspecs=None,
) -> TrainStep:
    """The ZeRO-1 step body (see make_train_step). Split out so the
    unsharded path above stays byte-identical to its pre-zero1 shape.

    Layout (docs/TRAINING.md): params and gradients stay REPLICATED over
    the dp axis (forward/backward need whole params); only the optimizer
    state shards. The whole step is one shard_map region —

    1. local ``lax.scan`` micro accumulation on each replica's batch
       block (the shared helper, fp32 sum-form carry),
    2. fixed-gather-order cross-replica reduction (bitwise — the
       quantized_psum trick; a psum's ring order varies by position),
    3. the global-norm clip stage on the FULL replicated gradient
       (bitwise the unsharded chain's own first stage),
    4. the inner elementwise update on each replica's 1/dp slice of
       (grads, params) against its resident 1/dp optimizer-state shard —
       elementwise math is slice-invariant, proven bitwise in tests,
    5. one tiled all_gather re-replicates the updated params.

    The inner update must be SHARD-LOCAL (elementwise): adam/adamw/sgd
    qualify; adafactor's factored second moments do not and are refused.
    A plain optax transformation (not from ``make_optimizer``) is trusted
    to be shard-local — wrap global-norm stages via ``make_optimizer`` so
    the clip split applies.

    ``tp_axis`` composes the step with explicit tensor parallelism
    (docs/SHARDING.md): params enter/leave the region as their LOCAL
    serving shards (``tp_pspecs``), step 0.5 all-gathers each sharded
    leaf whole along its own sharded dim (tiled — exact reassembly, so
    the forward/backward sees bitwise the unsharded weights), the
    reduction in step 2 runs over ``dp_axis`` only (grads land
    replicated over tp for free: every tp peer saw the same batch
    block and the same full params), and steps 4-5 slice by the
    flattened ``data_idx · tp + tp_idx`` device index and re-gather
    over BOTH axes in that order — optimizer state persists 1/(dp·tp)
    per device. With ``tp_axis=None`` every helper degenerates to the
    plain zero1 shape above."""
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        raise ValueError("zero1=True requires a mesh with a dp axis")
    if dp_axis not in dict(mesh.shape):
        raise ValueError(f"mesh has no {dp_axis!r} axis: {dict(mesh.shape)}")
    dp = int(mesh.shape[dp_axis])
    if dp < 2:
        raise ValueError(
            f"zero1 needs {dp_axis} > 1 (got {dp}) — the planner picks the "
            "unsharded step for single-replica meshes"
        )
    if n_micro % dp != 0:
        raise ValueError(
            f"zero1 needs n_micro ({n_micro}) divisible by {dp_axis}={dp} "
            "so each replica scans whole micro-batches"
        )
    grad_clip = getattr(optimizer, "grad_clip", None)
    inner = getattr(optimizer, "inner", optimizer)
    if getattr(optimizer, "name", "") == "adafactor":
        raise ValueError(
            "zero1 requires a shard-local (elementwise) optimizer update; "
            "adafactor's factored second moments are not — use adamw/sgd"
        )
    tp = int(mesh.shape[tp_axis]) if tp_axis else 1
    world = dp * tp  # the flattened update-slice grid
    local_micro = n_micro // dp
    replicated = NamedSharding(mesh, P())

    def _tp_dim(spec):
        """Index of the tp-sharded dim in a weight's partition spec, or
        None for replicated leaves (norms, embeddings)."""
        for i, part in enumerate(tuple(spec)):
            if part == tp_axis:
                return i
        return None

    def gather_full(params):
        """Reassemble whole weights from this device's serving shards —
        tiled all_gather along each leaf's own sharded dim is EXACT
        (concatenation of the original column blocks in axis order), so
        downstream forward/backward math is bitwise the unsharded
        step's."""
        if tp_axis is None:
            return params
        return jax.tree.map(
            lambda x, sp: x if _tp_dim(sp) is None else lax.all_gather(
                x, tp_axis, axis=_tp_dim(sp), tiled=True
            ),
            params, tp_pspecs,
        )

    def slice_leaf(x, idx):
        shape = tuple(x.shape)
        if not _dp_shardable(shape, world):
            return x
        blk = shape[0] // world
        return lax.dynamic_slice_in_dim(x, idx * blk, blk, axis=0)

    def region(params, opt_state, tokens, loss_mask):
        # runs per replica inside shard_map: this replica's batch shard is
        # its contiguous block of the global micro sequence, scanned with
        # the SAME fp32 sum-form carry as the unsharded path
        params = gather_full(params)
        mb = tokens.shape[0] // local_micro
        toks = tokens.reshape(local_micro, mb, -1)
        lm = (
            loss_mask.reshape(local_micro, mb, -1)
            if loss_mask is not None else None
        )
        partial, nll, ntok = _accum_micro_grads(
            sum_grads, params, toks, lm
        )

        # Fixed-order cross-replica reduction: all_gather the partial
        # sums, then add them left-to-right in replica order — the
        # accumulation tree extends the scan carry exactly, so with one
        # micro per replica the reduced gradient is BITWISE the unsharded
        # scan's (unlike psum, whose ring accumulation order varies with
        # device position — the same reasoning as ring.quantized_psum).
        # Every replica computes the identical full value, which is what
        # lets out_specs declare the results replicated. (Sole caveat: an
        # exact-zero partial may normalize -0.0 → +0.0 — invisible to
        # every downstream op.)
        def ordered(x):
            g = lax.all_gather(x, dp_axis, axis=0)
            acc = g[0]
            for i in range(1, dp):
                acc = acc + g[i]
            return acc

        grads = jax.tree.map(ordered, partial)
        nll_sum, tok_sum = ordered(nll), ordered(ntok)
        tok_sum = jnp.maximum(tok_sum, 1.0)
        grads = jax.tree.map(
            lambda g, p: (g / tok_sum).astype(p.dtype), grads, params
        )
        loss = nll_sum / tok_sum
        gnorm = optax.global_norm(grads)

        # the global-norm clip needs the FULL gradient (a shard's norm is
        # not the global norm): run the chain's own clip stage on the
        # replicated grads — the exact transformation the unsharded chain
        # applies, on bitwise-identical inputs
        if grad_clip is not None:
            clip_t = optax.clip_by_global_norm(grad_clip)
            grads_in, _ = clip_t.update(grads, clip_t.init(params), params)
            clip_state, inner_state = opt_state[0], opt_state[1]
        else:
            grads_in = grads
            clip_state, inner_state = None, opt_state

        # the sharded weight update: this device's 1/world slice of grads
        # + params against its RESIDENT 1/world optimizer-state shard
        # (the in_specs delivered it as local blocks — state never
        # re-replicates); elementwise updates are slice-invariant, so the
        # gathered result is bitwise the full update's. Under TP the
        # slice index is the FLATTENED (dp, tp) grid position — the
        # device order serving_mesh documents.
        idx = lax.axis_index(dp_axis)
        if tp_axis is not None:
            idx = idx * tp + lax.axis_index(tp_axis)
        g_r = jax.tree.map(lambda x: slice_leaf(x, idx), grads_in)
        p_r = jax.tree.map(lambda x: slice_leaf(x, idx), params)
        u_r, new_inner = inner.update(g_r, inner_state, p_r)
        newp_r = optax.apply_updates(p_r, u_r)

        def unslice(full, piece):
            if _dp_shardable(tuple(full.shape), world):
                axes = (dp_axis, tp_axis) if tp_axis is not None else dp_axis
                return lax.all_gather(piece, axes, axis=0, tiled=True)
            return piece

        new_params = jax.tree.map(unslice, params, newp_r)
        if tp_axis is not None:
            # hand the updated weights back as this device's SERVING
            # shard (the out_specs layout): exact column re-slice of the
            # full update — the serve-train hot-swap publishes these
            # without any relayout
            def reslice(x, sp):
                d = _tp_dim(sp)
                if d is None:
                    return x
                sz = x.shape[d] // tp
                return lax.dynamic_slice_in_dim(
                    x, lax.axis_index(tp_axis) * sz, sz, axis=d
                )

            new_params = jax.tree.map(reslice, new_params, tp_pspecs)
        new_state = (
            (clip_state, new_inner) if grad_clip is not None else new_inner
        )
        return new_params, new_state, loss, gnorm

    def step(params, opt_state, batch):
        tokens = batch["tokens"]
        loss_mask = batch.get("loss_mask")
        B = tokens.shape[0]
        if B % n_micro != 0:
            raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")
        mb = B // n_micro
        toks = tokens[: mb * n_micro]
        lm = loss_mask[: mb * n_micro] if loss_mask is not None else None
        pspecs = (
            tp_pspecs if tp_axis is not None
            else jax.tree.map(lambda _: P(), params)
        )
        state_axes = (dp_axis, tp_axis) if tp_axis is not None else dp_axis
        sspecs = optimizer_state_specs(
            optimizer, params, jax.tree.map(lambda _: P(), params),
            dp_axis=state_axes, dp_size=world,
        )
        out_sspecs = (
            (sspecs[0], sspecs[1]) if grad_clip is not None else sspecs
        )
        # the TP forward's fixed-order all_gathers are typed varying though
        # every shard holds the same bytes (as in the serving step,
        # engine/paged.py) — the replicated outputs hold by construction
        check_vma = tp_axis is None
        if lm is None:
            fn = jax.shard_map(
                lambda p, s, t: region(p, s, t, None),
                mesh=mesh,
                in_specs=(pspecs, sspecs, P(dp_axis)),
                out_specs=(pspecs, out_sspecs, P(), P()),
                check_vma=check_vma,
            )
            new_params, new_state, loss, gnorm = fn(params, opt_state, toks)
        else:
            fn = jax.shard_map(
                region, mesh=mesh,
                in_specs=(pspecs, sspecs, P(dp_axis), P(dp_axis)),
                out_specs=(pspecs, out_sspecs, P(), P()),
                check_vma=check_vma,
            )
            new_params, new_state, loss, gnorm = fn(
                params, opt_state, toks, lm
            )
        return new_params, new_state, {"loss": loss, "grad_norm": gnorm}

    jit_step = jax.jit(step, donate_argnums=(0, 1) if donate else ())

    def step_fn(params, opt_state, batch):
        # bounded-compile discipline: entry params may arrive committed
        # anywhere (init_params: one device; a checkpoint restore: host)
        # — normalize them to ONE layout before the jit (replicated, or
        # the serving shards under TP), so the cache holds at most the
        # cold-entry program plus the steady-state program whose inputs
        # are the previous step's outputs (tests pin n_programs() <= 2,
        # churn-free)
        if tp_axis is not None:
            params = jax.tree.map(
                lambda x, sp: x
                if getattr(x, "sharding", None) == NamedSharding(mesh, sp)
                else jax.device_put(x, NamedSharding(mesh, sp)),
                params, tp_pspecs,
            )
        else:
            params = jax.tree.map(
                lambda x: x if getattr(x, "sharding", None) == replicated
                else jax.device_put(x, replicated),
                params,
            )
        return jit_step(params, opt_state, batch)

    step_fn._cache_size = jit_step._cache_size  # the compile-guard probe
    return TrainStep(
        step_fn=step_fn,
        optimizer=optimizer, mode="zero1", mesh=mesh, dp_axis=dp_axis,
        tp_axis=tp_axis,
    )


def optimizer_state_specs(
    optimizer: optax.GradientTransformation, params, param_specs,
    *, dp_axis: "str | tuple | None" = None, dp_size: int = 0,
):
    """PartitionSpec pytree for the optax state: any sub-tree that mirrors
    the param tree (adam moments, momentum buffers) shards like the params;
    scalars (step counts) replicate. The reference keeps optimizer state on
    each worker next to its modules (ml/optim.py init fan-out) — same
    locality, but declared to the compiler instead of managed by RPC.

    ``dp_axis``/``dp_size`` is the ZeRO-1 extension (docs/TRAINING.md):
    every state leaf whose leading dim divides ``dp_size`` additionally
    shards over ``dp_axis`` (only where the param spec leaves dim 0
    unsharded — composing with an existing dim-0 axis is refused rather
    than guessed), dropping persistent per-replica bytes to ~1/dp. Under
    GSPMD the dp sharding is pure LAYOUT: elementwise update math is
    partition-invariant, so this never changes a step's values.
    ``dp_axis`` may be a TUPLE of mesh axes — the zero1 × TP step passes
    ``(dp_axis, tp_axis)`` so state shards over the flattened device
    grid (~1/(dp·tp) resident bytes).

    Hardened for optax states whose sub-trees DON'T mirror the param tree
    (``optax.masked`` moment trees carry ``MaskedNode`` placeholders,
    factored states carry row/col vectors, chains nest ``EmptyState``):
    a non-mirroring array leaf inherits the spec of the unique same-shape
    param when one exists, else shards over ``dp_axis`` when divisible —
    a moment buffer is never silently replicated; leaves we genuinely
    can't place replicate with a WARNING (unit-tested in
    tests/test_zero1.py)."""
    from jax.sharding import PartitionSpec as P

    from ..core.logging import get_logger

    state_shapes = jax.eval_shape(optimizer.init, params)
    pdef = jax.tree.structure(params)

    def is_param_tree(node):
        try:
            return jax.tree.structure(node) == pdef
        except Exception:
            return False

    def with_dp(spec, shape):
        """Extend ``spec`` with the dp axis on an unsharded, divisible
        leading dim; anything else passes through unchanged."""
        if not dp_axis or dp_size <= 1:
            return spec
        if not shape or shape[0] < dp_size or shape[0] % dp_size:
            return spec
        parts = list(tuple(spec))
        parts += [None] * (len(shape) - len(parts))
        if parts[0] is not None:
            return spec  # dim 0 already sharded — never compose, refuse
        parts[0] = dp_axis
        return P(*parts)

    def _shape(leaf) -> tuple:
        return tuple(getattr(leaf, "shape", ()) or ())

    # shape → candidate specs, the fallback for state leaves OUTSIDE a
    # mirroring sub-tree (masked/chained/factored optax states)
    shape_specs: dict[tuple, list] = {}
    spec_leaves = jax.tree.leaves(
        param_specs, is_leaf=lambda x: isinstance(x, P)
    )
    for pl, sp in zip(jax.tree.leaves(params), spec_leaves):
        cands = shape_specs.setdefault(_shape(pl), [])
        if sp not in cands:
            cands.append(sp)

    log = get_logger("engine.training")

    def spec_for_stray(leaf):
        shape = _shape(leaf)
        cands = shape_specs.get(shape, [])
        if len(cands) == 1:
            return with_dp(cands[0], shape)
        if dp_axis and dp_size > 1 and shape \
                and shape[0] >= dp_size and shape[0] % dp_size == 0:
            # moment-like buffer with no (unambiguous) param twin: dp
            # sharding is safe layout — never silently replicate it
            return with_dp(P(), shape)
        if shape and any(d > 1 for d in shape):
            log.warning(
                "optimizer state leaf of shape %s matches no unique param "
                "layout — replicating it (candidates: %s)", shape, cands,
            )
        return P()

    def map_node(node):
        if is_param_tree(node):
            return jax.tree.map(
                lambda sp, leaf: with_dp(sp, _shape(leaf)),
                param_specs, node,
                is_leaf=lambda x: isinstance(x, P),
            )
        return spec_for_stray(node)

    return jax.tree.map(map_node, state_shapes, is_leaf=is_param_tree)
