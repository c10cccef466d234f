"""What decides ``correct``, after the window, in the same process:

(a) two seeded prompts go through ``/v1/generate`` and the plain float32
    reference (``benchmarks/reference/decoder.py``) runs teacher-forced over
    prompt + served tokens on the hosted job's own weights: every served
    token's reference logit lies within ``tolerance.json``'s
    ``max_gap_sigmas`` of that position's reference maximum;
(b) every stream that ended has the length it asked for;
(c) ``check_page_conservation()`` is clean on the idle engine;
(d) every request went through the ``ContinuousEngine`` (and, on the chip,
    its lowered step program holds the Pallas kernel: ``tpu_custom_call``).
"""

from __future__ import annotations

import json
import time

import numpy as np

from .cluster import BenchFailure
from .plan import Req, content_seed
from .spec import BENCH_DIR
from .tokenizer import text_to_ids


def _holds(seq: list[int], part: list[int]) -> bool:
    n = len(part)
    return any(seq[i:i + n] == part for i in range(len(seq) - n + 1))


def wait_idle(cont, timeout: float = 90.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not cont.has_work() and cont.live_slots == 0:
            return True
        time.sleep(0.05)
    return False


def after_window_checks(drv, cont, submitted: list, cell, seed: int, *,
                        require_kernel: bool):
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    from benchmarks.reference import decoder

    with open(BENCH_DIR / "reference" / "tolerance.json") as f:
        tol = json.load(f)
    notes: list[str] = []
    ok = True

    # (d) a request that was not served by the slot engine is a failure of
    # the run, not a wrong answer
    served = [r for r in drv.recs if r.status in ("ok", "cut") and r.stamps]
    if not isinstance(cont, ContinuousEngine) or len(submitted) < len(served):
        raise BenchFailure(
            f"{len(served)} streams were served but the slot engine admitted "
            f"{len(submitted)}: something else served requests"
        )
    if require_kernel and not cont.use_kernel:
        raise BenchFailure("the slot engine runs without its Pallas kernel")
    notes.append(f"(d) {len(submitted)} admissions to the ContinuousEngine for "
                 f"{len(served)} served streams, kernel={cont.use_kernel}")

    # (b) lengths
    short = [r for r in drv.recs if r.status == "ok" and len(r.stamps) != r.asked]
    engine_short = [q for q in submitted
                    if q.finished and q.error is None and len(q.tokens) != q.budget]
    if short or engine_short:
        ok = False
    notes.append(f"(b) {len(short)} stream(s) and {len(engine_short)} engine "
                 "record(s) shorter than asked")

    # (a) the reference, while what the window left behind drains
    t = time.monotonic()
    arch = decoder.arch_of(cell.config)
    pairs = []
    for i in range(int(tol["prompts"])):
        before = len(submitted)
        spec = Req(idx=-10 - i, prompt_tokens=int(tol["prompt_tokens"]),
                   output_tokens=int(tol["new_tokens"]),
                   content_seed=content_seed(seed, 9, i), count_template=False)
        message = text_to_ids(drv.body_for(spec)["message"])
        rec = drv.one(spec)
        # the engine's own record of this request: the one admission since
        # it was sent whose prompt holds the message
        mine = [q for q in submitted[before:] if _holds(list(q.prompt), message)]
        if rec.status != "ok" or len(mine) != 1:
            ok = False
            notes.append(f"(a) check request {i}: {rec.status} {rec.detail}; "
                         f"{len(mine)} matching admission(s)")
            continue
        req = mine[0]
        ids = text_to_ids(rec.text)
        if ids != list(req.tokens):
            ok = False
            notes.append(f"(a) prompt {i}: the client read {ids[:4]}.. but the "
                         f"engine emitted {list(req.tokens)[:4]}..")
            continue
        pairs.append((list(req.prompt), ids))
    t_req = time.monotonic() - t
    t = time.monotonic()
    worst = float("inf")
    if pairs and len({len(p) + len(s) for p, s in pairs}) == 1:
        gaps = decoder.served_gaps(cont.engine.params, [p for p, _ in pairs],
                                   [s for _, s in pairs], arch)
        worst = float(gaps.max())
        for i, g in enumerate(gaps):
            notes.append(
                f"(a) prompt {i} ({len(pairs[i][0])} tokens): served-token "
                f"logit under the reference maximum by max {g.max():.4f}, mean "
                f"{g.mean():.4f} deviations; {int((g == 0).sum())}/{g.size} "
                "are the reference's argmax"
            )
    if not np.isfinite(worst) or worst > float(tol["max_gap_sigmas"]):
        ok = False
    notes.append(f"(a) worst gap {worst:.4f} deviations against tolerance "
                 f"{tol['max_gap_sigmas']} (requests {t_req:.1f}s, reference "
                 f"{time.monotonic() - t:.1f}s)")

    # (c) pages, and (d) the kernel in the lowered program: on the idle engine
    t = time.monotonic()
    if not wait_idle(cont):
        ok = False
        notes.append("(c) the engine did not go idle")
    else:
        try:
            cont.check_page_conservation()
            notes.append(f"(c) page conservation clean (idle after "
                         f"{time.monotonic() - t:.1f}s)")
        except AssertionError as e:
            ok = False
            notes.append(f"(c) page conservation: {e}")
        if require_kernel and (
            "tpu_custom_call" not in cont.lower_step().as_text()
        ):
            raise BenchFailure("no tpu_custom_call in the lowered step program")
    return ok, notes
