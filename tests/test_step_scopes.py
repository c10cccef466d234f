"""The step program's device phases carry the program's own names
(engine/paged.py ``STEP_PHASES``), read back from ``lower_step()``.

A profiler trace's events keep operation names and no scope, so what ties
the first, second and third top-level loop of ``paged_ragged_step`` to
``tlink.ragged_pass``, ``tlink.verify_emit`` and ``tlink.decode_cont`` is
this file: the reduction that reads the three as phases counts on the
order pinned here. Scopes are metadata: they change no instruction. The
Pallas kernels are named by ``pl.pallas_call(name=...)`` after their entry
points, which the reduction matches, and sit under an ``attn`` scope.
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.engine.continuous import ContinuousEngine
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.paged import STEP_PHASES, paged_ragged_step
from tensorlink_tpu.models import ModelConfig, init_params


@pytest.fixture(scope="module")
def tiny_engine():
    cfg = ModelConfig(
        family="llama", vocab_size=128, d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=64,
        dtype=jnp.float32, tie_embeddings=False,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    return GenerationEngine(
        cfg, params, seq_buckets=(8, 32), batch_buckets=(1,), max_seq_len=64
    )


def _cont(eng, spec_width: int) -> ContinuousEngine:
    return ContinuousEngine(
        eng, max_slots=4, page_size=8, chunk_steps=4,
        spec_decode=spec_width > 1, spec_draft=max(spec_width - 1, 1),
    )


def _idle_operands(ce) -> tuple:
    """The step program's operands at the engine's shapes, nothing live
    (what ``lower_step`` lowers with)."""
    S, C = ce.max_slots, ce.prefill_chunk
    zi = np.zeros(S, np.int32)
    return ce._step_operands(
        np.zeros((S, C), np.int32), zi, zi, zi, np.zeros(S, bool), zi,
        np.full((S, ce._EOS_WIDTH), -1, np.int32),
    )


def _loc_names(text: str) -> dict:
    """``#locN`` -> the scope path of a named location, aliases resolved
    (``#loc7 = loc("jit(f)/tlink.a/while"(#loc3))``)."""
    names = {}
    for ref, name in re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M):
        names[ref] = name
    return names


def _main_body(text: str) -> list[str]:
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines) if "func.func public @main" in ln)
    end = next(i for i in range(start + 1, len(lines))
               if lines[i].startswith("  func.func") or lines[i] == "}")
    return lines[start:end]


def top_level_loops(text: str) -> list[str]:
    """Scope path of each ``stablehlo.while`` directly in ``@main`` (not
    nested in another loop's region), in program order. A loop's location
    is on the line that closes its ``do`` region."""
    names = _loc_names(text)
    out, depth = [], 0
    for ln in _main_body(text)[1:]:
        if "stablehlo.while" in ln:
            depth += 1
        m = re.match(r"\s*} loc\((#loc\d+)\)", ln)
        if m and depth:
            depth -= 1
            if depth == 0:
                out.append(names[m.group(1)])
    return out


@pytest.mark.parametrize("rung", [-1, 0, 1], ids=["wide", "narrow", "flat"])
@pytest.mark.parametrize("spec_width", [1, 9])
def test_three_top_level_loops_in_phase_order(tiny_engine, spec_width, rung):
    """At each rung of the ladder (one program a rung): what reads the
    phases by loop order reads a narrow or a flat chunk's as a wide
    one's (the flat rung's gathers live inside ``tlink.ragged_pass``)."""
    ce = _cont(tiny_engine, spec_width)
    assert ce.spec_width == spec_width
    assert ce.block_widths == (8 if spec_width == 1 else 16, 64)
    assert ce.rungs[1] == (64, 128)
    width, flat_rows = ce.rungs[rung]
    lower_step = partial(ce.lower_step, width, flat=flat_rows > 0)
    text = lower_step().as_text(debug_info=True)
    assert f"tensor<4x{width}xi32>" in text  # the packed block's shape
    assert (f"tensor<1x{flat_rows}x" in text) == (flat_rows > 0)
    loops = top_level_loops(text)
    assert len(loops) == 3, loops
    for path, phase in zip(loops, STEP_PHASES):
        assert path.split("/")[1:] == [phase, "while"], (path, phase)
    assert STEP_PHASES == (
        "tlink.ragged_pass", "tlink.verify_emit", "tlink.decode_cont")
    # compiled, the three are still loops of the entry computation, in
    # that order: the layer scan with its trip count known, the verify
    # walk with none (its bound is data: the longest emitting draft + 1),
    # so no pass can inline it even at spec_width 1
    compiled = lower_step().compile().as_text()
    whiles = [ln for ln in compiled[compiled.index("ENTRY"):].splitlines()
              if " while(" in ln]
    assert [re.search(r'op_name="[^"]*/(tlink\.\w+)/while"', ln).group(1)
            for ln in whiles] == list(STEP_PHASES)
    assert "known_trip_count" in whiles[0]
    assert "known_trip_count" not in whiles[1]
    # the inner names are there, under the phases: an operation inside a
    # loop's body names its whole path only once compiled
    paths = set(re.findall(r'op_name="([^"]*)"', compiled))
    for phase, inner in (("tlink.ragged_pass", "attn"),
                         ("tlink.ragged_pass", "kv_write"),
                         ("tlink.ragged_pass", "mlp"),
                         ("tlink.ragged_pass", "lm_head"),
                         ("tlink.verify_emit", "sample"),
                         ("tlink.decode_cont", "attn"),
                         ("tlink.decode_cont", "kv_write"),
                         ("tlink.decode_cont", "mlp"),
                         ("tlink.decode_cont", "lm_head"),
                         ("tlink.decode_cont", "sample")):
        assert any(f"/{phase}/" in p and f"/{inner}/" in p for p in paths), (
            phase, inner)
    ce.close()


def test_scopes_change_no_instruction(tiny_engine, monkeypatch):
    """Metadata only: traced with ``jax.named_scope`` made a no-op, the
    step's body lowers to the same text, operation for operation, so the
    compiled program and its cost are what they were. (Fresh ``jax.jit``
    objects, so neither lowering is the other's cached trace.)"""
    import contextlib
    from functools import partial

    from tensorlink_tpu.engine import paged

    ce = _cont(tiny_engine, 9)
    ops = _idle_operands(ce)

    def lowered():
        step = jax.jit(
            partial(paged._ragged_step_impl, cfg=ce.cfg,
                    n_steps=ce.chunk_steps, spec_width=9, kernel=False))
        return step.lower(*ops)

    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = lowered()
    scoped = lowered()
    assert "tlink." not in bare.as_text(debug_info=True)
    assert "tlink.ragged_pass" in scoped.as_text(debug_info=True)
    assert scoped.as_text() == bare.as_text()
    ce.close()


def _functions(text: str) -> dict:
    """Function name -> its lines, for every ``func.func`` of the module."""
    out, cur = {}, None
    for ln in text.splitlines():
        m = re.match(r"\s*func\.func (?:public|private) @([\w.]+)\(", ln)
        if m:
            cur = out.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(ln)
    return out


def scope_paths(text: str, pattern: str) -> list[list[str]]:
    """For each line matching ``pattern``: the scope names from ``@main``
    down to it. An operation inside a called function names only its own
    part, so its callers' locations are put in front (every call site)."""
    names, funcs = _loc_names(text), _functions(text)

    def loc_of(ln):
        m = re.search(r"loc\((#loc\d+)\)$", ln)
        return names.get(m.group(1), "").split("/") if m else []

    def callers(fn):
        if fn == "main":
            return [[]]
        return [up + loc_of(ln)
                for host, lines in funcs.items() for ln in lines
                if re.search(rf"call @{re.escape(fn)}\(", ln)
                for up in callers(host)]

    return [up + loc_of(ln)
            for fn, lines in funcs.items() for ln in lines
            if re.search(pattern, ln) for up in callers(fn)]


@pytest.mark.parametrize("spec_width", [1, 9])
def test_every_kernel_call_is_named_and_under_attn(tiny_engine, spec_width):
    """The step program lowered for the TPU platform (nothing compiles
    or runs here): each ``tpu_custom_call`` carries the kernel name the
    trace reduction matches and lies under an ``attn`` scope of its
    phase."""
    ce = _cont(tiny_engine, spec_width)
    ops = _idle_operands(ce)
    text = paged_ragged_step.trace(
        *ops, ce.cfg, ce.chunk_steps, ce.spec_width, True
    ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert sorted(re.findall(r'kernel_name = "(\w+)"', text)) == [
        "paged_attention", "ragged_paged_attention"]
    paths = scope_paths(text, r"custom_call @tpu_custom_call")
    assert len(paths) == 2
    where = {}
    for path in paths:
        kernel = path[path.index("pallas_call") - 1]
        assert "attn" in path[:path.index(kernel)], path
        where[kernel] = next(p for p in path if p.startswith("tlink."))
    assert where == {"ragged_paged_attention": "tlink.ragged_pass",
                     "paged_attention": "tlink.decode_cont"}
    ce.close()


def test_kernel_names_are_the_entry_points():
    """``name=`` on every ``pl.pallas_call``: renaming a Python function
    cannot rename a kernel in a trace."""
    import inspect

    from tensorlink_tpu.ops import attention

    src = inspect.getsource(attention)
    named = re.findall(r'pl\.pallas_call\(\s*kernel,\s*name=("?\w+"?)', src)
    assert named == ['"flash_attention"', "name"]
    assert src.count("pl.pallas_call(") == len(named)
    # the live-span walk is one pallas_call behind two entry points, and
    # each hands it its own name as a literal
    assert re.findall(r'_paged_walk\(\s*"(\w+)"', src) == [
        "ragged_paged_attention", "paged_attention"]


def test_every_kernel_entry_point_has_a_caller_in_the_package():
    """A public function of ``ops/attention.py`` that reaches a
    ``pl.pallas_call`` is imported by the package outside ``ops/``: a
    kernel whose caller is retired goes with it."""
    import ast
    from pathlib import Path

    from tensorlink_tpu.ops import attention

    src = Path(attention.__file__)
    funcs = {n.name: n for n in ast.parse(src.read_text()).body
             if isinstance(n, ast.FunctionDef)}
    calls = {
        name: {c.func.id for c in ast.walk(fn)
               if isinstance(c, ast.Call) and isinstance(c.func, ast.Name)}
        for name, fn in funcs.items()
    }
    reaches = {n for n, fn in funcs.items()
               if "pl.pallas_call(" in ast.unparse(fn)}
    while more := {n for n, cs in calls.items() if cs & reaches} - reaches:
        reaches |= more  # callers of what reaches a kernel reach it too
    entry_points = {n for n in reaches if not n.startswith("_")}
    # found through the helper that holds the walk's ``pallas_call``
    assert {"ragged_paged_attention", "paged_attention"} <= entry_points

    pkg = src.parents[1]
    imported = {
        alias.name
        for path in pkg.rglob("*.py") if pkg / "ops" not in path.parents
        for node in ast.walk(ast.parse(path.read_text()))
        if isinstance(node, ast.ImportFrom)
        and (node.module or "").endswith("ops.attention")
        for alias in node.names
    }
    assert entry_points <= imported, entry_points - imported
