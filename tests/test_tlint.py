"""tlint self-tests (tools/tlint — docs/STATIC_ANALYSIS.md).

Five layers: (1) fixture snippets, good + bad, for every TL rule —
thread family TL0xx and JAX trace family TL1xx; (2) call-graph
propagation units (hot-path/holds-lock context through 1- and 2-hop
intra-project calls, recursion-safe, nested-def isolation preserved);
(3) the suppression/baseline machinery round-trip, both families, plus
the --format github annotation grammar; (4) the meta-test — every rule
caught at least one REAL violation in the pre-PR tree (fixed in that
PR, kept behind a reasoned suppression, or baselined with a reason), so
no rule is theater — except TL103, whose sweep proved the tree clean
and which pins the near-miss instead; (5) the two order-dependence
regressions TL006 diagnosed, pinned in the exact shape that failed at
tier-1 position.
"""

import json
import textwrap

import pytest

from tools.tlint import (
    DEFAULT_BASELINE,
    RULES,
    check_project,
    check_source,
    format_report_github,
    load_baseline,
    run,
)
from tools.tlint.engine import write_baseline


def _lint(src, rel="tensorlink_tpu/engine/fake.py", rule=None):
    """Violations for an in-memory snippet, optionally one rule only."""
    rules = {rule: RULES[rule]} if rule else None
    out, _ = check_source(textwrap.dedent(src), rel, rules=rules)
    return out


# ---------------------------------------------------------------------------
# fixture snippets per rule: the bad shape fires, the good shape is clean
# ---------------------------------------------------------------------------

# (rule, bad snippet, good snippet, rel). Each bad snippet is the
# minimal shape of the hazard the rule exists for; each good snippet is
# the discipline docs/STATIC_ANALYSIS.md prescribes.
FIXTURES = (
    (
        "TL001",
        """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self.slots = {}  #: guarded by self._lock

            def count(self):
                return len(self.slots)
        """,
        """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self.slots = {}  #: guarded by self._lock

            def count(self):
                with self._lock:
                    return len(self.slots)

            # tlint: holds-lock(self._lock)
            def count_locked(self):
                return len(self.slots)
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL002",
        """
        import time

        class Engine:
            def wait(self):
                with self._lock:
                    time.sleep(0.5)
                    item = self.work_q.get()
        """,
        """
        import time

        class Engine:
            def wait(self):
                with self._lock:
                    item = self.work_q.get(timeout=1.0)
                time.sleep(0.5)
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL003",
        """
        import numpy as np

        # tlint: hot-path
        def decode_chunk(tokens, logits):
            host = np.asarray(logits)
            return host.argmax(), tokens.item()
        """,
        """
        import jax.numpy as jnp

        # tlint: hot-path
        def decode_chunk(tokens, logits):
            return jnp.argmax(logits), tokens
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL004",
        """
        import time

        def timed(step):
            t0 = time.time()
            step()
            return time.time() - t0
        """,
        """
        import time

        def timed(step):
            t0 = time.monotonic()
            step()
            return time.monotonic() - t0
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL005",
        """
        def node_loop(conn):
            while True:
                try:
                    conn.pump()
                except Exception:
                    pass
        """,
        """
        import logging

        def node_loop(conn):
            while True:
                try:
                    conn.pump()
                except Exception:
                    logging.getLogger(__name__).warning(
                        "pump failed", exc_info=True
                    )
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL006",
        """
        REGISTRY = {}

        def register(name, fn):
            REGISTRY[name] = fn

        def reset():
            global COUNT
            COUNT = 0
        """,
        """
        FAMILIES = ("llama", "mixtral")

        class Registry:
            def __init__(self):
                self.entries = {}
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL007",
        """
        import numpy as np
        import random

        def draw(shape):
            return np.random.randn(*shape) * random.random()
        """,
        """
        import numpy as np
        import random

        def draw(shape, seed):
            rng = np.random.default_rng(seed)
            return rng.standard_normal(shape) * random.Random(seed).random()
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL101",
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        # tlint: one-program
        def ragged_step(params, blk, cache, n):
            return cache

        def step_chunk(mesh, params, blk, cache, reqs, counts):
            n = len(reqs)
            cache = ragged_step(params, blk, cache, n)
            return jax.device_put(counts, NamedSharding(mesh, P()))
        """,
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        # tlint: one-program
        def ragged_step(params, blk, cache, n):
            return cache

        def step_chunk(mesh, params, blk, cache, reqs, counts):
            n = len(reqs)
            cache = ragged_step(params, blk, cache, jnp.int32(n))
            spec = P(*([None] * counts.ndim))
            return jax.device_put(counts, NamedSharding(mesh, spec))
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL102",
        """
        import jax

        def sample(seed, shape):
            key = jax.random.PRNGKey(seed)
            a = jax.random.normal(key, shape)
            b = jax.random.uniform(key, shape)
            return a, b
        """,
        """
        import jax

        def sample(key, step, shape):
            k = jax.random.fold_in(key, step)
            k1, k2 = jax.random.split(k)
            a = jax.random.normal(k1, shape)
            b = jax.random.uniform(k2, shape)
            return a, b
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL103",
        """
        from functools import partial

        import jax
        import jax.numpy as jnp

        @partial(jax.jit, donate_argnames=("cache",))
        def copy_page(cache, src, dst):
            return cache

        def admit(cache):
            out = copy_page(cache, jnp.int32(3), jnp.int32(7))
            return cache, out
        """,
        """
        from functools import partial

        import jax
        import jax.numpy as jnp

        @partial(jax.jit, donate_argnames=("cache",))
        def copy_page(cache, src, dst):
            return cache

        def admit(cache):
            cache = copy_page(cache, jnp.int32(3), jnp.int32(7))
            return cache
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL104",
        """
        import jax.numpy as jnp

        # tlint: hot-path
        def step(tok):
            logits = jnp.argmax(tok)
            if logits > 0:
                return 1
            return int(logits)
        """,
        """
        import jax.numpy as jnp

        # tlint: hot-path
        def step(tok):
            logits = jnp.argmax(tok)
            return jnp.where(logits > 0, 1, 0)
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL105",
        """
        from tensorlink_tpu.core import faults

        def chaos(plan):
            faults.inject("worker.sesion_step")
            return {"site": "worker.sesion_step", "op": "crash", "nth": 1}
        """,
        """
        from tensorlink_tpu.core import faults

        def chaos(plan):
            faults.inject("worker.session_step")
            return {"site": "worker.session_step", "op": "crash", "nth": 1}
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
    (
        "TL106",
        """
        class Pool:
            def __init__(self):
                self.stats = {"hits": 0, "evictions": 0}

            def hit(self):
                self.stats["hits"] += 1
        """,
        """
        from tensorlink_tpu.core.metrics import counter

        class Pool:
            def __init__(self):
                self.hits = counter("tlink_pool_hits_total", "page hits")

            def hit(self):
                self.hits.inc()
        """,
        "tensorlink_tpu/engine/fake.py",
    ),
)


@pytest.mark.parametrize(
    "rule,bad,good,rel", FIXTURES, ids=[f[0] for f in FIXTURES]
)
def test_rule_fixture(rule, bad, good, rel):
    hits = _lint(bad, rel=rel, rule=rule)
    assert hits, f"{rule} did not fire on its bad fixture"
    assert all(v.rule == rule for v in hits)
    assert not _lint(good, rel=rel, rule=rule), (
        f"{rule} fired on its good fixture"
    )


def test_every_rule_has_a_fixture():
    assert {f[0] for f in FIXTURES} == set(RULES)


# ---------------------------------------------------------------------------
# rule-specific edges worth pinning
# ---------------------------------------------------------------------------


def test_tl001_init_is_exempt():
    # __init__ predates any concurrency: bare writes there are the
    # annotation SITE, not a violation
    src = """
    class Engine:
        def __init__(self):
            self.slots = {}  #: guarded by self._lock
    """
    assert not _lint(src, rule="TL001")


def test_tl001_nested_def_inherits_no_lock():
    # a closure spawned while the lock is held may RUN later, unlocked
    src = """
    class Engine:
        def __init__(self):
            self.slots = {}  #: guarded by self._lock

        def kick(self):
            with self._lock:
                def later():
                    return len(self.slots)
                return later
    """
    hits = _lint(src, rule="TL001")
    assert len(hits) == 1 and hits[0].symbol == "self.slots"


def test_tl004_dict_style_queue_get_not_flagged():
    # dict.get(key) takes a positional key; only the no-arg, no-timeout
    # blocking-queue shape is a TL002 hazard
    src = """
    class C:
        def peek(self):
            with self._lock:
                return self.routes_q.get("k")
    """
    assert not _lint(src, rule="TL002")


def test_tl005_skips_test_code():
    src = """
    def poll():
        try:
            step()
        except Exception:
            pass
    """
    assert _lint(src, rel="tensorlink_tpu/nodes/x.py", rule="TL005")
    assert not _lint(src, rel="tests/test_x.py", rule="TL005")


def test_tl007_scoped_to_engine_and_tests():
    src = """
    import numpy as np
    x = np.random.rand(3)
    """
    assert _lint(src, rel="tensorlink_tpu/engine/x.py", rule="TL007")
    assert _lint(src, rel="tests/test_x.py", rule="TL007")
    assert not _lint(src, rel="tensorlink_tpu/p2p/x.py", rule="TL007")


def test_tl006_flags_class_attr_patch_in_tests():
    src = """
    def test_patch():
        Engine.step = lambda self: None
    """
    hits = _lint(src, rel="tests/test_x.py", rule="TL006")
    assert hits and hits[0].symbol == "Engine.step"
    # ...but not in library code (instance wiring, monkeypatch fixtures
    # have their own discipline there)
    assert not _lint(src, rel="tensorlink_tpu/engine/x.py", rule="TL006")


# ---------------------------------------------------------------------------
# call-graph propagation (tools/tlint/callgraph.py): guard contexts flow
# through resolved intra-project calls
# ---------------------------------------------------------------------------

_HOT_CALLER = """
from tensorlink_tpu.engine.helpers import drain

# tlint: hot-path
def step_chunk(tokens):
    return drain(tokens)
"""


def _project(files, rule):
    return check_project(
        {rel: textwrap.dedent(src) for rel, src in files.items()},
        rules={rule: RULES[rule]},
    )


def test_tl003_propagates_one_hop():
    hits = _project(
        {
            "tensorlink_tpu/engine/hot.py": _HOT_CALLER,
            "tensorlink_tpu/engine/helpers.py": """
            def drain(tokens):
                return tokens.block_until_ready()
            """,
        },
        "TL003",
    )
    assert len(hits) == 1 and hits[0].rel == "tensorlink_tpu/engine/helpers.py"
    assert "reachable from hot-path" in hits[0].message
    # the provenance names the hot root
    assert "step_chunk" in hits[0].message


def test_tl003_propagates_two_hops():
    hits = _project(
        {
            "tensorlink_tpu/engine/hot.py": _HOT_CALLER,
            "tensorlink_tpu/engine/helpers.py": """
            from tensorlink_tpu.engine.deep import pull

            def drain(tokens):
                return pull(tokens)
            """,
            "tensorlink_tpu/engine/deep.py": """
            def pull(tokens):
                return tokens.item()
            """,
        },
        "TL003",
    )
    assert len(hits) == 1 and hits[0].rel == "tensorlink_tpu/engine/deep.py"
    assert "reachable from hot-path" in hits[0].message


def test_tl003_propagation_is_recursion_safe():
    # mutually recursive helpers under a hot root: the BFS must
    # terminate AND still flag the sync
    hits = _project(
        {
            "tensorlink_tpu/engine/hot.py": _HOT_CALLER,
            "tensorlink_tpu/engine/helpers.py": """
            def drain(tokens):
                return spin(tokens)

            def spin(tokens):
                if tokens is None:
                    return drain(tokens)
                return tokens.item()
            """,
        },
        "TL003",
    )
    assert len(hits) == 1 and "item" in hits[0].message


def test_tl003_nested_def_isolation_survives_propagation():
    # a closure defined inside a REACHABLE function may run later, off
    # the hot path — propagation must not leak into nested defs (the
    # same isolation the single-file rule always had)
    hits = _project(
        {
            "tensorlink_tpu/engine/hot.py": _HOT_CALLER,
            "tensorlink_tpu/engine/helpers.py": """
            def drain(tokens):
                def later():
                    return tokens.item()
                return later
            """,
        },
        "TL003",
    )
    assert hits == []


def test_tl003_propagated_weak_syncs_stay_quiet():
    # np.asarray is a legitimate boundary drain in ordinary helpers —
    # only the STRONG syncs (.item/.tolist/block_until_ready/device_get)
    # propagate, or every engine utility would light up
    hits = _project(
        {
            "tensorlink_tpu/engine/hot.py": _HOT_CALLER,
            "tensorlink_tpu/engine/helpers.py": """
            import numpy as np

            def drain(tokens):
                return np.asarray(tokens)
            """,
        },
        "TL003",
    )
    assert hits == []


def test_tl002_lock_context_propagates_with_provenance():
    hits = _project(
        {
            "tensorlink_tpu/ml/mod.py": """
            import time

            class Model:
                def apply(self):
                    with self._repair_lock:
                        self._retry()

                def _retry(self):
                    time.sleep(0.5)
            """,
        },
        "TL002",
    )
    assert len(hits) == 1 and hits[0].scope == "Model._retry"
    assert "held by caller Model.apply" in hits[0].message


def test_tl101_one_program_resolves_cross_file():
    hits = _project(
        {
            "tensorlink_tpu/engine/paged_fake.py": """
            # tlint: one-program
            def ragged_step(params, blk, cache, n):
                return cache
            """,
            "tensorlink_tpu/engine/cont_fake.py": """
            from tensorlink_tpu.engine.paged_fake import ragged_step

            def step_chunk(params, blk, cache, reqs):
                width = len(reqs)
                return ragged_step(params, blk, cache, width)
            """,
        },
        "TL101",
    )
    assert len(hits) == 1 and hits[0].rel == "tensorlink_tpu/engine/cont_fake.py"
    assert "ragged_step" in hits[0].message and "width" in hits[0].message


def test_tl105_sites_resolve_from_linted_faults_module():
    # a project that carries its own faults.py: SITES comes from the
    # linted tree, not the repo fallback
    files = {
        "tensorlink_tpu/core/faults.py": """
        SITES = ("a.one", "b.two")
        """,
        "tensorlink_tpu/engine/chaos.py": """
        def go(faults):
            faults.inject("a.oen")
        """,
    }
    hits = _project(files, "TL105")
    assert len(hits) == 1 and "a.oen" in hits[0].message
    # the hint proposes the registered near-match
    assert "a.one" in hits[0].message


def test_tl103_donation_tracks_argnames_positionally():
    # donate_argnames donors are almost always CALLED positionally —
    # the back-mapping from names to positions is load-bearing
    src = """
    from functools import partial

    import jax

    @partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
    def step(params, tok, cache, cfg):
        return cache

    def loop(params, tok, cache, cfg):
        new = step(params, tok, cache, cfg)
        stale = cache.sum()
        return new, stale
    """
    hits = _lint(src, rule="TL103")
    assert len(hits) == 1 and "cache" in hits[0].message


# ---------------------------------------------------------------------------
# suppressions: reasoned ones silence, bare ones are themselves reported
# ---------------------------------------------------------------------------


def test_suppression_with_reason_silences():
    src = """
    import time

    def timed(step):
        t0 = time.time()
        step()
        # tlint: disable=TL004(epoch delta is persisted to the job record)
        return time.time() - t0
    """
    out, ctx = check_source(
        textwrap.dedent(src), "tensorlink_tpu/engine/fake.py"
    )
    assert not [v for v in out if v.rule == "TL004"]
    assert not ctx.bad_suppressions


def test_suppression_without_reason_is_reported():
    src = """
    import time

    def timed(step):
        t0 = time.time()
        step()
        return time.time() - t0  # tlint: disable=TL004
    """
    out, ctx = check_source(
        textwrap.dedent(src), "tensorlink_tpu/engine/fake.py"
    )
    # the violation is NOT silenced, and the bare disable is flagged too
    assert [v for v in out if v.rule == "TL004"]
    assert ctx.bad_suppressions and ctx.bad_suppressions[0].rule == "TL004"


def test_suppression_in_string_literal_is_inert():
    # comments come from tokenize, so "# tlint:" inside a string cannot
    # silence anything
    src = '''
    import time

    DOC = "# tlint: disable=TL004(not a comment)"

    def timed(step):
        t0 = time.time()
        return time.time() - t0
    '''
    assert [v for v in _lint(src) if v.rule == "TL004"]


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------

_BASELINE_SRC = textwrap.dedent(
    """
    PENDING = {}

    def note(k, v):
        PENDING[k] = v
    """
)


def test_baseline_round_trip(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(_BASELINE_SRC)
    bl = tmp_path / "baseline.json"

    # 1. no baseline: the TL006 violation is actionable
    rep = run([tmp_path], baseline_path=None)
    assert rep.failed and rep.violations[0].rule == "TL006"

    # 2. write-baseline records it — but with an EMPTY reason, which the
    # loader rejects: a freshly generated baseline fails until every
    # entry is justified
    n = write_baseline(rep, bl)
    assert n == 1
    with pytest.raises(ValueError, match="empty reason"):
        load_baseline(bl)

    # 3. justified entries make the run clean (violation now baselined)
    data = json.loads(bl.read_text())
    for e in data["violations"]:
        e["reason"] = "deferred: registry reset discipline tracked in #42"
    bl.write_text(json.dumps(data))
    rep = run([tmp_path], baseline_path=bl)
    assert not rep.failed
    assert len(rep.baselined) == 1 and not rep.stale_baseline

    # 4. fixing the violation makes the entry STALE (warning, not a
    # failure — but it must be surfaced so the entry gets deleted)
    mod.write_text("PENDING = ()\n")
    rep = run([tmp_path], baseline_path=bl)
    assert not rep.failed and not rep.violations
    assert len(rep.stale_baseline) == 1


def test_baseline_missing_field_rejected(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"violations": [{"rule": "TL006"}]}))
    with pytest.raises(ValueError, match="missing"):
        load_baseline(bl)


def test_baseline_round_trip_tl1xx(tmp_path):
    """The deferral machinery carries the new rule family identically:
    a TL102 key reuse baselines by (rule, file, scope, symbol) and goes
    stale when fixed."""
    mod = tmp_path / "mod.py"
    mod.write_text(
        textwrap.dedent(
            """
            import jax

            def pair(key, shape):
                a = jax.random.normal(key, shape)
                b = jax.random.uniform(key, shape)
                return a, b
            """
        )
    )
    bl = tmp_path / "baseline.json"
    rep = run([tmp_path], baseline_path=None)
    assert [v.rule for v in rep.violations] == ["TL102"]
    write_baseline(rep, bl)
    data = json.loads(bl.read_text())
    data["violations"][0]["reason"] = (
        "fixture streams are compared for inequality, reuse is the point"
    )
    bl.write_text(json.dumps(data))
    rep = run([tmp_path], baseline_path=bl)
    assert not rep.failed and len(rep.baselined) == 1

    mod.write_text(
        textwrap.dedent(
            """
            import jax

            def pair(key, shape):
                k1, k2 = jax.random.split(key)
                a = jax.random.normal(k1, shape)
                b = jax.random.uniform(k2, shape)
                return a, b
            """
        )
    )
    rep = run([tmp_path], baseline_path=bl)
    assert not rep.failed and len(rep.stale_baseline) == 1


# ---------------------------------------------------------------------------
# --format github: inline PR annotations
# ---------------------------------------------------------------------------


def test_github_format_emits_escaped_error_annotations(tmp_path):
    (tmp_path / "mod.py").write_text(
        "import time\n\n"
        "def f(step):\n"
        "    t0 = time.time()\n"
        "    step()\n"
        "    return time.time() - t0\n"
    )
    rep = run([tmp_path], baseline_path=None)
    assert rep.failed
    out = format_report_github(rep)
    ann = [ln for ln in out.splitlines() if ln.startswith("::error ")]
    assert len(ann) == len(rep.violations)
    v = rep.violations[0]
    assert ann[0].startswith(
        f"::error file={v.rel},line={v.line},col={v.col + 1},title=TL004::"
    )
    # workflow-command grammar: the free-text message after :: must not
    # contain a raw newline, and %/CR/LF are escaped in data
    msg = ann[0].split("::", 2)[2]
    assert "\n" not in msg and "%" not in msg.replace("%0A", "").replace(
        "%25", ""
    ).replace("%0D", "")
    # the plain human-readable report still follows the annotations
    assert f"{v.rel}:{v.line}" in out.splitlines()[-2]


# ---------------------------------------------------------------------------
# the gate + the meta-test: rules earned their keep on the real tree
# ---------------------------------------------------------------------------


def test_tree_is_clean_and_baseline_fresh():
    """The CI gate, as a test: zero actionable violations on the tree,
    no bare suppressions, and no stale baseline entries (a stale entry
    means a deferred violation got fixed — delete it)."""
    from tools.tlint.engine import REPO_ROOT

    rep = run(
        [
            REPO_ROOT / "tensorlink_tpu",
            REPO_ROOT / "tests",
            REPO_ROOT / "tools",
        ],
        baseline_path=DEFAULT_BASELINE,
    )
    assert not rep.parse_errors
    assert not rep.failed, "\n".join(
        f"{v.rel}:{v.line}: {v.rule} {v.message}" for v in rep.violations
    ) + "\n".join(f"{f}:{ln}: {m}" for f, ln, m in rep.bad_suppressions)
    assert not rep.stale_baseline, rep.stale_baseline


# The pre-PR tree's real catches. TL002/TL003/TL006 catches (and the
# TL101/TL104/TL106 ones from the JAX family) were DELIBERATE designs —
# they live in baseline.json with reasons. The TL001/TL004/TL005/TL007
# catches, and TL101's P()-spelling and TL102's key-reuse sites, were
# plain bugs — fixed in their PR; TL105's typo'd-site catches are kept
# as the negative tests they are, behind reasoned suppressions. The
# snippets below are the pre-fix shapes condensed from the actual
# sites, so the meta-test keeps proving each rule detects the bug class
# it was built for.
_FIXED_CATCHES = (
    # engine/continuous.py (pre-fix): RequestScheduler calls outside the
    # engine lock in the finish path
    (
        "TL001",
        "tensorlink_tpu/engine/fake.py",
        """
        class Engine:
            def __init__(self):
                self.sched = None  #: guarded by self._lock

            def _finish(self, req):
                self.sched.note_finished(req)
        """,
    ),
    # ml/validator.py &c. (pre-fix): 29 wall-clock duration sites
    (
        "TL004",
        "tensorlink_tpu/ml/fake.py",
        """
        import time

        def handle(req, deadline):
            start = time.time()
            work(req)
            if time.time() - start > deadline:
                raise TimeoutError
        """,
    ),
    # p2p/node.py &c. (pre-fix): ~44 except-pass handlers, these in the
    # node maintenance loop
    (
        "TL005",
        "tensorlink_tpu/p2p/fake.py",
        """
        def maintenance_loop(self):
            while self.running:
                try:
                    self.refresh_routes()
                except Exception:
                    continue
        """,
    ),
    # tests/test_serialization.py (pre-fix): unseeded np.random payloads
    (
        "TL007",
        "tests/test_fake.py",
        """
        import numpy as np

        def test_roundtrip():
            x = np.random.randn(16, 8)
        """,
    ),
    # ml/worker.py::_to_device + engine/continuous.py tp __init__
    # (pre-fix): the empty P() spelling reaching a NamedSharding — the
    # OTHER half of the PR 17 split the runtime _canon dispatcher papers
    # over per chunk
    (
        "TL101",
        "tensorlink_tpu/ml/fake.py",
        """
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec

        def to_device(mesh, arr):
            return jax.device_put(
                np.asarray(arr), NamedSharding(mesh, PartitionSpec())
            )
        """,
    ),
    # tests/test_expert_parallel.py (pre-fix): five draws off ONE
    # PRNGKey — correlated router/expert weights in the FLOP fixture
    (
        "TL102",
        "tests/test_fake.py",
        """
        import jax
        import jax.numpy as jnp

        def test_flops(cfg, d, f, E):
            key = jax.random.PRNGKey(0)
            p = {
                "router": jax.random.normal(key, (d, E), jnp.float32),
                "w_gate": jax.random.normal(key, (E, d, f), jnp.float32),
            }
            h = jax.random.normal(key, (1, 256, d), jnp.float32)
        """,
    ),
    # tests/test_faults.py::test_unknown_site_rejected_loudly: the
    # deliberately typo'd and empty site literals — real pre-PR catches,
    # kept on purpose behind reasoned inline suppressions (they ARE the
    # negative tests for the runtime validator TL105 front-runs)
    (
        "TL105",
        "tests/test_fake.py",
        """
        def test_unknown_site_rejected(FaultPlan):
            FaultPlan.from_dict({"rules": [
                {"site": "worker.sesion_step", "op": "crash", "nth": 1},
            ]})
            FaultPlan.from_dict(
                {"rules": [{"site": "", "op": "drop", "nth": 1}]}
            )
        """,
    ),
)


@pytest.mark.parametrize(
    "rule,rel,pre_fix", _FIXED_CATCHES, ids=[c[0] for c in _FIXED_CATCHES]
)
def test_meta_rule_caught_real_fixed_violation(rule, rel, pre_fix):
    hits = _lint(pre_fix, rel=rel, rule=rule)
    assert hits, f"{rule} no longer detects the bug class it fixed"


def test_meta_rules_with_deliberate_catches_are_baselined():
    """TL002 (repair RPC under _repair_lock is the dedup design — now
    including the call-graph-propagated retry-helper sites), TL003 (the
    ONE host sync per decode chunk), TL006 (process-global caches with
    reset discipline), TL101 (the zero1 mixed-rank tree where P() IS the
    canonical spelling), TL106 (the two pre-registry stats dicts whose
    key sets are byte-compat-pinned): real catches, deliberately kept,
    every one carried in baseline.json with its reason. (TL104's one
    entry, ``int(n_exec)``, went with the read itself: the chunk's one
    ``np.asarray`` is its sync now.)"""
    by_rule = {}
    for e in load_baseline(DEFAULT_BASELINE):
        by_rule.setdefault(e["rule"], []).append(e)
    for rule in ("TL002", "TL003", "TL006", "TL101", "TL106"):
        assert by_rule.get(rule), f"no baselined real catch for {rule}"
        assert all(len(e["reason"]) > 20 for e in by_rule[rule])


def test_meta_tl103_tree_is_disciplined_and_the_near_miss_fires():
    """TL103's sweep of the pre-PR tree found ZERO live violations: all
    26 resolved donor call sites (paged/generate/training donors, across
    engine, tests, soak) rebind the donated name in the same
    statement, so there was nothing to fix or baseline — the donation
    discipline genuinely held. What the rule buys is enforcement: this
    pins it against the near-miss every one of those sites individually
    avoids, condensed from the real COW test (tests/test_continuous.py,
    the PR 7 shape) with its np.asarray pre-donation snapshot removed —
    exactly the read-after-donate that passes every CPU test and
    corrupts on TPU."""
    src = """
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    @partial(jax.jit, donate_argnames=("cache",))
    def copy_page(cache, src, dst):
        return cache

    def test_cow_copies_page(cache):
        src_k = cache.k[:, 3]
        out = copy_page(cache, jnp.int32(3), jnp.int32(7))
        assert np.array_equal(np.asarray(cache.k[:, 7]), src_k)
    """
    hits = _lint(src, rel="tests/test_fake.py", rule="TL103")
    assert len(hits) == 1 and hits[0].symbol == "cache"
    assert "DONATED" in hits[0].message
    # and the real tree, swept with the rule alone, is clean — the claim
    # above stays checked, not asserted
    from tools.tlint.engine import REPO_ROOT

    rep = run(
        [
            REPO_ROOT / "tensorlink_tpu",
            REPO_ROOT / "tests",
            REPO_ROOT / "tools",
        ],
        baseline_path=None,
        rules={"TL103": RULES["TL103"]},
    )
    assert rep.violations == [], rep.violations


# ---------------------------------------------------------------------------
# order-dependence regressions (the 2 tier-1 failures TL006 diagnosed)
# ---------------------------------------------------------------------------


def test_order_regression_lookahead_descriptor_restore():
    """tests/test_engine.py patches GenerationEngine staticmethods; the
    old getattr save/restore (`orig = GenerationEngine._lookup_draft`)
    resolved PAST the staticmethod descriptor and restored a plain
    function — which then bound `self` as `history` in every later
    lookahead in the process: the order-dependent
    test_nodes_e2e::test_lookahead_serving_matches_greedy failure. Pin
    the fixed discipline: save the descriptor from __dict__, and after a
    patch + restore cycle the descriptor must still be a staticmethod."""
    from tensorlink_tpu.engine.generate import GenerationEngine

    for name in ("_lookup_draft", "_spec_worthwhile"):
        desc = GenerationEngine.__dict__[name]
        assert isinstance(desc, staticmethod), (
            f"{name} is no longer a staticmethod descriptor — update the "
            "save/restore discipline in tests/test_engine.py"
        )
        # the trap the fix avoids: getattr resolves the descriptor away,
        # so restoring ITS result would corrupt the class
        assert not isinstance(getattr(GenerationEngine, name), staticmethod)

    # a patch + restore cycle with the fixed discipline leaves the
    # descriptor intact
    orig = GenerationEngine.__dict__["_lookup_draft"]
    try:
        # tlint: disable=TL006(regression test: restored from __dict__ two lines down)
        GenerationEngine._lookup_draft = staticmethod(
            lambda history, n_draft, **_k: [1] * n_draft
        )
    finally:
        # tlint: disable=TL006(restoring the saved staticmethod descriptor)
        GenerationEngine._lookup_draft = orig
    assert isinstance(
        GenerationEngine.__dict__["_lookup_draft"], staticmethod
    )


@pytest.mark.slow  # tiny-model compile; unfiltered in CI's unit job
def test_order_regression_lookahead_after_patch_cycle():
    """The failing order end-to-end at unit scale: (1) an engine-suite
    test patches and restores a GenerationEngine staticmethod; (2) a
    later suite's serving path runs lookahead — which must still match
    greedy (with the old getattr restore it raised, `history` bound as
    self)."""
    import jax
    import jax.numpy as jnp

    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models import ModelConfig, init_params

    orig = GenerationEngine.__dict__["_lookup_draft"]
    try:
        # tlint: disable=TL006(regression test: restored from __dict__ in the finally)
        GenerationEngine._lookup_draft = staticmethod(
            lambda history, n_draft, **_k: [1] * n_draft
        )
    finally:
        # tlint: disable=TL006(restoring the saved staticmethod descriptor)
        GenerationEngine._lookup_draft = orig

    cfg = ModelConfig(
        family="llama", vocab_size=64, d_model=16, n_layers=1, n_heads=1,
        n_kv_heads=1, head_dim=16, d_ff=32, max_seq_len=32,
        dtype=jnp.float32, tie_embeddings=False,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = GenerationEngine(
        cfg, params, seq_buckets=(8, 16), batch_buckets=(1,), max_seq_len=32
    )
    rep = ([5, 9, 2, 7] * 3)[:10]  # recurring pairs: the prescan arms
    ref = eng.generate_compiled([rep], max_new_tokens=8)
    spec = eng.generate_lookahead([rep], max_new_tokens=8)
    assert spec.sequences == ref.sequences


@pytest.mark.slow  # two tiny-model compiles; unfiltered in CI's unit job
def test_order_regression_jit_cache_is_process_global():
    """engine/paged.py's jitted programs are module-level, so their
    caches are PROCESS-global: an earlier test module serving config A
    leaves its programs resident, and test_continuous's absolute
    `decode_chunk == 1` failed at tier-1 position while passing solo.
    Pin the failing order at unit scale: serve config A, then run
    config B's compile-set check — the per-engine DELTA is 1 while the
    absolute count is >1 (the assertion shape that was order-dependent)."""
    import jax
    import jax.numpy as jnp

    from tensorlink_tpu.engine.continuous import ContinuousEngine
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models import ModelConfig, init_params

    def serve(vocab, d_model):
        cfg = ModelConfig(
            family="llama", vocab_size=vocab, d_model=d_model, n_layers=1,
            n_heads=1, n_kv_heads=1, head_dim=16, d_ff=32, max_seq_len=32,
            dtype=jnp.float32, tie_embeddings=False,
        )
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = GenerationEngine(
            cfg, params, seq_buckets=(8, 16), batch_buckets=(1,),
            max_seq_len=32,
        )
        ce = ContinuousEngine(eng, max_slots=2, page_size=8, chunk_steps=2)
        pre = ce.jit_cache_sizes()
        ce.submit([1, 2], max_new_tokens=2)
        ce.run_until_idle()
        return pre, ce.jit_cache_sizes()

    serve(64, 16)  # the "earlier module": leaves its programs resident
    pre_b, after_b = serve(80, 16)  # distinct shapes -> distinct program
    # the default path's step program is the unified ragged_step (PR 6);
    # the leak class is identical — one program per engine SHAPE in a
    # process-global cache
    assert after_b["ragged_step"] - pre_b["ragged_step"] == 1
    # and the absolute count really IS > 1 now — the shape the old
    # assertion used, which is why it was order-dependent
    assert after_b["ragged_step"] > 1
