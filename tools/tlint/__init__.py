"""tlint — project-native static analysis for tensorlink-tpu.

Two rule families enforcing the coding disciplines the runtime
contracts depend on (docs/STATIC_ANALYSIS.md).

Thread rules (TL0xx):

- TL001 guarded-by: ``#: guarded by self._lock`` attributes only under
  the lock (or in ``# tlint: holds-lock`` methods).
- TL002 no-blocking-under-lock: no socket I/O, un-timed queue ops,
  sleeps, RPCs, or device syncs while holding a thread lock — including
  locks held by CALLERS, propagated through the project call graph.
- TL003 hot-path-sync: ``# tlint: hot-path`` functions — and functions
  reachable from them through resolved calls — never host-sync.
- TL004 monotonic-durations: elapsed time uses ``time.monotonic()``.
- TL005 no-swallowed-exceptions: no ``except: pass``-only handlers.
- TL006 mutable-module-global: no leakable module-level mutable state.
- TL007 unseeded-rng: no process-global RNG in ``engine/`` or ``tests/``.

JAX trace rules (TL1xx):

- TL101 jit-cache-keys: no shape-derived args into ``# tlint:
  one-program`` calls; no ``NamedSharding`` from the empty ``P()``.
- TL102 rng-discipline: keys derive via ``fold_in``/``split``, are
  never consumed twice, never a raw seed in ``engine/``/``ops/``.
- TL103 donation-safety: no read of a buffer after passing it at a
  ``donate_argnums``/``donate_argnames`` position.
- TL104 implicit-host-sync: no ``bool()``/``int()``/``float()``/truth
  tests/``np.*`` on traced arrays in hot-path-reachable code.
- TL105 fault-sites: every injection-site literal exists in
  ``faults.SITES`` (resolved cross-module).
- TL106 adhoc-counters: ``self.stats`` dict counters belong in the
  core.metrics registry.

Run: ``python -m tools.tlint tensorlink_tpu tests tools``
(blocking in CI; ``--format github`` for inline PR annotations).
"""

from .callgraph import Project
from .context import FileContext
from .engine import (
    DEFAULT_BASELINE,
    RULES,
    Report,
    check_project,
    check_source,
    format_report,
    format_report_github,
    load_baseline,
    main,
    run,
)
from .rules import Violation

__all__ = [
    "DEFAULT_BASELINE",
    "FileContext",
    "Project",
    "RULES",
    "Report",
    "Violation",
    "check_project",
    "check_source",
    "format_report",
    "format_report_github",
    "load_baseline",
    "main",
    "run",
]
