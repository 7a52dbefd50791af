"""Static contract checking — the repo's fourth leg after ``parallel/``,
``robust/``, and ``obs/``.

Seven PRs of aggregation, robustness, and observability work accreted a
web of *implicit* contracts: obs flags never enter run identity, fused
and unfused paths are bit-identical, no host sync inside the round body,
mid-run collectives must match across SPMD processes, no bare ``assert``
on contract paths. Each is enforced at runtime by one hand-written test
(or by nothing). This package enforces the *class* at lint time instead
of one instance per test — the Tricorder lesson (Sadowski et al., 2018)
that workflow-integrated analyzers with near-zero false positives are
the ones that actually prevent regressions.

Three analyzer families behind one ``scripts/lint_gate.py`` CLI
(exit codes: 0 clean / 1 findings / 2 config error):

* :mod:`analysis.astlint` — AST trace-purity lint over the jit-path
  packages (host-sync and nondeterminism idioms inside traced code,
  bare-assert on auto-discovered contract paths, deprecated imports,
  xfail hygiene over ``tests/``).
* :mod:`analysis.jaxpr_audit` — trace the central algorithms' round and
  fused-scan entry points via ``jax.make_jaxpr`` on tiny synthetic
  shapes (no training compute, CPU-safe) and check the dtype whitelist,
  the no-callbacks-on-the-hot-path rule, SPMD collective consistency
  (fused vs unfused multiset equality, ``lax.cond`` branch invariance —
  a branch-dependent collective deadlocks real multi-host SPMD), and
  the donation audit that ROADMAP Open item 2's refactor starts from.
* :mod:`analysis.identity` — cross-reference the flag registry
  (``experiments/config.py``) against ``run_identity``: every flag is
  classified identity-bearing / inert / unkeyed, and a new flag landing
  in no bucket — or an obs flag leaking into identity — fails the gate.

Pre-existing deliberate findings are pinned in the reviewed baseline
``results/lint_baseline.json`` (one-line justification each), never
hidden in the rules.
"""
from .findings import Finding, load_baseline  # noqa: F401
from .gate import run_gate  # noqa: F401
