"""graftlint: AST-based invariant linter for the lightgbm_tpu codebase.

No reference equivalent — the reference's correctness rules live in C++
type signatures; here they live in *idioms* (trace-time guards, atomic
write protocols, schema dicts) that no compiler checks. This package
turns the hand-maintained ones into machine-checked rules
(docs/Static-Analysis.md has the catalogue with each rule's
provenance):

- ``unguarded-collective``  blocking device syncs in parallel paths
                            outside ``collective_guard`` (watchdog /
                            straggler attribution goes blind otherwise)
- ``non-atomic-shared-write``  shared run artifacts written without the
                            tmp+fsync+rename / manifest-last discipline
- ``precision-contract``    f64 leaking into device-traced builders,
                            f32 accumulation in documented-f64 host
                            reductions, raw ``float()`` on Kahan pairs
- ``nondeterminism``        wall clocks / unseeded RNG in modules under
                            the serial==parallel bit-parity contract
- ``journal-schema``        journal ``.event()`` record types missing
                            from telemetry/journal.py SCHEMA (the
                            static face of tools/check_journal.py)
- ``prometheus-naming``     metric name literals that violate the
                            exposition naming contract
                            (telemetry/prometheus.py lint_family_name —
                            the SAME implementation the runtime page
                            lint uses)
- ``config-doc-drift``      config.py knobs without a docs/Parameters.md
                            row or without any read site

Zero third-party deps (stdlib ``ast`` only), runs in well under 10s.
Suppression: inline ``# graftlint: disable=<rule>`` pragmas (same or
preceding line) and the committed baseline ``tools/lint_baseline.json``
(every entry carries a justification). CLI:

    python -m lightgbm_tpu.analysis [--json out.json] [--self-check]
    python tools/graftlint.py ...      # same, without importing jax

``make verify-lint`` gates both the fixture corpus (--self-check) and
the live tree (clean modulo the baseline) in CI.
"""

from .core import (REGISTRY, Fixture, ParsedFile, Project, Rule,
                   Severity, Violation, register)
from .engine import lint_project, load_rules
from .baseline import Baseline

__all__ = ["REGISTRY", "Fixture", "ParsedFile", "Project", "Rule",
           "Severity", "Violation", "register", "lint_project",
           "load_rules", "Baseline"]
