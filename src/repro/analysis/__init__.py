"""Static analysis for compiled crossbar programs and the repo itself.

Three passes, one diagnostics currency:

* :mod:`repro.analysis.verify` — an execution-free program verifier over
  ``BlockPatternWeight`` / ``CompiledNetwork`` / ``NetworkPartition`` /
  serialized manifests (rules ``V1xx``–``V4xx``, ``M0xx``).  Runs at the
  trust boundaries: ``CompileOptions(verify=...)``,
  ``load_program(verify=True)``, ``partition_network``.
* :mod:`repro.analysis.ranges` — the range & bit-width certification
  pass (rules ``V5xx``): an abstract interpreter that propagates
  interval bounds through the compiled schedule and proves accumulator
  and cell-budget facts about the quantized path, emitting a
  :class:`~repro.analysis.ranges.RangeCertificate` that
  ``hardware_report()`` prices and manifest v4 persists.
* :mod:`repro.analysis.lint` — an AST lint over ``src/repro/`` (rules
  ``L0xx``) enforcing jit-purity, tracer discipline, and lock
  discipline in CI.

CLI::

    python -m repro.analysis verify <saved-program-dir> [--json]
    python -m repro.analysis ranges <saved-program-dir> [--json]
    python -m repro.analysis lint [paths...] [--json]
    python -m repro.analysis all <saved-program-dir> [--paths ...]

(exit codes documented in :mod:`repro.analysis.__main__`).
"""

from repro.analysis.diagnostics import (
    ERROR,
    WARNING,
    Diagnostic,
    ProgramFormatError,
    Report,
    VerificationError,
)

__all__ = [
    "Diagnostic",
    "Report",
    "ProgramFormatError",
    "VerificationError",
    "ERROR",
    "WARNING",
]
