"""Test configuration: force CPU jax with a virtual 8-device mesh.

Must run before any jax import (SURVEY.md section 4 rebuild test plan:
multi-chip tests via host-platform device-count simulation).

The runtime sanitizers (``analysis/``) are switched on for the WHOLE
suite -- env vars must be set before any geomesa_tpu module import so
module-level state is built instrumented; subprocesses spawned by the
chaos suite inherit them:

- lock-order checker (``GEOMESA_TPU_LOCKCHECK``, analysis/lockcheck.py):
  acquisition-graph cycles + held-across-blocking events.
- context checker (``GEOMESA_TPU_CTXCHECK``, analysis/ctxcheck.py):
  blessed-spawn worker tasks with orphaned or mismatched request
  context (trace/cost/degraded/compile-scope accounting).
- compile checker (``GEOMESA_TPU_COMPILECHECK``,
  analysis/compilecheck.py): backend compiles while a server is live
  that carry no blessed ``compile_scope`` attribution.

The session-end hooks print each checker's summary; any finding fails
the run. tests/test_lockcheck.py, tests/test_ctxcheck.py and
tests/test_compilecheck.py additionally assert the zero-findings
invariants mid-run plus the seeded detections.
"""

import os

# no persistent compile cache: the suite writes nothing into the tree
# (the checkout is what gets copied to the chip); subprocesses inherit it
os.environ["GEOMESA_TPU_COMPILE_CACHE"] = "off"
os.environ.setdefault("GEOMESA_TPU_LOCKCHECK", "1")
os.environ.setdefault("GEOMESA_TPU_CTXCHECK", "1")
os.environ.setdefault("GEOMESA_TPU_COMPILECHECK", "1")

from geomesa_tpu.jaxconf import force_cpu_devices

force_cpu_devices(8)

import numpy as np
import pytest

# Tests run the host-parity path: float64 quantization + uint64 z lanes on
# CPU jax. (The TPU 32-bit lane path is covered by the hi/lo encode tests.)
from geomesa_tpu.jaxconf import require_x64

require_x64()

# Arm the observer seams now that the package is importable: install()
# is a no-op when the env var is off, and idempotent when on.
from geomesa_tpu.analysis import compilecheck as _compilecheck
from geomesa_tpu.analysis import ctxcheck as _ctxcheck

if _ctxcheck.enabled():
    _ctxcheck.install()
if _compilecheck.enabled():
    _compilecheck.install()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


def pytest_terminal_summary(terminalreporter):
    """One line of sanitizer state per checker at session end; any
    global finding is spelled out (and fails the session, see below).
    The per-checker tests additionally assert the invariants mid-run."""
    from geomesa_tpu.analysis import compilecheck, ctxcheck
    from geomesa_tpu.analysis.lockcheck import CHECKER, enabled

    if enabled():
        rep = CHECKER.report()
        terminalreporter.write_line(
            f"lockcheck: {len(rep['locks'])} locks, {len(rep['edges'])} "
            f"order edges, {len(rep['cycles'])} cycles, "
            f"{len(rep['blocking'])} held-across-blocking events"
        )
        for c in rep["cycles"]:
            terminalreporter.write_line(f"lockcheck CYCLE: {c}")
        for b in rep["blocking"]:
            terminalreporter.write_line(f"lockcheck BLOCKING: {b}")
    if ctxcheck.enabled():
        rep = ctxcheck.CHECKER.report()
        terminalreporter.write_line(
            f"ctxcheck: {rep['tasks']} blessed tasks, {rep['attaches']} "
            f"attaches, {rep['charges']} charges, {rep['compiles']} "
            f"compiles, {len(rep['findings'])} findings"
        )
        for f in rep["findings"]:
            terminalreporter.write_line(f"ctxcheck FINDING: {f}")
    if compilecheck.enabled():
        rep = compilecheck.CHECKER.report()
        terminalreporter.write_line(
            f"compilecheck: {rep['compiles']} compiles "
            f"({rep['serving_compiles']} while serving), "
            f"{len(rep['violations'])} unattributed"
        )
        for v in rep["violations"]:
            terminalreporter.write_line(f"compilecheck VIOLATION: {v}")


def pytest_sessionfinish(session, exitstatus):
    """The enforcement half: a lock-order cycle, a held-across-blocking
    event, an orphaned-context worker task, or an unattributed
    serving-path compile ANYWHERE in the session (including suites that
    ran after the checkers' in-run assertions) fails the run."""
    from geomesa_tpu.analysis import compilecheck, ctxcheck
    from geomesa_tpu.analysis.lockcheck import CHECKER, enabled

    bad = False
    if enabled():
        rep = CHECKER.report()
        bad = bool(rep["cycles"] or rep["blocking"])
    if ctxcheck.enabled() and ctxcheck.CHECKER.report()["findings"]:
        bad = True
    if compilecheck.enabled() and (
        compilecheck.CHECKER.report()["violations"]
    ):
        bad = True
    if bad and session.exitstatus == 0:
        session.exitstatus = 1
