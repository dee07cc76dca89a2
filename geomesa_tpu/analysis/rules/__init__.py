"""The GT001-GT012 rule modules, one per rule, plus shared AST helpers.

A rule module exposes ``CODE`` (the GTnnn id), ``TITLE`` (one line for
the README/CLI table) and ``check(ctx)`` yielding
:class:`~geomesa_tpu.analysis.lint.Finding`s. Register new rules by
appending the module to :data:`ALL_RULES`.
"""

from __future__ import annotations

from geomesa_tpu.analysis.astutil import (  # noqa: F401 (re-export)
    receiver_name,
    str_arg,
    terminal_name,
    walk_no_defs,
)
from geomesa_tpu.analysis.rules import (
    gt001_bare_locks,
    gt002_blocking_under_lock,
    gt003_wall_clock,
    gt004_host_sync,
    gt005_failpoint_names,
    gt006_metric_discipline,
    gt007_publish_fsync,
    gt008_conf_keys,
    gt009_slo_registries,
    gt010_blessed_spawn,
    gt011_classify_bypass,
    gt012_unbucketed_dims,
)

ALL_RULES = (
    gt001_bare_locks,
    gt002_blocking_under_lock,
    gt003_wall_clock,
    gt004_host_sync,
    gt005_failpoint_names,
    gt006_metric_discipline,
    gt007_publish_fsync,
    gt008_conf_keys,
    gt009_slo_registries,
    gt010_blessed_spawn,
    gt011_classify_bypass,
    gt012_unbucketed_dims,
)

RULE_TABLE = [(r.CODE, r.TITLE) for r in ALL_RULES]
