"""GT011: serving-path ``except Exception`` that bypasses the fault
classification.

The resilience layer (PR 7) threads ONE classification through the serving
path: every fault is classified (``resilience.classify``) and then
retried, degraded (``note_degraded``) or surfaced typed. A handler that
catches ``Exception`` (or bare ``except``) and neither re-raises, nor
routes through the classification, nor even USES the caught exception
swallows faults silently — the next device OOM or corrupt partition
vanishes instead of degrading visibly. Scoped to the serving-path
modules; an intentional swallow (best-effort observability, last-resort
guards) must carry a reasoned disable so the justification sits next to
the code.

A handler passes when its body (including nested handlers) re-raises,
calls ``classify``/``note_degraded``, or references the bound exception
name (surfacing the error via a response, log, trace stamp or typed
wrapper counts as routing it somewhere visible).
"""

from __future__ import annotations

import ast

from geomesa_tpu.analysis.astutil import terminal_name

CODE = "GT011"
TITLE = (
    "serving-path `except Exception` swallows the fault -- re-raise, "
    "classify() / note_degraded(), or use the bound exception"
)

_HOT_PREFIXES = (
    "sched/",
    "store/",
    "query/",
    "pubsub/",
    "join/",
    "results/",
    "stream/",
)
_HOT_FILES = {
    "server.py",
    "router.py",
    "replica.py",
    "warmup.py",
}

#: classification entry points: a call to any of these routes the fault
_CLASSIFY_CALLS = {"classify", "note_degraded", "is_oom"}

_BROAD = {"Exception", "BaseException"}


def _applies(rel: str) -> bool:
    rel = rel.removeprefix("geomesa_tpu/")
    return rel in _HOT_FILES or any(rel.startswith(p) for p in _HOT_PREFIXES)


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True  # bare except:
    if isinstance(t, ast.Name) and t.id in _BROAD:
        return True
    if isinstance(t, ast.Tuple):
        return any(
            isinstance(e, ast.Name) and e.id in _BROAD for e in t.elts
        )
    return False


def _routes_fault(handler: ast.ExceptHandler) -> bool:
    bound = handler.name
    for node in ast.walk(ast.Module(body=handler.body, type_ignores=[])):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            if terminal_name(node.func) in _CLASSIFY_CALLS:
                return True
        if bound and isinstance(node, ast.Name) and node.id == bound:
            return True
    return False


def check(ctx):
    if not _applies(ctx.rel):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _is_broad(node):
            continue
        if _routes_fault(node):
            continue
        yield ctx.finding(
            CODE,
            node,
            "broad except swallows the fault without classify()/"
            "note_degraded()/re-raise (and never uses the exception) -- "
            "route it through the resilience classification, or justify the "
            "swallow with a reasoned disable",
        )
