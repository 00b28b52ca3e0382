"""Flight-recorder event names and span stages must be named constants.

The observability layer (``repro.obs``) registers every flight-recorder
event name in ``EVENT_CATALOG`` and every span stage in ``SPAN_STAGES``:
``record_event`` raises on an unknown name precisely so a typo'd emission
site fails loudly instead of producing an event no dashboard query ever
matches.  That guarantee only holds if call sites reference the registered
``EV_*`` / ``STAGE_*`` constants — an inline string literal re-introduces
the typo class at every emission site and unmoors grep from the catalog.

The same holds for the host spans that ``repro.obs.trace.stage`` writes
on the profiler's clock (``HOST_SPANS``, named ``<lane>.<stage>``) and for
profiler annotations: the benchmark's trace readers find a span by its
name, so an inline literal that drifts from the catalog silences them.

This rule flags any call of ``record_event``, ``stamp``, ``stage`` or
``TraceAnnotation`` (as a method or a plain function) that
passes a catalog name -- the event, stage, lane or span -- as an inline
string literal.  Passing the module constant (``repro.obs.events`` /
``repro.obs.trace``) is the fix; a deliberate literal (e.g. a test
asserting the unknown-name ValueError) carries ``# event-ok: <why>``.
"""

from __future__ import annotations

import ast
from typing import List

from repro.analysis.findings import Finding
from repro.analysis.lint import LintModule, check_suppression

#: Callables (method or plain function) -> positions of their positional
#: arguments that are catalog names.
_EVENT_CALLS = {
    "record_event": (0,),
    "stamp": (0,),
    "stage": (0, 1),
    "TraceAnnotation": (0,),
}


def _callee(fn: ast.expr):
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def check(mod: LintModule) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _callee(node.func)
        literals = [
            node.args[i].value for i in _EVENT_CALLS.get(callee, ())
            if i < len(node.args) and isinstance(node.args[i], ast.Constant)
            and isinstance(node.args[i].value, str)
        ]
        if not literals:
            continue  # Names: the EV_*/STAGE_*/LANE_* convention wanted
        suppressed, extra = check_suppression(mod, node.lineno, "event-ok")
        findings.extend(extra)
        if not suppressed:
            findings.append(
                Finding(
                    rule="event-name",
                    path=mod.path,
                    line=node.lineno,
                    message=(
                        f"inline event/stage/span name "
                        f"{', '.join(map(repr, literals))} passed to "
                        f"{callee}(): emission sites must reference the "
                        "registered EV_*/STAGE_*/LANE_*/SPAN_* constants "
                        "(repro.obs.events / repro.obs.trace) so typos fail "
                        "at import time and grep stays anchored to the "
                        "catalog"
                    ),
                )
            )
    return findings
