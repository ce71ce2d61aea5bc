"""Device time of a jitted step by the program's named scopes.

The program names each layer of its train step with ``jax.named_scope``
(``kge.gather``, ``kge.message``, ``kge.aggregate``, ``kge.decoder_loss``,
``kge.optimizer``), which reaches the compiled HLO as ``op_name``
metadata.  The TPU trace names each device operation by its HLO
instruction text without that metadata, so the scopes are read from the
step's compiled HLO text (``compiled.as_text()``) and joined to the trace
by instruction name:

* an instruction belongs to the innermost ``kge.*`` scope on its
  ``op_name`` path, which covers the forward pass, the backward pass
  (``transpose(jvp(kge.message))``) and rematerialized copies alike;
* a fusion counts under its own metadata, its root's; where it carries
  none (the TPU's scatter fusions), under the nearest instruction to its
  root, inside the computations it calls, that carries some;
* an instruction with no ``kge.*`` scope is ``unscoped`` (among them the
  index sorts that the TPU's scatter lowering adds with no metadata).

Instruction names are unique within a module but not across modules, so
only operations that start inside one of the step module's runs (the
device plane's ``XLA Modules`` line) count; other programs of the window
(key splits, the loss fetch) are left out.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

SCOPES = ("kge.gather", "kge.message", "kge.aggregate", "kge.decoder_loss",
          "kge.optimizer")
UNSCOPED = "unscoped"

_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_SCOPE = re.compile(r"kge\.[a-z_]+")


def innermost(op_name: str) -> str:
    """The innermost ``kge.*`` scope on an ``op_name`` path."""
    found = [s for s in _SCOPE.findall(op_name) if s in SCOPES]
    return found[-1] if found else UNSCOPED


def instruction_scopes(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """``(module name, {instruction: scope})`` of a compiled module's
    text, for every instruction of every computation."""
    module = ""
    comps: Dict[str, List[str]] = {}
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, List[str]] = {}
    current: Optional[List[str]] = None
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            module = m.group(1)
            continue
        m = _COMPUTATION.match(line)
        if m:
            current = comps.setdefault(m.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if m is None or current is None:
            continue
        name = m.group(1)
        current.append(name)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op and op.group(1) else None
        calls[name] = _CALLS.findall(line)

    resolved: Dict[str, Optional[str]] = {}

    def metadata(name: str) -> Optional[str]:
        if name not in resolved:
            resolved[name] = None           # guards a cycle
            found = own.get(name)
            for callee in calls.get(name, ()):
                if found:
                    break
                for inner in reversed(comps.get(callee, ())):
                    found = metadata(inner)
                    if found:
                        break
            resolved[name] = found
        return resolved[name]

    return module, {name: innermost(metadata(name) or "") for name in own}


def _events(line):
    for e in line.events:
        start = int(e.start_ns)
        yield e.name, start, start + int(e.duration_ns)


def _instruction(op_text: str) -> str:
    """``fusion.3`` of ``%fusion.3 = f32[8,75]{...} fusion(...)``."""
    return op_text.split(" = ", 1)[0].strip().lstrip("%")


def attribute(profile, chips: int, lo: int, hi: int, module: str,
              scopes: Dict[str, str]) -> dict:
    """Device seconds of the step module's operations inside ``[lo, hi)``
    (nanoseconds on the profile's clock), by scope, summed over the chips
    and divided by their number: ``scope_s`` (every scope of ``SCOPES``
    and ``unscoped``) and ``step_module_s``, their sum."""
    from bench.trace import clip, device_planes, length

    planes = device_planes(profile, chips)
    ns = {s: 0 for s in SCOPES + (UNSCOPED,)}
    prefix = module + "("
    for plane in planes:
        runs, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                runs = sorted((a, b) for n, a, b in _events(line)
                              if n == module or n.startswith(prefix))
            elif line.name == "XLA Ops":
                ops = list(_events(line))
        starts = [a for a, _ in runs]
        for name, a, b in ops:
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or a >= runs[i][1]:
                continue
            iv = clip([(a, b)], lo, hi)
            if iv:
                ns[scopes.get(_instruction(name), UNSCOPED)] += length(iv)
    n = max(len(planes), 1)
    scope_s = {k: v / n / 1e9 for k, v in ns.items()}
    return {"scope_s": scope_s, "step_module_s": sum(scope_s.values())}
