"""Shared post-optimization HLO text-parsing core.

One module owns the dtype/collective tables and the instruction/shape
grammar that both consumers build on:

* ``repro.sharding.hlo_analysis`` — loop-aware FLOPs / HBM bytes /
  collective bytes for the roofline (the original consumer; its public
  ``analyze_hlo`` / ``collective_stats`` API is unchanged and now thin
  wrappers over this core);
* ``repro.analysis.contracts`` — the SPMD contract auditor, which needs
  strictly more: replica groups classified onto mesh axes, donation
  metadata (``input_output_alias`` / ``buffer_donor``), entry-parameter
  usage, and nested-tuple result shapes.

Parsing conventions (all checked against live ``compiled.as_text()``
per-device modules from the CPU backend by ``tests/test_analysis.py``):

* a rank-0 shape ``f32[]`` is ONE element (4 bytes) — not zero;
* tuple-shaped results ``(f32[2], s32[2])`` sum their members; tuples
  nest (``((f32[2,4], f32[]), s32[])``) and members may carry
  ``/*index=N*/`` comments and ``{...}`` layouts;
* ``-start``/``-done`` async collective pairs are counted once (at the
  ``-start``; ``-done`` lines carry no shape of their own);
* ``replica_groups`` come either explicit (``{{0,1},{2,3}}``) or in iota
  form (``[2,2]<=[4]`` with an optional ``T(perm)`` transpose);
* wire bytes follow the roofline convention: ring all-reduce moves ~2x
  the buffer, every other collective is counted at result size.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

DTYPE_BYTES: Dict[str, int] = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVE_KINDS: Tuple[str, ...] = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute")

# wire-byte convention per kind (multiplier on the result size): a ring
# all-reduce moves ~2x the buffer over the wire; everything else is
# counted at result size.
COLLECTIVE_WIRE_FACTOR: Dict[str, float] = {"all-reduce": 2.0}

SHAPE_RE = re.compile(r"\b(" + "|".join(DTYPE_BYTES) + r")\[([0-9,]*)\]")
HEADER_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->")
PARAM_RE = re.compile(r"%?([\w.\-]+)\s*:\s*((?:\([^)]*\)|[\w\[\],{} ]+))")
OPERAND_RE = re.compile(r"%([\w.\-]+)")

_SIMPLE_TYPE_RE = re.compile(r"[\w.\-]+\[[0-9,]*\](?:\{[^{}]*\})?")
_NAME_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OP_RE = re.compile(r"\s*([\w\-]+)\(")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply|comparator)=%?([\w.\-]+)")
_CONST_RE = re.compile(r"=\s*s32\[\]\s*constant\((\d+)\)")
_RG_EXPLICIT_INNER_RE = re.compile(r"\{([0-9, ]*)\}")
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\]"
    r"(?:T\(([0-9,]+)\))?")
_ALIAS_ENTRY_RE = re.compile(
    r"\{([0-9, ]*)\}:\s*\((\d+),\s*\{([0-9, ]*)\}"
    r"(?:,\s*([\w\-]+))?\)")
_DONOR_ENTRY_RE = re.compile(r"\((\d+),\s*\{([0-9, ]*)\}\)")


# ---------------------------------------------------------------------- #
# shapes
# ---------------------------------------------------------------------- #
def shape_bytes(text: str) -> int:
    """Total bytes of every dtype[dims] shape token in ``text``.

    ``f32[]`` (rank 0) is one element; tuple types sum their members —
    pass a full (possibly nested) tuple type string and each member
    token is counted once.
    """
    total = 0
    for dtype, dims in SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def shape_dims(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every ``(dtype, dims)`` shape token in ``text``, in order."""
    return [(dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims in SHAPE_RE.findall(text)]


def first_shape_dims(type_str: str) -> Optional[List[int]]:
    m = SHAPE_RE.search(type_str)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


def scan_type(line: str, pos: int) -> Optional[Tuple[str, int]]:
    """Scan one HLO type starting at ``pos``: a simple ``dtype[dims]``
    (with optional ``{layout}``) or a balanced — possibly nested — tuple
    ``(...)``.  Returns ``(type_text, end_pos)`` or ``None``."""
    if pos < len(line) and line[pos] == "(":
        depth = 0
        for i in range(pos, len(line)):
            c = line[i]
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if depth == 0:
                    return line[pos:i + 1], i + 1
        return None
    m = _SIMPLE_TYPE_RE.match(line, pos)
    if m is None:
        return None
    return m.group(0), m.end()


# ---------------------------------------------------------------------- #
# instructions
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Instruction:
    """One parsed HLO instruction line."""

    name: str
    type_str: str      # full result type, including nested tuples
    op: str
    rest: str          # text after the op's opening parenthesis
    is_root: bool
    line: str


def parse_instruction(line: str) -> Optional[Instruction]:
    """Parse ``[ROOT] %name = <type> op(...)`` with balanced tuple types
    (the legacy single-regex parser rejected nested tuples)."""
    nm = _NAME_RE.match(line)
    if nm is None:
        return None
    scanned = scan_type(line, nm.end())
    if scanned is None:
        return None
    type_str, end = scanned
    om = _OP_RE.match(line, end)
    if om is None:
        return None
    return Instruction(
        name=nm.group(2), type_str=type_str, op=om.group(1),
        rest=line[om.end():], is_root=bool(nm.group(1)), line=line)


# ---------------------------------------------------------------------- #
# module: computations, loop graph, trip multipliers
# ---------------------------------------------------------------------- #
class HloModule:
    """Parsed HLO module text: computations, the loop graph and its trip
    multipliers (loop trip counts recovered from ``i < N`` conditions),
    and which computations are top-level (entry / loop bodies) versus
    fusion/call internals."""

    def __init__(self, hlo_text: str, default_trip: int = 1):
        self.text = hlo_text
        self.comps: Dict[str, List[str]] = {}
        self.entry: str = ""
        cur: Optional[List[str]] = None
        for line in hlo_text.splitlines():
            h = HEADER_RE.match(line)
            if h and line.rstrip().endswith("{"):
                name = h.group(1)
                cur = []
                self.comps[name] = cur
                if line.lstrip().startswith("ENTRY"):
                    self.entry = name
                # keep the header line: parameters feed the shape table
                cur.append(line)
                continue
            if cur is not None:
                cur.append(line)
                if line.strip() == "}":
                    cur = None

        # loop graph: parent comp -> [(body, cond, trip)]
        self.loops: Dict[str, List[Tuple[str, str, int]]] = {}
        self.call_targets: Set[str] = set()
        for name, lines in self.comps.items():
            for line in lines:
                b = _BODY_RE.search(line)
                c = _COND_RE.search(line)
                if b and c:
                    trip = self._trip_from_cond(c.group(1), default_trip)
                    self.loops.setdefault(name, []).append(
                        (b.group(1), c.group(1), trip))
                for t in _CALLS_RE.findall(line):
                    self.call_targets.add(t)

        # multipliers by DFS from entry
        self.mult: Dict[str, float] = {}
        if self.entry:
            self._assign(self.entry, 1.0)
        # computations never reached (e.g. dead) default to 1 when visited

    def _trip_from_cond(self, cond: str, default: int) -> int:
        lines = self.comps.get(cond, [])
        consts = [int(m.group(1)) for line in lines
                  for m in [_CONST_RE.search(line)] if m]
        return max(consts) if consts else default

    def _assign(self, comp: str, mult: float, depth: int = 0) -> None:
        if depth > 32:
            return
        self.mult[comp] = max(self.mult.get(comp, 0.0), mult)
        for body, cond, _trip in self.loops.get(comp, []):
            self._assign(body, mult * _trip, depth + 1)
            self._assign(cond, mult * _trip, depth + 1)

    def multiplier(self, comp: str) -> float:
        return self.mult.get(comp, 1.0)

    def top_level(self, comp: str) -> bool:
        """entry / loop bodies / conds — not fusion internals."""
        return comp == self.entry or comp not in self.call_targets

    def instructions(self, comp: str) -> Iterator[Instruction]:
        """Parsed instructions of one computation (header line skipped)."""
        for line in self.comps.get(comp, [])[1:]:
            inst = parse_instruction(line)
            if inst is not None:
                yield inst


# ---------------------------------------------------------------------- #
# collectives
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Collective:
    """One collective instruction, loop-scaled."""

    kind: str                      # one of COLLECTIVE_KINDS
    type_str: str                  # full result type
    result_bytes: int              # per occurrence, unscaled
    wire_bytes: float              # scale * wire factor * result_bytes
    replica_groups: Optional[Tuple[Tuple[int, ...], ...]]
    comp: str
    scale: float                   # loop trip multiplier
    line: str


def _iota_replica_groups(g: int, s: int, dims: Sequence[int],
                         perm: Sequence[int]
                         ) -> Tuple[Tuple[int, ...], ...]:
    """Expand ``replica_groups=[g,s]<=[dims]T(perm)``: device ids are the
    row-major iota over ``dims``, transposed by ``perm``, flattened, then
    reshaped to ``(g, s)`` rows."""
    n = 1
    for d in dims:
        n *= d
    if perm:
        strides = [1] * len(dims)
        for i in range(len(dims) - 2, -1, -1):
            strides[i] = strides[i + 1] * dims[i + 1]
        tdims = [dims[p] for p in perm]
        flat: List[int] = []
        idx = [0] * len(tdims)
        for _ in range(n):
            flat.append(sum(idx[k] * strides[perm[k]]
                            for k in range(len(perm))))
            for k in range(len(tdims) - 1, -1, -1):
                idx[k] += 1
                if idx[k] < tdims[k]:
                    break
                idx[k] = 0
    else:
        flat = list(range(n))
    return tuple(tuple(flat[r * s: (r + 1) * s]) for r in range(g))


def parse_replica_groups(line: str
                         ) -> Optional[Tuple[Tuple[int, ...], ...]]:
    """The instruction's replica groups: ``None`` when the attribute is
    absent, ``()`` for the empty ``replica_groups={}`` (one flat group of
    every device), explicit groups otherwise.  Handles both the explicit
    ``{{0,1},{2,3}}`` and the iota ``[2,2]<=[4]T(1,0)`` forms."""
    m = _RG_IOTA_RE.search(line)
    if m:
        dims = [int(d) for d in m.group(3).split(",") if d]
        perm = ([int(p) for p in m.group(4).split(",") if p]
                if m.group(4) else [])
        return _iota_replica_groups(int(m.group(1)), int(m.group(2)),
                                    dims, perm)
    key = "replica_groups={"
    i = line.find(key)
    if i < 0:
        return None
    depth, j = 0, i + len(key) - 1
    for j in range(i + len(key) - 1, len(line)):
        if line[j] == "{":
            depth += 1
        elif line[j] == "}":
            depth -= 1
            if depth == 0:
                break
    region = line[i + len(key): j]
    return tuple(
        tuple(int(d) for d in g.split(",") if d.strip())
        for g in (mm.group(1) for mm in
                  _RG_EXPLICIT_INNER_RE.finditer(region)))


def collective_kind(op: str) -> Optional[str]:
    """Map an op name (including ``-start`` async forms) onto a
    collective kind, or ``None``."""
    kind = op[:-len("-start")] if op.endswith("-start") else op
    return kind if kind in COLLECTIVE_KINDS else None


def iter_collectives(mod: HloModule) -> List[Collective]:
    """Every collective in the module, loop-scaled, with parsed replica
    groups.  Async pairs are counted once at the ``-start``."""
    out: List[Collective] = []
    for comp, lines in mod.comps.items():
        scale = mod.multiplier(comp)
        for line in lines:
            if "-done(" in line:
                continue
            inst = parse_instruction(line)
            if inst is None:
                continue
            kind = collective_kind(inst.op)
            if kind is None:
                continue
            size = shape_bytes(inst.type_str)
            factor = COLLECTIVE_WIRE_FACTOR.get(kind, 1.0)
            out.append(Collective(
                kind=kind, type_str=inst.type_str, result_bytes=size,
                wire_bytes=scale * factor * size,
                replica_groups=parse_replica_groups(line),
                comp=comp, scale=scale, line=line.strip()))
    return out


def _unravel(device: int, sizes: Sequence[int]) -> Tuple[int, ...]:
    coords = []
    for size in reversed(sizes):
        coords.append(device % size)
        device //= size
    return tuple(reversed(coords))


def group_axes(groups: Optional[Tuple[Tuple[int, ...], ...]],
               mesh_axes: Sequence[Tuple[str, int]]) -> frozenset:
    """Classify replica groups onto mesh axes: which axes of the
    row-major ``(name, size)`` device mesh the groups span.

    ``{{0,1},{2,3}}`` on a 2x2 ``(data, model)`` mesh spans ``{model}``
    (members differ only in the minor coordinate); ``{{0,2},{1,3}}``
    spans ``{data}``.  ``None``/empty groups (a flat all-device
    collective) span every axis; all-singleton groups span none — the
    collective moves no bytes.
    """
    names = [n for n, _ in mesh_axes]
    sizes = [s for _, s in mesh_axes]
    if not groups:
        return frozenset(names)
    spanned = set()
    for g in groups:
        if len(g) <= 1:
            continue
        coords = [_unravel(d, sizes) for d in g]
        for i, name in enumerate(names):
            if len({c[i] for c in coords}) > 1:
                spanned.add(name)
    return frozenset(spanned)


# ---------------------------------------------------------------------- #
# donation metadata + entry parameters
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class IOAlias:
    """One ``input_output_alias`` entry: output index tuple aliases the
    given parameter (at ``param_index`` inside its tuple, if nested)."""

    output_index: Tuple[int, ...]
    param: int
    param_index: Tuple[int, ...]
    kind: str                     # "may-alias" | "must-alias"


def _balanced_attr(text: str, key: str) -> str:
    """The balanced ``{...}`` region (exclusive) of ``key={...}`` in the
    module header, or ``""`` when absent."""
    i = text.find(key + "={")
    if i < 0:
        return ""
    start = i + len(key) + 1
    depth = 0
    for j in range(start, len(text)):
        if text[j] == "{":
            depth += 1
        elif text[j] == "}":
            depth -= 1
            if depth == 0:
                return text[start + 1: j]
    return ""


def _index_tuple(text: str) -> Tuple[int, ...]:
    return tuple(int(d) for d in text.split(",") if d.strip())


def input_output_aliases(hlo_text: str) -> List[IOAlias]:
    """Parsed ``input_output_alias`` header entries — the donations XLA
    actually ESTABLISHED as output aliases."""
    region = _balanced_attr(hlo_text, "input_output_alias")
    return [
        IOAlias(output_index=_index_tuple(m.group(1)),
                param=int(m.group(2)),
                param_index=_index_tuple(m.group(3)),
                kind=m.group(4) or "may-alias")
        for m in _ALIAS_ENTRY_RE.finditer(region)]


def buffer_donors(hlo_text: str) -> Set[Tuple[int, Tuple[int, ...]]]:
    """Parsed ``buffer_donor`` header entries — parameters XLA retains as
    donatable (donated by the caller, not yet bound to an output)."""
    region = _balanced_attr(hlo_text, "buffer_donor")
    return {(int(m.group(1)), _index_tuple(m.group(2)))
            for m in _DONOR_ENTRY_RE.finditer(region)}


def entry_parameters(mod: HloModule) -> Dict[int, Tuple[str, str]]:
    """Entry-computation parameters: number -> (name, type)."""
    out: Dict[int, Tuple[str, str]] = {}
    for inst in mod.instructions(mod.entry):
        if inst.op == "parameter":
            num = inst.rest.split(")")[0].strip()
            if num.isdigit():
                out[int(num)] = (inst.name, inst.type_str)
    return out


def used_parameter_numbers(mod: HloModule) -> Set[int]:
    """Entry parameters referenced by at least one non-parameter entry
    instruction (operand names match with or without the ``%`` sigil)."""
    params = entry_parameters(mod)
    by_name = {name: num for num, (name, _t) in params.items()}
    used: Set[int] = set()
    for inst in mod.instructions(mod.entry):
        if inst.op == "parameter":
            continue
        for name, num in by_name.items():
            if num in used:
                continue
            if re.search(r"(?<![\w.%-])%?" + re.escape(name)
                         + r"(?![\w.\-])", inst.rest):
                used.add(num)
    return used
