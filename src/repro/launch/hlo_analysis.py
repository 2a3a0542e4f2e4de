"""Post-SPMD HLO analysis: per-device collective wire-bytes extraction,
and the ops inside a program's loops that produce an array of a given
shape.

Separate module (no XLA_FLAGS side effects) so tests and benchmarks can
import it without touching jax device state.
"""
from __future__ import annotations

import re


_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1,
                "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

# instruction lines look like:  %name = <shapes> <op>(operands), ...
# <shapes> may be one shape or a (possibly huge) tuple with /*index=N*/
# comments (e.g. a 256-way all-to-all or a whole-gradient-pytree
# all-reduce), so shapes are findall'd from the text between '=' and the op.
_COLL_RE = re.compile(
    r" = (.*?)\s?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _tensor_bytes(shapes_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device bytes-on-wire per collective kind, ring estimates:
    all-reduce 2(n-1)/n, all-gather/reduce-scatter/all-to-all (n-1)/n of the
    (full) tensor, collective-permute 1x."""
    out = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0, "count": 0}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        shapes_str, kind = m.groups()
        size = _tensor_bytes(shapes_str)
        gm = _GROUPS_IOTA_RE.search(line)
        if gm:
            n = int(gm.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            n = len(gl.group(1).split(",")) if gl else 1
        if kind == "collective-permute":
            wire = float(size)     # point-to-point: no group discount
        elif n <= 1:
            continue
        elif kind == "all-reduce":
            wire = 2.0 * size * (n - 1) / n
        else:
            wire = float(size) * (n - 1) / n
        out[kind] += wire
        out["count"] += 1
    out["total_bytes"] = sum(out[k] for k in
                             ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute"))
    return out




# ``[ROOT] %name = <shapes> <opcode>(<operands>)<attributes>``
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%(\S+) = (.*?) ([a-z][\w\-]*)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%(\S+) .*\{\s*$")
#: ops that pass a loop's carried arrays along without touching their words
_PASS_THROUGH = ("parameter", "get-tuple-element", "tuple", "bitcast",
                 "while", "call")


def _computations(hlo_text: str) -> dict:
    """{computation: [(name, shapes, opcode, rest of line, is_root)]}"""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = _COMP_RE.match(line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            m = _INSTR_RE.match(line)
            if m:
                root, iname, shapes, op, rest = m.groups()
                comps[name].append((iname, shapes, op, rest, bool(root)))
    return comps


def _writes_operand_in_place(comp: list, shape: str, updates) -> bool:
    """A fusion whose root updates one of its parameters in place, and
    which makes no other array of ``shape``."""
    params = {i[0] for i in comp if i[2] == "parameter"}
    for iname, shapes, op, rest, root in comp:
        if root:
            first = rest.split(",", 1)[0].strip().lstrip("%")
            if op not in updates or first not in params:
                return False
        elif shape in shapes and op != "parameter":
            return False
    return True


def loop_wide_ops(hlo_text: str, shape: str,
                  updates=("dynamic-update-slice", "scatter")) -> list:
    """``(computation, op, opcode)`` of every op in the body of a while
    loop (or in what the body calls) that produces an array whose shape
    text contains ``shape`` (``"s32[650000]"``), other than an in-place
    update of a carried array: an op of ``updates``, bare or as the root of
    a fusion, or a TPU kernel whose outputs alias its operands.  An empty
    list says the loop touches arrays of that shape only where it writes
    them, never in a fill or a copy.  (XLA's TPU scatter passes its whole
    operand through VMEM while it fits there: pass ``updates=()`` to count
    it too.)"""
    comps = _computations(hlo_text)
    todo = [b for c in comps.values() for _, _, op, rest, _ in c
            if op == "while" for b in re.findall(r"body=%([\w.\-]+)", rest)]
    seen, found = set(), []
    while todo:
        body = todo.pop()
        if body in seen:
            continue
        seen.add(body)
        for iname, shapes, op, rest, _ in comps[body]:
            todo += re.findall(r"(?:body|to_apply)=%([\w.\-]+)", rest) \
                if op in ("while", "call") else []
            if shape not in shapes or op in _PASS_THROUGH + tuple(updates):
                continue
            if op == "fusion":
                called = re.search(r"calls=%([\w.\-]+)", rest).group(1)
                if _writes_operand_in_place(comps[called], shape, updates):
                    continue
            if op == "custom-call" and "tpu_custom_call" in rest and \
                    "output_to_operand_aliasing" in rest:
                continue
            found.append((body, iname, op))
    return found
