"""Call-graph-aware cost roll-up over compiled HLO text.

``compiled.cost_analysis()`` counts every computation ONCE — a ``while``
loop body (every ``lax.scan``: the layer stack, flash-attention KV chunks)
is charged a single iteration, so FLOPs / bytes / collective bytes of
scanned models are undercounted by roughly the trip count.  This module
re-derives the three roofline inputs from ``compiled.as_text()`` with the
call graph walked explicitly:

- ``while``    -> body and condition costs x trip count (trip count
                  recovered from the loop-bound constant in the condition
                  computation — lax.scan always lowers to a counted loop);
- ``fusion``   -> FLOPs of the fused computation count, but only the
                  fusion's *surface* operands/results count as bytes
                  (fused intermediates never touch HBM);
- ``call``     -> costs x 1.

FLOPs: ``dot`` = 2 * prod(result_dims) * prod(lhs contracting dims)
(batch dims included in the result product).  Elementwise FLOPs are
ignored — they ride on the byte traffic in the memory term.

Bytes: sum of (result + operand) sizes of every materialising top-level
instruction (parameters, constants, tuples, GTEs, bitcasts are free).
This approximates post-fusion HBM traffic.

Collectives: result bytes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (and their ``-start`` forms), times the
path multiplier.
"""
from __future__ import annotations

import dataclasses
import math
import re

__all__ = ["analyse_hlo", "HloCost", "collective_bytes"]

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_FREE_OPS = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
             "after-all", "iota", "partition-id", "replica-id", "reshape",
             # control surfaces account their bodies via call edges; the
             # loop carry stays resident, it is not re-streamed per step
             "while", "conditional", "call"}

# Ops that touch only a window of their big operand: charged by the window,
# not by the operand's full size.
_SLICING_OPS = {"dynamic-slice", "slice", "gather"}
_PASSTHRU_OPS = {"bitcast", "reshape"}


def _instr_bytes(ins: "_Instr", ttable: dict[str, str],
                 comps: dict[str, list["_Instr"]]) -> float:
    """HBM traffic estimate for one top-level instruction.

    Windowed ops (dynamic-slice / gather / dynamic-update-slice / scatter)
    are charged by the touched window, not the resident operand — a scan
    that dynamic-slices its stacked parameters per iteration reads one
    layer, not the whole stack.  Fusions charge their surface operands,
    except parameters that the fused computation only ever slices/gathers,
    which are charged by the slice results (and a root dynamic-update-slice
    writes only its update window).
    """
    res_b = _type_bytes(ins.type_str)
    ops = _operands(ins.rest)

    def opnd_b(i: int) -> float:
        return _type_bytes(ttable.get(ops[i], "")) if i < len(ops) else 0.0

    if ins.op in _SLICING_OPS:
        extra = sum(opnd_b(i) for i in range(1, len(ops)))  # indices
        return 2.0 * res_b + extra                          # read win + write
    if ins.op == "dynamic-update-slice":
        upd = opnd_b(1)
        return 2.0 * upd + sum(opnd_b(i) for i in range(2, len(ops)))
    if ins.op == "scatter":
        upd = opnd_b(2) if len(ops) > 2 else res_b
        idx = opnd_b(1)
        return 3.0 * upd + idx                              # rmw + indices
    if ins.op == "fusion":
        callee = _attr(ins.rest, "calls")
        instrs = comps.get(callee or "", [])
        ftable = ttable_of(instrs)
        by_name = {fi.name: fi for fi in instrs}
        params: dict[int, str] = {}
        users: dict[str, list["_Instr"]] = {}
        for fi in instrs:
            if fi.op == "parameter":
                m = re.match(r"(\d+)\)", fi.rest)
                if m:
                    params[int(m.group(1))] = fi.name
            for o in _operands(fi.rest):
                users.setdefault(o, []).append(fi)

        def touched(name: str, depth: int = 0) -> float | None:
            """Bytes read from a param if only sliced / in-place updated."""
            if depth > 8:
                return None
            total = 0.0
            for u in users.get(name, []):
                u_ops = _operands(u.rest)
                if u.op in _SLICING_OPS:
                    total += _type_bytes(u.type_str)
                elif (u.op == "dynamic-update-slice" and u_ops
                      and u_ops[0] == name):
                    # in-place window write: read nothing but the window
                    total += _type_bytes(ftable.get(u_ops[1], "")) \
                        if len(u_ops) > 1 else 0.0
                elif u.op in _PASSTHRU_OPS or u.op == "get-tuple-element":
                    sub = touched(u.name, depth + 1)
                    if sub is None:
                        return None
                    total += sub
                else:
                    return None
            return total

        total = 0.0
        for i in range(len(ops)):
            full = opnd_b(i)
            pname = params.get(i)
            win = touched(pname) if pname else None
            total += min(win, full) if win is not None else full

        def write_bytes(name: str, full: float, depth: int = 0) -> float:
            """Written bytes for one root value: a dynamic-update-slice
            (possibly behind bitcast/reshape) writes only its window."""
            fi = by_name.get(name)
            if fi is None or depth > 8:
                return full
            if fi.op == "dynamic-update-slice":
                f_ops = _operands(fi.rest)
                upd = _type_bytes(ftable.get(f_ops[1], "")) \
                    if len(f_ops) > 1 else 0.0
                return upd or full
            if fi.op in _PASSTHRU_OPS:
                f_ops = _operands(fi.rest)
                if f_ops:
                    return write_bytes(f_ops[0], full, depth + 1)
            return full

        root = next((fi for fi in instrs if fi.is_root),
                    instrs[-1] if instrs else None)
        if root is None:
            total += res_b
        elif root.op == "tuple":
            for o in _operands(root.rest):
                total += write_bytes(o, _type_bytes(ftable.get(o, "")))
        else:
            total += write_bytes(root.name, res_b)
        return total
    return res_b + sum(opnd_b(i) for i in range(len(ops)))


def ttable_of(instrs: list["_Instr"]) -> dict[str, str]:
    return {i.name: i.type_str for i in instrs}

# Result type may be a tuple containing `/*index=N*/` comments; match it
# non-greedily up to the ` opcode(` that follows.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*((?:\(.*?\))|(?:\S+))\s+"
    r"([\w\-]+)\((.*)$")
# Header like `%name (args...) -> type {` — args may contain nested parens
# (tuple-typed params), so just grab the leading %name and require `->`/`{`.
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s*\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in compiled HLO (per device),
    bucketed by collective kind."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"^(\S+)\s*=\s*((?:\([^)]*\))|(?:\S+))\s+(\S+)\(", line)
        if not m:
            continue
        op = m.group(3)
        base = op.split(".")[0]
        # match e.g. all-gather, all-gather-start, all-reduce-start
        for kind in _COLLECTIVES:
            if base == kind or base.startswith(kind + "-"):
                if base.endswith("-done"):
                    break
                out[kind] += _type_bytes(m.group(2))
                counts[kind] += 1
                break
    out_total = sum(out.values())
    return {"per_kind": out, "counts": counts, "total": out_total}


def _shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",")] if m.group(2) else []


@dataclasses.dataclass
class _Instr:
    name: str
    type_str: str
    op: str
    rest: str           # raw text after the opening '('
    is_root: bool = False


@dataclasses.dataclass
class HloCost:
    flops: float
    bytes_accessed: float
    collective_bytes: dict[str, float]
    collective_counts: dict[str, float]
    unresolved_loops: int = 0

    @property
    def collective_total(self) -> float:
        return sum(self.collective_bytes.values())


def _parse_computations(text: str) -> dict[str, list[_Instr]]:
    comps: dict[str, list[_Instr]] = {}
    cur: list[_Instr] | None = None
    for line in text.splitlines():
        if cur is None:
            stripped0 = line.strip()
            m = _COMP_RE.match(stripped0)
            if (m and line.rstrip().endswith("{") and "->" in stripped0):
                comps[m.group(1)] = cur = []
            continue
        stripped = line.strip()
        if stripped == "}":
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if m:
            cur.append(_Instr(m.group(1), m.group(2), m.group(3), m.group(4),
                              is_root=stripped.startswith("ROOT ")))
    return comps


def _operands(rest: str) -> list[str]:
    """Operand %names from the call-paren contents (first paren group)."""
    depth = 1
    out, cur = [], []
    for ch in rest:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        if depth >= 1:
            cur.append(ch)
    arglist = "".join(cur)
    return re.findall(r"%[\w.\-]+", arglist)


def _attr(rest: str, key: str) -> str | None:
    m = re.search(key + r"=(%[\w.\-]+)", rest)
    return m.group(1) if m else None


def _dims_attr(rest: str, key: str) -> list[int]:
    m = re.search(key + r"=\{([\d,]*)\}", rest)
    if not m or not m.group(1):
        return []
    return [int(x) for x in m.group(1).split(",")]


def _trip_count(cond_instrs: list[_Instr]) -> int | None:
    """Loop bound = the largest integer constant in the condition (lax.scan
    lowers to `i < C`; any auxiliary constants are smaller indices)."""
    best = None
    for ins in cond_instrs:
        if ins.op == "constant" and ins.type_str.startswith(("s32", "s64",
                                                             "u32", "u64")):
            m = re.match(r"([\-\d]+)\)", ins.rest)
            if m:
                v = int(m.group(1))
                if best is None or v > best:
                    best = v
    return best


def analyse_hlo(text: str) -> HloCost:
    comps = _parse_computations(text)
    types: dict[str, dict[str, str]] = {
        cname: {i.name: i.type_str for i in instrs}
        for cname, instrs in comps.items()}

    # entry = computation never referenced as callee; fall back to the one
    # whose name starts with %main.
    callees: set[str] = set()
    for instrs in comps.values():
        for ins in instrs:
            for key in ("condition", "body", "calls", "to_apply",
                        "branch_computations"):
                for ref in re.findall(key + r"=\{?([%\w.\-, ]+)\}?",
                                      ins.rest):
                    callees.update(re.findall(r"%[\w.\-]+", ref))
    entry = None
    for name in comps:
        if name not in callees and name.startswith("%main"):
            entry = name
            break
    if entry is None:
        cands = [n for n in comps if n not in callees]
        entry = cands[0] if cands else next(iter(comps))

    memo: dict[tuple[str, bool], HloCost] = {}
    unresolved = [0]

    def visit(cname: str, in_fusion: bool) -> HloCost:
        key = (cname, in_fusion)
        if key in memo:
            return memo[key]
        flops = 0.0
        byts = 0.0
        coll = {k: 0.0 for k in _COLLECTIVES}
        counts = {k: 0.0 for k in _COLLECTIVES}
        ttable = types.get(cname, {})
        for ins in comps.get(cname, []):
            ops = None
            # --- flops
            if ins.op == "dot":
                k = 1
                lhs_dims = _dims_attr(ins.rest, "lhs_contracting_dims")
                ops = _operands(ins.rest)
                if ops:
                    lhs_shape = _shape_dims(ttable.get(ops[0], ""))
                    for d in lhs_dims:
                        if d < len(lhs_shape):
                            k *= lhs_shape[d]
                flops += 2.0 * k * math.prod(_shape_dims(ins.type_str))
            # --- collectives
            base = ins.op
            for kind in _COLLECTIVES:
                if base == kind or base.startswith(kind + "-"):
                    if not base.endswith("-done"):
                        coll[kind] += _type_bytes(ins.type_str)
                        counts[kind] += 1
                    break
            # --- bytes (only outside fusions; collective payloads belong to
            # the collective term, not the HBM term)
            if (not in_fusion and ins.op not in _FREE_OPS
                    and not any(ins.op == k or ins.op.startswith(k + "-")
                                for k in _COLLECTIVES)):
                byts += _instr_bytes(ins, ttable, comps)
            # --- call edges
            if ins.op == "while":
                body = _attr(ins.rest, "body")
                cond = _attr(ins.rest, "condition")
                trip = _trip_count(comps.get(cond, [])) if cond else None
                if trip is None:
                    trip = 1
                    unresolved[0] += 1
                for callee in (body, cond):
                    if callee and callee in comps:
                        sub = visit(callee, in_fusion)
                        flops += trip * sub.flops
                        byts += trip * sub.bytes_accessed
                        for k2 in _COLLECTIVES:
                            coll[k2] += trip * sub.collective_bytes[k2]
                            counts[k2] += trip * sub.collective_counts[k2]
            elif ins.op == "fusion":
                callee = _attr(ins.rest, "calls")
                if callee and callee in comps:
                    sub = visit(callee, True)
                    flops += sub.flops
                    for k2 in _COLLECTIVES:
                        coll[k2] += sub.collective_bytes[k2]
                        counts[k2] += sub.collective_counts[k2]
            elif ins.op in ("call", "async-start", "custom-call"):
                callee = (_attr(ins.rest, "to_apply")
                          or _attr(ins.rest, "calls"))
                if callee and callee in comps:
                    sub = visit(callee, in_fusion)
                    flops += sub.flops
                    byts += sub.bytes_accessed
                    for k2 in _COLLECTIVES:
                        coll[k2] += sub.collective_bytes[k2]
                        counts[k2] += sub.collective_counts[k2]
            elif ins.op == "conditional":
                branches = re.search(r"branch_computations=\{([^}]*)\}",
                                     ins.rest)
                names = (re.findall(r"%[\w.\-]+", branches.group(1))
                         if branches else
                         [x for x in (_attr(ins.rest, "true_computation"),
                                      _attr(ins.rest, "false_computation"))
                          if x])
                subs = [visit(n, in_fusion) for n in names if n in comps]
                if subs:  # charge the most expensive branch
                    big = max(subs, key=lambda s: s.flops + s.bytes_accessed)
                    flops += big.flops
                    byts += big.bytes_accessed
                    for k2 in _COLLECTIVES:
                        coll[k2] += big.collective_bytes[k2]
                        counts[k2] += big.collective_counts[k2]
        res = HloCost(flops=flops, bytes_accessed=byts, collective_bytes=coll,
                      collective_counts=counts)
        memo[key] = res
        return res

    out = visit(entry, False)
    out.unresolved_loops = unresolved[0]
    return out
