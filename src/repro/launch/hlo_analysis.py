"""Trip-count-aware HLO analysis for the dry-run roofline.

``compiled.cost_analysis()`` undercounts scanned (while-loop) bodies — it
counts them once, not trip_count times — and reports per-device numbers.
This module parses ``compiled.as_text()`` directly:

  * builds a per-computation symbol table (every def line carries its type),
  * propagates execution multipliers through the call graph
    (``while`` bodies x ``known_trip_count``, fusions/calls x1),
  * counts dot FLOPs (2 * prod(out) * prod(contracting dims)),
  * sums collective operand bytes per collective kind,
  * sums a bytes-written traffic proxy (every op's output, once per execution).

All results are **per-device** (the module is the post-GSPMD per-device
program); roofline terms divide by per-chip peaks directly.
"""

from __future__ import annotations

import math
import re
from collections import defaultdict

_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3fn": 1,
          "f8e5m2": 1, "s16": 2, "u16": 2, "c64": 8, "token": 0,
          "u4": 1, "s4": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_START = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->.*{\s*$")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^=]*?\)|[\w\[\]{},\d]+))\s+"
    r"([\w\-]+)\(")
_SUBCOMP_RE = re.compile(r"(?:body|calls|to_apply|condition)=%([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations={([^}]*)}")
_TRIP_RE = re.compile(r'"known_trip_count":{"n":"(\d+)"}')

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _dims(type_str: str) -> list[tuple[str, list[int]]]:
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dims = [int(d) for d in m.group(2).split(",") if d] if m.group(2) else []
        out.append((m.group(1), dims))
    return out


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _dims(type_str):
        total += math.prod(dims) * _BYTES.get(dt, 0)
    return total


class HloModule:
    def __init__(self, text: str):
        self.comps: dict[str, list[dict]] = {}
        self._parse(text)

    _COMMENT_RE = re.compile(r"/\*.*?\*/")

    def _parse(self, text: str):
        cur = None
        for raw in text.splitlines():
            line = self._COMMENT_RE.sub("", raw).rstrip()
            m = _COMP_START.match(line)
            if m and ("->" in line):
                cur = m.group(1)
                self.comps[cur] = []
                continue
            if cur is None:
                continue
            if line.strip() == "}":
                cur = None
                continue
            om = _OP_RE.match(line)
            if not om:
                continue
            name, type_str, op = om.groups()
            subs = [sm.group(1) for sm in _SUBCOMP_RE.finditer(line)]
            for bm in _BRANCHES_RE.finditer(line):
                subs += [p.strip().lstrip("%") for p in bm.group(1).split(",")]
            trip = None
            tm = _TRIP_RE.search(line)
            if tm:
                trip = int(tm.group(1))
            # operand names: inside the first (...) after op
            paren = line[line.index(op + "(") + len(op) + 1:]
            depth, args, buf = 1, [], ""
            for ch in paren:
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        break
                if depth >= 1:
                    buf += ch
            # operands print as bare names ("dot(%a.1, %b.1)")
            operands = [a.strip().lstrip("%")
                        for a in _split_top(buf) if a.strip()]
            self.comps[cur].append({
                "name": name, "type": type_str, "op": op,
                "operands": operands, "subs": subs, "trip": trip,
                "line": line,
            })

    # ---- multipliers through the call graph ----

    def multipliers(self, entry: str | None = None) -> dict[str, float]:
        entry = entry or self._entry()
        mult: dict[str, float] = defaultdict(float)
        mult[entry] = 1.0
        order = [entry]
        seen = {entry}
        # BFS; HLO call graphs are DAGs
        i = 0
        while i < len(order):
            comp = order[i]
            i += 1
            for op in self.comps.get(comp, []):
                factor = 1.0
                if op["op"] == "while":
                    factor = float(op["trip"] if op["trip"] else 1)
                for sub in op["subs"]:
                    if sub not in self.comps:
                        continue
                    mult[sub] += mult[comp] * factor
                    if sub not in seen:
                        seen.add(sub)
                        order.append(sub)
        return dict(mult)

    def _entry(self) -> str:
        # ENTRY computation is usually named main.*
        for name in self.comps:
            if name.startswith("main"):
                return name
        return next(iter(self.comps))

    # ---- analyses ----

    def _symbols(self, comp: str) -> dict[str, str]:
        return {op["name"]: op["type"] for op in self.comps[comp]}

    def dot_flops(self) -> float:
        mult = self.multipliers()
        total = 0.0
        for comp, ops in self.comps.items():
            m = mult.get(comp, 0.0)
            if m == 0.0:
                continue
            syms = self._symbols(comp)
            for op in ops:
                if op["op"] not in ("dot", "convolution"):
                    continue
                out_elems = sum(math.prod(d) for _, d in _dims(op["type"]))
                contract = 1
                cm = re.search(r"lhs_contracting_dims={([\d,]*)}", op["line"])
                lhs_type = syms.get(op["operands"][0]) if op["operands"] else None
                if cm and lhs_type:
                    lhs_dims = _dims(lhs_type)
                    if lhs_dims:
                        dims = lhs_dims[0][1]
                        for idx in cm.group(1).split(","):
                            if idx:
                                contract *= dims[int(idx)]
                total += m * 2.0 * out_elems * contract
        return total

    def collective_bytes(self) -> dict[str, float]:
        """Wire bytes per collective kind (trip-corrected, per device).

        The XLA *host* backend's all-reduce-promotion pass rewrites bf16
        all-reduces as convert->f32-AR->convert (marked by a ``_promoted``
        reduction computation).  On real TPUs these stay bf16 on the wire,
        so promoted ARs are counted at half their printed f32 size.
        """
        mult = self.multipliers()
        out = {k: 0.0 for k in COLLECTIVES}
        for comp, ops in self.comps.items():
            m = mult.get(comp, 0.0)
            for op in ops:
                base = op["op"].removesuffix("-start").removesuffix("-done")
                if base in out:
                    if op["op"].endswith("-done"):
                        continue  # counted at -start
                    b = _type_bytes(op["type"])
                    if base == "all-reduce" and "_promoted" in op["line"]:
                        b //= 2  # logically bf16 (host-backend promotion)
                    out[base] += m * b
        return out

    def bytes_written(self) -> float:
        """Upper-bound traffic proxy: every op's output, once per execution.
        Heavily overcounts HBM traffic (fusion internals never leave VMEM)."""
        mult = self.multipliers()
        total = 0.0
        skip = {"parameter", "constant", "get-tuple-element", "tuple",
                "bitcast", "copy-done", "all-gather-done", "all-reduce-done"}
        for comp, ops in self.comps.items():
            m = mult.get(comp, 0.0)
            for op in ops:
                if op["op"] in skip:
                    continue
                total += m * _type_bytes(op["type"])
        return total

    def op_histogram(self) -> dict[str, float]:
        """Trip-weighted op execution counts (per device).  The static
        linter (``repro.analysis.trace_lint``) reads this to flag ops that
        have no business inside a decode hot loop — host↔device copies,
        dynamic reshards — without re-implementing the call-graph walk."""
        mult = self.multipliers()
        out: dict[str, float] = defaultdict(float)
        for comp, ops in self.comps.items():
            m = mult.get(comp, 0.0)
            if m == 0.0:
                continue
            for op in ops:
                out[op["op"]] += m
        return dict(out)

    def dot_bytes(self) -> float:
        """HBM-traffic proxy for the memory roofline term: operand + output
        bytes of every dot/convolution (trip-corrected).  A *lower* bound —
        elementwise chains fuse on TPU, so matmul traffic dominates; see
        EXPERIMENTS §Roofline for the convention."""
        mult = self.multipliers()
        total = 0.0
        for comp, ops in self.comps.items():
            m = mult.get(comp, 0.0)
            if m == 0.0:
                continue
            syms = self._symbols(comp)
            for op in ops:
                if op["op"] not in ("dot", "convolution"):
                    continue
                b = _type_bytes(op["type"])
                for operand in op["operands"][:2]:
                    t = syms.get(operand)
                    if t:
                        b += _type_bytes(t)
                total += m * b
        return total


def _split_top(s: str) -> list[str]:
    """Split on commas at paren/brace depth 0."""
    out, depth, buf = [], 0, ""
    for ch in s:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 0:
            out.append(buf)
            buf = ""
        else:
            buf += ch
    if buf.strip():
        out.append(buf)
    return out


def analyze(hlo_text: str) -> dict:
    mod = HloModule(hlo_text)
    return {
        "hlo_dot_flops_per_device": mod.dot_flops(),
        "hlo_bytes_written_per_device": mod.bytes_written(),
        "hlo_dot_bytes_per_device": mod.dot_bytes(),
        "hlo_collective_bytes_per_device": mod.collective_bytes(),
        "hlo_op_histogram": mod.op_histogram(),
    }
