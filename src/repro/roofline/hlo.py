"""Trip-count-aware analyzer for compiled (post-SPMD) HLO text.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body ONCE, which makes
it useless for scan-over-layers models (a 61-layer scan reports ~1 layer of
FLOPs). This module parses the compiled HLO, builds the call graph, and
multiplies through ``known_trip_count`` annotations, producing:

  * flops          — dot/conv (2*M*N*K) + elementwise, per device
  * hbm_bytes      — operand+result traffic at fusion granularity (fusion
                     internals are free; scatter / dynamic-update-slice are
                     counted as in-place: 2x update + indices)
  * collective_bytes / counts per kind — operand bytes of all-gather /
                     all-reduce / reduce-scatter / all-to-all /
                     collective-permute, loop-multiplied

`instruction_scopes` maps each instruction to the `jax.named_scope` phase
it belongs to, which attributes a profile's device ops to phases.

Shapes in post-SPMD HLO are per-partition, so every number here is
per-device. This is an HBM *traffic model*, not a simulator — documented
assumptions in EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_EW_OPS = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "log", "tanh", "logistic", "sqrt", "rsqrt",
    "power", "compare", "select", "and", "or", "xor", "not", "floor",
    "ceil", "sign", "cosine", "sine", "atan2", "expm1", "log1p",
    "round-nearest-afz", "round-nearest-even", "clamp", "erf",
}

_CHEAP_OPS = {
    "convert", "broadcast", "copy", "transpose", "reshape", "slice",
    "dynamic-slice", "pad", "concatenate", "gather", "reverse",
    "reduce", "reduce-window", "select-and-scatter", "iota", "map",
}

# fusions made only of these are dtype/layout changes the CPU backend
# materializes but a TPU feeds straight into the MXU — counted free
_LAYOUT_ONLY = {
    "convert", "bitcast", "copy", "transpose", "reshape", "broadcast",
    "parameter", "constant", "get-tuple-element", "tuple", "slice",
}

_FREE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "rng-get-and-update-state",
    "opt-barrier", "add-dependency", "domain",
}

_ARRAY_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _array_dims(shape_str: str):
    """First array in a shape string -> (dtype, [dims])."""
    m = _ARRAY_RE.search(shape_str)
    if not m:
        return None, []
    dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
    return m.group(1), dims


def shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _ARRAY_RE.finditer(shape_str):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if m.group(2):
            for d in m.group(2).split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


@dataclass
class Instr:
    name: str
    shape: str
    opcode: str
    operands: List[str]
    attrs: str
    called: List[str] = field(default_factory=list)
    trip_count: Optional[int] = None


@dataclass
class Totals:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_bytes: Dict[str, float] = field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVE_OPS})
    coll_counts: Dict[str, float] = field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVE_OPS})

    def add(self, other: "Totals", mult: float = 1.0):
        self.flops += other.flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        for k in COLLECTIVE_OPS:
            self.coll_bytes[k] += other.coll_bytes[k] * mult
            self.coll_counts[k] += other.coll_counts[k] * mult

    @property
    def collective_total(self):
        return sum(self.coll_bytes.values())


_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_NAME_REF = re.compile(r"%([\w.\-]+)")
_TRIP = re.compile(r'known_trip_count[":{\s]*[^0-9]*(\d+)')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)="
                     r"(\{[^}]*\}|%[\w.\-]+)")


class HloModule:
    def __init__(self, text: str):
        self.computations: Dict[str, List[Instr]] = {}
        self.entry: Optional[str] = None
        self._parse(text)
        self._memo: Dict[str, Totals] = {}

    # ------------------------------------------------------------ parsing
    def _parse(self, text: str):
        cur = None
        for raw in text.splitlines():
            line = raw.rstrip()
            if not line:
                continue
            if cur is None:
                m = _COMP_HDR.match(line.strip())
                if m and "{" in line:
                    cur = m.group(1)
                    self.computations[cur] = []
                    if line.strip().startswith("ENTRY"):
                        self.entry = cur
                continue
            if line.strip() == "}":
                cur = None
                continue
            m = _INSTR.match(line)
            if not m:
                continue
            name, rhs = m.group(1), m.group(2)
            instr = self._parse_instr(name, rhs)
            if instr:
                self.computations[cur].append(instr)

    @staticmethod
    def _parse_instr(name: str, rhs: str) -> Optional[Instr]:
        rhs = rhs.strip()
        # shape: tuple "(...)" or single token
        if rhs.startswith("("):
            depth = 0
            end = 0
            for i, ch in enumerate(rhs):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            shape = rhs[:end + 1]
            rest = rhs[end + 1:].strip()
        else:
            sp = rhs.find(" ")
            if sp < 0:
                return None
            shape = rhs[:sp]
            rest = rhs[sp + 1:].strip()
        par = rest.find("(")
        if par < 0:
            return None
        opcode = rest[:par].strip()
        # operand section (balanced parens)
        depth = 0
        end = par
        for i in range(par, len(rest)):
            if rest[i] == "(":
                depth += 1
            elif rest[i] == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_str = rest[par + 1:end]
        attrs = rest[end + 1:]
        operands = _NAME_REF.findall(operand_str)
        called = []
        for cm in _CALLED.finditer(attrs):
            called.extend(_NAME_REF.findall(cm.group(1)))
        trip = None
        tm = _TRIP.search(attrs)
        if tm:
            trip = int(tm.group(1))
        return Instr(name, shape, opcode, operands, attrs, called, trip)

    # ----------------------------------------------------------- analysis
    def _symtab(self, comp: str) -> Dict[str, str]:
        return {i.name: i.shape for i in self.computations.get(comp, [])}

    def _dot_flops(self, instr: Instr, symtab) -> float:
        out_elems = _prod(_array_dims(instr.shape)[1])
        lhs_shape = symtab.get(instr.operands[0], "") if instr.operands else ""
        _, lhs_dims = _array_dims(lhs_shape)
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.attrs)
        contract = 1
        if m and m.group(1):
            for i in m.group(1).split(","):
                idx = int(i)
                if idx < len(lhs_dims):
                    contract *= lhs_dims[idx]
        return 2.0 * out_elems * contract

    def _conv_flops(self, instr: Instr, symtab) -> float:
        out_elems = _prod(_array_dims(instr.shape)[1])
        rhs_shape = symtab.get(instr.operands[1], "") if len(instr.operands) > 1 else ""
        _, rhs_dims = _array_dims(rhs_shape)
        rhs_elems = max(_prod(rhs_dims), 1)
        out_feat = 1
        m = re.search(r"dim_labels=[^_]*_([\w?]+)->", instr.attrs)
        if m and rhs_dims:
            rl = m.group(1)
            oi = rl.find("o")
            if 0 <= oi < len(rhs_dims):
                out_feat = rhs_dims[oi]
        return 2.0 * out_elems * rhs_elems / max(out_feat, 1)

    def _flops_only(self, comp: str) -> float:
        """Flops of a computation's instructions (fusion-internal use)."""
        total = 0.0
        symtab = self._symtab(comp)
        for instr in self.computations.get(comp, []):
            if instr.opcode == "dot":
                total += self._dot_flops(instr, symtab)
            elif instr.opcode == "convolution":
                total += self._conv_flops(instr, symtab)
            elif instr.opcode in _EW_OPS:
                total += _prod(_array_dims(instr.shape)[1])
            for c in instr.called:
                if c in self.computations:
                    total += self._flops_only(c)
        return total

    def analyze(self, comp: Optional[str] = None) -> Totals:
        """SSA value-traffic model: every materialized value costs one HBM
        write (when produced) and one read (if consumed), regardless of
        fan-out — fan-out reads are assumed fused/cached, as the TPU
        backend's fusion would arrange. In-place ops (scatter /
        dynamic-update-slice) cost the update slice, not the full buffer.
        While bodies multiply by known_trip_count."""
        comp = comp or self.entry
        if comp in self._memo:
            return self._memo[comp]
        tot = Totals()
        symtab = self._symtab(comp)
        reads = set()

        for instr in self.computations.get(comp, []):
            op = instr.opcode
            if op in _FREE_OPS or op.endswith("-done"):
                continue
            if op == "while":
                trip = instr.trip_count or 1
                for c in instr.called:
                    if c in self.computations:
                        tot.add(self.analyze(c), trip)
                continue
            if op in ("call", "conditional"):
                for c in instr.called:
                    if c in self.computations:
                        tot.add(self.analyze(c), 1.0)
                continue
            if op.startswith(COLLECTIVE_OPS):
                kind = next(k for k in COLLECTIVE_OPS if op.startswith(k))
                ob = sum(shape_bytes(symtab.get(o, ""))
                         for o in instr.operands)
                tot.coll_bytes[kind] += ob
                tot.coll_counts[kind] += 1
                tot.hbm_bytes += shape_bytes(instr.shape)
                reads.update(instr.operands)
                continue
            if op == "fusion":
                tot.flops += sum(self._flops_only(c) for c in instr.called
                                 if c in self.computations)
                inner = [i for c in instr.called
                         for i in self.computations.get(c, [])]
                inner_ops = {i.opcode for i in inner}
                if inner_ops <= _LAYOUT_ONLY:
                    continue  # dtype/layout-change fusion: free on TPU
                if "scatter" in inner_ops or "dynamic-update-slice" in inner_ops:
                    upd = (shape_bytes(symtab.get(instr.operands[-1], ""))
                           if instr.operands else 0)
                    tot.hbm_bytes += 2 * upd
                elif "dynamic-slice" in inner_ops:
                    # a fusion that dynamic-slices a big operand (scan-xs
                    # layer slicing) reads only the slice, not the buffer
                    ds = sum(shape_bytes(i.shape) for i in inner
                             if i.opcode == "dynamic-slice")
                    cap = ds + shape_bytes(instr.shape)
                    tot.hbm_bytes += shape_bytes(instr.shape)
                    for o in instr.operands:
                        tot.hbm_bytes += min(
                            shape_bytes(symtab.get(o, "")), cap)
                else:
                    tot.hbm_bytes += shape_bytes(instr.shape)
                    reads.update(instr.operands)
                continue
            if op == "dynamic-update-slice":
                upd = (shape_bytes(symtab.get(instr.operands[1], ""))
                       if len(instr.operands) > 1 else 0)
                tot.hbm_bytes += 2 * upd
                continue
            if op == "scatter":
                upd = (shape_bytes(symtab.get(instr.operands[-1], ""))
                       if instr.operands else 0)
                tot.hbm_bytes += 2 * upd
                continue
            if op == "dot":
                tot.flops += self._dot_flops(instr, symtab)
            elif op == "convolution":
                tot.flops += self._conv_flops(instr, symtab)
            elif op in _EW_OPS:
                tot.flops += _prod(_array_dims(instr.shape)[1])
            # generic value traffic: one write now, reads deduped below
            tot.hbm_bytes += shape_bytes(instr.shape)
            reads.update(instr.operands)

        for name in reads:
            tot.hbm_bytes += shape_bytes(symtab.get(name, ""))
        self._memo[comp] = tot
        return tot


def analyze_hlo_text(text: str) -> Totals:
    return HloModule(text).analyze()


# --------------------------------------------- per-collective attribution


@dataclass
class Collective:
    """One collective instruction, attributed to its call path.

    kind:          which of COLLECTIVE_OPS
    name:          HLO instruction name
    operand_bytes: per-device operand bytes (post-SPMD shapes), one
                   execution
    mult:          loop multiplicity (product of enclosing while
                   known_trip_counts); total loop-traffic contribution is
                   operand_bytes * mult
    path:          call path from entry, e.g. ('entry', 'while',
                   'cond[1]') — conditionals record the branch INDEX so
                   callers can attribute a collective to, say, the GGC
                   refresh branch rather than summing both branches (which
                   `HloModule.analyze` deliberately does as an upper
                   bound)
    group_size:    devices per replica group, when the replica_groups
                   attribute is parseable (else None)
    attrs:         raw attribute text, for bespoke classification
    """
    kind: str
    name: str
    operand_bytes: int
    mult: int
    path: tuple
    group_size: Optional[int]
    attrs: str


def replica_group_size(attrs: str) -> Optional[int]:
    """Devices per replica group from a replica_groups attribute: the
    iota form [G,S]<=[dims]T(perm) has S devices per group; explicit
    {{...},{...}} lists are measured (None when ragged or absent)."""
    m = _RG_IOTA.search(attrs)
    if m:
        return int(m.group(2))
    m = _RG_LIST.search(attrs)
    if m:
        sizes = {len([x for x in grp.split(",") if x.strip() != ""])
                 for grp in m.group(1).split("},{")}
        if len(sizes) == 1:
            return sizes.pop()
    return None


def collect_collectives(text_or_module) -> List[Collective]:
    """Every collective reachable from entry, loop-multiplied and
    path-attributed. Unlike `HloModule.analyze` — a traffic upper bound
    that sums BOTH branches of a conditional — this keeps each branch's
    collectives distinct via the path tuple, which the commaudit needs to
    separate the every-round Eq.-4 exchange from the conditional GGC
    refresh. ``-start``/``-done`` async pairs count once (at -start)."""
    m = text_or_module if isinstance(text_or_module, HloModule) \
        else HloModule(text_or_module)
    out: List[Collective] = []
    if m.entry is None:
        return out

    def walk(comp: str, mult: int, path: tuple):
        symtab = {i.name: i.shape for i in m.computations.get(comp, [])}
        for i in m.computations.get(comp, []):
            if i.opcode == "while":
                t = i.trip_count or 1
                for c in i.called:
                    if c in m.computations:
                        walk(c, mult * t, path + ("while",))
                continue
            if i.opcode == "call":
                for c in i.called:
                    if c in m.computations:
                        walk(c, mult, path + ("call",))
                continue
            if i.opcode == "conditional":
                for bi, c in enumerate(i.called):
                    if c in m.computations:
                        walk(c, mult, path + (f"cond[{bi}]",))
                continue
            if i.opcode.endswith("-done"):
                continue
            if i.opcode.startswith(COLLECTIVE_OPS):
                kind = next(k for k in COLLECTIVE_OPS
                            if i.opcode.startswith(k))
                ob = sum(shape_bytes(symtab.get(o, ""))
                         for o in i.operands)
                out.append(Collective(
                    kind=kind, name=i.name, operand_bytes=ob, mult=mult,
                    path=path, group_size=replica_group_size(i.attrs),
                    attrs=i.attrs))

    walk(m.entry, 1, ("entry",))
    return out


# ------------------------------------------------- cross-pod classification

_RG_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                      r"(?:T\(([\d,]+)\))?")
_RG_LIST = re.compile(r"replica_groups=\{\{([^}]*(?:\},\{[^}]*)*)\}\}")


def _groups_cross_pod(attrs: str, pod_size: int) -> Optional[bool]:
    """Do this collective's replica groups span the pod boundary?
    Handles the iota format [G,S]<=[dims]T(perm) and explicit lists."""
    m = _RG_IOTA.search(attrs)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        import numpy as np
        n = 1
        for d in dims:
            n *= d
        ids = np.arange(n).reshape(dims)
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",")]
            ids = ids.transpose(perm)
        groups = ids.reshape(g, s)
        pods = groups // pod_size
        return bool((pods != pods[:, :1]).any())
    m = _RG_LIST.search(attrs)
    if m:
        for grp in m.group(1).split("},{"):
            ids = [int(x) for x in grp.split(",") if x]
            if ids and (min(ids) // pod_size) != (max(ids) // pod_size):
                return True
        return False
    return None  # unknown format: caller decides


def cross_pod_collective_bytes(text: str, pod_size: int = 256) -> dict:
    """Split collective operand bytes into pod-local vs cross-pod, loop
    multiplied. The DPFL communication-efficiency claim lives here: its
    gradient sync stays pod-local; only graph mixing crosses pods."""
    m = HloModule(text)
    out = {"local": 0.0, "cross": 0.0, "unknown": 0.0}

    def walk(comp, mult):
        symtab = {i.name: i.shape for i in m.computations.get(comp, [])}
        for i in m.computations.get(comp, []):
            if i.opcode in ("while", "call", "conditional"):
                t = (i.trip_count or 1) if i.opcode == "while" else 1
                for c in i.called:
                    if c in m.computations:
                        walk(c, mult * t)
                continue
            if i.opcode.endswith("-done"):
                continue
            if i.opcode.startswith(COLLECTIVE_OPS):
                b = sum(shape_bytes(symtab.get(o, "")) for o in i.operands)
                crosses = _groups_cross_pod(i.attrs, pod_size)
                key = ("unknown" if crosses is None
                       else "cross" if crosses else "local")
                out[key] += b * mult

    walk(m.entry, 1)
    return out


# ------------------------------------------------- named-scope attribution

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_scopes(text_or_module,
                       prefix: str) -> Dict[str, Optional[str]]:
    """Instruction name -> the innermost `jax.named_scope` whose name
    starts with ``prefix`` in the instruction's ``op_name`` metadata
    (``op_name="jit(round_step)/round.mix/round.refresh/..."`` ->
    ``round.refresh`` for prefix ``"round."``), for every instruction of
    the compiled module: the map a profile needs to attribute each
    device op, which it names but does not scope, to a phase.

    An instruction XLA made itself carries no such scope (a broadcast or
    iota hoisted out of a loop, a copy): it takes the scope of a
    consumer that has one, else of the instruction that calls its
    computation (a ``while`` or ``conditional``), else of an operand (a
    copy of a program output), transitively. ``None`` where none gives
    one."""
    m = text_or_module if isinstance(text_or_module, HloModule) \
        else HloModule(text_or_module)
    scope: Dict[str, Optional[str]] = {}
    comp_of: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    users: Dict[str, List[str]] = {}
    caller: Dict[str, str] = {}
    for comp, instrs in m.computations.items():
        for i in instrs:
            op_name = _OP_NAME.search(i.attrs)
            parts = [p for p in (op_name.group(1).split("/") if op_name
                                 else ()) if p.startswith(prefix)]
            scope[i.name] = parts[-1] if parts else None
            comp_of[i.name] = comp
            operands[i.name] = i.operands
            for o in i.operands:
                users.setdefault(o, []).append(i.name)
            for c in i.called:
                caller.setdefault(c, i.name)
    changed = True
    while changed:
        changed = False
        for name, s in scope.items():
            if s is not None:
                continue
            near = [scope[u] for u in users.get(name, ()) if scope[u]]
            up = caller.get(comp_of[name])
            if not near and up is not None and scope[up]:
                near = [scope[up]]
            if not near:
                near = [scope[o] for o in operands[name]
                        if scope.get(o)]
            if near:
                scope[name] = near[0]
                changed = True
    return scope
