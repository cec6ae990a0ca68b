"""Trip-count-aware cost model over optimized (post-SPMD) HLO text.

This is the *static watcher* of the Synapse adaptation: it treats the
compiled executable as a black box and derives per-chip resource consumption
from its HLO — FLOPs, HBM bytes and collective (ICI) wire bytes by kind.

Why not ``compiled.cost_analysis()``?  XLA's HloCostAnalysis visits a
``while`` body ONCE, so anything under ``lax.scan`` (our layer stacks, flash
KV loops, loss chunking) is undercounted by the trip count (verified
empirically; see EXPERIMENTS.md §Dry-run).  This walker parses the module
into computations, recurses through fusions/whiles/conditionals, multiplies
while bodies by their parsed trip counts, and accounts:

  * flops       — dot (2·M·N·K via operand-shape lookup), elementwise,
                  reductions, transcendentals
  * hbm_bytes   — operand + result bytes of top-level (unfused) instructions;
                  fusions count only their boundary operands/results
  * collectives — wire bytes per chip per kind, ring-model:
        all-reduce       2·size·(n-1)/n
        all-gather       size_out·(n-1)/n
        reduce-scatter   size_out·(n-1)          (input = out·n)
        all-to-all       size·(n-1)/n
        collective-permute  size
    attributed to a mesh axis by replica-group stride.  One collective is
    counted once per execution however the compiler splits it (see
    ``HloCost.add``), and the TPU's all-reduce-scatter fusion counts as the
    reduce-scatter it is (see ``ModuleCost._instr_cost``).
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\([^=]*?\)|\S+)\s+([\w\-]+)\(")
_OPERANDS_RE = re.compile(r"%([\w.\-]+)")

ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "and", "or", "xor", "not", "negate", "abs", "sign", "compare", "select",
    "clamp", "remainder", "atan2",
}
TRANSCENDENTAL = {"exponential", "log", "tanh", "rsqrt", "sqrt", "logistic",
                  "sine", "cosine", "expm1", "log1p", "cbrt", "erf"}
ZERO_COST = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "reshape", "copy", "transpose", "broadcast", "iota", "convert", "slice",
    "dynamic-slice", "dynamic-update-slice", "concatenate", "pad", "reverse",
    "gather", "scatter", "reduce", "reduce-window", "rng", "rng-bit-generator",
    "map", "sort", "after-all", "custom-call", "copy-start", "copy-done",
    "partition-id", "replica-id", "optimization-barrier", "domain",
    "get-dimension-size", "send", "recv", "send-done", "recv-done", "infeed",
    "outfeed", "dot", "convolution", "fusion", "while", "conditional", "call",
    "cholesky", "triangular-solve",
}  # ops handled specially or counted as data movement only


def shape_bytes(shape_str: str) -> float:
    """'f32[512,1024]{1,0}' or '(f32[2], s32[])' -> bytes."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += DTYPE_BYTES[dt] * n
    return total


def shape_numel(shape_str: str) -> float:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return 0.0
    dims = m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return float(n)


def _shape_dims(shape_str: str) -> List[int]:
    m = _SHAPE_RE.search(shape_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


@dataclass
class CollectiveOp:
    kind: str
    wire_bytes: float            # per chip, per execution
    group_size: int
    stride: int                  # replica-id stride within a group
    count: float = 1.0           # executions (after trip-count multiply)
    shape: str = ""
    #: the op's ``channel_id`` while it is summed into the computation
    #: that runs it once; None after a loop repeats it (see ``HloCost.add``)
    channel: Optional[int] = None

    @property
    def total_bytes(self) -> float:
        return self.wire_bytes * self.count


@dataclass
class HloCost:
    flops: float = 0.0
    transcendentals: float = 0.0
    hbm_bytes: float = 0.0       # upper bound: all unfused op boundaries
    dot_bytes: float = 0.0       # operand+result bytes of dot/conv only
    collectives: List[CollectiveOp] = field(default_factory=list)
    op_flops: Dict[str, float] = field(default_factory=dict)   # by metadata op

    def scaled(self, k: float) -> "HloCost":
        return HloCost(
            flops=self.flops * k, transcendentals=self.transcendentals * k,
            hbm_bytes=self.hbm_bytes * k, dot_bytes=self.dot_bytes * k,
            collectives=[CollectiveOp(c.kind, c.wire_bytes, c.group_size,
                                      c.stride, c.count * k, c.shape)
                         for c in self.collectives],
            op_flops={n: v * k for n, v in self.op_flops.items()})

    def add(self, other: "HloCost") -> "HloCost":
        """Both costs, run once each, in one execution of a computation.

        A collective of ``other`` whose ``channel_id`` is already among
        ``self``'s is the same collective and is not counted again.  XLA
        gives each collective of a partitioned module its own channel; the
        TPU compiler splits one all-gather into a chain of async collective
        fusions (``AsyncCollectiveStart``, ``async_collective_fusion``s
        overlapped with matmuls, ``AsyncCollectiveDone``), each piece
        holding an ``all-gather`` of the whole result under the op's
        channel and ``chain_id``.  The pieces run once each per execution
        of the computation that holds them, so one channel there is one
        collective.  ``scaled`` drops the channel, since a loop's
        iterations are separate executions."""
        of = dict(self.op_flops)
        for n, v in other.op_flops.items():
            of[n] = of.get(n, 0.0) + v
        seen = {c.channel for c in self.collectives if c.channel is not None}
        return HloCost(
            flops=self.flops + other.flops,
            transcendentals=self.transcendentals + other.transcendentals,
            hbm_bytes=self.hbm_bytes + other.hbm_bytes,
            dot_bytes=self.dot_bytes + other.dot_bytes,
            collectives=self.collectives + [
                c for c in other.collectives
                if c.channel is None or c.channel not in seen],
            op_flops=of)

    def collective_bytes(self) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for c in self.collectives:
            out[c.kind] += c.total_bytes
        return dict(out)

    @property
    def collective_total(self) -> float:
        return sum(c.total_bytes for c in self.collectives)


# ---------------------------------------------------------------------------
# Module parsing
# ---------------------------------------------------------------------------

@dataclass
class Instruction:
    name: str
    shape: str
    opcode: str
    line: str
    operands: List[str]


@dataclass
class Computation:
    name: str
    instructions: List[Instruction]
    by_name: Dict[str, Instruction]


_COMP_START = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*)?\{\s*$")


def parse_module(text: str) -> Tuple[Dict[str, Computation], str]:
    comps: Dict[str, Computation] = {}
    entry = None
    cur_name, cur_instrs = None, []
    for raw in text.splitlines():
        line = re.sub(r"/\*.*?\*/", "", raw.rstrip())
        if cur_name is None:
            clean = line.strip()
            m = _COMP_START.match(clean)
            if m and clean.endswith("{") and " -> " in clean and \
                    " = " not in clean:
                cur_name = m.group(1)
                cur_instrs = []
                if clean.startswith("ENTRY"):
                    entry = cur_name
            continue
        if line.strip() == "}":
            comps[cur_name] = _finish(cur_name, cur_instrs)
            cur_name = None
            continue
        mi = _INSTR_RE.match(line)
        if mi:
            name, shape, opcode = mi.groups()
            # operand names: between the opcode '(' and the next '),' boundary
            tail = line[mi.end():]
            depth = 1
            args = []
            buf = ""
            for ch in tail:
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        args.append(buf)
                        break
                if depth >= 1:
                    buf += ch
            ops = _OPERANDS_RE.findall(args[0]) if args else []
            cur_instrs.append(Instruction(name, shape, opcode, line, ops))
    return comps, entry


def _finish(name, instrs):
    return Computation(name, instrs, {i.name: i for i in instrs})


# ---------------------------------------------------------------------------
# Per-instruction costing
# ---------------------------------------------------------------------------

_ATTR_RE = {
    "calls": re.compile(r"calls=%?([\w.\-]+)"),
    "body": re.compile(r"body=%?([\w.\-]+)"),
    "condition": re.compile(r"condition=%?([\w.\-]+)"),
    "branches": re.compile(r"branch_computations=\{([^}]*)\}"),
    "true": re.compile(r"true_computation=%?([\w.\-]+)"),
    "false": re.compile(r"false_computation=%?([\w.\-]+)"),
    "groups_explicit": re.compile(r"replica_groups=\{\{([\d,]+)\}"),
    "groups_iota": re.compile(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?"),
    "contracting": re.compile(r"lhs_contracting_dims=\{([\d,]*)\}"),
    "metadata_op": re.compile(r'op_name="([^"]*)"'),
    "channel": re.compile(r"channel_id=(\d+)"),
    "pairs": re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}"),
}


def _dot_flops(instr: Instruction, comp: Computation) -> float:
    out_numel = shape_numel(instr.shape)
    k = 1.0
    mc = _ATTR_RE["contracting"].search(instr.line)
    if mc and instr.operands:
        lhs = comp.by_name.get(instr.operands[0])
        if lhs is not None:
            dims = _shape_dims(lhs.shape)
            for di in (mc.group(1).split(",") if mc.group(1) else []):
                i = int(di)
                if i < len(dims):
                    k *= dims[i]
    return 2.0 * out_numel * k


_DIM_LABELS_RE = re.compile(r"dim_labels=([\w]+)_([\w]+)->([\w]+)")
_WINDOW_RE = re.compile(r"window=\{([^}]*)\}")


def _window_attr(window: str, key: str, n: int, default: str) -> List[str]:
    m = re.search(rf"\b{key}=([\w\-]+)", window)
    return m.group(1).split("x") if m else [default] * n


def _valid_taps(n_in: int, n_out: int, size: int, stride: int, lo: int,
                lhs_dil: int, rhs_dil: int) -> int:
    """(output position, window tap) pairs of one spatial dim that land on a
    real input element rather than on padding or a dilation hole."""
    span = (n_in - 1) * lhs_dil + 1
    count = 0
    for k in range(size):
        first = lo - k * rhs_dil          # o * stride must reach [first,
        if lhs_dil == 1:                  # first + span)
            o_lo = max(0, -(-first // stride))
            o_hi = min(n_out - 1, (first + span - 1) // stride)
            count += max(0, o_hi - o_lo + 1)
            continue
        for o in range(n_out):
            pos = o * stride - first
            if 0 <= pos < span and pos % lhs_dil == 0:
                count += 1
    return count


def _conv_flops(instr: Instruction, comp: Computation) -> float:
    """2 x multiply-adds of a convolution, read from its ``dim_labels``.

    The TPU backend emits every matmul as a ``convolution``: feature dims
    carry the contraction (rhs ``i``), and a batch-like dim may ride as a
    padded spatial window (``bf0_i0o->b0f``, window 12 padded 11_11), where
    only the taps that land on real input count.  Layout order varies
    (``bf_io`` vs ``bf_oi``), so dims are looked up by label, never by
    position.
    """
    m = _DIM_LABELS_RE.search(instr.line)
    if m is None or len(instr.operands) < 2:
        return 2.0 * shape_numel(instr.shape)
    lhs_l, rhs_l, out_l = m.groups()
    lhs = comp.by_name.get(instr.operands[0])
    rhs = comp.by_name.get(instr.operands[1])
    if lhs is None or rhs is None:
        return 2.0 * shape_numel(instr.shape)
    lhs_d, rhs_d = _shape_dims(lhs.shape), _shape_dims(rhs.shape)
    out_d = _shape_dims(instr.shape)
    macs = float(out_d[out_l.index("b")] * out_d[out_l.index("f")]
                 * rhs_d[rhs_l.index("i")])
    n = sum(c.isdigit() for c in out_l)
    if n:
        wm = _WINDOW_RE.search(instr.line)
        window = wm.group(1) if wm else ""
        size = _window_attr(window, "size", n, "1")
        stride = _window_attr(window, "stride", n, "1")
        pad = _window_attr(window, "pad", n, "0_0")
        lhs_dil = _window_attr(window, "lhs_dilate", n, "1")
        rhs_dil = _window_attr(window, "rhs_dilate", n, "1")
        for j in range(n):             # window dim j is spatial label "j"
            c = str(j)
            macs *= _valid_taps(
                lhs_d[lhs_l.index(c)], out_d[out_l.index(c)],
                int(size[j]), int(stride[j]), int(pad[j].split("_")[0]),
                int(lhs_dil[j]), int(rhs_dil[j]))
    return 2.0 * macs


_TRIP_RE = re.compile(r'"known_trip_count":\s*\{\s*"n":\s*"(\d+)"')


def _trip_count_from_line(line: str) -> Optional[float]:
    """XLA annotates `backend_config={"known_trip_count":{"n":"48"}}`."""
    m = _TRIP_RE.search(line)
    return float(m.group(1)) if m else None


def _trip_count(cond: Computation) -> float:
    """Fallback: parse the condition computation.  The compare may be fused
    (`ROOT %wrapped_compare = fusion(%gte, %constant.N)`), so resolve constant
    operands of the root instruction."""
    consts: Dict[str, float] = {}
    for i in cond.instructions:
        m = re.search(r"constant\((-?\d+)\)", i.line)
        if m and i.opcode == "constant":
            consts[i.name] = float(m.group(1))
    root = None
    for i in cond.instructions:
        if i.line.lstrip().startswith("ROOT"):
            root = i
    for i in ([root] if root else []) + list(reversed(cond.instructions)):
        if i is None or i.opcode not in ("compare", "fusion"):
            continue
        vals = [consts[op] for op in i.operands if op in consts]
        if vals:
            return max(max(vals), 1.0)
    return 1.0


def _collective_wire_bytes(kind: str, out_bytes: float, n: int) -> float:
    if n <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * out_bytes * (n - 1) / n
    if kind == "all-gather":
        return out_bytes * (n - 1) / n
    if kind == "reduce-scatter":
        return out_bytes * (n - 1)
    if kind == "all-to-all":
        return out_bytes * (n - 1) / n
    if kind == "collective-permute":
        return out_bytes
    return out_bytes


def _parse_groups(line: str) -> Tuple[int, int]:
    """-> (group_size, stride). stride 1 == innermost mesh axis.  A
    collective-permute's group is the devices of its source-target pairs,
    its stride the shortest hop between a source and its target."""
    m = _ATTR_RE["pairs"].search(line)
    if m:
        pairs = [tuple(map(int, p)) for p in
                 re.findall(r"\{(\d+),(\d+)\}", m.group(1))]
        hops = [abs(t - s) for s, t in pairs if t != s]
        if not hops:
            return 1, 1
        return len({d for p in pairs for d in p}), min(hops)
    m = _ATTR_RE["groups_explicit"].search(line)
    if m:
        ids = [int(x) for x in m.group(1).split(",")]
        size = len(ids)
        stride = (ids[1] - ids[0]) if size > 1 else 1
        return size, stride
    m = _ATTR_RE["groups_iota"].search(line)
    if m:
        n_groups, size = int(m.group(1)), int(m.group(2))
        reshape = [int(x) for x in m.group(3).split(",")]
        perm = [int(x) for x in m.group(4).split(",")] if m.group(4) else \
            list(range(len(reshape)))
        # stride of the last (fastest-varying) permuted axis:
        # device ids laid out in `reshape` row-major; groups take the
        # transposed-last dim.  stride = product of reshape dims after the
        # permuted last axis.
        last_axis = perm[-1]
        stride = 1
        for d in reshape[last_axis + 1:]:
            stride *= d
        return size, stride
    return 1, 1


COLLECTIVE_BASES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                    "collective-permute")


def _collective_kind(opcode: str) -> Optional[str]:
    for base in COLLECTIVE_BASES:
        if opcode == base or opcode == base + "-start":
            return base
    return None


def _tuple_elements(shape: str) -> List[str]:
    """The top-level elements of a tuple shape ``(a, b, ...)``; a shape
    that is not a tuple is its own one element."""
    if not shape.startswith("("):
        return [shape]
    out, depth, start = [], 0, 1
    for i, ch in enumerate(shape):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                out.append(shape[start:i])
                break
        elif ch == "," and depth == 1:
            out.append(shape[start:i])
            start = i + 1
    return [e.strip() for e in out]


def _collective_result(instr: Instruction) -> str:
    """The shape a collective delivers.  An async ``-start`` whose shape is
    a tuple holds (operand, result, context...): the result is the
    second element, and the operand and context move nothing more."""
    parts = _tuple_elements(instr.shape)
    if instr.opcode.endswith("-start") and len(parts) > 1:
        return parts[1]
    return instr.shape


class ModuleCost:
    def __init__(self, text: str):
        self.comps, self.entry = parse_module(text)
        self._memo: Dict[str, HloCost] = {}

    def cost(self, comp_name: Optional[str] = None) -> HloCost:
        name = comp_name or self.entry
        if name is None:
            return HloCost()
        if name in self._memo:
            return self._memo[name]
        comp = self.comps.get(name)
        if comp is None:
            return HloCost()
        total = HloCost()
        for instr in comp.instructions:
            total = total.add(self._instr_cost(instr, comp))
        self._memo[name] = total
        return total

    # -- helpers ------------------------------------------------------------

    def _operand_bytes(self, instr: Instruction, comp: Computation) -> float:
        b = 0.0
        for op in instr.operands:
            src = comp.by_name.get(op)
            if src is not None:
                b += shape_bytes(src.shape)
        return b

    def _instr_cost(self, instr: Instruction, comp: Computation) -> HloCost:
        op = instr.opcode
        kind = _collective_kind(op)
        if kind is not None:
            out_b = shape_bytes(_collective_result(instr))
            size, stride = _parse_groups(instr.line)
            wire = _collective_wire_bytes(kind, out_b, size)
            ch = _ATTR_RE["channel"].search(instr.line)
            return HloCost(hbm_bytes=0.0, collectives=[
                CollectiveOp(kind, wire, size, stride, 1.0, instr.shape,
                             int(ch.group(1)) if ch else None)])
        if op.endswith("-done") or op in ("after-all",):
            return HloCost()

        if op == "fusion":
            m = _ATTR_RE["calls"].search(instr.line)
            inner = self.cost(m.group(1)) if m else HloCost()
            io_bytes = shape_bytes(instr.shape) + self._operand_bytes(instr, comp)
            colls = inner.collectives
            if m and m.group(1).startswith("all-reduce-scatter"):
                # the TPU compiler's reduce-scatter: an all-reduce whose
                # result the fusion slices to this chip's 1/n, moved as a
                # reduce-scatter of the fusion's result
                out_b = shape_bytes(instr.shape)
                colls = [dataclasses.replace(
                    c, kind="reduce-scatter",
                    wire_bytes=_collective_wire_bytes(
                        "reduce-scatter", out_b, c.group_size))
                    if c.kind == "all-reduce" else c for c in colls]
            return HloCost(flops=inner.flops,
                           transcendentals=inner.transcendentals,
                           hbm_bytes=io_bytes,
                           dot_bytes=inner.dot_bytes,
                           collectives=colls,
                           op_flops=inner.op_flops)
        if op == "while":
            body = _ATTR_RE["body"].search(instr.line)
            cond = _ATTR_RE["condition"].search(instr.line)
            trips = _trip_count_from_line(instr.line)
            if trips is None:
                trips = _trip_count(self.comps[cond.group(1)]) if cond and \
                    cond.group(1) in self.comps else 1.0
            inner = self.cost(body.group(1)) if body else HloCost()
            return inner.scaled(trips)
        if op == "conditional":
            branches = []
            m = _ATTR_RE["branches"].search(instr.line)
            if m:
                branches = _OPERANDS_RE.findall(m.group(1))
            else:
                for key in ("true", "false"):
                    mm = _ATTR_RE[key].search(instr.line)
                    if mm:
                        branches.append(mm.group(1))
            if not branches:
                return HloCost()
            costs = [self.cost(b) for b in branches]
            return max(costs, key=lambda c: c.flops + c.hbm_bytes)
        if op == "call":
            m = re.search(r"to_apply=%?([\w.\-]+)", instr.line)
            return self.cost(m.group(1)) if m else HloCost()

        # leaf instructions ---------------------------------------------------
        cost = HloCost()
        out_numel = shape_numel(instr.shape)
        if op == "dot":
            cost.flops = _dot_flops(instr, comp)
            cost.dot_bytes = shape_bytes(instr.shape) + \
                self._operand_bytes(instr, comp)
        elif op == "convolution":
            cost.flops = _conv_flops(instr, comp)
            cost.dot_bytes = shape_bytes(instr.shape) + \
                self._operand_bytes(instr, comp)
        elif op in ELEMENTWISE:
            cost.flops = out_numel
        elif op in TRANSCENDENTAL:
            cost.flops = out_numel
            cost.transcendentals = out_numel
        elif op == "reduce" or op == "reduce-window":
            in_b = 0.0
            if instr.operands:
                src = comp.by_name.get(instr.operands[0])
                if src is not None:
                    in_b = shape_numel(src.shape)
            cost.flops = in_b
        elif op in ("exponential-minus-one",):
            cost.flops = out_numel
            cost.transcendentals = out_numel

        if op not in ("parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast"):
            cost.hbm_bytes = shape_bytes(instr.shape) + \
                self._operand_bytes(instr, comp)
        if cost.flops:
            mm = _ATTR_RE["metadata_op"].search(instr.line)
            if mm:
                cost.op_flops = {_short_op(mm.group(1)): cost.flops}
        return cost


def _short_op(op_name: str) -> str:
    # "jit(train_step)/jvp(...)/transformer/attn/dot_general" -> trailing parts
    parts = op_name.split("/")
    return "/".join(parts[-2:]) if len(parts) > 1 else op_name


def analyze_hlo(text: str) -> HloCost:
    return ModuleCost(text).cost()


def sample_breakdown(text: str, max_samples: int = 4096):
    """Ordered (label, HloCost) samples from the entry computation.

    The execution order of the entry computation is the profiler's clock:
    straight-line segments accumulate into one sample; each ``while`` (a
    layer scan, flash KV loop, loss chunk loop) emits trip-count samples of
    its body cost.  This is the static analog of the paper's time-sampled
    profiling — granularity follows program structure instead of wall time.
    Consecutive identical whiles collapse into (label, cost, count) runs to
    bound sample counts for very long loops.
    """
    mc = ModuleCost(text)
    if mc.entry is None:
        return []
    comp = mc.comps[mc.entry]
    out = []          # list of (label, HloCost, count)
    cur = HloCost()

    def flush(label):
        nonlocal cur
        if cur.flops or cur.hbm_bytes or cur.collectives:
            out.append((label, cur, 1))
        cur = HloCost()

    for instr in comp.instructions:
        if instr.opcode == "while":
            flush("glue")
            body = _ATTR_RE["body"].search(instr.line)
            cond = _ATTR_RE["condition"].search(instr.line)
            trips = _trip_count_from_line(instr.line)
            if trips is None:
                trips = _trip_count(mc.comps[cond.group(1)]) if cond and \
                    cond.group(1) in mc.comps else 1.0
            inner = mc.cost(body.group(1)) if body else HloCost()
            n = int(max(trips, 1))
            if n > max_samples:
                inner = inner.scaled(n / max_samples)
                n = max_samples
            out.append((f"scan:{instr.name}", inner, n))
        else:
            cur = cur.add(mc._instr_cost(instr, comp))
    flush("glue")
    return out


def attribute_axes(cost: HloCost, mesh_shape: Dict[str, int]) -> Dict[str, float]:
    """Map collective wire bytes to mesh axes by replica-group stride.

    mesh axes are row-major: last axis has stride 1 in device ids.
    """
    axes = list(mesh_shape.items())                     # [(name, size), ...]
    strides = {}
    s = 1
    for name, size in reversed(axes):
        strides[name] = s
        s *= size
    out: Dict[str, float] = defaultdict(float)
    for c in cost.collectives:
        matched = None
        for name, size in axes:
            if c.stride == strides[name] and c.group_size <= size:
                matched = name
                break
        if matched is None:
            # groups spanning multiple axes (e.g. ('data','model')) — match by
            # total span
            for name, size in axes:
                if c.group_size == size:
                    matched = name
                    break
        out[matched or "unknown"] += c.total_bytes
    return dict(out)
