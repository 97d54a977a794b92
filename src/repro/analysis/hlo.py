"""HLO-level analysis: call-graph walker + roofline terms.

This is the dry-run 'profiler': there is no TPU wall clock, so the three
roofline terms are derived from the compiled (SPMD-partitioned, per-device)
HLO module —

  compute term    = HLO_FLOPs_per_device / peak_FLOPs        [s]
  memory term     = HLO_bytes_per_device / HBM_bw            [s]
  collective term = wire_bytes_per_device / link_bw          [s]

CRITICAL: XLA's ``compiled.cost_analysis()`` counts a ``while`` body ONCE,
so anything under ``lax.scan`` (layer stacks, grad-accum microbatches,
chunked attention) is undercounted by the trip count. We therefore parse the
HLO text into its computation call graph, derive trip counts from while
conditions, and scale every nested computation's FLOPs / bytes / collective
traffic by the product of enclosing trip counts.

Per-instruction accounting (post-fusion, per-device module):

  * FLOPs  — dot: 2 * prod(out dims) * prod(lhs contracting dims); operand
    shapes resolved through a per-computation symbol table (post-opt HLO
    omits operand shapes inline). conv: 2 * prod(out) * window.
  * bytes  — output + resolved operand buffer sizes for every top-level
    instruction, excluding view/plumbing ops (parameter, GTE, tuple,
    bitcast, constant). dynamic-update-slice counts the update slice, not
    the aliased full buffer (XLA updates in place inside scan bodies).
    This is an HBM-traffic proxy (no cache modeling) — consistent across
    variants, which is what hillclimbing needs.
  * wire   — ring-algorithm factors per collective kind (per device):
               all-reduce          2(S-1)/S * buffer
               all-gather          (S-1)/S  * result
               reduce-scatter      (S-1)    * result   (= (S-1)/S * input)
               all-to-all          (S-1)/S  * buffer
               collective-permute  1        * buffer
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")
_INSTR_HEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*")
_PARAM_RE = re.compile(r"%?([\w\.\-]+):\s*((?:\([^)]*\)|[a-z]\w*\[[\d,]*\](?:\{[\d,]*\})?))")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CALLED_RE = re.compile(
    r"(?:to_apply|calls|body|condition|branch_computations|"
    r"true_computation|false_computation)="
    r"(?:\{([^}]*)\}|%?([\w\.\-]+))")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*?)\}")
_GROUPS_ID_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CONST_RE = re.compile(r"constant\((\d+)\)")

_VIEW_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast", "constant",
             "after-all", "partition-id", "replica-id", "iota",
             "opt-barrier", "optimization-barrier"}
_COLL_OPS = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute", "all-reduce-start", "all-gather-start",
             "collective-permute-start", "reduce-scatter-start",
             "all-to-all-start"}


def shape_bytes(shape_str: str) -> int:
    """Total bytes of 'bf16[2,3]{1,0}' or '(f32[4], s32[])' strings."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(shape_str: str) -> list[int]:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    m = _GROUPS_ID_RE.search(line)
    if m:  # iota format [n_groups,group_size]
        return int(m.group(2))
    return 1


def _wire_bytes(kind: str, rb: int, s: int) -> float:
    if kind.startswith("collective-permute"):
        return float(rb)
    if s <= 1:
        return 0.0
    if kind.startswith("all-reduce"):
        return 2.0 * (s - 1) / s * rb
    if kind.startswith("all-gather"):
        return (s - 1) / s * rb
    if kind.startswith("reduce-scatter"):
        return float(s - 1) * rb
    if kind.startswith("all-to-all"):
        return (s - 1) / s * rb
    return float(rb)


# --------------------------------------------------------------------------
# module parsing
# --------------------------------------------------------------------------

@dataclass
class Instr:
    name: str
    shape: str
    op: str
    line: str
    operands: list[str]


@dataclass
class Computation:
    name: str
    is_entry: bool = False
    instrs: list[Instr] = field(default_factory=list)
    symbols: dict[str, str] = field(default_factory=dict)   # name -> shape
    # if the root is a dynamic-update-slice (in-place scan-carry write),
    # callers must charge the UPDATE size, not the aliased full buffer
    root_dus_update: int | None = None
    # local (unscaled) stats, filled by _local_stats
    flops: float = 0.0
    bytes_: float = 0.0
    wire: dict[str, float] = field(default_factory=dict)
    coll_counts: dict[str, int] = field(default_factory=dict)
    coll_ops: list[tuple[str, int, int]] = field(default_factory=list)
    calls: list[tuple[str, str]] = field(default_factory=list)
    while_cond: dict[str, str] = field(default_factory=dict)


def _operand_names(line: str, op_end: int) -> list[str]:
    """Names referenced inside op( ... ) — up to the closing paren."""
    depth = 0
    i = op_end - 1            # index of '('
    end = len(line)
    for j in range(i, len(line)):
        ch = line[j]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = j
                break
    return _OPERAND_RE.findall(line[i:end])


def _parse_instr(s: str) -> Instr | None:
    """Parse '%name = SHAPE op(args...), attrs' with balanced-paren shape
    handling (tuple shapes contain '/*index=N*/' comments)."""
    m = _INSTR_HEAD_RE.match(s)
    if not m:
        return None
    name = m.group(1)
    i = m.end()
    if i >= len(s):
        return None
    if s[i] == "(":               # tuple shape
        depth = 0
        j = i
        while j < len(s):
            if s[j] == "(":
                depth += 1
            elif s[j] == ")":
                depth -= 1
                if depth == 0:
                    break
            j += 1
        shape = s[i:j + 1]
        rest = s[j + 1:]
    else:
        sp = s.find(" ", i)
        if sp < 0:
            return None
        shape = s[i:sp]
        rest = s[sp:]
    mo = re.match(r"\s*([\w\-]+)\(", rest)
    if not mo:
        return None
    op = mo.group(1)
    op_paren = len(s) - len(rest) + mo.end()
    return Instr(name, shape, op, s, _operand_names(s, op_paren))


def _split_computations(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    depth = 0
    for raw in text.splitlines():
        line = raw.rstrip()
        s = line.strip()
        if cur is None:
            if s.endswith("{") and ("->" in s or s.startswith("ENTRY")):
                is_entry = s.startswith("ENTRY")
                body = s[len("ENTRY"):].strip() if is_entry else s
                name = body.split()[0].lstrip("%").split("(")[0]
                cur = Computation(name=name, is_entry=is_entry)
                depth = 1
                # parameters into the symbol table
                for pname, pshape in _PARAM_RE.findall(s):
                    cur.symbols[pname] = pshape
            continue
        depth += s.count("{") - s.count("}")
        if depth <= 0:
            comps[cur.name] = cur
            cur = None
            continue
        ins = _parse_instr(s)
        if ins is not None:
            cur.symbols[ins.name] = ins.shape
            cur.instrs.append(ins)
    if cur is not None:
        comps[cur.name] = cur
    return comps


def _find_root_dus(c: Computation) -> None:
    """Detect fusions whose root writes a slice in place (scan carries)."""
    for ins in c.instrs:
        if "ROOT" in ins.line.split("=", 1)[0] or ins is c.instrs[-1]:
            if ins.op == "dynamic-update-slice" and len(ins.operands) >= 2:
                c.root_dus_update = shape_bytes(
                    c.symbols.get(ins.operands[1], ""))
            return


def _local_stats(c: Computation, comps: dict[str, "Computation"]
                 | None = None) -> None:
    comps = comps or {}
    for ins in c.instrs:
        op = ins.op
        # ---- flops
        if op == "dot":
            out_n = 1
            for d in _shape_dims(ins.shape):
                out_n *= d
            k = 1
            mc = _CONTRACT_RE.search(ins.line)
            if mc and ins.operands:
                lhs_shape = c.symbols.get(ins.operands[0], "")
                lhs_dims = _shape_dims(lhs_shape)
                for ci in (int(x) for x in mc.group(1).split(",") if x):
                    if ci < len(lhs_dims):
                        k *= lhs_dims[ci]
            c.flops += 2.0 * out_n * k
        elif op == "convolution":
            out_n = 1
            for d in _shape_dims(ins.shape):
                out_n *= d
            kn = 1
            if len(ins.operands) >= 2:
                kd = _shape_dims(c.symbols.get(ins.operands[1], ""))
                for d in kd[:-1]:
                    kn *= d
            c.flops += 2.0 * out_n * kn

        # ---- collectives
        if op in _COLL_OPS:
            rb = shape_bytes(ins.shape)
            s = _group_size(ins.line)
            kind = op.replace("-start", "")
            c.wire[kind] = c.wire.get(kind, 0.0) + _wire_bytes(kind, rb, s)
            c.coll_counts[kind] = c.coll_counts.get(kind, 0) + 1
            c.coll_ops.append((kind, rb, s))

        # ---- call edges
        for grp, single in _CALLED_RE.findall(ins.line):
            names = ([single.lstrip("%")] if single else
                     [x.strip().lstrip("%") for x in grp.split(",")
                      if x.strip()])
            if op == "while":
                body = cond = None
                mb = re.search(r"body=%?([\w\.\-]+)", ins.line)
                mc2 = re.search(r"condition=%?([\w\.\-]+)", ins.line)
                body = mb.group(1) if mb else None
                cond = mc2.group(1) if mc2 else None
                if body and (body, "while") not in c.calls:
                    c.calls.append((body, "while"))
                    if cond:
                        c.while_cond[body] = cond
            else:
                kind = "fusion" if op == "fusion" else "call"
                for n in names:
                    if (n, kind) not in c.calls:
                        c.calls.append((n, kind))

        # ---- memory traffic
        if op in _VIEW_OPS or op == "while":
            continue   # while carry traffic is accounted inside the body
        if op == "dynamic-update-slice" and len(ins.operands) >= 2:
            upd = shape_bytes(c.symbols.get(ins.operands[1], ""))
            c.bytes_ += 2.0 * upd          # read update + write slice
            continue
        if op == "fusion":
            # a fusion whose root is a DUS aliases its big operand in
            # place: charge the update slice, not the full buffer
            callee = None
            mcall = re.search(r"calls=%?([\w\.\-]+)", ins.line)
            if mcall:
                callee = comps.get(mcall.group(1))
            if callee is not None and callee.root_dus_update is not None:
                big = shape_bytes(ins.shape)
                in_b = sum(shape_bytes(c.symbols.get(o, ""))
                           for o in ins.operands)
                # drop the aliased buffer from both sides
                c.bytes_ += max(in_b - big, 0) + 2.0 * callee.root_dus_update
                continue
        out_b = shape_bytes(ins.shape)
        in_b = sum(shape_bytes(c.symbols.get(o, "")) for o in ins.operands)
        c.bytes_ += out_b + in_b


def _trip_count(cond: Computation) -> int | None:
    consts = []
    for ins in cond.instrs:
        consts += [int(x) for x in _CONST_RE.findall(ins.line)]
    if consts:
        return max(consts)
    return None


@dataclass
class ModuleStats:
    flops: float = 0.0
    bytes_: float = 0.0
    wire_bytes: dict[str, float] = field(default_factory=dict)
    coll_counts: dict[str, float] = field(default_factory=dict)
    top_ops: list[tuple[str, int, int, float]] = field(default_factory=list)
    top_bytes_ops: list[tuple[str, float, float]] = field(
        default_factory=list)       # (op, scaled bytes, mult)
    unparsed_while: int = 0

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())


def analyze_module(text: str) -> ModuleStats:
    comps = _split_computations(text)
    for c in comps.values():
        _find_root_dus(c)
    for c in comps.values():
        _local_stats(c, comps)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None and comps:
        entry = next(iter(comps.values()))

    stats = ModuleStats()
    top_ops: list[tuple[str, int, int, float]] = []
    top_bytes: list[tuple[str, float, float]] = []
    stack: set[str] = set()

    def walk(name: str, mult: float, in_fusion: bool):
        comp = comps.get(name)
        if comp is None or name in stack:
            return
        stack.add(name)
        stats.flops += comp.flops * mult
        if not in_fusion:
            stats.bytes_ += comp.bytes_ * mult
            for ins in comp.instrs:
                if ins.op in _VIEW_OPS or ins.op == "while":
                    continue
                ob = shape_bytes(ins.shape)
                if ins.op == "fusion":           # DUS-root fusions alias
                    mc = re.search(r"calls=%?([\w\.\-]+)", ins.line)
                    callee = comps.get(mc.group(1)) if mc else None
                    if callee is not None and \
                            callee.root_dus_update is not None:
                        ob = 2 * callee.root_dus_update
                if ob * mult > 1 << 28:          # track >256MiB-equivalents
                    top_bytes.append(
                        (f"{ins.op}:{ins.shape[:48]}", ob * mult, mult))
        for k, v in comp.wire.items():
            stats.wire_bytes[k] = stats.wire_bytes.get(k, 0.0) + v * mult
        for k, v in comp.coll_counts.items():
            stats.coll_counts[k] = stats.coll_counts.get(k, 0.0) + v * mult
        for kind, rb, s in comp.coll_ops:
            top_ops.append((kind, rb, s, mult))
        for callee, kind in comp.calls:
            m2 = mult
            f2 = in_fusion or kind == "fusion"
            if kind == "while":
                cond_name = comp.while_cond.get(callee)
                trip = None
                if cond_name and cond_name in comps:
                    trip = _trip_count(comps[cond_name])
                if trip is None:
                    stats.unparsed_while += 1
                    trip = 1
                m2 = mult * trip
            walk(callee, m2, f2)
        stack.discard(name)

    if entry is not None:
        walk(entry.name, 1.0, False)
    top_ops.sort(key=lambda t: -(t[1] * t[3]))
    stats.top_ops = top_ops[:12]
    top_bytes.sort(key=lambda t: -t[1])
    stats.top_bytes_ops = top_bytes[:12]
    return stats


# --------------------------------------------------------------------------
# op -> named scope
# --------------------------------------------------------------------------

UNSCOPED = "unscoped"
_COPIES = {"copy", "copy-start", "copy-done", "bitcast"}
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_METADATA_RE = re.compile(r", metadata=\{[^}]*\}")
_SOURCE_TABLES = {"FileNames", "FunctionNames", "FileLocations", "StackFrames"}


def scope_of(op_name: str, scopes, cast: str) -> str | None:
    """The innermost of ``scopes`` in an ``op_name`` path such as
    ``jit(f)/while/body/closed_call/moe/experts/cast/convert_element_type``,
    or ``cast`` where that segment is in it; None where neither is. The
    path's last segment names the primitive and is not a scope."""
    segs = op_name.split("/")[:-1]
    if cast in segs:
        return cast
    best, end = None, -1
    for sc in scopes:
        parts = sc.split("/")
        for i in range(len(segs) - len(parts) + 1):
            if segs[i:i + len(parts)] == parts and i + len(parts) > end:
                best, end = sc, i + len(parts)
    return best


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name (``fusion.192``, ``convert.82``, ...) -> the named
    scope of ``repro.models.blocks.SCOPES`` it was traced in, ``cast``
    for a weight's conversion, or ``unscoped``; for every instruction of
    an optimized HLO text (``jitted.lower(...).compile().as_text()``).

    An instruction's scope is the innermost known one in its
    ``metadata={op_name=...}``. XLA gives a fusion the op_name of its
    root, so a fusion that merges a weight's cast into the matmul that
    consumes it is charged to that matmul's sublayer, not to ``cast``:
    the cast costs no pass of its own there. Where XLA left an
    instruction without a known scope:

    - a fusion with no op_name (a multi-output fusion, whose root is a
      tuple) takes its root's scope, else the one most of its fused
      instructions carry;
    - a conversion that XLA hoisted out of the layer loop, of a weight
      (an entry parameter of the program's ``params`` argument, or a
      prefetched copy of one), is ``cast``;
    - any other takes the scope of the instructions that feed it, where
      those with a scope agree (a conversion or layout copy XLA split off
      its producer).
    """
    from repro.models.blocks import CAST, SCOPES
    comps = _split_computations(hlo_text)
    ops = {i.name: i for c in comps.values() for i in c.instrs}
    entry = next((c for c in comps.values() if c.is_entry), None)
    entry_ops = {i.name: i for i in entry.instrs} if entry is not None else {}
    memo: dict[str, str | None] = {}

    def weight(name: str) -> bool:
        ins = entry_ops.get(name)
        while ins is not None and ins.op in _COPIES and ins.operands:
            ins = entry_ops.get(ins.operands[0])
        return (ins is not None and ins.op == "parameter"
                and ins.name.startswith("params"))

    def fused(ins):
        m = re.search(r"calls=%?([\w\.\-]+)", ins.line)
        c = comps.get(m.group(1)) if ins.op == "fusion" and m else None
        return c if c is not None and c.instrs else None

    def root(c):
        return next((i for i in c.instrs
                     if i.line.lstrip().startswith("ROOT")), c.instrs[-1])

    def own(ins) -> str | None:
        m = _OP_NAME_RE.search(ins.line)
        return scope_of(m.group(1), SCOPES, CAST) if m else None

    def of(ins) -> str | None:
        if ins.name in memo:
            return memo[ins.name]
        memo[ins.name] = None            # a cycle reads as no scope
        sc = own(ins)
        callee = fused(ins)
        if sc is None and callee is not None:
            sc = of(root(callee))
            if sc is None:
                votes = [v for v in map(of, callee.instrs) if v is not None]
                if votes:
                    sc = max(set(votes), key=votes.count)
        converts = ins.op == "convert" or (
            callee is not None and root(callee).op == "convert")
        if (sc is None and converts and ins.operands
                and all(weight(o) for o in ins.operands)):
            sc = CAST
        if sc is None:
            fed = {of(ops[o]) for o in ins.operands if o in ops} - {None}
            if len(fed) == 1:
                sc = fed.pop()
        memo[ins.name] = sc
        return sc

    return {name: of(ins) or UNSCOPED for name, ins in ops.items()}


def strip_metadata(hlo_text: str) -> str:
    """An HLO text without its source metadata (each instruction's
    ``metadata={...}`` and the module's file, function and stack-frame
    tables): what named scopes change, and nothing else."""
    out, tables = [], False
    for line in hlo_text.splitlines():
        if line in _SOURCE_TABLES:
            tables = True
            continue
        if tables and line.startswith(("%", "ENTRY")):
            tables = False
        if not tables:
            out.append(_METADATA_RE.sub("", line))
    return "\n".join(out)


# --------------------------------------------------------------------------
# roofline
# --------------------------------------------------------------------------

@dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    model_flops_per_device: float

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.wire_bytes_per_device / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — catches remat / redundancy waste."""
        if self.flops_per_device <= 0:
            return 0.0
        return self.model_flops_per_device / self.flops_per_device

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute seconds / max(all terms): what fraction of the
        compute roofline the step achieves if the dominant term is the
        critical path."""
        dom = max(self.compute_s, self.memory_s, self.collective_s)
        if dom <= 0:
            return 0.0
        return (self.model_flops_per_device / PEAK_FLOPS) / dom

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "model_flops_per_device": self.model_flops_per_device,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops(cfg, shape, chips: int) -> float:
    """Analytic MODEL_FLOPS for the step, per device.

    train: 6 * N_active * tokens      (fwd 2N + bwd 4N)
    prefill: 2 * N_active * tokens
    decode: 2 * N_active * batch      (one token per sequence)
    """
    n_active = cfg.param_counts()["active"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n_active * tokens
    else:
        total = 2.0 * n_active * shape.global_batch
    return total / chips
