"""Trip-count-aware roofline extraction from optimized (partitioned) HLO.

``compiled.cost_analysis()`` visits every while-loop body exactly ONCE, so
for scan-heavy programs (layers x local-steps x grad-accum x KV blocks) it
underestimates FLOPs/bytes by the product of trip counts. This module
re-derives the roofline quantities by walking the HLO text:

* computations are parsed into instruction lists;
* every call site (while body/cond, fusion, call, conditional) propagates a
  multiplier; while trip counts are read off the loop condition's
  ``compare(%iv, %constant)`` (jax scans always lower to 0..N counters);
* FLOPs: dots contribute 2 * prod(result) * prod(contracting dims);
  elementwise arithmetic contributes prod(result); reduces contribute
  prod(operand);
* HBM bytes: operand+result bytes of every *materializing* instruction
  (fusion boundaries, dots, collectives, copies, slices); instructions
  inside fused computations count zero (they live in registers/VMEM) --
  the same memory model XLA's own cost analysis uses;
* collective bytes: operand bytes of all-reduce / all-gather /
  reduce-scatter / all-to-all / collective-permute, times the enclosing
  multiplier, bucketed per op type; the largest contributors are kept for
  bottleneck attribution (which aggregation/timescale is hot).

The result is the per-device cost of one full step (one MTGC global round
for train; one prefill/decode for serve).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DT_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "token": 0, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z][0-9a-z]*)\[([0-9,]*)\]")
# dtype-shaped tokens missing from _DT_BYTES (new narrow float formats
# etc.) fall back to 4 bytes/elem rather than silently costing zero.
_DT_FALLBACK_RE = re.compile(r"^(?:[fsuc]|bf)[0-9]")
_DT_FALLBACK_BYTES = 4
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{$")
_NAME_RE = re.compile(r"%([\w.\-]+)")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_FRAME_RE = re.compile(r"stack_frame_id=(\d+)")

# semantic buckets from jax.named_scope tags planted in the model code and
# the MTGC engine (core/engine.py, core/packer.py); the chip benchmark's
# trace reader attributes device time to the same names
BUCKETS = ("attn", "moe", "mlp", "rwkv", "ssm", "xent", "embed",
           "group_agg", "global_agg", "client_step", "local_update",
           "state_repack")

# (file substring, function name) -> bucket; resolved through the HLO
# stack-frame tables, which survive jvp/transpose/remat (named scopes don't).
_FUNC_BUCKETS = [
    ("layers.py", "blocked_attention", "attn"),
    ("layers.py", "naive_attention", "attn"),
    ("layers.py", "attention_block", "attn"),
    ("layers.py", "apply_rope", "attn"),
    ("layers.py", "swiglu", "mlp"),
    ("layers.py", "embed", "embed"),
    ("layers.py", "unembed", "xent"),
    ("moe.py", "", "moe"),
    ("rwkv6.py", "", "rwkv"),
    ("ssm.py", "", "ssm"),
    ("transformer.py", "chunked_xent", "xent"),
    ("transformer.py", "_rwkv_cmix", "rwkv"),
    ("train.py", "group_round", "group_agg"),
    ("train.py", "round_fn", "global_agg"),
]


def _bucket(op_name: str) -> str | None:
    for b in BUCKETS:
        if f"/{b}/" in op_name or op_name.endswith(f"/{b}"):
            return b
    return None


def parse_stack_tables(hlo: str) -> dict[int, str]:
    """stack_frame_id -> bucket, via FileNames/FunctionNames/FileLocations/
    StackFrames header tables (walking parent frames until a match)."""
    head = hlo.split("ENTRY", 1)[0]

    def table(name, rx):
        out = {}
        sec = re.search(rf"^{name}\n((?:\d+ .*\n)+)", head, re.M)
        if not sec:
            return out
        for line in sec.group(1).splitlines():
            m = re.match(rx, line)
            if m:
                out[int(m.group(1))] = m.group(2)
        return out

    files = table("FileNames", r'(\d+) "(.*)"')
    funcs = table("FunctionNames", r'(\d+) "(.*)"')
    locs = {}
    sec = re.search(r"^FileLocations\n((?:\d+ \{.*\}\n)+)", head, re.M)
    if sec:
        for line in sec.group(1).splitlines():
            m = re.match(r"(\d+) \{file_name_id=(\d+) function_name_id=(\d+)", line)
            if m:
                locs[int(m.group(1))] = (files.get(int(m.group(2)), ""),
                                         funcs.get(int(m.group(3)), ""))
    frames = {}
    sec = re.search(r"^StackFrames\n((?:\d+ \{.*\}\n)+)", head, re.M)
    if sec:
        for line in sec.group(1).splitlines():
            m = re.match(r"(\d+) \{file_location_id=(\d+)(?: parent_frame_id=(\d+))?", line)
            if m:
                frames[int(m.group(1))] = (int(m.group(2)),
                                           int(m.group(3)) if m.group(3) else 0)

    def loc_bucket(loc):
        fn, fun = loc
        for fsub, fname, b in _FUNC_BUCKETS:
            if fsub in fn and (not fname or fun == fname):
                return b
        return None

    out: dict[int, str] = {}
    for fid in frames:
        cur = fid
        b = None
        for _ in range(30):
            if cur not in frames:
                break
            loc_id, parent = frames[cur]
            b = loc_bucket(locs.get(loc_id, ("", "")))
            if b or not parent or parent == cur:
                break
            cur = parent
        if b:
            out[fid] = b
    return out

ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "rsqrt", "sqrt", "cbrt", "negate", "abs", "sign", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "logistic", "atan2", "remainder",
    "and", "or", "xor", "not", "select", "clamp", "compare", "sine", "cosine",
    "erf", "expm1",
}
ZERO_COST = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "rng-bit-generator",
    "rng-get-and-update-state", "opt-barrier", "domain",
}
# ops that do not touch HBM themselves (control / pure aliasing)
NO_BYTES = ZERO_COST | {"while", "conditional", "call"}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    result_bytes: int
    result_elems: int
    operand_bytes: int
    operand_elems: int
    flops: float
    attrs: str


@dataclasses.dataclass
class HloCosts:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    per_collective: dict = dataclasses.field(default_factory=dict)
    top_collectives: list = dataclasses.field(default_factory=list)
    top_flops: list = dataclasses.field(default_factory=list)
    top_bytes: list = dataclasses.field(default_factory=list)
    by_scope: dict = dataclasses.field(default_factory=dict)  # scope -> {flops, bytes, collective}
    notes: list = dataclasses.field(default_factory=list)


def xla_cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty when XLA gives none)."""
    return compiled.cost_analysis() or {}


def _shape_of(text: str):
    """(bytes, elems, dims-of-first-shape) of a result-type string."""
    b = e = 0
    first_dims = None
    for dt, dims in _SHAPE_RE.findall(text):
        nbytes = _DT_BYTES.get(dt)
        if nbytes is None:
            if not _DT_FALLBACK_RE.match(dt):
                continue  # not a dtype token (identifier-ish match)
            nbytes = _DT_FALLBACK_BYTES
        dd = [int(d) for d in dims.split(",") if d]
        n = 1
        for d in dd:
            n *= d
        if first_dims is None:
            first_dims = dd
        e += n
        b += n * nbytes
    return b, e, (first_dims or [])


def _split_result(rhs: str):
    """rhs = '<result type> <opcode>(<operands>), attrs...' -> parts."""
    if rhs.startswith("("):  # tuple result: find matching paren
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        result, rest = rhs[: i + 1], rhs[i + 1:].strip()
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return rhs, None, ("", "")
        result, rest = rhs[:sp], rhs[sp + 1:]
    m = re.match(r"([\w\-]+)\(", rest)
    if not m:
        return result, None, ("", "")
    opcode = m.group(1)
    depth = 0
    start = m.end() - 1
    i = start
    for i in range(start, len(rest)):
        depth += rest[i] == "("
        depth -= rest[i] == ")"
        if depth == 0:
            break
    operands = rest[start + 1: i]
    attrs = rest[i + 1:]
    return result, opcode, (operands, attrs)


def parse_computations(hlo: str) -> dict[str, list[Instr]]:
    """Parse every computation. Operand sizes resolve through a per-module
    symbol table (HLO prints operands as bare %names); constants feeding
    while-conditions are tracked for trip counts via the same table."""
    comps: dict[str, list[Instr]] = {}
    parse_computations._frames = parse_stack_tables(hlo)
    # symbol table: name -> (bytes, elems, dims, const_value|None)
    sym: dict[str, tuple] = {}
    cur: list[Instr] | None = None
    for raw in hlo.splitlines():
        s = raw.strip()
        if cur is None:
            m = _COMP_RE.match(s)
            if m:
                comps[m.group(1)] = cur = []
            continue
        if s == "}" or s.startswith("} //"):
            cur = None
            continue
        m = _INSTR_RE.match(s)
        if not m:
            continue
        name, rhs = m.groups()
        result, opcode, (operands, attrs) = _split_result(rhs)
        if opcode is None:
            continue
        rb, re_, rdims = _shape_of(result)
        cval = None
        if opcode == "constant":
            cm = re.match(r"\s*(\d+)\s*$", operands)
            if cm and result.startswith(("s", "u")):
                cval = int(cm.group(1))
        sym[name] = (rb, re_, rdims, cval)
        # operand sizes: inline shapes if printed, else look up names
        ob, oe, _ = _shape_of(operands)
        op_names = _NAME_RE.findall(operands)
        if ob == 0 and op_names:
            for nm in op_names:
                ent = sym.get(nm)
                if ent:
                    ob += ent[0]
                    oe += ent[1]
        lhs_dims = None
        if op_names and op_names[0] in sym:
            lhs_dims = sym[op_names[0]][2]
        flops = _instr_flops(opcode, operands, attrs, re_, oe, lhs_dims)
        ins = Instr(name, opcode, rb, re_, ob, oe, flops, attrs)
        ins.operand_names = op_names
        mm = _OPNAME_RE.search(attrs)
        ins.scope = _bucket(mm.group(1)) if mm else None
        if ins.scope is None:
            fm = _FRAME_RE.search(attrs)
            if fm:
                ins.scope = parse_computations._frames.get(int(fm.group(1)))
        cur.append(ins)
    parse_computations._sym = sym  # stashed for trip-count lookup
    return comps


def _instr_flops(opcode, operands, attrs, result_elems, operand_elems, lhs_dims):
    if opcode == "dot":
        m = re.search(r"lhs_contracting_dims=\{([^}]*)\}", operands + " " + attrs)
        csize = 1
        if m and lhs_dims:
            for d in (int(x) for x in m.group(1).split(",") if x):
                if d < len(lhs_dims):
                    csize *= lhs_dims[d]
        return 2.0 * result_elems * csize
    if opcode == "convolution":
        return 2.0 * result_elems
    if opcode in ("reduce", "reduce-window"):
        return float(operand_elems)
    if opcode in ELEMENTWISE:
        return float(result_elems)
    return 0.0


def _while_trip(cond_name: str, comps, sym) -> int:
    """Trip count: the integer constant feeding the condition's compare."""
    best = 0
    for ins in comps.get(cond_name, ()):
        names = list(getattr(ins, "operand_names", ()))
        if ins.opcode == "compare" or "compare" in ins.attrs or ins.opcode == "fusion":
            for nm in names:
                ent = sym.get(nm)
                if ent and ent[3] is not None:
                    best = max(best, ent[3])
    if best == 0:  # fall back: any integer constant defined in the condition
        for ins in comps.get(cond_name, ()):
            ent = sym.get(ins.name)
            if ent and ent[3] is not None:
                best = max(best, ent[3])
    return max(best, 1)


def analyze(hlo: str, entry: str | None = None) -> HloCosts:
    comps = parse_computations(hlo)
    sym = parse_computations._sym
    if not comps:
        return HloCosts(notes=["no computations parsed"])

    if entry is None:
        # ENTRY computation: the one never called by others
        called = set()
        for instrs in comps.values():
            for ins in instrs:
                for rx in (_CALLS_RE, _TO_APPLY_RE, _COND_RE, _BODY_RE):
                    called.update(rx.findall(ins.attrs))
                bm = _BRANCHES_RE.search(ins.attrs)
                if bm:
                    called.update(x.strip().lstrip("%") for x in bm.group(1).split(","))
        entries = [c for c in comps if c not in called]
        # dead comparators etc. can also be uncalled: prefer the real entry
        mains = [c for c in entries if "main" in c]
        if mains:
            entry = mains[0]
        elif entries:
            entry = max(entries, key=lambda c: len(comps[c]))
        else:
            entry = next(iter(comps))

    # fusion bodies: instructions there cost flops but zero HBM bytes
    fusion_bodies = set()
    for instrs in comps.values():
        for ins in instrs:
            if ins.opcode == "fusion":
                fusion_bodies.update(_CALLS_RE.findall(ins.attrs))

    mult: dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    order = [entry]
    seen = {entry}
    # BFS in call order; HLO call graphs are acyclic
    i = 0
    while i < len(order):
        c = order[i]
        i += 1
        for ins in comps[c]:
            targets: list[tuple[str, float]] = []
            if ins.opcode == "while":
                body = _BODY_RE.search(ins.attrs)
                cond = _COND_RE.search(ins.attrs)
                trip = _while_trip(cond.group(1), comps, sym) if cond else 1
                if body:
                    targets.append((body.group(1), float(trip)))
                if cond:
                    targets.append((cond.group(1), float(trip)))
            elif ins.opcode == "fusion":
                for t in _CALLS_RE.findall(ins.attrs):
                    targets.append((t, 1.0))
            elif ins.opcode in ("call", "reduce", "reduce-window", "sort",
                                 "scatter", "select-and-scatter", "map",
                                 "all-reduce", "reduce-scatter"):
                for t in _TO_APPLY_RE.findall(ins.attrs):
                    targets.append((t, 0.0))  # tiny scalar lambdas: ignore
            elif ins.opcode == "conditional":
                bm = _BRANCHES_RE.search(ins.attrs)
                if bm:
                    for t in bm.group(1).split(","):
                        targets.append((t.strip().lstrip("%"), 1.0))
            for t, k in targets:
                if t not in comps:
                    continue
                mult[t] += mult[c] * k
                if t not in seen:
                    seen.add(t)
                    order.append(t)

    # fusions whose root is a dynamic-update-slice update their big operand
    # in place: HBM traffic is the update slice (r/w), not the whole buffer.
    dus_root = set()
    for cname, instrs in comps.items():
        if any(i.opcode == "dynamic-update-slice" for i in instrs):
            dus_root.add(cname)

    out = HloCosts(per_collective={c: 0.0 for c in COLLECTIVES})
    flop_items: list[tuple[float, str]] = []
    coll_items: list[tuple[float, str]] = []
    byte_items: list[tuple[float, str]] = []
    for c, instrs in comps.items():
        m = mult.get(c, 0.0)
        if m == 0.0:
            continue
        in_fusion = c in fusion_bodies
        for ins in instrs:
            sc = getattr(ins, "scope", None) or "other"
            bucket = out.by_scope.setdefault(
                sc, {"flops": 0.0, "bytes": 0.0, "collective": 0.0})
            if ins.flops:
                out.flops += m * ins.flops
                bucket["flops"] += m * ins.flops
                if ins.opcode == "dot":
                    flop_items.append((m * ins.flops, f"{c}/{ins.name}"))
            opc = ins.opcode.replace("-start", "")
            if opc in COLLECTIVES:
                b = ins.operand_bytes or ins.result_bytes
                out.collective_bytes += m * b
                out.per_collective[opc] += m * b
                bucket["collective"] += m * b
                coll_items.append((m * b, f"{c}/{ins.name} {opc} x{m:g}"))
            if not in_fusion and ins.opcode not in NO_BYTES and not ins.opcode.endswith("-done"):
                rw = ins.operand_bytes + ins.result_bytes
                is_dus = ins.opcode == "dynamic-update-slice" or (
                    ins.opcode == "fusion"
                    and any(t in dus_root for t in _CALLS_RE.findall(ins.attrs))
                )
                if is_dus and ins.operand_bytes >= ins.result_bytes:
                    # in-place: subtract the aliased whole-buffer read+write
                    rw = max(rw - 2 * ins.result_bytes, 2 * (
                        ins.operand_bytes - ins.result_bytes))
                elif ins.opcode == "dynamic-slice":
                    rw = 2 * ins.result_bytes  # reads only the slice
                b = m * rw
                out.bytes += b
                bucket["bytes"] += b
                byte_items.append((b, f"{c}/{ins.name} {ins.opcode} x{m:g}"))
    out.top_flops = sorted(flop_items, reverse=True)[:8]
    out.top_collectives = sorted(coll_items, reverse=True)[:12]
    out.top_bytes = sorted(byte_items, reverse=True)[:16]
    return out
