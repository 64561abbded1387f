"""Structural HLO inspection: zero-copy verification and collective bytes.

Two jobs:

1. **Zero-copy verification** (paper §4: "no process-local explicit copying
   of data whatsoever").  For a lowered factorized all-to-all we count the
   data-movement ops that survive between the component collectives —
   ``copy``/``transpose``/``gather`` — and assert the natural variant emits
   none and that the paper variant's transposes cancel.

2. **Collective byte accounting** for the roofline analysis (§Roofline):
   ``cost_analysis`` does not expose collective traffic, so we parse the
   (optimized or unoptimized) HLO text and sum operand bytes of every
   ``all-gather`` / ``all-reduce`` / ``reduce-scatter`` / ``all-to-all`` /
   ``collective-permute`` / ``*-start`` op.
"""

from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0, "u1": 0.125, "s2": 0.25, "u2": 0.25,
}

# e.g. "bf16[16,128]{1,0}" or "f32[]" or "(f32[2,4], u32[4])"
_SHAPE_RE = re.compile(r"([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")

COLLECTIVE_KINDS = (
    "all-to-all", "all-gather", "all-reduce", "reduce-scatter",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)

# ops that would constitute an explicit local copy between rounds
LOCAL_MOVEMENT_KINDS = ("copy", "transpose", "gather", "dynamic-slice",
                        "concatenate", "reshape")


def shape_bytes(shape_str: str) -> float:
    """Sum byte sizes of every typed shape token inside ``shape_str``."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for tok in dims.split(","):
                n *= int(tok)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class HloOp:
    name: str
    kind: str
    result_bytes: float
    line: str


@dataclass
class HloReport:
    ops: list[HloOp] = field(default_factory=list)

    @property
    def op_counts(self) -> Counter:
        return Counter(op.kind for op in self.ops)

    def collective_ops(self) -> list[HloOp]:
        return [o for o in self.ops
                if any(o.kind.startswith(k) or o.kind == k + "-start"
                       for k in COLLECTIVE_KINDS)]

    def collective_bytes(self) -> float:
        """Bytes *moved by* collectives = sum of their result bytes.

        ``*-done`` ops are skipped (the matching ``*-start`` carries the
        shape); sync ops are counted directly.
        """
        total = 0.0
        for o in self.ops:
            base = o.kind.removesuffix("-start")
            if o.kind.endswith("-done"):
                continue
            if base in COLLECTIVE_KINDS:
                total += o.result_bytes
        return total

    def collective_bytes_by_kind(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for o in self.ops:
            base = o.kind.removesuffix("-start")
            if o.kind.endswith("-done"):
                continue
            if base in COLLECTIVE_KINDS:
                out[base] = out.get(base, 0.0) + o.result_bytes
        return out

    def movement_ops_between_collectives(self) -> list[HloOp]:
        """Local data-movement ops appearing between the first and last
        collective — the paper's zero-copy criterion.  ``reshape`` and
        ``bitcast`` are excluded (metadata-only in XLA); ``copy`` /
        ``transpose`` / ``gather`` / ``concatenate`` count."""
        coll_idx = [i for i, o in enumerate(self.ops)
                    if o.kind.removesuffix("-start").removesuffix("-done")
                    in COLLECTIVE_KINDS]
        if len(coll_idx) < 2:
            return []
        lo, hi = coll_idx[0], coll_idx[-1]
        bad_kinds = ("copy", "transpose", "gather", "concatenate",
                     "dynamic-slice")
        return [o for o in self.ops[lo + 1:hi]
                if o.kind in bad_kinds and o.result_bytes > 0]


@dataclass
class InterleaveReport:
    """Program-order interleaving of collectives and compute stages.

    Built for verifying the overlap engine (``core.overlap``): a program
    that pipelines per-dimension rounds against per-chunk compute emits
    collectives *between* the compute stages of consecutive chunks, while
    the strictly sequential communicate->compute->communicate program has
    exactly one collective run before and one after its compute block.

    ``events`` is the lowered program filtered to collective / compute
    ops, in emission order.
    """
    events: list[tuple[str, str]] = field(default_factory=list)  # (cls, op)

    @property
    def runs(self) -> list[tuple[str, int]]:
        """Run-length encoding of the event classes."""
        out: list[tuple[str, int]] = []
        for cls, _ in self.events:
            if out and out[-1][0] == cls:
                out[-1] = (cls, out[-1][1] + 1)
            else:
                out.append((cls, 1))
        return out

    @property
    def collective_runs(self) -> int:
        """Maximal collective runs separated by compute.  Sequential
        comm->compute->comm programs have <= 2; a pipelined program has
        one extra run per interleaved chunk boundary."""
        return sum(1 for cls, _ in self.runs if cls == "collective")

    @property
    def interleaved_collectives(self) -> int:
        """Collectives with a compute stage both before AND after them in
        program order — the rounds the schedule can hide behind compute."""
        classes = [cls for cls, _ in self.events]
        try:
            first = classes.index("compute")
            last = len(classes) - 1 - classes[::-1].index("compute")
        except ValueError:
            return 0
        return sum(1 for cls in classes[first + 1:last]
                   if cls == "collective")


def interleave_report(text: str,
                      compute_kinds: tuple[str, ...] = ("dot",),
                      collective_kind: str | None = "all-to-all") \
        -> InterleaveReport:
    """Classify the program's ops into collectives vs compute, in order.

    Use the *unoptimized* HLO (``lowered.as_text(dialect="hlo")``): there
    program order is trace order, so the report verifies exactly what the
    overlap engine emitted.  ``collective_kind`` restricts to one
    collective family (default ``all-to-all`` — the per-dimension rounds);
    pass ``None`` to count every collective.
    """
    rep = InterleaveReport()
    for op in parse_hlo(text).ops:
        base = op.kind.removesuffix("-start")
        if op.kind.endswith("-done"):
            continue
        if base in COLLECTIVE_KINDS and (collective_kind is None
                                         or base == collective_kind):
            rep.events.append(("collective", op.name))
        elif op.kind in compute_kinds:
            rep.events.append(("compute", op.name))
    return rep


_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9_]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"([a-z][a-z0-9\-]*)\(")


def parse_hlo(text: str) -> HloReport:
    report = HloReport()
    for line in text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        name, shape_str, kind = m.groups()
        report.ops.append(HloOp(name=name, kind=kind,
                                result_bytes=shape_bytes(shape_str),
                                line=line.strip()))
    return report


_METADATA_RE = re.compile(r",?\s*metadata=\{[^}]*\}")
_NAME_RE = re.compile(r"%[\w.\-]+")


def program_structure(text: str) -> str:
    """Compiled HLO text reduced to the program itself: without the debug
    tables (``FileNames`` .. ``StackFrames``), without any
    ``metadata={...}``, and with every ``%name`` renumbered in order of
    first use.  Instruction names follow the lowered ops' locations, so
    two programs that differ only in their ``jax.named_scope``s compare
    equal here."""
    lines, debug = [], False
    for line in text.splitlines():
        if line == "FileNames":
            debug = True
        elif debug and (line.startswith("%") or line.startswith("ENTRY")):
            debug = False
        if not debug:
            lines.append(_METADATA_RE.sub("", line))
    names: dict[str, str] = {}
    return _NAME_RE.sub(
        lambda m: names.setdefault(m.group(0), f"%i{len(names)}"),
        "\n".join(lines))


def collective_bytes_of(lowered_or_text) -> float:
    text = lowered_or_text if isinstance(lowered_or_text, str) \
        else lowered_or_text.as_text()
    return parse_hlo(text).collective_bytes()


# ---------------------------------------------------------------------------
# Loop-aware whole-module analysis.
#
# XLA's HloCostAnalysis (and a naive text scan) counts ``while`` bodies
# ONCE, but a scan-over-layers body executes trip-count times — for a
# 64-layer model that understates FLOPs/bytes/collective traffic by ~64x.
# We parse the module into computations, recover while trip counts from
# the condition computation's loop-bound constant, propagate execution
# multipliers through the call graph (while/call/fusion/to_apply), and
# accumulate dot FLOPs, a read+write byte proxy, and collective bytes
# weighted by multiplier.
# ---------------------------------------------------------------------------

_COMP_HDR_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->\s*.+\{\s*$")
_PARAM_RE = re.compile(r"([\w.\-]+)\s*:\s*([a-z0-9_]+\[[0-9,]*\])")
_CALLSITE_RE = re.compile(
    r"(?:condition|body|to_apply|calls|branch_computations)="
    r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_CONST_INT_RE = re.compile(r"constant\((\d+)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERAND_RE = re.compile(r"\(\s*((?:%[\w.\-]+|\w[\w.\-]*)"
                         r"(?:\s*,\s*(?:%[\w.\-]+|\w[\w.\-]*))*)\s*\)")
_DIMS_RE = re.compile(r"\[([0-9,]*)\]")


def _shape_dims(shape_str: str) -> list[int]:
    m = _DIMS_RE.search(shape_str)
    if not m or not m.group(1):
        return []
    return [int(t) for t in m.group(1).split(",")]


@dataclass
class _Comp:
    name: str
    params: dict          # param name -> shape str
    ops: list             # (name, shape_str, kind, line)
    callees: list         # (kind, [names])

    def symbol(self, ref: str) -> str | None:
        ref = ref.lstrip("%")
        if ref in self.params:
            return self.params[ref]
        for (n, shape, _, _) in self.ops:
            if n == ref:
                return shape
        return None


def _parse_computations(text: str) -> dict[str, _Comp]:
    comps: dict[str, _Comp] = {}
    cur: _Comp | None = None
    for line in text.splitlines():
        hdr = _COMP_HDR_RE.match(line)
        if hdr:
            params = dict(_PARAM_RE.findall(hdr.group(3)))
            cur = _Comp(hdr.group(2), params, [], [])
            comps[cur.name] = cur
            if hdr.group(1):
                comps["__entry__"] = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _OP_RE.match(line)
        if m:
            name, shape_str, kind = m.groups()
            cur.ops.append((name, shape_str, kind, line.strip()))
        for cm in _CALLSITE_RE.finditer(line):
            names = [n.strip().lstrip("%")
                     for n in cm.group(1).split(",")]
            key = line.split("=")[0] if "=" in line else ""
            cur.callees.append((("while" if " while(" in line else "call"),
                                names, key))
    return comps


def _while_trip(comps, cond_name: str) -> int:
    cond = comps.get(cond_name)
    if cond is None:
        return 1
    best = 1
    for (_, _, _, line) in cond.ops:
        for c in _CONST_INT_RE.findall(line):
            best = max(best, int(c))
    return best


def _multipliers(comps: dict[str, _Comp]) -> dict[str, float]:
    entry = comps.get("__entry__")
    mult: dict[str, float] = {}
    if entry is None:
        return {name: 1.0 for name in comps}

    def visit(comp: _Comp, m: float, depth=0):
        if depth > 50:
            return
        mult[comp.name] = mult.get(comp.name, 0.0) + m
        handled = set()
        for (_, _, _, line) in comp.ops:
            if " while(" in line:
                cm = re.search(r"condition=%?([\w.\-]+)", line)
                bm = re.search(r"body=%?([\w.\-]+)", line)
                if cm and bm:
                    trip = _while_trip(comps, cm.group(1))
                    if bm.group(1) in comps:
                        visit(comps[bm.group(1)], m * trip, depth + 1)
                        handled.add(bm.group(1))
                    handled.add(cm.group(1))
            else:
                for cs in _CALLSITE_RE.finditer(line):
                    for n in cs.group(1).split(","):
                        n = n.strip().lstrip("%")
                        if n in comps and n not in handled:
                            visit(comps[n], m, depth + 1)
                            handled.add(n)
    visit(entry, 1.0)
    return mult


def _comp_dot_flops(comp: _Comp) -> float:
    total = 0.0
    for (name, shape_str, kind, line) in comp.ops:
        if kind != "dot":
            continue
        result_elems = 1
        for d in _shape_dims(shape_str):
            result_elems *= d
        cm = _CONTRACT_RE.search(line)
        contract = [int(t) for t in cm.group(1).split(",")] \
            if cm and cm.group(1) else []
        # first operand ref after "dot(" — some XLA versions print typed
        # operands, e.g. ``dot(f32[8,64]{1,0} %Arg_0.1, ...)``, so prefer
        # %-prefixed refs and fall back to the first bare token
        args_m = re.search(r"dot\(([^)]*)", line)
        refs = re.findall(r"%([\w.\-]+)", args_m.group(1)) if args_m else []
        if not refs:
            bare = re.search(r"dot\(\s*([\w.\-]+)", line)
            refs = [bare.group(1)] if bare else []
        k = 1
        if refs:
            lhs_shape = comp.symbol(refs[0])
            if lhs_shape:
                dims = _shape_dims(lhs_shape)
                for c in contract:
                    if c < len(dims):
                        k *= dims[c]
        total += 2.0 * result_elems * k
    return total


# ops that move no HBM bytes themselves (metadata / layout / tuple plumbing)
_FREE_KINDS = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "after-all", "iota", "partition-id",
               "replica-id"}


_SLICE_KINDS = ("dynamic-slice", "slice", "gather")


def _op_operand_refs(line: str, kind: str) -> list[str]:
    after = line.split(f"{kind}(", 1)
    if len(after) != 2:
        return []
    args = after[1].split(")", 1)[0]
    return re.findall(r"%([\w.\-]+)", args)


def _fusion_param_bytes(body: _Comp, operand_shapes: list[str | None]) \
        -> float:
    """Effective read bytes of a fusion: a parameter consumed ONLY by
    slice-like ops costs the slice results, not the whole buffer (the
    stacked-parameter scan pattern); a parameter consumed only as the
    TARGET of dynamic-update-slice costs the update region (in-place DUS
    — the residual-stacking scan pattern); otherwise the full operand."""
    param_names = list(body.params)
    total = 0.0
    for i, pname in enumerate(param_names):
        full = shape_bytes(body.params[pname])
        uses = []
        for (_, shape_str, kind, line) in body.ops:
            if kind == "parameter":
                continue
            rhs = line.split("=", 1)[-1]
            if re.search(rf"%{re.escape(pname)}\b", rhs):
                refs = _op_operand_refs(line, kind)
                total_refs = [r for r in refs if r == pname]
                is_dus_target = (kind == "dynamic-update-slice" and refs
                                 and refs[0] == pname)
                update_b = 0.0
                if is_dus_target and len(refs) >= 2:
                    s = body.symbol(refs[1])
                    update_b = shape_bytes(s) if s else 0.0
                uses.append((kind, shape_str, is_dus_target, update_b))
        if not uses:
            continue
        if all(k in _SLICE_KINDS for k, _, _, _ in uses):
            total += sum(shape_bytes(s) for _, s, _, _ in uses)
        elif all(dus for _, _, dus, _ in uses):
            total += sum(2 * ub for _, _, _, ub in uses)
        else:
            total += full
    return total


def _comp_bytes(comp: _Comp, comps: dict | None = None) -> float:
    """Read+write byte proxy at fusion granularity: every *top-level* op
    writes its result once and reads each operand once.  Fusion-internal
    intermediates (registers/VMEM) are excluded by the caller skipping
    fusion-body computations; the ``fusion`` op at its call site accounts
    for the body's HBM traffic (effective operands in, result out).

    Slicing ops (top-level or as sole consumers inside a fusion body)
    charge the slice, not the sliced buffer; dynamic-update-slice charges
    ~2x the update region (XLA performs it in place inside loops)."""
    total = 0.0
    for (name, shape_str, kind, line) in comp.ops:
        if kind in _FREE_KINDS:
            continue
        result_b = shape_bytes(shape_str)
        if kind in _SLICE_KINDS:
            total += 2 * result_b          # read slice + write result
            continue
        if kind == "dynamic-update-slice":
            refs = _op_operand_refs(line, kind)
            update_b = 0.0
            if len(refs) >= 2:
                s = comp.symbol(refs[1])
                if s:
                    update_b = shape_bytes(s)
            total += 2 * update_b if update_b else result_b
            continue
        if kind == "fusion" and comps is not None:
            m = re.search(r"calls=%?([\w.\-]+)", line)
            body = comps.get(m.group(1)) if m else None
            if body is not None:
                pbytes = _fusion_param_bytes(
                    body, [comp.symbol(r)
                           for r in _op_operand_refs(line, kind)])
                # in-place DUS fusion: the result IS the aliased buffer;
                # the 2x-update charge in pbytes already covers the write.
                inplace = any(
                    k == "dynamic-update-slice"
                    and (_op_operand_refs(ln, k) or [None])[0] in body.params
                    for (_, _, k, ln) in body.ops)
                total += pbytes if inplace else result_b + pbytes
                continue
        total += result_b
        for ref in _op_operand_refs(line, kind):
            s = comp.symbol(ref)
            if s:
                total += shape_bytes(s)
    return total


def _comp_collective_bytes(comp: _Comp) -> dict[str, float]:
    out: dict[str, float] = {}
    for (name, shape_str, kind, line) in comp.ops:
        base = kind.removesuffix("-start")
        if kind.endswith("-done"):
            continue
        if base in COLLECTIVE_KINDS:
            out[base] = out.get(base, 0.0) + shape_bytes(shape_str)
    return out


_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def collective_group_stride(line: str) -> tuple[int, int] | None:
    """(group_size, member_stride) of a collective's first replica group.

    Supports both explicit ``replica_groups={{0,16,32,...},...}`` and
    iota-tile ``replica_groups=[n,m]<=[dims]T(perm)`` forms.  The stride
    identifies WHICH mesh axis the collective spans (stride 1 = innermost
    mesh axis, etc.), which is how we attribute collective bytes to ICI
    vs DCN links."""
    out = collective_group_geometry(line)
    return None if out is None else (out[0], out[1])


def collective_group_geometry(line: str) -> tuple[int, int, int] | None:
    """(group_size, member_stride, span): span = max-min member id of a
    group — a group whose span reaches across the pod-axis stride crosses
    DCN even if its *member* stride is small (direct all-to-all over a
    multi-axis product has mixed strides)."""
    m = _GROUPS_RE.search(line)
    if m:
        members = [int(t) for t in m.group(1).split(",")]
        if len(members) < 2:
            return (len(members), 0, 0)
        return (len(members), members[1] - members[0],
                max(members) - min(members))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        ngroups, gsize = int(m.group(1)), int(m.group(2))
        dims = [int(t) for t in m.group(3).split(",")]
        perm = [int(t) for t in m.group(4).split(",")] if m.group(4) \
            else list(range(len(dims)))
        strides = []
        acc = 1
        for d in reversed(dims):
            strides.append(acc)
            acc *= d
        strides = list(reversed(strides))     # stride per original dim
        covered = 1
        member_stride = 1
        span = 0
        first = True
        for p in reversed(perm):
            if covered >= gsize:
                break
            take = min(dims[p], max(1, gsize // covered))
            if first:
                member_stride = strides[p]
                first = False
            span += strides[p] * (take - 1)
            covered *= take
        return (gsize, member_stride, span)
    return None


def collective_bytes_by_stride(text: str, loop_aware: bool = True,
                               use_span: bool = False) \
        -> dict[tuple[str, int], float]:
    """{(kind, member_stride-or-span): bytes} with loop multipliers
    applied.  ``use_span=True`` keys by the group's id span instead —
    the right classifier for ICI-vs-DCN attribution (a direct all-to-all
    over (data, pod) has member stride 16 but span >= 256)."""
    comps = _parse_computations(text)
    mult = _multipliers(comps) if loop_aware else \
        {n: 1.0 for n in comps}
    out: dict[tuple[str, int], float] = {}
    for name, comp in comps.items():
        if name == "__entry__":
            continue
        m = mult.get(name, 0.0)
        if m == 0.0:
            continue
        for (_, shape_str, kind, line) in comp.ops:
            base = kind.removesuffix("-start")
            if kind.endswith("-done") or base not in COLLECTIVE_KINDS:
                continue
            gg = collective_group_geometry(line)
            key_val = -1 if gg is None else (gg[2] if use_span else gg[1])
            key = (base, key_val)
            out[key] = out.get(key, 0.0) + m * shape_bytes(shape_str)
    return out


def _inlined_computations(comps: dict[str, _Comp]) -> set[str]:
    """Computations referenced via calls=/to_apply= (fusion bodies,
    reducers, comparators): their ops run in registers/VMEM, not HBM."""
    out: set[str] = set()
    pat = re.compile(r"(?:calls|to_apply)=\{?%?([\w.\-]+"
                     r"(?:,\s*%?[\w.\-]+)*)\}?")
    for comp in comps.values():
        for (_, _, _, line) in comp.ops:
            for m in pat.finditer(line):
                for n in m.group(1).split(","):
                    out.add(n.strip().lstrip("%"))
    return out


def loop_aware_analysis(text: str) -> dict:
    """Whole-module flops / byte-proxy / collective bytes, with while
    bodies weighted by their trip counts.  FLOPs count dots everywhere
    (incl. inside fusions); bytes count only at fusion granularity."""
    comps = _parse_computations(text)
    mult = _multipliers(comps)
    inlined = _inlined_computations(comps)
    flops = 0.0
    bytes_proxy = 0.0
    coll: dict[str, float] = {}
    for name, comp in comps.items():
        if name == "__entry__":
            continue
        m = mult.get(name, 0.0)
        if m == 0.0:
            continue
        flops += m * _comp_dot_flops(comp)
        if name not in inlined:
            bytes_proxy += m * _comp_bytes(comp, comps)
        for k, v in _comp_collective_bytes(comp).items():
            coll[k] = coll.get(k, 0.0) + m * v
    return {
        "flops": flops,
        "bytes_proxy": bytes_proxy,
        "collective_bytes": sum(coll.values()),
        "collective_bytes_by_kind": coll,
        "n_computations": len(comps) - 1,
    }
