"""Optimized-HLO text parser: per-instruction cost attribution.

XLA's ``compiled.cost_analysis()`` reports executable TOTALS only.  To
say *which* HLOs eat them, this module parses ``compiled.as_text()``
(the scheduled post-optimization module) and attributes an analytic
FLOP/byte estimate to every instruction, bucketed into five categories:

==================  ==================================================
category            opcodes
==================  ==================================================
conv_dot            convolution, dot, matmul/gemm/conv custom-calls --
                    the MXU work
collective          all-reduce/-gather/-to-all, reduce-scatter,
                    collective-permute, send/recv -- the ICI work
transpose_layout    transpose, copy, bitcast, reshape, pad, slice,
                    concatenate, gather, broadcast -- pure data
                    movement (the NHWC/NCHW tax lives here)
elementwise_fusion  arithmetic/compare/select/reduce/rng -- what XLA
                    fuses around the big ops
other               scatter, sort, fft, custom-calls, anything unknown
==================  ==================================================

Attribution rules:

- Fused computations' *instructions* carry the FLOPs (fusion bodies
  never touch HBM); the fusion *call site* carries the bytes (its
  operands + output are the real memory traffic), attributed to the
  body's dominant category.
- ``while`` bodies are counted once (per-iteration cost; trip counts
  are not in the HLO text) -- scan-based programs report their loop
  body, matching ``TrainStep.run_steps``'s documented convention.
- ``to_apply`` regions of reduce/scatter/sort are per-element lambdas
  and are not walked (the caller instruction already carries the cost).

The estimates are RECONCILED against the executable totals in
``cost.py`` so per-category numbers sum exactly to what XLA measured;
the raw analytic estimates are preserved alongside.
"""
from __future__ import annotations

import re

CATEGORIES = ("conv_dot", "collective", "transpose_layout",
              "elementwise_fusion", "other")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,<=\s]*)\]")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\)\s*->")
_METADATA_RE = re.compile(r",?\s*metadata=\{[^}]*\}")
_STRING_RE = re.compile(r'"[^"]*"')
_OPNAME_RE = re.compile(r'op_name="([^"]+)"')
_CALLS_RE = re.compile(r"\bcalls=%([\w.\-]+)")
_BODY_RE = re.compile(r"\bbody=%([\w.\-]+)")
_COND_RE = re.compile(r"\bcondition=%([\w.\-]+)")
_BRANCHES_RE = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_TRUE_RE = re.compile(r"\btrue_computation=%([\w.\-]+)")
_FALSE_RE = re.compile(r"\bfalse_computation=%([\w.\-]+)")
_TOAPPLY_RE = re.compile(r"\bto_apply=%([\w.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,\s]*)\}")
_WINDOW_SIZE_RE = re.compile(r"window=\{[^}]*?size=([0-9x]+)")
_DIM_LABELS_RE = re.compile(r"dim_labels=\w+_(\w+)->")
_CUSTOM_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')

_CONV_DOT = {"convolution", "dot"}
_COLLECTIVE = {
    "all-reduce", "all-reduce-start", "all-reduce-done",
    "all-gather", "all-gather-start", "all-gather-done",
    "all-to-all", "reduce-scatter", "collective-permute",
    "collective-permute-start", "collective-permute-done",
    "collective-broadcast", "send", "send-done", "recv", "recv-done",
    "partition-id", "replica-id",
}
_LAYOUT = {
    "transpose", "copy", "copy-start", "copy-done", "bitcast",
    "bitcast-convert", "reshape", "dynamic-reshape", "pad", "slice",
    "dynamic-slice", "dynamic-update-slice", "concatenate", "reverse",
    "broadcast", "gather",
}
_OTHER = {"scatter", "sort", "fft", "triangular-solve", "cholesky",
          "custom-call", "infeed", "outfeed", "domain", "optimization-barrier"}
# zero-cost bookkeeping, skipped entirely
_SKIP = {"parameter", "constant", "tuple", "get-tuple-element",
         "after-all", "add-dependency"}
# control-flow call sites: cost lives in the callee computations
_CONTROL = {"fusion", "while", "conditional", "call", "async-start",
            "async-update", "async-done"}

# estimated-FLOPs-per-element > 1 for transcendentals would double-count
# against XLA's separate 'transcendentals' tally; keep 1/elem everywhere.


def _prod(dims):
    out = 1
    for d in dims:
        out *= d
    return out


def _parse_shapes(text):
    """All ``dtype[dims]`` arrays in ``text`` as (dtype, dims-tuple)."""
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        dims = dims.replace("<=", "").strip()
        try:
            shape = tuple(int(d) for d in dims.split(",") if d.strip()) \
                if dims else ()
        except ValueError:
            continue
        out.append((dt, shape))
    return out


def _nbytes(shapes):
    return sum(_DTYPE_BYTES.get(dt, 4) * _prod(dims)
               for dt, dims in shapes)


class Instr:
    __slots__ = ("name", "opcode", "out_shapes", "operand_shapes", "attrs",
                 "op_name")

    def __init__(self, name, opcode, out_shapes, operand_shapes, attrs,
                 op_name):
        self.name = name
        self.opcode = opcode
        self.out_shapes = out_shapes
        self.operand_shapes = operand_shapes
        self.attrs = attrs
        self.op_name = op_name


def parse_module(text):
    """Parse the HLO text into ``(entry_name, {comp_name: [Instr]},
    {comp_name: callee refs})``."""
    comps = {}
    refs = {}
    entry = None
    cur = None
    for line in text.splitlines():
        m = _COMP_RE.match(line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = []
            refs[cur] = []
            if line.startswith("ENTRY"):
                entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        op_name_m = _OPNAME_RE.search(rhs)
        op_name = op_name_m.group(1) if op_name_m else None
        clean = _METADATA_RE.sub("", rhs)
        clean_noquote = _STRING_RE.sub('""', clean)
        # output type: a tuple "(...)" or a single array shape
        if clean_noquote.startswith("("):
            depth = 0
            for i, ch in enumerate(clean_noquote):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        break
            out_txt, rest = clean_noquote[:i + 1], clean_noquote[i + 1:]
        else:
            sm = _SHAPE_RE.match(clean_noquote)
            if sm is None:
                continue
            j = sm.end()
            # optional layout suffix {1,0}
            if j < len(clean_noquote) and clean_noquote[j] == "{":
                j = clean_noquote.index("}", j) + 1
            out_txt, rest = clean_noquote[:j], clean_noquote[j:]
        rest = rest.strip()
        om = re.match(r"([\w\-]+)\(", rest)
        if om is None:
            continue
        opcode = om.group(1)
        # operand section: the opcode's balanced parens
        start = om.end() - 1
        depth = 0
        end = len(rest)
        for i in range(start, len(rest)):
            if rest[i] == "(":
                depth += 1
            elif rest[i] == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operands = rest[start + 1:end]
        attrs = rest[end + 1:]
        instr = Instr(name, opcode, _parse_shapes(out_txt),
                      _parse_shapes(operands), attrs, op_name)
        comps[cur].append(instr)
        for rx in (_CALLS_RE, _BODY_RE, _COND_RE, _TRUE_RE, _FALSE_RE):
            refs[cur].extend(rx.findall(clean_noquote))
        bm = _BRANCHES_RE.search(clean_noquote)
        if bm:
            refs[cur].extend(n.strip().lstrip("%")
                             for n in bm.group(1).split(","))
        if opcode == "call":
            refs[cur].extend(_TOAPPLY_RE.findall(clean_noquote))
    return entry, comps, refs


_MODULE_NAME_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.MULTILINE)


def module_name(text):
    """The compiled module's name (``jit_step_fn``), or None."""
    m = _MODULE_NAME_RE.search(text)
    return m.group(1) if m else None


def scope_map(text):
    """``{instruction name: op_name}`` of a compiled module's text: the
    ``jax.named_scope`` path each instruction was traced under, for a
    reader of a device trace, whose events carry the instruction's name
    and not its metadata.  An instruction the compiler made itself (a
    layout copy, a rematerialised fusion) has none and is left out."""
    _entry, comps, _refs = parse_module(text)
    return {ins.name: ins.op_name for instrs in comps.values()
            for ins in instrs if ins.op_name}


def category_of(instr):
    op = instr.opcode
    if op in _CONV_DOT:
        return "conv_dot"
    if op == "custom-call":
        tm = _CUSTOM_TARGET_RE.search(instr.attrs)
        t = (tm.group(1) if tm else "").lower()
        if any(k in t for k in ("conv", "dot", "matmul", "gemm")):
            return "conv_dot"
        if any(k in t for k in ("allreduce", "all_reduce", "allgather",
                                "all_gather", "alltoall",
                                "reducescatter", "reduce_scatter",
                                "permute")):
            return "collective"
        return "other"
    if op in _COLLECTIVE:
        return "collective"
    if op in _LAYOUT:
        return "transpose_layout"
    if op in _OTHER:
        return "other"
    return "elementwise_fusion"


def _flops_of(instr):
    out_elems = _prod(instr.out_shapes[0][1]) if instr.out_shapes else 0
    op = instr.opcode
    if op == "dot":
        k = 1
        cm = _LHS_CONTRACT_RE.search(instr.attrs)
        if cm and instr.operand_shapes:
            lhs = instr.operand_shapes[0][1]
            for d in cm.group(1).split(","):
                d = d.strip()
                if d and int(d) < len(lhs):
                    k *= lhs[int(d)]
        return 2 * out_elems * k
    if op == "convolution":
        win = 1
        wm = _WINDOW_SIZE_RE.search(instr.attrs)
        if wm:
            for d in wm.group(1).split("x"):
                win *= int(d)
        in_ch = 1
        dm = _DIM_LABELS_RE.search(instr.attrs)
        if dm and len(instr.operand_shapes) > 1:
            rhs_labels = dm.group(1)
            if "i" in rhs_labels:
                idx = rhs_labels.index("i")
                rhs = instr.operand_shapes[1][1]
                if idx < len(rhs):
                    in_ch = rhs[idx]
        return 2 * out_elems * win * in_ch
    if op in _LAYOUT or op in _SKIP or op in _CONTROL or op in _COLLECTIVE:
        return 0
    if op in ("reduce", "reduce-window", "select-and-scatter"):
        return _prod(instr.operand_shapes[0][1]) \
            if instr.operand_shapes else out_elems
    if op == "custom-call":
        return 0   # opaque; the reconciliation residual covers it
    return out_elems


def analyze(text, top=12):
    """Walk the compiled module and return::

        {"categories": {cat: {"flops", "bytes", "instructions"}},
         "provenance": [{"op_name", "category", "flops"}, ...]}

    ``provenance`` is the top FLOP-consuming framework scopes, taken
    from the ``op_name`` trace metadata (the scope names the executors
    and ``profiler.scope`` emit during tracing).
    """
    entry, comps, refs = parse_module(text)
    cats = {c: {"flops": 0, "bytes": 0, "instructions": 0}
            for c in CATEGORIES}
    prov = {}

    def body_cost(name, seen):
        """Aggregate a computation's instruction costs; recursing into
        fusion/control callees.  ``in_fusion`` bodies contribute flops
        only -- their HBM traffic is the call site's."""
        if name not in comps or name in seen:
            return
        seen.add(name)
        for ins in comps[name]:
            walk_instr(ins, seen, in_fusion=True)

    def fusion_body_summary(name):
        """(dominant category, flops per cat, instr count per cat) of a
        fused computation, for attributing the call site's bytes."""
        fl = {c: 0 for c in CATEGORIES}
        n = {c: 0 for c in CATEGORIES}

        def acc(nm, seen):
            if nm not in comps or nm in seen:
                return
            seen.add(nm)
            for ins in comps[nm]:
                if ins.opcode in _SKIP:
                    continue
                if ins.opcode == "fusion":
                    for callee in _CALLS_RE.findall(ins.attrs):
                        acc(callee, seen)
                    continue
                c = category_of(ins)
                fl[c] += _flops_of(ins)
                n[c] += 1
        acc(name, set())
        by_flops = max(fl, key=lambda c: fl[c])
        if fl[by_flops] > 0:
            return by_flops
        n["elementwise_fusion"] += 0  # stable tie-break below
        priority = {"conv_dot": 4, "collective": 3, "transpose_layout": 2,
                    "elementwise_fusion": 1, "other": 0}
        return max(CATEGORIES, key=lambda c: (n[c], priority[c]))

    def record(cat, flops, nbytes, ins):
        cats[cat]["flops"] += flops
        cats[cat]["bytes"] += nbytes
        cats[cat]["instructions"] += 1
        if ins.op_name and flops:
            key = ins.op_name
            ent = prov.setdefault(key, {"op_name": key, "category": cat,
                                        "flops": 0})
            ent["flops"] += flops

    def walk_instr(ins, seen, in_fusion=False):
        op = ins.opcode
        if op in _SKIP:
            return
        if op == "fusion":
            callees = _CALLS_RE.findall(ins.attrs)
            for callee in callees:
                body_cost(callee, seen)
            if not in_fusion:
                cat = fusion_body_summary(callees[0]) if callees \
                    else "elementwise_fusion"
                nbytes = _nbytes(ins.operand_shapes) + \
                    _nbytes(ins.out_shapes)
                cats[cat]["bytes"] += nbytes
            return
        if op in ("while", "conditional", "call") or \
                op.startswith("async-"):
            text_refs = []
            for rx in (_BODY_RE, _COND_RE, _TRUE_RE, _FALSE_RE,
                       _CALLS_RE, _TOAPPLY_RE):
                text_refs.extend(rx.findall(ins.attrs))
            bm = _BRANCHES_RE.search(ins.attrs)
            if bm:
                text_refs.extend(n.strip().lstrip("%")
                                 for n in bm.group(1).split(","))
            for callee in text_refs:
                walk_comp(callee, seen)
            return
        cat = category_of(ins)
        nbytes = 0 if in_fusion else \
            _nbytes(ins.operand_shapes) + _nbytes(ins.out_shapes)
        record(cat, _flops_of(ins), nbytes, ins)

    def walk_comp(name, seen):
        """Top-level walk: instructions here DO touch HBM."""
        if name not in comps or name in seen:
            return
        seen.add(name)
        for ins in comps[name]:
            walk_instr(ins, seen, in_fusion=False)

    if entry is not None:
        walk_comp(entry, set())
    provenance = sorted(prov.values(), key=lambda e: -e["flops"])[:top]
    return {"categories": cats, "provenance": provenance}
