"""perflint (ISSUE 10 tentpole): TPU performance linter + compiled-HLO
efficiency auditor.

PR 6 measures per-HLO cost and PRs 1/5/7 lint for *correctness*
(trace safety, concurrency, sharding); nothing named the perf hazards
ROADMAP item 2 is chasing (ResNet-50 MFU 0.248 -> >=0.32).  This pass
does, in the same two layers as the sharding sanitizer:

**Static layer** (AST, under the PR-1 rule framework; runs in
``mxlint --self``):

- ``layout-hostile-conv``: a Conv/Pool layer constructed with the
  *silent* NCHW default in model code.  The framework has a complete
  channels-last path (``layout="NHWC"``, ``tests/test_layout.py``) and
  on TPU the NCHW tax is real transpose traffic around every conv
  (docs/perf_resnet50.md); construction sites must choose a layout
  explicitly -- thread a ``layout`` parameter (model_zoo does) or pass
  the literal deliberately.
- ``pad-waste``: a literal layer dim (Dense units, Conv channels,
  Embedding width) not aligned to the TPU tile -- 128 lanes in the
  minor dim, 8 (f32) / 16 (bf16) sublanes in the second-minor.  The
  waste fraction is computed and a did-you-mean dim suggested.
- ``python-loop-unroll``: a Python ``for`` over ``range(N)`` or a
  homogeneous layer stack inside a traced scope
  (``hybrid_forward``/``_forward_impl``) or a jitted step function --
  the loop unrolls N copies into the trace, scaling compile time and
  program size linearly where ``jax.lax.scan``/``fori_loop`` compiles
  once.
- ``scalar-recompile``: a per-step-varying Python scalar (``lr``,
  ``t``, ``loss_scale``, ...) passed by keyword into an op invocation
  when that name is not threaded dynamically by the eager engine
  (``ndarray._DYNAMIC_PARAMS``) -- the static call-site twin of PR 1's
  registry-level retrace auditor: every distinct value recompiles.
- ``eager-in-step-loop``: an un-jitted eager ``nd.*`` op dispatched
  inside a detected training loop -- per-step Python dispatch the
  compiled step (or a ``bulk`` scope) should absorb.

**Compiled layer**: :func:`perf_audit` walks PR 6's persistent
``profiling.store.executables()`` registry, lowers each entry (hitting
jax's executable cache) and emits ranked advisories from the existing
category/roofline machinery -- transpose/layout share above threshold,
elementwise bytes XLA failed to fuse, actual-vs-tile-padded shape waste
on the MXU ops, and memory-bound executables whose arithmetic intensity
sits far below the device ridge.  Every advisory names the executable,
the HLO category, ``op_name`` provenance, and its estimated cost
share.  ``save_audit``/``diff_audit`` + the committed
``ci/perf_baseline.json`` gate drift exactly like the sharding
baseline: ``mxlint --perf-diff BASE CUR`` errors on growth, passes on
improvement (rule ``perf-drift``; CI stage ``perflint``;
docs/perf_lint.md).
"""
from __future__ import annotations

import ast
import json
import os
from typing import Dict, List, Optional

from .core import Diagnostic, WARNING, rule
from .retrace import VARYING_PARAM_NAMES, eager_dynamic_params
from .sharding import (_call_name, _file_defs_and_assigns, _is_jit_call,
                       _resolve_body)
from .trace_lint import TRACED_SCOPES

__all__ = [
    "AUDIT_SCHEMA", "THRESHOLDS",
    "audit_hlo_text", "perf_audit", "save_audit", "load_audit",
    "diff_audit",
]

# ----------------------------------------------------------------------
# TPU tiling constants (see /opt accelerator guide: vector memory is
# tiled (sublane, lane) = (8, 128) for 4-byte types; 2-byte types pack
# 16 sublanes, 1-byte types 32)
# ----------------------------------------------------------------------

TILE_LANE = 128
SUBLANE_F32 = 8
SUBLANE_BF16 = 16
# literal dims below this are structural (class counts, stem widths) --
# rounding them up changes the task, not the padding
_PAD_MIN_DIM = 16

# layer constructors whose dim/layout choices the static rules inspect
_DIM_LAYERS = {"Dense": 0, "Conv1D": 0, "Conv2D": 0, "Conv3D": 0,
               "Embedding": 1}
_DIM_KWARGS = {"units", "channels", "output_dim"}
_LAYOUT_LAYERS = {
    "Conv1D", "Conv2D", "Conv3D", "Conv2DTranspose", "Conv1DTranspose",
    "MaxPool1D", "MaxPool2D", "MaxPool3D",
    "AvgPool1D", "AvgPool2D", "AvgPool3D",
    "GlobalMaxPool1D", "GlobalMaxPool2D", "GlobalMaxPool3D",
    "GlobalAvgPool1D", "GlobalAvgPool2D", "GlobalAvgPool3D",
}
# iterables that read as a homogeneous layer/step stack
import re as _re
_STACK_NAME_RE = _re.compile(r"(layers|blocks|cells|steps|stack)s?$",
                             _re.I)
_MIN_UNROLL = 4


def _ceil_to(d, g):
    return ((d + g - 1) // g) * g


# ----------------------------------------------------------------------
# layout-hostile-conv
# ----------------------------------------------------------------------

@rule("layout-hostile-conv", "ast",
      "A Conv/Pool layer constructed with the silent NCHW default in "
      "model code; the channels-last (NHWC) path exists and NCHW costs "
      "transpose traffic around every conv on TPU.  Thread a layout "
      "parameter (model_zoo idiom) or pass layout= explicitly.")
def _lint_layout_hostile(tree, path, ctx):
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _call_name(node) in _LAYOUT_LAYERS):
            continue
        kwnames = {kw.arg for kw in node.keywords}
        if "layout" in kwnames:
            continue
        if None in kwnames:
            continue      # a **kwargs splat may carry layout; not decidable
        yield Diagnostic(
            "layout-hostile-conv",
            "%s constructed without an explicit layout= relies on the "
            "silent NCHW default; a channels-last path exists "
            "(layout=\"NHWC\") and on TPU the NCHW tax is transpose "
            "traffic around every conv.  Thread a layout parameter or "
            "pass the literal deliberately (docs/perf_lint.md)"
            % _call_name(node),
            file=path, line=node.lineno)


# ----------------------------------------------------------------------
# pad-waste
# ----------------------------------------------------------------------

def _literal_dim(call: ast.Call) -> Optional[int]:
    name = _call_name(call)
    pos = _DIM_LAYERS.get(name)
    cand = None
    if pos is not None and len(call.args) > pos:
        cand = call.args[pos]
    for kw in call.keywords:
        if kw.arg in _DIM_KWARGS:
            cand = kw.value
    if isinstance(cand, ast.Constant) and isinstance(cand.value, int):
        return cand.value
    return None


@rule("pad-waste", "ast",
      "A literal layer dim not aligned to the TPU tile (lane 128, "
      "sublane 8 f32 / 16 bf16): XLA pads the dim up and the pad "
      "fraction is dead MXU/VPU work.  Round the dim to the suggested "
      "tile multiple, or suppress where the dim is semantic (class "
      "count, reference architecture).")
def _lint_pad_waste(tree, path, ctx):
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _call_name(node) in _DIM_LAYERS):
            continue
        d = _literal_dim(node)
        if d is None or d < _PAD_MIN_DIM or d % SUBLANE_F32 == 0:
            continue
        pad8 = _ceil_to(d, SUBLANE_F32)
        pad128 = _ceil_to(d, TILE_LANE)
        waste8 = (pad8 - d) / pad8
        waste128 = (pad128 - d) / pad128
        # suggest the lane multiple when it costs <= 15% extra over the
        # literal; otherwise the cheap sublane fix
        suggest = pad128 if (pad128 - d) / d <= 0.15 else pad8
        yield Diagnostic(
            "pad-waste",
            "%s dim %d is not a multiple of the TPU sublane (8 f32 / "
            "16 bf16): pads to %d sublanes (%.1f%% waste) and %d lanes "
            "(%.1f%% waste); did you mean %d?"
            % (_call_name(node), d, pad8, 100 * waste8, pad128,
               100 * waste128, suggest),
            file=path, line=node.lineno)


# ----------------------------------------------------------------------
# python-loop-unroll
# ----------------------------------------------------------------------

def _jitted_fn_nodes(tree):
    """Function defs in ``tree`` that are passed to ``jax.jit`` --
    their bodies are traced, so Python loops there unroll."""
    defs, assigns = _file_defs_and_assigns(tree)
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit_call(node) and node.args:
            body = _resolve_body(node.args[0], defs, assigns)
            if body is not None and body[2] is not None:
                out.append(body[2])
    return out


def _own_loops(fn):
    """For loops lexically in ``fn``'s body, nested defs excluded
    (their loops belong to another trace decision)."""
    stack = list(fn.body)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        if isinstance(n, ast.For):
            yield n
        stack.extend(ast.iter_child_nodes(n))


def _range_trip(it) -> Optional[int]:
    if not (isinstance(it, ast.Call) and _call_name(it) == "range"):
        return None
    args = it.args
    lits = [a.value for a in args
            if isinstance(a, ast.Constant) and isinstance(a.value, int)]
    if len(lits) != len(args) or not args:
        return None
    if len(lits) == 1:
        return lits[0]
    if len(lits) >= 2:
        return lits[1] - lits[0]
    return None


def _calls_loop_target(loop) -> bool:
    if not isinstance(loop.target, ast.Name):
        return False
    tgt = loop.target.id
    for n in ast.walk(loop):
        if isinstance(n, ast.Call):
            f = n.func
            if isinstance(f, ast.Name) and f.id == tgt:
                return True
            if isinstance(f, ast.Attribute) and \
                    isinstance(f.value, ast.Name) and f.value.id == tgt:
                return True
    return False


def _iter_stack_name(it) -> Optional[str]:
    base = it
    if isinstance(base, ast.Call) and isinstance(base.func, ast.Attribute) \
            and base.func.attr in ("values", "items"):
        base = base.func.value
    if isinstance(base, ast.Attribute):
        name = base.attr
    elif isinstance(base, ast.Name):
        name = base.id
    else:
        return None
    return name if _STACK_NAME_RE.search(name) else None


@rule("python-loop-unroll", "ast",
      "A Python for over range(N)/a homogeneous layer stack inside a "
      "traced scope (hybrid_forward/_forward_impl or a jitted step "
      "fn): the loop unrolls N copies into the trace -- compile time "
      "and program size scale linearly; jax.lax.scan/fori_loop over "
      "stacked params compiles the body once.")
def _lint_loop_unroll(tree, path, ctx):
    scopes = [n for n in ast.walk(tree)
              if isinstance(n, ast.FunctionDef)
              and n.name in TRACED_SCOPES]
    seen = {id(s) for s in scopes}
    for fn in _jitted_fn_nodes(tree):
        if id(fn) not in seen:
            seen.add(id(fn))
            scopes.append(fn)
    for fn in scopes:
        for loop in _own_loops(fn):
            trip = _range_trip(loop.iter)
            if trip is not None and trip >= _MIN_UNROLL:
                yield Diagnostic(
                    "python-loop-unroll",
                    "python for over range(%d) inside traced scope %r "
                    "unrolls %d copies of the body into the trace; use "
                    "jax.lax.fori_loop/scan so the body compiles once"
                    % (trip, fn.name, trip),
                    file=path, line=loop.lineno)
                continue
            stack = _iter_stack_name(loop.iter)
            if stack is not None and _calls_loop_target(loop):
                yield Diagnostic(
                    "python-loop-unroll",
                    "python for over homogeneous stack %r inside "
                    "traced scope %r unrolls one body copy per layer "
                    "into the trace; stack the per-layer params and "
                    "jax.lax.scan the body once" % (stack, fn.name),
                    file=path, line=loop.lineno)


# ----------------------------------------------------------------------
# scalar-recompile
# ----------------------------------------------------------------------

def _chain(func) -> List[str]:
    parts: List[str] = []
    node = func
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return list(reversed(parts))


def _is_op_invoke(func) -> bool:
    parts = _chain(func)
    if not parts:
        return False
    if parts[0] in ("F", "nd", "sym"):
        return len(parts) > 1
    return len(parts) > 2 and parts[0] == "mx" and parts[1] in ("nd", "sym")


@rule("scalar-recompile", "ast",
      "A per-step-varying Python scalar (lr/t/loss_scale/...) passed "
      "by keyword into an op invocation when the eager engine does not "
      "thread that name dynamically (ndarray._DYNAMIC_PARAMS) -- the "
      "param is baked into the compile-cache key and every distinct "
      "value recompiles.  The static call-site twin of the retrace "
      "auditor.")
def _lint_scalar_recompile(tree, path, ctx):
    try:
        dynamic = set(eager_dynamic_params())
    except Exception:
        dynamic = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _is_op_invoke(node.func)):
            continue
        for kw in node.keywords:
            if kw.arg not in VARYING_PARAM_NAMES or kw.arg in dynamic:
                continue
            if isinstance(kw.value, ast.Constant):
                continue      # a literal is one cache entry, not a leak
            yield Diagnostic(
                "scalar-recompile",
                "op call passes varying scalar %r=%s outside the eager "
                "engine's dynamic set %s; each distinct value is a new "
                "compile-cache key (fresh XLA executable per step).  "
                "Add the name to ndarray._DYNAMIC_PARAMS or thread it "
                "as a tensor input"
                % (kw.arg, ast.unparse(kw.value), sorted(dynamic)),
                file=path, line=node.lineno)


# ----------------------------------------------------------------------
# eager-in-step-loop
# ----------------------------------------------------------------------

# ingest/sync entry points, not per-step compute dispatch
_EAGER_EXEMPT = {"array", "NDArray", "waitall", "save", "load"}


def _is_eager_nd_call(func) -> bool:
    parts = _chain(func)
    if len(parts) < 2:
        return False
    if parts[0] == "nd" or (len(parts) > 2 and parts[0] == "mx"
                            and parts[1] == "nd"):
        leaf = parts[-1]
        return leaf not in _EAGER_EXEMPT and not leaf[:1].isupper()
    return False


def _is_train_loop(loop) -> bool:
    """A loop whose body dispatches a train step (bare ``step(...)`` or
    ``trainer.step(...)``), nested defs excluded."""
    stack = list(loop.body)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        if isinstance(n, ast.Call):
            f = n.func
            if (isinstance(f, ast.Name) and f.id == "step") or \
                    (isinstance(f, ast.Attribute) and f.attr == "step"):
                return True
        stack.extend(ast.iter_child_nodes(n))
    return False


@rule("eager-in-step-loop", "ast",
      "An un-jitted eager nd.* op dispatched inside a detected "
      "training loop (a loop whose body calls step()): per-step eager "
      "dispatch the compiled step or a bulk scope should absorb -- "
      "each call is a host round trip between device steps.")
def _lint_eager_in_step_loop(tree, path, ctx):
    for node in ast.walk(tree):
        if not isinstance(node, (ast.For, ast.While)):
            continue
        if not _is_train_loop(node):
            continue
        stack = list(node.body)
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.For, ast.While)):
                continue          # inner loops report themselves
            if isinstance(n, ast.Call) and _is_eager_nd_call(n.func):
                yield Diagnostic(
                    "eager-in-step-loop",
                    "eager op %s dispatched inside a training loop; "
                    "move it into the compiled step (TrainStep) or "
                    "wrap the loop in engine.bulk() so the region "
                    "replays as one program"
                    % ".".join(_chain(n.func)),
                    file=path, line=n.lineno)
            stack.extend(ast.iter_child_nodes(n))


# ======================================================================
# Compiled layer: the HLO efficiency auditor
# ======================================================================

AUDIT_SCHEMA = "mxperf.audit.v1"

# advisory thresholds -- shares of the executable's analytic byte
# traffic (transpose/unfused) or of tile-padded MXU bytes (pad waste);
# memory-bound fires when intensity < ridge / factor
THRESHOLDS = {
    "transpose_share": 0.20,
    "unfused_elementwise_share": 0.15,
    "pad_waste": 0.15,
    "membound_ridge_factor": 8.0,
}


def _sublane_for(dtype: str) -> int:
    from ..profiling.hlo import _DTYPE_BYTES
    nbytes = _DTYPE_BYTES.get(dtype, 4)
    if nbytes <= 1:
        return 32
    if nbytes == 2:
        return SUBLANE_BF16
    return SUBLANE_F32


def _tile_pad_bytes(dtype: str, dims) -> int:
    """Bytes of the tile-padded shape: minor dim to 128 lanes, second
    minor to the dtype's sublane count (rank<2 shapes are stored as one
    (sublane, lane) tile row and not charged here)."""
    from ..profiling.hlo import _DTYPE_BYTES
    if len(dims) < 2:
        return _DTYPE_BYTES.get(dtype, 4) * max(1, _prod(dims))
    padded = list(dims)
    padded[-1] = _ceil_to(max(dims[-1], 1), TILE_LANE)
    padded[-2] = _ceil_to(max(dims[-2], 1), _sublane_for(dtype))
    return _DTYPE_BYTES.get(dtype, 4) * _prod(padded)


def _prod(dims):
    out = 1
    for d in dims:
        out *= d
    return out


def audit_hlo_text(text: str) -> Dict:
    """Raw efficiency counters of one compiled module's HLO text.

    Walks the module like ``hlo.analyze`` (fusion call sites carry the
    HBM bytes, while/cond bodies count once) but keeps the numbers the
    advisories need: per-category bytes, the bytes of *top-level*
    elementwise instructions XLA failed to fuse, tile-padding waste on
    the conv/dot operands, and transpose-op provenance.
    """
    from ..profiling import hlo

    entry, comps, _refs = hlo.parse_module(text)
    out = {
        "bytes_total": 0, "flops_total": 0,
        "category_bytes": {c: 0 for c in hlo.CATEGORIES},
        "unfused_elementwise_bytes": 0, "unfused_elementwise_count": 0,
        "transpose_ops": {},          # op_name -> bytes
        "mxu_actual_bytes": 0, "mxu_padded_bytes": 0,
    }

    def fusion_flops(name, seen):
        total = 0
        if name not in comps or name in seen:
            return 0
        seen.add(name)
        for ins in comps[name]:
            if ins.opcode == "fusion":
                for callee in hlo._CALLS_RE.findall(ins.attrs):
                    total += fusion_flops(callee, seen)
                continue
            total += hlo._flops_of(ins)
            _mxu_pad(ins)
        return total

    def fusion_category(name):
        fl = {c: 0 for c in hlo.CATEGORIES}
        n = {c: 0 for c in hlo.CATEGORIES}

        def acc(nm, seen):
            if nm not in comps or nm in seen:
                return
            seen.add(nm)
            for ins in comps[nm]:
                if ins.opcode in hlo._SKIP:
                    continue
                if ins.opcode == "fusion":
                    for callee in hlo._CALLS_RE.findall(ins.attrs):
                        acc(callee, seen)
                    continue
                c = hlo.category_of(ins)
                fl[c] += hlo._flops_of(ins)
                n[c] += 1
        acc(name, set())
        best = max(fl, key=lambda c: fl[c])
        if fl[best] > 0:
            return best
        prio = {"conv_dot": 4, "collective": 3, "transpose_layout": 2,
                "elementwise_fusion": 1, "other": 0}
        return max(hlo.CATEGORIES, key=lambda c: (n[c], prio[c]))

    def _mxu_pad(ins):
        if ins.opcode not in ("convolution", "dot"):
            return
        for dt, dims in list(ins.operand_shapes) + list(ins.out_shapes):
            if len(dims) < 2:
                continue
            from ..profiling.hlo import _DTYPE_BYTES
            actual = _DTYPE_BYTES.get(dt, 4) * _prod(dims)
            out["mxu_actual_bytes"] += actual
            out["mxu_padded_bytes"] += _tile_pad_bytes(dt, dims)

    def walk(name, seen):
        if name not in comps or name in seen:
            return
        seen.add(name)
        for ins in comps[name]:
            op = ins.opcode
            if op in hlo._SKIP:
                continue
            if op == "fusion":
                callees = hlo._CALLS_RE.findall(ins.attrs)
                nbytes = hlo._nbytes(ins.operand_shapes) + \
                    hlo._nbytes(ins.out_shapes)
                cat = fusion_category(callees[0]) if callees \
                    else "elementwise_fusion"
                out["category_bytes"][cat] += nbytes
                out["bytes_total"] += nbytes
                for callee in callees:
                    out["flops_total"] += fusion_flops(callee, seen)
                if cat == "transpose_layout" and ins.op_name:
                    rec = out["transpose_ops"]
                    rec[ins.op_name] = rec.get(ins.op_name, 0) + nbytes
                continue
            if op in ("while", "conditional", "call") or \
                    op.startswith("async-"):
                refs = []
                for rx in (hlo._BODY_RE, hlo._COND_RE, hlo._TRUE_RE,
                           hlo._FALSE_RE, hlo._CALLS_RE, hlo._TOAPPLY_RE):
                    refs.extend(rx.findall(ins.attrs))
                bm = hlo._BRANCHES_RE.search(ins.attrs)
                if bm:
                    refs.extend(n.strip().lstrip("%")
                                for n in bm.group(1).split(","))
                for callee in refs:
                    walk(callee, seen)
                continue
            cat = hlo.category_of(ins)
            nbytes = hlo._nbytes(ins.operand_shapes) + \
                hlo._nbytes(ins.out_shapes)
            out["bytes_total"] += nbytes
            out["category_bytes"][cat] += nbytes
            out["flops_total"] += hlo._flops_of(ins)
            _mxu_pad(ins)
            if cat == "transpose_layout":
                key = ins.op_name or op
                rec = out["transpose_ops"]
                rec[key] = rec.get(key, 0) + nbytes
            elif cat == "elementwise_fusion":
                out["unfused_elementwise_bytes"] += nbytes
                out["unfused_elementwise_count"] += 1

    if entry is not None:
        walk(entry, set())
    return out


def _merge_counters(agg: Dict, cur: Dict):
    for k, v in cur.items():
        if k == "category_bytes":
            for c, b in v.items():
                agg["category_bytes"][c] = \
                    agg["category_bytes"].get(c, 0) + b
        elif k == "transpose_ops":
            for nm, b in v.items():
                agg["transpose_ops"][nm] = \
                    agg["transpose_ops"].get(nm, 0) + b
        else:
            agg[k] = agg.get(k, 0) + v


def _metrics_of(counters: Dict, xla_flops=0.0, xla_bytes=0.0) -> Dict:
    total_b = counters["bytes_total"] or 1
    flops = xla_flops or counters["flops_total"]
    nbytes = xla_bytes or counters["bytes_total"]
    metrics = {
        "transpose_share": round(
            counters["category_bytes"]["transpose_layout"] / total_b, 4),
        "unfused_elementwise_share": round(
            counters["unfused_elementwise_bytes"] / total_b, 4),
        "unfused_elementwise_count":
            counters["unfused_elementwise_count"],
        "pad_waste": round(
            1.0 - counters["mxu_actual_bytes"]
            / counters["mxu_padded_bytes"], 4)
            if counters["mxu_padded_bytes"] else 0.0,
        "intensity": round(flops / nbytes, 4) if nbytes else 0.0,
        "flops": flops,
        "bytes": nbytes,
    }
    return metrics


def _advisories_for(label: str, metrics: Dict, counters: Dict,
                    ridge: Optional[float], thresholds: Dict) -> List[Dict]:
    adv = []
    top_transpose = sorted(counters["transpose_ops"].items(),
                           key=lambda kv: -kv[1])[:3]
    if metrics["transpose_share"] >= thresholds["transpose_share"]:
        adv.append({
            "kind": "transpose-share",
            "category": "transpose_layout",
            "share": metrics["transpose_share"],
            "op_names": [nm for nm, _b in top_transpose],
            "message": "%.0f%% of %r's memory traffic is pure layout "
                       "movement (transpose/copy/pad); top scopes: %s "
                       "-- a channels-last layout or explicit sharding "
                       "usually removes it"
                       % (100 * metrics["transpose_share"], label,
                          ", ".join(nm for nm, _b in top_transpose)
                          or "<unnamed>"),
        })
    if metrics["unfused_elementwise_share"] >= \
            thresholds["unfused_elementwise_share"]:
        adv.append({
            "kind": "unfused-elementwise",
            "category": "elementwise_fusion",
            "share": metrics["unfused_elementwise_share"],
            "op_names": [],
            "message": "%.0f%% of %r's memory traffic is %d elementwise "
                       "instruction(s) XLA left OUTSIDE fusions -- each "
                       "pays a full HBM round trip; check for "
                       "optimization barriers, aliasing, or "
                       "dtype-mismatch breaks in the op chain"
                       % (100 * metrics["unfused_elementwise_share"],
                          label, metrics["unfused_elementwise_count"]),
        })
    if metrics["pad_waste"] >= thresholds["pad_waste"]:
        adv.append({
            "kind": "hlo-pad-waste",
            "category": "conv_dot",
            "share": metrics["pad_waste"],
            "op_names": [],
            "message": "%.0f%% of %r's MXU operand bytes are tile "
                       "padding (shapes vs the (8,128) tile) -- align "
                       "the feature dims (static pad-waste rule names "
                       "the constructors)"
                       % (100 * metrics["pad_waste"], label),
        })
    factor = thresholds["membound_ridge_factor"]
    if ridge is not None and metrics["bytes"] \
            and metrics["intensity"] < ridge / factor:
        adv.append({
            "kind": "memory-bound",
            "category": "elementwise_fusion",
            "share": round(min(1.0, metrics["intensity"] / ridge), 4),
            "op_names": [],
            "message": "%r's arithmetic intensity %.2f flops/byte is "
                       ">%.0fx below the device ridge %.1f -- the "
                       "executable is HBM-bound; fuse more work per "
                       "byte (bigger batch, scan K steps, bf16 "
                       "activations)"
                       % (label, metrics["intensity"], factor, ridge),
        })
    adv.sort(key=lambda a: -a["share"])
    return adv


def perf_audit(thresholds=None, peaks=None) -> Dict:
    """Audit every executable the profiling capture surface registered.

    Lowers each registry entry (hits jax's executable cache), merges
    per-label counters, and returns the audit artifact::

        {"schema": ..., "ridge_intensity": ...,
         "executables": {label: {"metrics": {...},
                                 "advisories": [...]}}}

    ``thresholds`` overrides :data:`THRESHOLDS`; ``peaks`` is an
    optional ``(peak_flops, peak_bytes_per_s)`` pair pinning the ridge
    (tests).  Without it the ridge is the device's own
    (``profiling.roofline.DEVICE_PEAKS``); a CPU has none, so there
    ``ridge_intensity`` is ``None`` and no ``memory-bound`` advisory is
    judged.
    """
    import jax
    from ..profiling import roofline, store

    th = dict(THRESHOLDS)
    if thresholds:
        th.update(thresholds)
    if peaks is None:
        peaks = roofline.device_peaks()
    ridge = peaks[0] / peaks[1] if peaks is not None else None

    merged: Dict[str, Dict] = {}
    totals: Dict[str, List[float]] = {}
    for label, compiled in store.compiled_executables():
        try:
            text = compiled.as_text()
        except Exception:
            continue
        counters = audit_hlo_text(text)
        xf = xb = 0.0
        try:
            ca = compiled.cost_analysis() or {}
            xf = float(ca.get("flops", 0.0))
            xb = float(ca.get("bytes accessed", 0.0))
        except Exception:
            pass
        if label in merged:
            _merge_counters(merged[label], counters)
            totals[label][0] += xf
            totals[label][1] += xb
        else:
            merged[label] = counters
            totals[label] = [xf, xb]

    execs = {}
    for label, counters in merged.items():
        metrics = _metrics_of(counters, *totals[label])
        execs[label] = {
            "metrics": metrics,
            "advisories": _advisories_for(label, metrics, counters,
                                          ridge, th),
        }
    try:
        backend = jax.default_backend()
    except Exception:
        backend = "unknown"
    ranked = sorted(
        (dict(a, executable=label)
         for label, e in execs.items() for a in e["advisories"]),
        key=lambda a: -a["share"])
    return {
        "schema": AUDIT_SCHEMA,
        "backend": backend,
        "ridge_intensity": None if ridge is None else round(ridge, 3),
        "thresholds": th,
        "executables": execs,
        "advisories": ranked,
    }


def save_audit(path: str, audit=None) -> Dict:
    """Write the current perf audit as JSON (the artifact CI diffs
    against the committed ``ci/perf_baseline.json``)."""
    audit = audit if audit is not None else perf_audit()
    with open(path, "w") as f:
        json.dump(audit, f, indent=1, sort_keys=True)
        f.write("\n")
    return audit


def load_audit(path: str) -> Dict:
    with open(path) as f:
        data = json.load(f)
    if data.get("schema") != AUDIT_SCHEMA:
        raise ValueError("%s is not a %s artifact (schema=%r)"
                         % (path, AUDIT_SCHEMA, data.get("schema")))
    return data


def _audit_tol() -> float:
    try:
        return float(os.environ.get("MXNET_TPU_PERF_AUDIT_TOL", "0.02"))
    except ValueError:
        return 0.02


# share metrics where GROWTH is a regression
_GROWTH_METRICS = ("transpose_share", "unfused_elementwise_share",
                   "pad_waste")


def diff_audit(baseline: Dict, current: Dict,
               tol: Optional[float] = None) -> List[Diagnostic]:
    """Perf drift of ``current`` vs the blessed ``baseline``:

    - an advisory KIND the baseline doesn't carry for that executable
      (or a brand-new executable that audits with advisories) -> error;
    - a share metric (transpose / unfused-elementwise / pad-waste)
      grown more than ``tol`` (absolute; default
      ``MXNET_TPU_PERF_AUDIT_TOL`` = 0.02) -> error;
    - arithmetic intensity dropped >20% -> warning.

    Improvements (smaller shares, fewer advisories) pass silently --
    re-bless with :func:`save_audit` after an intentional change."""
    tol = _audit_tol() if tol is None else tol
    diags: List[Diagnostic] = []
    base_ex = baseline.get("executables", {})
    for label, cur in sorted(current.get("executables", {}).items()):
        base = base_ex.get(label, {"metrics": {}, "advisories": []})
        blessed_kinds = {a["kind"] for a in base.get("advisories", [])}
        for a in cur.get("advisories", []):
            if a["kind"] not in blessed_kinds:
                diags.append(Diagnostic(
                    "perf-drift",
                    "executable %r gained unblessed %r advisory "
                    "(category %s, cost share %.1f%%): %s -- fix the "
                    "regression or re-bless via analysis.perf."
                    "save_audit" % (label, a["kind"], a["category"],
                                    100 * a["share"], a["message"]),
                    node=label))
        bm = base.get("metrics", {})
        cm = cur.get("metrics", {})
        for m in _GROWTH_METRICS:
            b, c = bm.get(m, 0.0), cm.get(m, 0.0)
            if c > b + tol:
                diags.append(Diagnostic(
                    "perf-drift",
                    "executable %r: %s grew %.4f -> %.4f (tolerance "
                    "%.4f); the compiled step got less efficient than "
                    "the baseline blesses" % (label, m, b, c, tol),
                    node=label))
        b_int, c_int = bm.get("intensity", 0.0), cm.get("intensity", 0.0)
        if b_int > 0 and c_int < b_int * 0.8:
            diags.append(Diagnostic(
                "perf-drift",
                "executable %r: arithmetic intensity dropped %.3f -> "
                "%.3f (>20%%); the step is doing less compute per byte "
                "moved" % (label, b_int, c_int),
                node=label, severity=WARNING))
    return diags


@rule("perf-drift", "compiled",
      "A registered executable's efficiency metrics (transpose share, "
      "unfused elementwise bytes, MXU pad waste, intensity) drifted "
      "past the committed ci/perf_baseline.json -- a named, gated "
      "regression instead of a number drifting between bench rounds.  "
      "Gate: mxlint --perf-diff.")
def _rule_perf_drift(baseline, current):
    return diff_audit(baseline, current)
