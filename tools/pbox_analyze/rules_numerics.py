"""Numerics & recompilation safety: dtype/precision/device dataflow.

PR 13 made the serving path numerics-critical — int8/fp8 codes with one
f32 scale per row, dequant fused on-device so fp32 rows never
materialize host-side — and the whole stack runs on np.uint64 keys
whose precision silently dies above 2^53 the moment they touch a float
(and above 2^32 the moment they ride a jnp array: x64 is disabled, so
``jnp.asarray(u64)`` truncates to uint32).  Embedding inference is
bandwidth-bound (PAPERS.md), so an accidental fp32 materialization, a
silent jit retrace per step, or a per-step host sync is a real
regression the concurrency/typestate/SPMD passes (PRs 10-12) cannot
see.  Four rules on the shared Context + PR-11 call graph, with a
catalog in :mod:`num_catalog`:

``num-dtype-flow``
    Abstract dtype propagation per binding (seeds: np/jnp dtype
    literals, the ``quantize_rows`` (head, codes, scales) triple,
    ``load_q``/``store_q``, key-named parameters).  Flags quantized
    embedx codes converted back to float — ``codes.astype(f32)``,
    ``codes * scales``, any ``dequantize_rows`` call — outside the
    fused-gather files (inference/quant.py, inference/export.py), and
    float/non-float dtype mixing inside one ``np.concatenate``/``stack``
    merge: the publish/delta chain's runtime ``EmbeddingDtypeMismatch``
    guard only fires after the bytes shipped.

``num-key-width``
    uint64 keys flowing into narrower or float contexts: ``astype`` to
    any float (exact only below 2^53) / int64 (keys >= 2^63 go
    NEGATIVE) / 32-bit dtypes (truncation), float arithmetic (numpy
    promotes u64 x float to float64), any ``jnp.*``/``device_put`` call
    on a u64 value (x64-disabled: silent uint32 truncation — keys must
    ride as (hi, lo) uint32 pairs via keycodec ``split_u64``), and
    32-bit recombination of split halves (``hi << 32`` overflows; the
    convention is ``np.uint64(hi) << np.uint64(32) | lo``).  The
    split itself (``(keys >> np.uint64(32)).astype(np.uint32)``) is the
    recognized-legal narrowing.

``jit-retrace-hazard``
    Shapes that recompile silently per step: a fresh
    ``jax.jit``/``shard_map`` wrapper built inside a function body and
    invoked immediately (new cache key every call — the
    merge_device_axis bug this PR fixed), or built inside a loop; a
    jit-bound callable invoked with a data-dependent-shape argument
    (``np.unique``/``nonzero``/boolean-mask results — the padded-bucket
    discipline bypassed); python-scalar arguments built at the call
    site (``int(x)``/``float(x)``/``len(x)``/``.item()`` — weak-type
    flips retrace, and the build itself syncs); and a nested function
    handed to ``jit`` that closes over a device array from the
    enclosing scope (baked in as a constant at trace time — it will
    NOT track updates, and swapping it retraces).

``host-sync-in-hot-loop``
    ``jax.device_get``/``.item()``/``float()``/``bool()``/
    ``np.asarray`` on device values inside a per-batch/per-step loop —
    a loop is "hot" when its body dispatches a jit-bound callable or it
    iterates a feed (``.batches()``/``feeds()``), directly or through a
    resolved callee whose summary syncs one of its parameters.
    Recognized-legal without annotation: syncs AFTER the loop (the
    pass-boundary D2H snapshot / end-of-pass merge idiom), and syncs
    under a profiling/dump/debug guard (``if prof.enabled:`` — the
    deliberate instrumented path).

All per-function memos (dtype envs, sync summaries, jit-bound tables)
live under ``ctx.caches["numerics"]`` so a full ``--all`` stays inside
the asserted 5s wall-time budget.
"""

from __future__ import annotations

import ast

from .callgraph import CallGraph
from .core import Context, cached_walk, dotted
from .num_catalog import (
    DEVICE_PRODUCER_CALLS,
    DTYPE_TAGS,
    FLOAT_TAGS,
    FUSED_DEQUANT_FILES,
    GUARD_TOKENS,
    HOT_ITER_CALLS,
    JIT_WRAP_CALLS,
    KEY_ATTR_NAMES,
    KEY_PARAM_NAMES,
    NP_MATERIALIZERS,
    PY_SCALAR_CALLS,
    QUANT_CODE_NAMES,
    QUANT_PRODUCER_TAGS,
    QUANT_TRIPLE_PRODUCER,
    SHAPE_VARYING_CALLS,
    SYNC_ATTR_CALLS,
    SYNC_FUNC_CALLS,
    TAG_PRESERVING_METHODS,
)

RULES = {
    "num-dtype-flow": (
        "quantized (head, codes, scales) rows materialized to fp32 "
        "outside the fused gather, or dtype mixing inside one merge "
        "(the runtime EmbeddingDtypeMismatch guard fires after the "
        "bytes shipped)"
    ),
    "num-key-width": (
        "uint64 keys flowing into float/int32/int64/jnp contexts — "
        "precision dies above 2^53 (float), 2^63 (int64 sign) or 2^32 "
        "(jnp x64-disabled); carry keys as split_u64 (hi, lo) pairs"
    ),
    "jit-retrace-hazard": (
        "jit/shard_map callable built per call or fed shape-varying / "
        "python-scalar args / device-array closures — a silent "
        "recompile per step"
    ),
    "host-sync-in-hot-loop": (
        "device_get/.item()/float()/np.asarray on a device value "
        "inside a per-batch/per-step loop (pass-boundary snapshots and "
        "prof/dump-gated readbacks stay legal)"
    ),
}

_TOP = "⊤"
_NP_HEADS = ("np", "numpy")
_JNP_HEADS = ("jnp",)
_MERGE_CALLS = frozenset({
    "concatenate", "stack", "hstack", "vstack", "column_stack",
})


def _last(name: str) -> str:
    return name.rsplit(".", 1)[-1] if name else ""


def _head(name: str) -> str:
    return name.split(".", 1)[0] if name else ""


def _dtype_literal_tag(node):
    """'f32' for np.float32 / jnp.float32 / "float32" / np.dtype(...)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return DTYPE_TAGS.get(node.value)
    name = dotted(node)
    if name:
        return DTYPE_TAGS.get(_last(name))
    if isinstance(node, ast.Call) and _last(dotted(node.func)) == "dtype" \
            and node.args:
        return _dtype_literal_tag(node.args[0])
    return None


def _call_dtype_arg(call: ast.Call):
    """The dtype literal tag among a call's args/kwargs, if any."""
    for kw in call.keywords:
        if kw.arg == "dtype":
            return _dtype_literal_tag(kw.value)
    for a in call.args:
        t = _dtype_literal_tag(a)
        if t is not None:
            return t
    return None


class NumEngine:
    """Shared analysis state for one Context (built once, memoized in
    ``ctx.caches['numerics']``)."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.cg = CallGraph.of(ctx)
        cache = ctx.caches.setdefault("numerics", {})
        self._env = cache.setdefault("dtype_env", {})       # fid -> env
        self._sync = cache.setdefault("sync_params", {})    # fid -> frozenset
        self._jit = cache.setdefault("jit_bound", {})       # rel -> frozenset
        self._sync_inprog: set = set()

    @classmethod
    def of(cls, ctx: Context) -> "NumEngine":
        inst = ctx.caches.get("numerics_engine")
        if inst is None:
            inst = cls(ctx)
            ctx.caches["numerics_engine"] = inst
        return inst

    # -- jit-bound bindings -------------------------------------------------- #
    def jit_bound(self, sf) -> frozenset:
        """Dotted names in this file bound to a compiled callable:
        ``X = jax.jit(f)`` / ``self._fn = counted_jit(...)`` /
        ``@jit``-decorated defs / assignments from local jit factories
        (functions whose return expression is a jit-wrap call)."""
        cached = self._jit.get(sf.rel)
        if cached is not None:
            return cached
        names: set = set()
        factories: set = set()
        assigns: list = []
        for node in cached_walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for d in node.decorator_list:
                    base = _last(dotted(
                        d.func if isinstance(d, ast.Call) else d))
                    if base in JIT_WRAP_CALLS:
                        names.add(node.name)
            elif isinstance(node, ast.Return) and isinstance(
                    node.value, ast.Call) and _last(dotted(
                    node.value.func)) in JIT_WRAP_CALLS:
                parent = sf.parent(node)
                while parent is not None and not isinstance(
                        parent, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    parent = sf.parent(parent)
                if parent is not None:
                    factories.add(parent.name)
            elif isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call):
                assigns.append(node)
        for node in assigns:
            base = _last(dotted(node.value.func))
            if base in JIT_WRAP_CALLS or base in factories:
                for t in node.targets:
                    tn = dotted(t) if not isinstance(t, ast.Name) else t.id
                    if tn:
                        names.add(tn)
        out = frozenset(names)
        self._jit[sf.rel] = out
        return out

    def _is_jit_call(self, sf, call: ast.Call) -> bool:
        tn = dotted(call.func)
        return bool(tn) and tn in self.jit_bound(sf)

    # -- dtype environments --------------------------------------------------- #
    def dtype_env(self, fid: str, assigns=None) -> dict:
        cached = self._env.get(fid)
        if cached is not None:
            return cached
        fi = self.cg.functions.get(fid)
        env: dict = {}
        if fi is not None:
            args = fi.node.args
            for a in (args.posonlyargs + args.args + args.kwonlyargs):
                if a.arg in KEY_PARAM_NAMES:
                    env[a.arg] = "u64"
                elif a.arg in QUANT_CODE_NAMES:
                    env[a.arg] = "q"
                ann_t = _dtype_literal_tag(a.annotation) \
                    if a.annotation is not None else None
                if ann_t:
                    env[a.arg] = ann_t
            if assigns is None:
                assigns = [
                    n for n in self.cg._shallow_walk(fi.node)
                    if isinstance(n, (ast.Assign, ast.AnnAssign))
                ]
            changed = True
            laps = 0
            while changed and laps < 6:
                changed = False
                laps += 1
                for node in assigns:
                    value = node.value
                    targets = node.targets if isinstance(
                        node, ast.Assign) else [node.target]
                    if value is None:
                        continue
                    if (
                        len(targets) == 1
                        and isinstance(targets[0], ast.Tuple)
                        and isinstance(value, ast.Call)
                        and _last(dotted(value.func))
                        == QUANT_TRIPLE_PRODUCER
                        and len(targets[0].elts) == 3
                    ):
                        for t, tag in zip(targets[0].elts,
                                          ("f32", "q", "f32")):
                            changed |= self._bind(env, t, tag)
                        continue
                    tag = self.expr_tag(env, value)
                    for t in targets:
                        if isinstance(t, ast.Tuple):
                            continue  # unknown element-wise split
                        changed |= self._bind(env, t, tag)
        self._env[fid] = env
        return env

    @staticmethod
    def _bind(env: dict, target, tag) -> bool:
        name = target.id if isinstance(target, ast.Name) else dotted(target)
        if not name:
            return False
        if tag is None:
            return False
        old = env.get(name)
        if old == tag or old == _TOP:
            return False
        env[name] = tag if old is None else _TOP
        return True

    def expr_tag(self, env: dict, node):
        """Abstract dtype tag of an expression, or None (unknown)."""
        if node is None:
            return None
        if isinstance(node, ast.Name):
            t = env.get(node.id)
            return None if t == _TOP else t
        if isinstance(node, ast.Attribute):
            t = env.get(dotted(node))
            if t is not None:
                return None if t == _TOP else t
            bare = node.attr.lstrip("_")
            if bare in KEY_ATTR_NAMES or node.attr in KEY_ATTR_NAMES:
                return "u64"
            if bare in QUANT_CODE_NAMES or node.attr in QUANT_CODE_NAMES:
                return "q"
            return None
        if isinstance(node, ast.Constant):
            if isinstance(node.value, float):
                return "pyfloat"
            return None
        if isinstance(node, ast.Subscript):
            t = self.expr_tag(env, node.value)
            if t == "u32pair":
                sl = node.slice
                if isinstance(sl, ast.Tuple) and len(sl.elts) == 2:
                    return "u32half"
                return "u32pair"
            return t
        if isinstance(node, ast.UnaryOp):
            return self.expr_tag(env, node.operand)
        if isinstance(node, ast.IfExp):
            a = self.expr_tag(env, node.body)
            b = self.expr_tag(env, node.orelse)
            return a if a == b else None
        if isinstance(node, ast.BinOp):
            lt = self.expr_tag(env, node.left)
            rt = self.expr_tag(env, node.right)
            if lt == rt:
                return lt
            tags = {lt, rt}
            if "u64" in tags and (tags & (FLOAT_TAGS | {"pyfloat"})):
                return "f64"  # numpy's u64 x float promotion
            return None
        if isinstance(node, ast.Call):
            return self._call_tag(env, node)
        return None

    def _call_tag(self, env: dict, call: ast.Call):
        func = call.func
        name = dotted(func)
        base = _last(name) or (
            func.attr if isinstance(func, ast.Attribute) else "")
        if isinstance(func, ast.Attribute):
            if base == "astype" and call.args:
                return _dtype_literal_tag(call.args[0])
            if base in TAG_PRESERVING_METHODS:
                return self.expr_tag(env, func.value)
        if base == QUANT_TRIPLE_PRODUCER:
            return None  # tuple producer: handled at unpack sites
        if base in QUANT_PRODUCER_TAGS:
            return QUANT_PRODUCER_TAGS[base]
        if base in DTYPE_TAGS and (_head(name) in _NP_HEADS + _JNP_HEADS
                                   or name == base):
            return DTYPE_TAGS[base]  # np.uint64(x) ctor cast
        if base in ("asarray", "array", "ascontiguousarray"):
            t = _call_dtype_arg(call)
            if t is not None:
                return t
            return self.expr_tag(env, call.args[0]) if call.args else None
        if base in ("zeros", "ones", "empty", "full"):
            return _call_dtype_arg(call)
        if base.endswith("_like") and base[:-5] in (
                "zeros", "ones", "empty", "full"):
            t = _call_dtype_arg(call)
            if t is not None:
                return t
            return self.expr_tag(env, call.args[0]) if call.args else None
        return None

    # -- host-sync callee summaries ------------------------------------------ #
    def sync_params(self, fid: str, _depth: int = 0) -> frozenset:
        """Indices of parameters this function host-syncs (directly, or
        through a resolved callee's summary)."""
        cached = self._sync.get(fid)
        if cached is not None:
            return cached
        if fid in self._sync_inprog or _depth > 4:
            return frozenset()
        fi = self.cg.functions.get(fid)
        if fi is None:
            return frozenset()
        self._sync_inprog.add(fid)
        try:
            args = fi.node.args
            params = [a.arg for a in args.posonlyargs + args.args]
            # taint: param names plus same-function aliases of them
            tainted = {p: i for i, p in enumerate(params)}
            for node in self.cg._shallow_walk(fi.node):
                if isinstance(node, ast.Assign) and isinstance(
                        node.value, ast.Name) and \
                        node.value.id in tainted:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            tainted.setdefault(
                                t.id, tainted[node.value.id])
            out: set = set()
            for node in self.cg._shallow_walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                hit = self._sync_operand(node)
                if hit is not None:
                    for n in ast.walk(hit):
                        if isinstance(n, ast.Name) and n.id in tainted:
                            out.add(tainted[n.id])
                    continue
                tgt = self.cg._resolve_call_target(
                    fi, self.cg._local_types(fi), node.func)
                if tgt is None:
                    continue
                callee_sync = self.sync_params(tgt, _depth + 1)
                if not callee_sync:
                    continue
                offset = 1 if self._has_self(tgt) else 0
                for j, a in enumerate(node.args):
                    if (j + offset) in callee_sync and isinstance(
                            a, ast.Name) and a.id in tainted:
                        out.add(tainted[a.id])
        finally:
            self._sync_inprog.discard(fid)
        res = frozenset(out)
        self._sync[fid] = res
        return res

    def _has_self(self, fid: str) -> bool:
        fi = self.cg.functions.get(fid)
        if fi is None or fi.cls is None:
            return False
        args = fi.node.args
        allp = args.posonlyargs + args.args
        return bool(allp) and allp[0].arg in ("self", "cls")

    @staticmethod
    def _sync_operand(call: ast.Call):
        """The operand expression a sync call reads, or None."""
        func = call.func
        base = _last(dotted(func)) or (
            func.attr if isinstance(func, ast.Attribute) else "")
        if base in SYNC_FUNC_CALLS and call.args:
            return call.args[0]
        if isinstance(func, ast.Attribute) and func.attr in SYNC_ATTR_CALLS:
            return func.value
        if base in NP_MATERIALIZERS and _head(dotted(func)) in _NP_HEADS \
                and call.args:
            return call.args[0]
        if isinstance(func, ast.Name) and func.id in ("float", "int", "bool") \
                and len(call.args) == 1:
            return call.args[0]
        return None


# --------------------------------------------------------------------------- #
# per-function rule walkers (driven off ONE shallow walk in run())
# --------------------------------------------------------------------------- #
class _FnNodes:
    """The per-function node bundle every walker shares."""

    __slots__ = ("calls", "binops", "assigns", "loops", "defs")

    def __init__(self, eng, fn):
        self.calls: list = []
        self.binops: list = []
        self.assigns: list = []
        self.loops: list = []
        self.defs: list = []
        for node in eng.cg._shallow_walk(fn):
            if isinstance(node, ast.Call):
                self.calls.append(node)
            elif isinstance(node, ast.BinOp):
                self.binops.append(node)
            elif isinstance(node, (ast.Assign, ast.AnnAssign)):
                self.assigns.append(node)
            elif isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
                self.loops.append(node)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs.append(node)


def _dtype_flow(eng: NumEngine, fi, env, fnodes) -> list:
    findings: list = []
    sf = fi.sf
    if sf.rel.endswith(FUSED_DEQUANT_FILES):
        return findings
    for node in fnodes.calls + fnodes.binops:
        if isinstance(node, ast.Call):
            func = node.func
            base = _last(dotted(func)) or (
                func.attr if isinstance(func, ast.Attribute) else "")
            if isinstance(func, ast.Attribute) and base == "astype" \
                    and node.args:
                recv = eng.expr_tag(env, func.value)
                to = _dtype_literal_tag(node.args[0])
                if recv == "q" and to in FLOAT_TAGS:
                    findings.append(sf.finding(
                        "num-dtype-flow", node,
                        "quantized embedx codes dequantized to "
                        f"{to} here — fp32 rows must never materialize "
                        "outside the fused gather "
                        "(inference/quant.py scale layout: dequant runs "
                        "on-device inside export_serving_programs)",
                    ))
            elif base == "dequantize_rows":
                findings.append(sf.finding(
                    "num-dtype-flow", node,
                    "dequantize_rows() materializes full fp32 rows "
                    "host-side — it is the test oracle, not a serving "
                    "path; keep (head, codes, scales) quantized and let "
                    "the exported program dequantize on gather",
                ))
            elif base in _MERGE_CALLS and _head(dotted(func)) in (
                    _NP_HEADS + _JNP_HEADS):
                tags = set()
                elts: list = []
                for a in node.args:
                    if isinstance(a, (ast.List, ast.Tuple)):
                        elts.extend(a.elts)
                    else:
                        elts.append(a)
                for e in elts:
                    t = eng.expr_tag(env, e)
                    if t in FLOAT_TAGS or t in (
                            "q", "bytes", "u64", "i64", "i32", "u32"):
                        tags.add(t)
                floats = tags & FLOAT_TAGS
                others = tags - FLOAT_TAGS
                if floats and others:
                    findings.append(sf.finding(
                        "num-dtype-flow", node,
                        f"{base}() mixes {sorted(floats)} with "
                        f"{sorted(others)} rows in one merge — a mixed "
                        "publish/delta chain corrupts the table; the "
                        "runtime EmbeddingDtypeMismatch guard only "
                        "fires after the bytes shipped",
                    ))
        elif isinstance(node, ast.BinOp) and isinstance(
                node.op, (ast.Mult, ast.Div)):
            lt = eng.expr_tag(env, node.left)
            rt = eng.expr_tag(env, node.right)
            if ("q" in (lt, rt)) and (
                    {lt, rt} & (FLOAT_TAGS | {"pyfloat"})):
                findings.append(sf.finding(
                    "num-dtype-flow", node,
                    "arithmetic between quantized codes and a float "
                    "(implicit dequant) outside the fused gather — "
                    "ship (head, codes, scales) and dequantize "
                    "on-device",
                ))
    return findings


_NARROW_CAST_MSG = {
    "i64": "int64 flips the sign of keys >= 2^63",
    "i32": "int32 truncates keys to 32 bits",
    "u32": "uint32 drops the top 32 bits",
}


def _key_width(eng: NumEngine, fi, env, fnodes) -> list:
    findings: list = []
    sf = fi.sf
    for node in fnodes.calls + fnodes.binops:
        if isinstance(node, ast.Call):
            func = node.func
            name = dotted(func)
            base = _last(name) or (
                func.attr if isinstance(func, ast.Attribute) else "")
            if isinstance(func, ast.Attribute) and base == "astype" \
                    and node.args:
                recv_node = func.value
                recv = eng.expr_tag(env, recv_node)
                to = _dtype_literal_tag(node.args[0])
                if recv == "u64":
                    # the split convention's own narrowing is legal:
                    # (keys >> np.uint64(32)).astype(np.uint32)
                    shifted = isinstance(recv_node, ast.BinOp) and \
                        isinstance(recv_node.op, (ast.RShift, ast.BitAnd))
                    if to in FLOAT_TAGS:
                        findings.append(sf.finding(
                            "num-key-width", node,
                            f"uint64 keys cast to {to} — float carries "
                            "53 mantissa bits, keys above 2^53 collide "
                            "silently; keep keys u64 host-side and ride "
                            "devices as split_u64 (hi, lo) uint32 pairs "
                            "(utils/keycodec.py)",
                        ))
                    elif to in _NARROW_CAST_MSG and not (
                            shifted and to == "u32"):
                        findings.append(sf.finding(
                            "num-key-width", node,
                            f"uint64 keys cast to {to} — "
                            f"{_NARROW_CAST_MSG[to]}; only the "
                            "split_u64 (hi, lo) convention may narrow "
                            "(mask/shift first)",
                        ))
            elif base in ("float32", "float64", "float16", "int64",
                          "int32") and _head(name) in _NP_HEADS \
                    and len(node.args) == 1:
                if eng.expr_tag(env, node.args[0]) == "u64":
                    to = DTYPE_TAGS[base]
                    msg = _NARROW_CAST_MSG.get(
                        to, "float loses key precision above 2^53")
                    findings.append(sf.finding(
                        "num-key-width", node,
                        f"np.{base}() over uint64 keys — {msg}",
                    ))
            elif isinstance(func, ast.Name) and func.id == "float" \
                    and len(node.args) == 1:
                if eng.expr_tag(env, node.args[0]) == "u64":
                    findings.append(sf.finding(
                        "num-key-width", node,
                        "float() over a uint64 key — exact only below "
                        "2^53; compare/propagate keys as u64",
                    ))
            elif (_head(name) in _JNP_HEADS or base == "device_put") \
                    and node.args:
                for a in node.args:
                    if eng.expr_tag(env, a) == "u64":
                        findings.append(sf.finding(
                            "num-key-width", node,
                            "uint64 keys fed to jnp/device_put — JAX "
                            "runs x64-disabled, so the array silently "
                            "truncates to uint32 (top 32 bits GONE); "
                            "use utils/keycodec.split_u64 to carry "
                            "(hi, lo) uint32 pairs",
                        ))
                        break
        elif isinstance(node, ast.BinOp):
            lt = eng.expr_tag(env, node.left)
            rt = eng.expr_tag(env, node.right)
            if isinstance(node.op, (ast.Add, ast.Sub, ast.Mult, ast.Div,
                                    ast.FloorDiv, ast.Mod, ast.Pow)):
                if "u64" in (lt, rt) and (
                        {lt, rt} & (FLOAT_TAGS | {"pyfloat"})):
                    findings.append(sf.finding(
                        "num-key-width", node,
                        "uint64 keys in float arithmetic — numpy "
                        "promotes to float64, exact only below 2^53; "
                        "keys are identities, not quantities",
                    ))
            elif isinstance(node.op, ast.LShift) and lt == "u32half":
                findings.append(sf.finding(
                    "num-key-width", node,
                    "split_u64 half recombined with a 32-bit shift — "
                    "the hi half overflows uint32; recombine as "
                    "np.uint64(hi) << np.uint64(32) | lo",
                ))
    return findings


def _enclosing(sf, node, kinds):
    cur = sf.parent(node)
    while cur is not None:
        if isinstance(cur, kinds):
            return cur
        cur = sf.parent(cur)
    return None


def _retrace(eng: NumEngine, fi, fnodes) -> list:
    findings: list = []
    sf = fi.sf

    # device-producing names in this scope (for closure-capture checks)
    device_names: set = set()
    for node in fnodes.assigns:
        if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call):
            vname = dotted(node.value.func)
            if _head(vname) in _JNP_HEADS or _last(vname) in \
                    DEVICE_PRODUCER_CALLS:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        device_names.add(t.id)

    nested_defs = {n.name: n for n in fnodes.defs}

    for node in fnodes.calls:
        base = _last(dotted(node.func)) or (
            node.func.attr if isinstance(node.func, ast.Attribute) else "")
        # (a) fresh wrapper: jit(...) invoked immediately, or built in a loop
        if isinstance(node.func, ast.Call) and _last(dotted(
                node.func.func)) in JIT_WRAP_CALLS:
            findings.append(sf.finding(
                "jit-retrace-hazard", node,
                f"{_last(dotted(node.func.func))}(...) built and invoked "
                "in one expression — a fresh wrapper (new cache key) "
                "every call, so this retraces EVERY time; build once, "
                "cache, dispatch the cached callable",
            ))
            continue
        if base in JIT_WRAP_CALLS and _enclosing(
                sf, node, (ast.For, ast.While)) is not None and \
                _enclosing(sf, node, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)) is not None:
            findings.append(sf.finding(
                "jit-retrace-hazard", node,
                f"{base}(...) wrapper built inside a loop — its trace "
                "cache dies with each iteration; hoist the wrap out of "
                "the loop",
            ))
            continue
        # (d) nested def handed to jit that closes over a device array
        if base in JIT_WRAP_CALLS and node.args and isinstance(
                node.args[0], ast.Name) and \
                node.args[0].id in nested_defs and device_names:
            body_fn = nested_defs[node.args[0].id]
            own = {a.arg for a in body_fn.args.posonlyargs
                   + body_fn.args.args + body_fn.args.kwonlyargs}
            for sub in cached_walk(body_fn):
                if isinstance(sub, ast.Assign):
                    for t in sub.targets:
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name):
                                own.add(n.id)
            for sub in cached_walk(body_fn):
                if isinstance(sub, ast.Name) and isinstance(
                        sub.ctx, ast.Load) and sub.id in device_names \
                        and sub.id not in own:
                    findings.append(sf.finding(
                        "jit-retrace-hazard", node,
                        f"{body_fn.name}() closes over device array "
                        f"{sub.id!r} from the enclosing scope — baked "
                        "in as a trace-time constant (updates are NOT "
                        "tracked; swapping it retraces); pass it as an "
                        "argument",
                    ))
                    break
            continue
        # call sites of jit-bound callables
        if not eng._is_jit_call(sf, node):
            continue
        for a in list(node.args) + [kw.value for kw in node.keywords]:
            scalar = None
            if isinstance(a, ast.Call):
                if isinstance(a.func, ast.Name) and \
                        a.func.id in PY_SCALAR_CALLS:
                    scalar = a.func.id + "()"
                elif isinstance(a.func, ast.Attribute) and \
                        a.func.attr == "item":
                    scalar = ".item()"
            if scalar is not None:
                findings.append(sf.finding(
                    "jit-retrace-hazard", node,
                    f"python scalar {scalar} passed straight into a "
                    "jitted call — weak-type flips retrace, and "
                    "building the scalar syncs the host; pass a "
                    "fixed-dtype array or mark the arg static",
                ))
                continue
            for sub in ast.walk(a):
                if isinstance(sub, (ast.Lambda, ast.FunctionDef)):
                    break
                hit = None
                if isinstance(sub, ast.Call):
                    sbase = _last(dotted(sub.func)) or (
                        sub.func.attr
                        if isinstance(sub.func, ast.Attribute) else "")
                    if sbase in SHAPE_VARYING_CALLS:
                        hit = f"{sbase}()"
                    elif sbase == "where" and len(sub.args) == 1:
                        hit = "where(cond)"
                elif isinstance(sub, ast.Subscript) and isinstance(
                        sub.slice, ast.Compare):
                    hit = "boolean-mask indexing"
                if hit:
                    findings.append(sf.finding(
                        "jit-retrace-hazard", node,
                        f"data-dependent shape ({hit}) fed straight "
                        "into a jitted call — every distinct size is a "
                        "silent recompile; pad to the bucketed shape "
                        "first (the padded-bucket discipline plans and "
                        "the predictor ladder enforce)",
                    ))
                    break
    return findings


_STATIC_ATTRS = frozenset({"shape", "ndim", "dtype", "size", "sharding"})


def _static_access(sf, node) -> bool:
    """Is this device-value reference consumed only through a
    shape/dtype-style attribute (concrete host metadata under jax)?"""
    cur = sf.parent(node)
    while isinstance(cur, (ast.Attribute, ast.Subscript)):
        if isinstance(cur, ast.Attribute) and cur.attr in _STATIC_ATTRS:
            return True
        cur = sf.parent(cur)
    return False


def _names_mention_guard(expr) -> bool:
    for n in ast.walk(expr):
        ident = None
        if isinstance(n, ast.Name):
            ident = n.id
        elif isinstance(n, ast.Attribute):
            ident = n.attr
        elif isinstance(n, ast.Constant) and isinstance(n.value, str):
            ident = n.value
        if ident and any(tok in ident.lower() for tok in GUARD_TOKENS):
            return True
    return False


def _guarded(sf, node, stop) -> bool:
    """Is this sink under an If / with whose condition names a
    profiling/dump guard (within the hot loop)?"""
    cur = sf.parent(node)
    while cur is not None and cur is not stop:
        if isinstance(cur, ast.If) and _names_mention_guard(cur.test):
            return True
        if isinstance(cur, (ast.With, ast.AsyncWith)):
            for item in cur.items:
                if _names_mention_guard(item.context_expr):
                    return True
        cur = sf.parent(cur)
    return False


def _device_env(eng: NumEngine, fi, fnodes) -> set:
    """Names/dotted self-attrs holding device values in this function."""
    sf = fi.sf
    out: set = set()
    changed = True
    laps = 0
    while changed and laps < 4:
        changed = False
        laps += 1
        for node in fnodes.assigns:
            if not isinstance(node, ast.Assign):
                continue
            v = node.value
            is_dev = False
            if isinstance(v, ast.Call):
                vname = dotted(v.func)
                if _head(vname) in _JNP_HEADS \
                        or _last(vname) in DEVICE_PRODUCER_CALLS \
                        or eng._is_jit_call(sf, v):
                    is_dev = True
            elif isinstance(v, (ast.Name, ast.Attribute)):
                ref = v.id if isinstance(v, ast.Name) else dotted(v)
                is_dev = ref in out
            if not is_dev:
                continue
            for t in node.targets:
                names = [t]
                if isinstance(t, ast.Tuple):
                    names = list(t.elts)
                for n in names:
                    ref = n.id if isinstance(n, ast.Name) else dotted(n)
                    if ref and ref not in out:
                        out.add(ref)
                        changed = True
    return out


def _host_sync(eng: NumEngine, fi, fnodes) -> list:
    findings: list = []
    sf = fi.sf
    loops = fnodes.loops
    if not loops:
        return findings
    dev = _device_env(eng, fi, fnodes)

    def is_dev(expr) -> bool:
        for n in ast.walk(expr):
            hit = False
            if isinstance(n, ast.Name) and n.id in dev:
                hit = True
            elif isinstance(n, ast.Attribute) and dotted(n) in dev:
                hit = True
            elif isinstance(n, ast.Call) and eng._is_jit_call(sf, n):
                hit = True
            # x.shape / x.ndim / x.dtype on a device value is host
            # metadata, not a transfer — int(loss.shape[0]) is free
            if hit and not _static_access(sf, n):
                return True
        return False

    def loop_is_hot(loop) -> bool:
        head = getattr(loop, "iter", None) or getattr(loop, "test", None)
        if head is not None:
            for n in ast.walk(head):
                if isinstance(n, ast.Call):
                    b = _last(dotted(n.func)) or (
                        n.func.attr
                        if isinstance(n.func, ast.Attribute) else "")
                    if b in HOT_ITER_CALLS:
                        return True
        for n in ast.walk(loop):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            if isinstance(n, ast.Call) and eng._is_jit_call(sf, n):
                return True
        return False

    seen: set = set()
    for loop in loops:
        if not loop_is_hot(loop):
            continue
        for node in ast.walk(loop):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            operand = NumEngine._sync_operand(node)
            what = None
            if operand is not None:
                base = _last(dotted(node.func)) or (
                    node.func.attr
                    if isinstance(node.func, ast.Attribute) else "")
                if base in SYNC_FUNC_CALLS:
                    what = f"{base}()"  # device_get implies device
                elif is_dev(operand):
                    what = f"{base}()"
            else:
                tgt = eng.cg._resolve_call_target(
                    fi, eng.cg._local_types(fi), node.func)
                if tgt is not None:
                    callee_sync = eng.sync_params(tgt)
                    if callee_sync:
                        offset = 1 if eng._has_self(tgt) else 0
                        for j, a in enumerate(node.args):
                            if (j + offset) in callee_sync and is_dev(a):
                                callee = eng.cg.functions[tgt]
                                what = (
                                    f"call into {callee.name}() "
                                    f"({callee.sf.rel}:"
                                    f"{callee.node.lineno}, which "
                                    "host-syncs this argument)"
                                )
                                break
            if what is None:
                continue
            if _guarded(sf, node, loop):
                continue  # prof/dump-gated readback: deliberate
            seen.add(id(node))
            findings.append(sf.finding(
                "host-sync-in-hot-loop", node,
                f"{what} on a device value inside a per-batch/per-step "
                "loop — the host blocks on the device every iteration "
                "and the dispatch pipeline drains; move the readback to "
                "the pass boundary (the D2H snapshot idiom) or keep it "
                "on-device",
            ))
    return findings


# --------------------------------------------------------------------------- #
# pass driver
# --------------------------------------------------------------------------- #
_RETRACE_TOKENS = ("jit(", "shard_map")
_SYNC_TOKENS = ("jnp.", "device_get", "device_put", "_to_device",
                ".batches(", "feeds(")
#: a file can only grow u64/quant tags (the things the dtype/key sinks
#: fire on) if one of the SEED spellings appears somewhere in it — key
#: names all contain "keys", quant names "codes"/"embedx_q"/"quantize",
#: and every explicit cast spells "astype" or a ctor like np.uint64.
_DTYPE_TOKENS = ("keys", "uint64", "quantize", "codes", "embedx_q",
                 "split_u64", "astype")


def run(ctx: Context) -> list:
    eng = NumEngine.of(ctx)
    findings: list = []
    rel_files = {sf.rel for sf in ctx.files}
    gates: dict = {}
    for sf in ctx.files:
        text = sf.text
        gates[sf.rel] = (
            any(t in text for t in _DTYPE_TOKENS),
            any(t in text for t in _RETRACE_TOKENS),
            any(t in text for t in _SYNC_TOKENS),
        )
    for fid, fi in eng.cg.functions.items():
        rel = fi.sf.rel
        if rel not in rel_files:
            continue
        g_dtype, g_retrace, g_sync = gates[rel]
        if not (g_dtype or g_retrace or g_sync):
            continue
        if not g_retrace:
            # no jit/shard_map token anywhere in the file: its jit-bound
            # table is provably empty — skip the discovery walk
            eng._jit.setdefault(rel, frozenset())
        fnodes = _FnNodes(eng, fi.node)
        if g_dtype and (fnodes.calls or fnodes.binops):
            env = eng.dtype_env(fid, fnodes.assigns)
            findings.extend(_dtype_flow(eng, fi, env, fnodes))
            findings.extend(_key_width(eng, fi, env, fnodes))
        if g_retrace and fnodes.calls:
            findings.extend(_retrace(eng, fi, fnodes))
        if (g_sync or g_retrace) and fnodes.loops:
            findings.extend(_host_sync(eng, fi, fnodes))
    return findings
