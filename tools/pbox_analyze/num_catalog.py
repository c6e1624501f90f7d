"""The checked-in numerics catalog: dtype seeds, precision sinks, hot-loop
iterators and the sanctioned escapes the four ``num-*``/``jit-*``/
``host-sync-*`` passes reason with.

Every entry encodes a contract the quantized-serving and uint64-key
planes document in prose:

  * ``quantize_rows`` (inference/quant.py) splits f32 rows into the
    ``(head f32, codes int8|fp8, scale f32 per row)`` triple; dequant is
    FUSED into the serving program's gather (``export_serving_programs``)
    so fp32 rows never materialize host-side.  Any other site converting
    codes back to float defeats the bandwidth win PR 13 measured
    (payload 29.93% of fp32) — hence :data:`FUSED_DEQUANT_FILES`.
  * the whole stack runs on np.uint64 keys; JAX arrays are x64-disabled,
    so keys ride devices as uint32 ``(hi, lo)`` pairs via
    ``utils/keycodec.py split_u64``.  ``jnp.asarray(u64)`` silently
    truncates to uint32 (top 32 bits GONE), float arithmetic promotes to
    float64 (exact only below 2^53), and ``int64`` flips the sign of
    keys >= 2^63 — the three sink families of ``num-key-width``.
  * steady-state training and serving dispatch CACHED jitted programs;
    the feed side owns shape stability (plans pad key buffers to
    power-of-two bucket capacities, the predictor pads to its exported
    bucket ladder).  A shape-varying argument reaching a jitted callable
    is a silent recompile per step — ``jit-retrace-hazard``.
  * inside a per-batch/per-step loop the host must not synchronize with
    the device ("nothing syncs with the host inside a step",
    train/trainer.py module docstring; the loop itself is
    train/pass_loop.py run_pass, for both trainers, and the nan gate's
    opt-in readback sits in each trainer's after_step); pass-boundary D2H
    snapshots and end-of-pass merges are the designed exceptions,
    recognized by loop position, and profiling/dump-gated readbacks by
    their guard.
"""

from __future__ import annotations

#: dtype-name (last dotted segment or string literal) -> abstract tag.
#: Tags: floats f16/bf16/f32/f64; ints i8("q" codes)/i32/i64/u8/u32/u64.
DTYPE_TAGS = {
    "float16": "f16", "half": "f16",
    "bfloat16": "bf16",
    "float32": "f32", "single": "f32", "float": "f64",
    "float64": "f64", "double": "f64",
    "int8": "q",        # int8 embedx codes (quant.py symmetric grid)
    "uint8": "bytes",   # raw fp8 bytes on disk (quant.store_q)
    "int32": "i32",
    "int64": "i64", "long": "i64",
    "uint32": "u32",
    "uint64": "u64",
}

FLOAT_TAGS = frozenset({"f16", "bf16", "f32", "f64"})

#: parameter names conventionally carrying np.uint64 feature keys —
#: the seeds of ``num-key-width`` beyond explicit dtype literals.
KEY_PARAM_NAMES = frozenset({
    "keys", "uniq_keys", "batch_keys", "delta_keys", "new_keys",
    "sorted_keys", "pass_keys",
})

#: attribute names (leading underscores stripped) whose loads carry keys
#: (``self._keys``, ``batch.keys``).  A ``.keys`` that is immediately
#: CALLED is a dict view, not a key array — the pass excludes it.
KEY_ATTR_NAMES = frozenset({"keys", "uniq_keys"})

#: parameter/attribute names carrying quantized embedx codes.
QUANT_CODE_NAMES = frozenset({"embedx_q", "codes", "q"})

#: call base names producing tagged values (beyond dtype-literal casts).
#: quantize_rows yields the (f32 head, codes, f32 scales) triple — the
#: pass applies the tuple form at unpacking assignments.
QUANT_TRIPLE_PRODUCER = "quantize_rows"
QUANT_PRODUCER_TAGS = {
    "load_q": "q",
    "store_q": "bytes",
    "split_u64": "u32pair",
}

#: methods that preserve their receiver's dtype tag.
TAG_PRESERVING_METHODS = frozenset({
    "copy", "reshape", "ravel", "flatten", "squeeze", "transpose",
    "ascontiguousarray",
})

#: files where codes -> f32 conversion is the DESIGN, not a leak: the
#: codec module itself (dequantize_rows is the host-side test oracle)
#: and the serving-program builder whose fused gather dequantizes on
#: device.  Matched on repo-relative path suffix.
FUSED_DEQUANT_FILES = (
    "paddlebox_tpu/inference/quant.py",
    "paddlebox_tpu/inference/export.py",
)

#: np/jnp functions whose result shape depends on the DATA — the
#: signature of a padded-bucket-discipline bypass when fed straight into
#: a jitted callable.
SHAPE_VARYING_CALLS = frozenset({
    "unique", "nonzero", "flatnonzero", "argwhere", "compress",
    "extract", "trim_zeros", "setdiff1d", "intersect1d", "union1d",
})

#: builtins whose result is a python scalar: as a direct argument to a
#: jitted callable they flip weak types / force a host round-trip.
PY_SCALAR_CALLS = frozenset({"int", "float", "bool", "len"})

#: call bases that wrap a function into a compiled callable.
JIT_WRAP_CALLS = frozenset({"jit", "pjit", "counted_jit", "shard_map"})

#: call bases producing device-resident values (host-sync taint seeds),
#: beyond calls of jit-bound bindings and ``jnp.*``.
DEVICE_PRODUCER_CALLS = frozenset({
    "device_put", "_to_device", "to_device",
})

#: ``.m()`` receivers / functions that synchronize host<->device.
SYNC_ATTR_CALLS = frozenset({"item", "block_until_ready"})
SYNC_FUNC_CALLS = frozenset({"device_get"})
#: np.* materializers that force D2H when fed a device value.
NP_MATERIALIZERS = frozenset({"asarray", "array"})

#: iterator call bases that mark a loop as per-batch/per-step even when
#: no jitted dispatch is visible in its body (prefetchers hide it).
HOT_ITER_CALLS = frozenset({"batches", "feeds"})

#: a sink under an ``if`` whose condition mentions one of these tokens
#: is a deliberate, gated readback (profiling sync, field dumping) —
#: recognized legal, no annotation needed.
GUARD_TOKENS = ("prof", "debug", "trace", "dump", "verbose")

