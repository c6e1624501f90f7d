"""Sequence/context parallelism: ring attention + all-to-all (Ulysses) SP.

The CTR reference has no long-sequence path (SURVEY.md §5.7: its "sequences"
are unordered slot key-sets pooled by segment-sum, and rank_attention tops
out at max_rank=3) — but sequence parallelism is a first-class capability of
this framework so user models that DO consume long behavior sequences
(e.g. search/browse history towers feeding the CTR net) scale past one
chip's memory.  Two TPU-native strategies over one ``seq`` mesh axis:

  * ``ring_attention`` — every device holds one contiguous sequence chunk of
    Q/K/V; K/V blocks circulate the ICI ring via ``ppermute`` while each
    device folds one block per tick into a numerically-stable online-softmax
    accumulator (the flash/ring-attention recursion: running max ``m``,
    normalizer ``l``, weighted sum ``acc``).  Peak memory is O(T_local²)
    per device and the ring transfer overlaps the matmuls under XLA.
    Causal masking uses global chunk offsets (device j's block after t
    shifts came from chunk (j - t) mod P).
  * ``ulysses_attention`` — two ``all_to_all``s trade the sequence axis for
    the head axis: each device attends over the FULL sequence for H/P of
    the heads, so any dense-attention kernel drops in unchanged between the
    two collectives.  Cheaper collectives for moderate T; needs H % P == 0.

Both are pure shard_map bodies (jit + autodiff through scan/ppermute/
all_to_all work out of the box) and reduce to plain attention at P=1.

On one device ``full_attention`` is that plain attention.  Its mask is a
description in three words -- ``causal``, with it a ``window`` of keys, or
``block_diffusion``, the block mask over a noised and a clean stream that
block-diffusion training runs under -- and queries may be grouped over
fewer key-value heads; asked for a window, a query block, grouped queries
or the block mask it runs blockwise (no [T, T] tensor, blocks outside the
mask never computed); the value head may have another width than the
query/key head.  The blockwise form is one algorithm in two forms, told
apart by what the code can observe (``_attention_form``): on a TPU, for the
three described masks and lengths a block divides, one flash-form Pallas
kernel and its backward (parallel/flash_attention.py: a tile's scores in
VMEM only, the output and a row's log-sum-exp the only things written; q,
k and v read where they lie, nothing copied around the call); everywhere
else -- every other backend, and the kernel's oracle -- the strips below,
each two products with a softmax between.  ``attn.form`` counts which form
a traced call took.  ``kernel_residuals`` tells a caller that rematerialises
a whole layer what to keep of the layer's attention, from the backend alone:
where the form may be the kernel, its output and log-sum-exp, so that the
forward kernel is not run a second time; elsewhere nothing.
``rotary_tables`` / ``apply_rotary`` are the
rotary position code, plain and YaRN, pairing dimension i with i + D / 2
or, ``interleaved``, 2i with 2i + 1; a caller that turns part of a head
hands them that slice.  The ring and Ulysses forms take ``causal`` only: a
window on them is not written yet.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp

from paddlebox_tpu.telemetry import metrics as _tm

SEQ_AXIS = "seq"
_log = logging.getLogger(__name__)

_FORM = _tm.counter(
    "attn.form", "traced calls of full_attention's blockwise form by the "
    "form they took (kernel: the flash-form Pallas kernel, on a TPU; "
    "strips: two products and a softmax a block of queries) and the mask "
    "(causal, window, block_diffusion, none)")


def full_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False,
    key_valid: Optional[jax.Array] = None, window: Optional[int] = None,
    block_q: Optional[int] = None, block_diffusion: Optional[int] = None,
) -> jax.Array:
    """Softmax attention on one device.

    q: [B, T, H, D]; k: [B, T, Hkv, D]; v: [B, T, Hkv, Dv]; returns
    [B, T, H, Dv] (Dv = D in most models; latent attention has a value
    head narrower than its query/key head).

    The mask is described, never passed, in three words: ``causal`` (key
    j <= query i); ``window`` (with causal: i - j < window, a query sees
    itself and the window - 1 keys before it); and ``block_diffusion`` = L
    (neither of the other two), the mask of block-diffusion training over
    two streams: the first half of the T positions is the noised stream,
    the second the clean one, each numbered 0 .. T/2 - 1 in blocks of L
    (block of i = i // L), and
        a noised query i sees the noised keys of its own block (both
            directions) and the clean keys of the blocks before it,
        a clean query i sees the clean keys of its own block and of the
            blocks before it, and no noised key.
    Given ``window``, ``block_q``, ``block_diffusion`` or fewer key-value
    heads than query heads (grouped queries: query head h reads key-value
    head h // (H / Hkv); K and V are never repeated in memory), the
    blockwise form runs: the queries in blocks of ``block_q``, no [T, T]
    tensor, blocks wholly outside the mask never computed.

    key_valid: optional bool [B, Tk] (dense form only) — padded key
    positions read zero attention weight (variable-length sequences); a
    query whose keys are ALL masked reads a zero vector, not NaN.
    """
    blockwise = (window is not None or block_q is not None
                 or block_diffusion is not None or q.shape[2] != k.shape[2])
    if blockwise and key_valid is not None:
        raise ValueError(
            "the blockwise form takes no key_valid: its mask is one of the "
            "three described ones (causal, window, block_diffusion)")
    if block_diffusion is not None and (causal or window is not None):
        raise ValueError(
            "block_diffusion is a mask of its own: neither causal nor "
            "a window goes with it")
    if blockwise:
        return _blockwise(q, k, v, causal, window, block_diffusion,
                          block_q or DEFAULT_BLOCK_Q)
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    if key_valid is not None:
        s = jnp.where(key_valid[:, None, None, :], s, -jnp.inf)
    # masked-stable softmax: exp(-inf)=0 rows normalize against a floored
    # denominator instead of producing NaN
    m = jnp.max(s, axis=-1, keepdims=True)
    w = jnp.exp(s - jnp.where(jnp.isneginf(m), 0.0, m))
    p = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


DEFAULT_BLOCK_Q = 256
_KEY_ALIGN = 128  # a strip's first key sits on a lane-tile boundary


def _kernel_backend() -> bool:
    """Whether the blockwise form may come as the kernel at all: on a TPU."""
    return jax.default_backend() == "tpu"


def kernel_residuals():
    """What a caller that rematerialises a whole layer (``jax.checkpoint``)
    should keep of the layer's attention beside the layer's input, as a
    checkpoint policy, or None where nothing: on a backend whose blockwise
    form may be the kernel, the two residuals of its backward that only the
    kernel can make, its output and a row's log-sum-exp -- q, k and v are
    projections, norms and rotary codes away from the input, but the output
    costs the forward kernel again.  The kernel's forward rule names the
    two (parallel/flash_attention.py); a call that fell back to the strips
    names nothing, so it keeps nothing and costs nothing.  Elsewhere no
    policy is named and the caller's program is what it was: the answer
    follows the backend alone, as the form does."""
    if not _kernel_backend():
        return None
    from paddlebox_tpu.parallel import flash_attention
    return jax.checkpoint_policies.save_only_these_names(
        flash_attention.ATTN_OUT, flash_attention.ATTN_LSE)


def _attention_form(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: str) -> tuple:
    """("kernel", (block_q, block_kv, key-value heads a grid step)) where
    the flash-form kernel runs the blockwise form, ("strips", why not) where
    the strips do: the kernel is a TPU's, takes the three described masks,
    one dtype for q, k and v, and lengths that one of its blocks divides."""
    if not _kernel_backend():
        return "strips", "not a TPU"
    if mask == "none":
        return "strips", "no mask: the kernel takes the described three"
    if not (q.dtype == k.dtype == v.dtype
            and jnp.issubdtype(q.dtype, jnp.floating)):
        return "strips", f"dtypes {q.dtype}, {k.dtype}, {v.dtype}"
    from paddlebox_tpu.parallel import flash_attention
    blocks = flash_attention.blocks_for(
        q.shape[1], k.shape[1], q.shape[2] // k.shape[2], q.shape[3],
        v.shape[3], k.shape[2])
    if blocks is None:
        return "strips", (f"no block divides {q.shape[1]} query and "
                          f"{k.shape[1]} key positions")
    return "kernel", blocks


def _blockwise(q, k, v, causal: bool, window: Optional[int],
               block_diffusion: Optional[int], block_q: int) -> jax.Array:
    """``full_attention``'s blockwise form, in the form ``_attention_form``
    finds: the same arguments are refused on every backend, then the choice
    is counted (it is static: once a traced call) and taken."""
    if window is not None and not causal:
        raise ValueError("a window is defined on causal attention only")
    t, h = q.shape[1:3]
    if h % k.shape[2]:
        raise ValueError(
            f"{h} query heads over {k.shape[2]} key-value heads")
    if block_diffusion is not None:
        if t % 2 or k.shape[1] != t:
            raise ValueError(
                f"block_diffusion runs over two streams of one length: {t} "
                f"query and {k.shape[1]} key positions")
        if block_diffusion < 1 or block_q % block_diffusion:
            raise ValueError(
                f"block_q {block_q} is no multiple of the block length "
                f"{block_diffusion}")
    mask = ("block_diffusion" if block_diffusion is not None
            else "window" if window is not None
            else "causal" if causal else "none")
    form, found = _attention_form(q, k, v, mask)
    _FORM.inc(form=form, mask=mask)
    if form == "kernel":
        from paddlebox_tpu.parallel import flash_attention
        return flash_attention.flash_attention(q, k, v, flash_attention.Spec(
            mask, block_diffusion if window is None else window, *found))
    _log.debug("full_attention %s in strips: %s", mask, found)
    if block_diffusion is not None:
        return _block_diffusion_attention(q, k, v, block_diffusion, block_q)
    return _blockwise_attention(q, k, v, causal, window, block_q)


def _visible_keys(q0: int, q1: int, t: int, causal: bool,
                  window: Optional[int]) -> tuple:
    """[k0, k1): the keys any query of [q0, q1) may see."""
    k1 = q1 if causal else t
    k0 = max(0, q0 - window + 1) if window is not None else 0
    return (k0 // _KEY_ALIGN) * _KEY_ALIGN, k1


def _strip_attention(qb, ks, vs, visible=None):
    """One block of queries against the keys it may see.  qb: [B, bq, Hkv,
    G, D] (query heads grouped over their key-value head); ks: [B, S, Hkv,
    D]; vs: [B, S, Hkv, Dv]; ``visible(bq, S)`` gives the strip's mask,
    bool [bq, S] (None: every key).  The scores [B, Hkv, G, bq, S] are the
    only score tensor."""
    d = qb.shape[-1]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, ks,
                   preferred_element_type=jnp.float32) / jnp.sqrt(float(d))
    if visible is not None:
        s = jnp.where(visible(qb.shape[1], ks.shape[1]), s, -jnp.inf)
    # every query sees itself under each described mask: no row is all -inf
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(vs.dtype), vs,
                      preferred_element_type=jnp.float32).astype(qb.dtype)


def _causal_strip_mask(q0: int, k0: int, window: Optional[int]):
    """Queries from q0 against keys from k0: key j <= query i, and with a
    window i - j < window."""
    def visible(n_q: int, n_k: int):
        qi = q0 + jnp.arange(n_q, dtype=jnp.int32)[:, None]
        kj = k0 + jnp.arange(n_k, dtype=jnp.int32)[None, :]
        mask = kj <= qi
        if window is not None:
            mask &= qi - kj < window
        return mask
    return visible


def _grouped(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [B, T, H, D] as [B, T, Hkv, H / Hkv, D]: the query heads grouped
    over the key-value head each reads."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"{h} query heads over {hkv} key-value heads")
    return q.reshape(b, t, hkv, h // hkv, d)


def _blockwise_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
    window: Optional[int], block_q: int,
) -> jax.Array:
    """``full_attention``'s blockwise form: the queries in blocks of
    ``block_q`` (the last may be shorter), each against the contiguous
    strip of keys its mask allows -- for a window a strip of at most
    ``window + block_q`` keys, for plain causal the keys up to the block's
    end -- so keys wholly outside the mask cost nothing, and one strip of
    scores is all that is ever held.  Each strip is rematerialised in the
    backward pass (``jax.checkpoint``): what a layer keeps of its attention
    is q, k, v and the output, never a probability.  The mask is static,
    so the strips are unrolled: no loop carries, no dynamic slices."""
    if window is not None and not causal:
        raise ValueError("a window is defined on causal attention only")
    b, t, h, d = q.shape
    qg = _grouped(q, k)
    out = []
    for q0 in range(0, t, block_q):
        q1 = min(q0 + block_q, t)
        k0, k1 = _visible_keys(q0, q1, k.shape[1], causal, window)
        strip = jax.checkpoint(functools.partial(
            _strip_attention,
            visible=_causal_strip_mask(q0, k0, window) if causal else None))
        out.append(strip(qg[:, q0:q1], k[:, k0:k1], v[:, k0:k1]))
    return jnp.concatenate(out, axis=1).reshape(b, t, h, v.shape[-1])


def _diffusion_strip_mask(q0: int, block: int, n_clean: Optional[int] = None):
    """Queries from position q0 of their stream against clean keys from
    position 0 -- all of the strip's keys for clean queries; for noised
    ones the first ``n_clean``, the noised keys from q0 after them: a clean
    key is seen from a block before the query's -- and by a clean query
    from its own block too --, a noised key from the query's own block."""
    def visible(n_q: int, n_k: int):
        qb = (q0 + jnp.arange(n_q, dtype=jnp.int32)[:, None]) // block
        col = jnp.arange(n_k, dtype=jnp.int32)[None, :]
        if n_clean is None:
            return col // block <= qb
        return jnp.where(col < n_clean, col // block < qb,
                         (q0 + col - n_clean) // block == qb)
    return visible


def _block_diffusion_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, block: int, block_q: int,
) -> jax.Array:
    """``full_attention`` under the block mask over two streams (its
    docstring has the three rules), in the strips of the blockwise form:
    a strip of noised queries [q0, q1) goes against the clean keys of the
    blocks before its last block and the noised keys [q0, q1) of its own
    blocks, under ONE softmax over both; a strip of clean queries against
    the clean keys [0, q1).  ``block_q`` is a multiple of the block
    length, so a strip holds whole blocks and nothing outside those keys
    is ever computed; the clean keys' end sits on a lane-tile boundary
    where that is inside the strip (the few keys more are masked)."""
    b, t2, h, d = q.shape
    if t2 % 2 or k.shape[1] != t2:
        raise ValueError(
            f"block_diffusion runs over two streams of one length: {t2} "
            f"query and {k.shape[1]} key positions")
    if block < 1 or block_q % block:
        raise ValueError(
            f"block_q {block_q} is no multiple of the block length {block}")
    t = t2 // 2
    qg = _grouped(q, k)
    k_clean, v_clean = k[:, t:], v[:, t:]
    noised, clean = [], []
    for q0 in range(0, t, block_q):
        q1 = min(q0 + block_q, t)
        # clean keys a noised query of the strip may see: the blocks before
        # the strip's last
        n_clean = (q1 - 1) // block * block
        n_clean = min(-(-n_clean // _KEY_ALIGN) * _KEY_ALIGN, q1)
        strip = jax.checkpoint(functools.partial(
            _strip_attention,
            visible=_diffusion_strip_mask(q0, block, n_clean)))
        noised.append(strip(
            qg[:, q0:q1],
            jnp.concatenate([k_clean[:, :n_clean], k[:, q0:q1]], axis=1),
            jnp.concatenate([v_clean[:, :n_clean], v[:, q0:q1]], axis=1)))
        strip = jax.checkpoint(functools.partial(
            _strip_attention, visible=_diffusion_strip_mask(q0, block)))
        clean.append(strip(qg[:, t + q0:t + q1], k_clean[:, :q1],
                           v_clean[:, :q1]))
    return jnp.concatenate(noised + clean, axis=1).reshape(
        b, t2, h, v.shape[-1])


def rotary_tables(positions: jax.Array, head_dim: int, theta: float,
                  yarn: Optional[dict] = None,
                  interleaved: bool = False) -> tuple:
    """(cos, sin), each [T, head_dim], of the rotary position code on
    ``head_dim`` dimensions (a whole head, or the slice of it that is
    turned): angle(t, i) = t * inv_freq[i], i < head_dim / 2, laid out
    twice (the rotate-half pairing of dimension i with i + head_dim / 2)
    or, ``interleaved``, each angle at 2i and 2i + 1 (adjacent pairs).
    Plain: inv_freq[i] = theta ** (-2i / head_dim).

    ``yarn`` (keys ``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``, ``attention_factor``) blends, per
    dimension, the plain frequency with the same divided by ``factor``:
    dimensions that turn more than ``beta_fast`` times within the original
    length keep the plain one, those that turn less than ``beta_slow``
    times take the divided one, a linear ramp between the two correction
    dimensions (floor / ceil, clipped to [0, head_dim - 1]) in between; cos
    and sin are scaled by ``attention_factor``."""
    half = head_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim)
    scale = 1.0
    if yarn is not None:
        import math

        def correction_dim(turns: float) -> float:
            return head_dim * math.log(
                yarn["original_max_position_embeddings"]
                / (turns * 2.0 * math.pi)) / (2.0 * math.log(theta))

        low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(yarn["beta_slow"])), head_dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0, 1)
        inv = inv / yarn["factor"] * ramp + inv * (1.0 - ramp)
        scale = float(yarn["attention_factor"])
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    if interleaved:
        ang = jnp.repeat(ang, 2, axis=-1)
    else:
        ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array,
                 interleaved: bool = False) -> jax.Array:
    """x [B, T, H, D] turned by the tables of ``rotary_tables`` ([T, D],
    built with the same ``interleaved``): dimension i with i + D / 2, or
    2i with 2i + 1."""
    if interleaved:
        pairs = x.reshape(*x.shape[:-1], -1, 2)
        turned = jnp.stack([-pairs[..., 1], pairs[..., 0]],
                           axis=-1).reshape(x.shape)
    else:
        half = x.shape[-1] // 2
        turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
    key_valid: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Ring attention over sequence chunks (call INSIDE shard_map over
    ``axis_name``; every array is this device's chunk [B, T_local, H, D],
    chunks laid out contiguously in mesh order).

    key_valid: optional bool [B, T_local] — this chunk's key validity; it
    rides the ring with its K/V block so padded positions are masked
    wherever the block is folded.
    positions: optional int32 [T_local] — this chunk's GLOBAL sequence
    positions.  They ride the ring with their K/V block, so causal masking
    needs no ``axis_index`` — which also makes the body legal inside an
    OUTER shard_map (composed data x seq meshes), where axis_index of a
    nested axis does not lower.  Default: derived from axis_index
    (standalone use).
    """
    p_axis = jax.lax.axis_size(axis_name)
    b, t, h, d = q.shape
    scale = 1.0 / jnp.sqrt(float(d))
    # positions are only consumed by causal masking: derive (axis_index) and
    # ring-carry them ONLY then, so a non-causal call never pays the carry
    # and stays free of axis_index — legal inside an outer shard_map with no
    # positions passed at all
    if causal and positions is None:
        idx = jax.lax.axis_index(axis_name)
        positions = idx * t + jnp.arange(t, dtype=jnp.int32)
    q_pos = positions  # global positions of local queries (None: non-causal)

    def fold(args):
        """One online-softmax fold (flash recursion) in f32 accumulators."""
        k_blk, v_blk, valid_blk, pos_blk, acc, m, l = args
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_blk,
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            mask = q_pos[:, None] >= pos_blk[None, :]  # [Tq, Tk]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        s = jnp.where(valid_blk[:, None, None, :], s, -jnp.inf)
        s_max = s.max(axis=-1)  # [B, H, Tq]
        m_new = jnp.maximum(m, s_max)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        w = jnp.exp(s - m_safe[..., None])  # exp(-inf)=0 handles masked
        l = l * alpha + w.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", w, v_blk.astype(jnp.float32)
        )
        return acc, m_new, l

    def tick(carry, j):
        k_blk, v_blk, valid_blk, pos_blk, acc, m, l = carry
        if causal:
            # a block entirely in the causal future folds to a no-op: skip
            # its matmuls at runtime (the ring shift still happens below).
            # "entirely in the future" reads off the riding positions, so
            # no axis_index is needed.
            acc, m, l = jax.lax.cond(
                pos_blk.min() <= q_pos.max(),
                fold,
                lambda args: (args[4], args[5], args[6]),
                (k_blk, v_blk, valid_blk, pos_blk, acc, m, l),
            )
        else:
            acc, m, l = fold((k_blk, v_blk, valid_blk, pos_blk, acc, m, l))
        # the last tick's rotation would be discarded: skip it (the scan
        # counter is replicated, so every device takes the same branch and
        # the collective stays coherent)
        ring = (k_blk, v_blk, valid_blk) + ((pos_blk,) if causal else ())
        ring = jax.lax.cond(
            j < p_axis - 1,
            lambda kv: jax.lax.ppermute(
                kv, axis_name,
                [(i, (i + 1) % p_axis) for i in range(p_axis)],
            ),
            lambda kv: kv,
            ring,
        )
        k_blk, v_blk, valid_blk = ring[:3]
        pos_blk = ring[3] if causal else pos_blk
        return (k_blk, v_blk, valid_blk, pos_blk, acc, m, l), None

    # accumulate in f32 whatever the input dtype (flash-attention practice:
    # bf16 inputs, f32 running max/normalizer/weighted-sum)
    # over every axis q varies over: nested in an outer shard_map that is
    # the outer (data) axis as well as axis_name
    vary = lambda x: jax.lax.pcast(x, tuple(jax.typeof(q).vma), to="varying")
    # the synthesized all-ones mask is replicated; the ring shift needs it
    # device-varying like the K/V blocks it rides with
    kv_valid = (
        vary(jnp.ones((b, t), bool)) if key_valid is None else key_valid
    )
    pos0 = (
        positions if causal
        else jnp.zeros((), jnp.int32)  # placeholder, never read or shifted
    )
    acc0 = jnp.zeros((b, h, t, d), jnp.float32)
    m0 = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    (_, _, _, _, acc, _, l), _ = jax.lax.scan(
        tick,
        (k, v, kv_valid, pos0, vary(acc0), vary(m0), vary(l0)),
        jnp.arange(p_axis),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, H, T, D] f32
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
    key_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """All-to-all sequence parallelism (call INSIDE shard_map over
    ``axis_name``): trade T-sharding for H-sharding, run full attention,
    trade back.  q/k/v: [B, T_local, H, D] with H divisible by the axis
    size; returns [B, T_local, H, D].
    key_valid: optional bool [B, T_local] — local chunk's key validity,
    allgathered to the full sequence for the head-sharded attention.
    """
    p_axis = jax.lax.axis_size(axis_name)
    b, t, h, d = q.shape
    if h % p_axis != 0:
        raise ValueError(f"heads {h} not divisible by seq axis size {p_axis}")
    valid_full = (
        None
        if key_valid is None
        else jax.lax.all_gather(key_valid, axis_name, axis=1, tiled=True)
    )

    def seq_to_heads(x):
        # [B, T_local, H, D] -> [B, P*T_local, H/P, D]: give every device
        # the FULL sequence for its H/P heads (one tiled all_to_all)
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    out = full_attention(
        seq_to_heads(q), seq_to_heads(k), seq_to_heads(v), causal=causal,
        key_valid=valid_full,
    )
    return heads_to_seq(out)
