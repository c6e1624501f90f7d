"""Plain reference of ``models/decoder_lm.py``'s ``DecoderMoeLM``.

The same equations in straightforward ``jax.numpy``: float32, every product
under ``jax.default_matmul_precision("highest")``, dense [T, T] masks built
from positions, keys and values repeated over their query heads, the
experts as a loop over the held ones with a zero weight where a token is
not routed, the whole [T, classes] logits of a sequence at once, loss and
gradients by ``jax.grad``.  No kernel and no blocks beyond a ``lax.map``
over the sequences.  It imports nothing of the program; ``sizes`` is a
plain dict:

    hidden, n_heads, n_kv_heads, head_dim, layer_types, window,
    rope_theta, yarn (None or factor / original_max_position_embeddings /
    beta_fast / beta_slow / attention_factor), n_experts,
    n_experts_per_tok, experts_held (lo, hi), rms_eps

``params`` is the model's tree (``layers``: n1, n2, wq, wk, wv, wo, router,
w_gate, w_up, w_down; ``norm_f``; ``head`` [classes, hidden]).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rotary(T: int, head_dim: int, theta: float, yarn=None) -> tuple:
    """cos, sin [T, head_dim] (float64 -> float32): angle(t, i) = t *
    theta**(-2i/d) on dimension pairs (i, i + d/2); YaRN blends each
    frequency with itself / factor by a linear ramp between the correction
    dimensions of beta_fast and beta_slow turns within the original
    length, and scales cos and sin by attention_factor."""
    half = head_dim // 2
    i = np.arange(half, dtype=np.float64)
    inv = theta ** (-2.0 * i / head_dim)
    scale = 1.0
    if yarn is not None:
        def dim_of(turns):
            return head_dim * math.log(
                yarn["original_max_position_embeddings"]
                / (turns * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(dim_of(yarn["beta_fast"])), 0)
        high = min(math.ceil(dim_of(yarn["beta_slow"])), head_dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
        inv = (inv / yarn["factor"]) * ramp + inv * (1.0 - ramp)
        scale = yarn["attention_factor"]
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=1)
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def turn(x, cos, sin):
    """x [T, heads, d] by the rotary tables."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def attention(sizes: dict, lp: dict, h, kind: str):
    """h [T, hidden] -> [T, hidden]: one sequence, dense mask."""
    T = h.shape[0]
    nq, nkv, d = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    sliding = kind == "sliding_attention"
    cos, sin = rotary(T, d, sizes["rope_theta"],
                      None if sliding else sizes["yarn"])
    q = turn((h @ lp["wq"]).reshape(T, nq, d), cos, sin)
    k = turn((h @ lp["wk"]).reshape(T, nkv, d), cos, sin)
    v = (h @ lp["wv"]).reshape(T, nkv, d)
    k = jnp.repeat(k, nq // nkv, axis=1)  # query head h reads kv head h//g
    v = jnp.repeat(v, nq // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = j <= i
    if sliding:
        mask &= i - j < sizes["window"]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(T, nq * d) @ lp["wo"]


def routed_layer(sizes: dict, lp: dict, h, held=None):
    """h [N, hidden] -> the part of the routed layer that the experts
    ``held = (lo, hi)`` give (default: ``sizes["experts_held"]``);
    ``lp["w_gate"]`` etc. hold exactly those experts.  Softmax over all
    experts, the k largest renormalised to sum 1, each held expert applied
    to every token with a zero weight where it is not among them."""
    lo, hi = held or sizes["experts_held"]
    k = sizes["n_experts_per_tok"]
    probs = jax.nn.softmax(h @ lp["router"], axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(lo, hi):
        w = jnp.where(top_e == e, top_w, 0.0).sum(axis=-1)
        i = e - lo
        act = jax.nn.silu(h @ lp["w_gate"][i]) * (h @ lp["w_up"][i])
        y = y + w[:, None] * (act @ lp["w_down"][i])
    return y


def sequence_loss(sizes: dict, params: dict, x, target):
    """x [T, hidden] one sequence's input embeddings; target [T] int: the
    class of the token at t + 1, -1 where there is none.  Returns (sum of
    the cross-entropies, how many)."""
    eps = sizes["rms_eps"]
    for lp, kind in zip(params["layers"], sizes["layer_types"]):
        x = x + attention(sizes, lp, rms_norm(x, lp["n1"], eps), kind)
        x = x + routed_layer(sizes, lp, rms_norm(x, lp["n2"], eps))
    logits = rms_norm(x, params["norm_f"], eps) @ params["head"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    scored = target >= 0
    picked = jnp.take_along_axis(
        logp, jnp.where(scored, target, 0)[:, None], axis=1)[:, 0]
    return -(picked * scored).sum(), scored.sum()


def loss(sizes: dict, params: dict, rows, seq_pos, key_class):
    """The program's inputs, plainly: ``rows`` [K, 2 + hidden] one pulled
    row an occurrence, ``seq_pos`` [B, T] each sequence's occurrences in
    order (K = padding), ``key_class`` [K] each occurrence's class.  Mean
    over all positions with a next token of the softmax cross-entropy
    against that token's class."""
    with jax.default_matmul_precision("highest"):
        K = rows.shape[0]
        emb = jnp.concatenate(
            [rows[:, 2:], jnp.zeros((1, rows.shape[1] - 2), rows.dtype)])
        cls = jnp.concatenate([key_class, jnp.full((1,), -1, jnp.int32)])
        x = emb[seq_pos]  # [B, T, hidden]
        c = cls[seq_pos]
        target = jnp.concatenate(
            [c[:, 1:], jnp.full((c.shape[0], 1), -1, jnp.int32)], axis=1)
        sums, counts = jax.lax.map(
            lambda a: sequence_loss(sizes, params, a[0], a[1]), (x, target))
        return sums.sum() / jnp.maximum(counts.sum(), 1)
