"""Mellum2-12B-A2.5B's decoder, plainly, as one chip's share of a layout in
which 8 chips share each layer: the reference of the ``mellum2`` model name.

From the model's published ``config.json`` (the configuration's file holds
it whole; ``cfg`` below is that file):

  layer l, x [T, hidden]:
      h = n1(x);  q, k, v = h Wq, h Wk, h Wv  (32 query heads over 4
      key-value heads of 128; no biases)
      q, k <- rotary on the whole head dimension, pairs (i, i + 64), theta
      500,000: plain on ``sliding_attention`` layers, YaRN on
      ``full_attention`` ones (per dimension a blend of theta**(-2i/128)
      and that / factor by the linear ramp between the correction
      dimensions of beta_fast and beta_slow turns within the original
      length; cos and sin scaled by attention_factor)
      x += softmax(mask(q k^T / sqrt(128))) v Wo     mask: key j <= query
      i, and on sliding layers i - j < sliding_window
      h = n2(x);  p = softmax(h Wr) over all 64 experts; the 8 largest,
      renormalised to sum 1 (norm_topk_prob); for each expert e HELD HERE
      (0 .. num_experts_held - 1):
      x += w_e * Wdown_e(silu(Wgate_e h) * Wup_e h), w_e = 0 where e is
      not among the token's 8.  What the absent experts would add is left
      out, here as in the program, and that partial sum goes on.
  n = RMSNorm, eps 1e-6, learned scale.
  logits = n_f(x) Whead^T over the vocab_size classes held here; loss =
  mean over the positions that have a next token of the softmax
  cross-entropy against that token's class (its key's rank among the
  table's sorted keys: ``key_rank[inv]`` of the next occurrence).

Written to fit beside the four copies of the parameters a step holds
(common.make_step donates its state: parameters, Adam's two moments and
the gradient, 16 bytes a parameter): one sequence at a time (``lax.map``),
every layer, every group of four query heads and the head with its loss
rematerialised (``jax.checkpoint``), so the largest tensor alive is one
[4, T, T] block of scores and ``lax.map`` keeps a sequence's [T, hidden]
inputs, not its [T, V] logits.  The arithmetic is the dense one: a [T, T]
mask from positions, every held expert on every token.  Every product goes
through ``ops``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HEADS_AT_ONCE = 4


def sizes(cfg: dict) -> dict:
    return {
        "H": cfg["hidden_size"], "nq": cfg["num_attention_heads"],
        "nkv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "F": cfg["moe_intermediate_size"], "E": cfg["num_experts"],
        "held": cfg["num_experts_held"], "k": cfg["num_experts_per_tok"],
        "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
        "T": cfg["feed"]["max_seq_len"],
    }


def init_params(cfg: dict, key) -> dict:
    """The program's tree (models/decoder_lm.py ``init``): normal weights
    scaled by 1/sqrt(fan-in), norm scales 1."""
    z = sizes(cfg)
    H, F, hq, hkv = z["H"], z["F"], z["nq"] * z["d"], z["nkv"] * z["d"]

    def w(k, *shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    keys = jax.random.split(key, z["L"] + 1)
    layers = []
    for lk in keys[1:]:
        ks = jax.random.split(lk, 8)
        layers.append({
            "n1": jnp.ones((H,), jnp.float32),
            "n2": jnp.ones((H,), jnp.float32),
            "wq": w(ks[0], H, hq, fan_in=H),
            "wk": w(ks[1], H, hkv, fan_in=H),
            "wv": w(ks[2], H, hkv, fan_in=H),
            "wo": w(ks[3], hq, H, fan_in=hq),
            "router": w(ks[4], H, z["E"], fan_in=H),
            "w_gate": w(ks[5], z["held"], H, F, fan_in=H),
            "w_up": w(ks[6], z["held"], H, F, fan_in=H),
            "w_down": w(ks[7], z["held"], F, H, fan_in=F),
        })
    return {"layers": layers, "norm_f": jnp.ones((H,), jnp.float32),
            "head": w(keys[0], z["V"], H, fan_in=H)}


def rotary(cfg: dict, kind: str, T: int) -> tuple:
    """cos, sin [T, head_dim] of ``rope_parameters[kind]``."""
    rp = cfg["rope_parameters"][kind]
    d, theta = cfg["head_dim"], float(rp["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    inv, scale = theta ** (-2.0 * i / d), 1.0
    if rp["rope_type"] == "yarn":
        def dim_of(turns):
            return d * math.log(rp["original_max_position_embeddings"]
                                / (turns * 2 * math.pi)) / (
                                    2 * math.log(theta))

        low = max(math.floor(dim_of(rp["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rp["beta_slow"])), d - 1)
        ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
        inv = inv / rp["factor"] * ramp + inv * (1.0 - ramp)
        scale = rp["attention_factor"]
    elif rp["rope_type"] != "default":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=1)
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def turn(x, cos, sin):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def attention(cfg: dict, ops, lp: dict, h, kind: str):
    z = sizes(cfg)
    T, nq, nkv, d = h.shape[0], z["nq"], z["nkv"], z["d"]
    cos, sin = rotary(cfg, kind, T)
    q = turn(ops.dot(h, lp["wq"]).reshape(T, nq, d), cos, sin)
    k = turn(ops.dot(h, lp["wk"]).reshape(T, nkv, d), cos, sin)
    v = ops.dot(h, lp["wv"]).reshape(T, nkv, d)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = j <= i
    if kind == "sliding_attention":
        mask &= i - j < cfg["sliding_window"]

    @jax.checkpoint
    def heads(qh, kh, vh):  # [T, n, d] queries on ONE key-value head
        s = ops.einsum("qhd,kd->hqk", qh, kh) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return ops.einsum("hqk,kd->qhd", p, vh)

    n = min(HEADS_AT_ONCE, nq // nkv)
    out = [heads(q[:, h0:h0 + n], k[:, h0 // (nq // nkv)],
                 v[:, h0 // (nq // nkv)]) for h0 in range(0, nq, n)]
    return ops.dot(jnp.concatenate(out, axis=1).reshape(T, nq * d), lp["wo"])


def routed(cfg: dict, ops, lp: dict, h):
    z = sizes(cfg)
    probs = jax.nn.softmax(ops.dot(h, lp["router"]), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, z["k"])
    top_w = top_w / top_w.sum(axis=-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(z["held"]):  # experts 0 .. held-1 live here
        w = jnp.where(top_e == e, top_w, 0.0).sum(axis=-1)
        act = jax.nn.silu(ops.dot(h, lp["w_gate"][e])) * ops.dot(
            h, lp["w_up"][e])
        y = y + w[:, None] * ops.dot(act, lp["w_down"][e])
    return y


def sequence_loss(cfg: dict, ops, params: dict, x, target):
    """x [T, hidden]; target [T]: the next token's class, -1 where none.
    Returns (sum of cross-entropies, how many)."""
    eps = cfg["rms_norm_eps"]
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]

    def layer(lp, x, kind):
        x = x + attention(cfg, ops, lp, rms_norm(x, lp["n1"], eps), kind)
        return x + routed(cfg, ops, lp, rms_norm(x, lp["n2"], eps))

    for lp, kind in zip(params["layers"], kinds):
        x = jax.checkpoint(layer, static_argnums=(2,))(lp, x, kind)
    scored = target >= 0

    @jax.checkpoint
    def head(x):  # else ``lax.map`` keeps every sequence's [T, V] logits
        logits = ops.dot(rms_norm(x, params["norm_f"], eps),
                         params["head"].T)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.where(scored, target, 0)[:, None], axis=1)[:, 0]
        return -(picked * scored).sum()

    return head(x), scored.sum()


def loss(cfg: dict, ops, params: dict, rows_occ, batch: dict):
    z = sizes(cfg)
    B, T = batch["B"], z["T"]
    # the occurrence (instance i, position p < T) is position p of
    # sequence i; padding goes to the overflow row
    taken = (batch["pos"] < T) & (batch["mask"] > 0)
    at = jnp.where(taken, batch["ins"] * T + batch["pos"], B * T)
    x = jax.ops.segment_sum(rows_occ[:, 2:], at, B * T + 1)[: B * T]
    cls = jnp.where(taken, batch["key_rank"][batch["inv"]], -1)
    cls = jnp.full((B * T + 1,), -1, jnp.int32).at[at].max(cls)[: B * T]
    cls = cls.reshape(B, T)
    target = jnp.concatenate(
        [cls[:, 1:], jnp.full((B, 1), -1, jnp.int32)], axis=1)
    sums, counts = jax.lax.map(
        lambda a: sequence_loss(cfg, ops, params, a[0], a[1]),
        (x.reshape(B, T, -1), target))
    return sums.sum() / jnp.maximum(counts.sum(), 1)
