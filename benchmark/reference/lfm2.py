"""LFM2-24B-A2B's decoder, plainly, as one chip's share of a layout in
which 8 chips share each layer: the reference of the ``lfm2`` model name.

From the model's published ``config.json`` (``model_type`` lfm2_moe; the
configuration's file holds it whole; ``cfg`` below is that file).  ``n`` =
RMSNorm, eps ``norm_eps`` 1e-5, learned scale; no biases (``conv_bias``
false); x [T, hidden].  The layers held here are ``layers_held``, numbered
as published: layer l's operator is ``layer_types[l]`` and its
feed-forward is dense where l < ``num_dense_layers``.  Each layer is
``x += op(n1 x); x += ffn(n2 x)``:

  op, "conv" (the gated short convolution), h = n1(x):
      h W_in -> [T, 3 * 2048], split B | C | u
      z = B * u
      c_t = sum_{j < 3} w_j * z_{t-2+j}: a causal depthwise convolution
      over time of conv_L_cache = 3 taps, one weight a channel and tap,
      zeros before the sequence's first token (w_2 weighs z_t itself)
      x += (C * c) W_out                                  ([2048, 2048])
  op, "full_attention", h = n1(x):
      q = h Wq -> [T, 32, 64], k = h Wk -> [T, 8, 64], v = h Wv likewise
      q = n_q(q), k = n_k(k): an RMSNorm with a learned scale over the 64
      floats of EACH query head and EACH key head, before the rotary code
      rotary on the whole head, theta 1,000,000, no scaling, dimension i
      paired with i + 32 (rotate-half)
      query head h reads key-value head h // 4
      x += softmax(causal(q k^T / sqrt(64))) v  Wo
  ffn, h = n2(x):
      dense (l < num_dense_layers 2):
          x += Wdown(silu(Wgate h) * Wup h), width 11,776
      sparse:
          s = sigmoid(h Wr) over all 64 experts; the 4 with the largest
          s + b (b = expert_bias, use_expert_bias); w_e = s_e / (sum of
          the chosen s + 1e-20) (norm_topk_prob) * routed_scaling_factor
          (1): b is in the choice and nowhere else
          x += sum over e chosen and HELD HERE (0 .. num_experts_held - 1)
               of w_e * Wdown_e(silu(Wgate_e h) * Wup_e h)  (width 1,536)
          What the absent experts would add is left out, here as in the
          program, and that partial sum goes on; no shared expert.
  logits = n_f(x) Whead^T over the vocab_size classes held here; loss =
  mean over the positions that have a next token of the softmax
  cross-entropy against that token's class (its key's rank among the
  table's sorted keys: ``key_rank[inv]`` of the next occurrence).

Written to fit beside the four copies of 469 M parameters a step holds
(common.make_step donates its state: parameters, Adam's two moments and
the gradient, 16 bytes a parameter): one sequence at a time (``lax.map``),
every layer rematerialised (``jax.checkpoint``), attention one head at a time
(``lax.map`` over the heads, each rematerialised: one [T, T] block of
scores alive), each held expert and each block of ``LOGIT_ROWS`` rows of
logits rematerialised.  The arithmetic is the dense one: a [T, T] mask
from positions, every held expert on every token, the convolution as
``conv_L_cache`` copies of z moved down the time axis.  Every product goes
through ``ops``; the gates and the taps are elementwise float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LOGIT_ROWS = 1024


def sizes(cfg: dict) -> dict:
    return {
        "H": cfg["hidden_size"], "nq": cfg["num_attention_heads"],
        "nkv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "K": cfg["conv_L_cache"], "Fd": cfg["intermediate_size"],
        "F": cfg["moe_intermediate_size"], "E": cfg["num_experts"],
        "held": cfg["num_experts_held"], "k": cfg["num_experts_per_tok"],
        "V": cfg["vocab_size"], "T": cfg["feed"]["max_seq_len"],
    }


def layers(cfg: dict) -> list:
    """(operator kind, whether the feed-forward is dense) of each layer
    held here, by its published number."""
    if len(cfg["layers_held"]) != cfg["num_hidden_layers"]:
        raise ValueError("layers_held does not list num_hidden_layers layers")
    return [(cfg["layer_types"][l], l < cfg["num_dense_layers"])
            for l in cfg["layers_held"]]


def init_params(cfg: dict, key) -> dict:
    """The program's tree (models/decoder_lm.py ``init``) for this
    description: normal weights scaled by 1/sqrt(fan-in), the taps by
    1/sqrt(3), norm scales 1, the selection bias normal * 0.1 (wide enough
    to change some choices)."""
    z = sizes(cfg)
    H, d = z["H"], z["d"]

    def w(k, *shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    kinds = layers(cfg)
    keys = jax.random.split(key, len(kinds) + 1)
    out = []
    for (op, dense), lk in zip(kinds, keys[1:]):
        ks = jax.random.split(lk, 9)
        lp = {"n1": jnp.ones((H,), jnp.float32),
              "n2": jnp.ones((H,), jnp.float32)}
        if op == "conv":
            lp.update(conv_in=w(ks[0], H, 3 * H, fan_in=H),
                      conv_w=w(ks[1], z["K"], H, fan_in=z["K"]),
                      conv_out=w(ks[2], H, H, fan_in=H))
        elif op == "full_attention":
            lp.update(wq=w(ks[0], H, z["nq"] * d, fan_in=H),
                      wk=w(ks[1], H, z["nkv"] * d, fan_in=H),
                      wv=w(ks[2], H, z["nkv"] * d, fan_in=H),
                      wo=w(ks[3], z["nq"] * d, H, fan_in=z["nq"] * d),
                      q_norm=jnp.ones((d,), jnp.float32),
                      k_norm=jnp.ones((d,), jnp.float32))
        else:
            raise ValueError(f"the reference has no operator {op!r}")
        if dense:
            lp.update(mlp_gate=w(ks[4], H, z["Fd"], fan_in=H),
                      mlp_up=w(ks[5], H, z["Fd"], fan_in=H),
                      mlp_down=w(ks[6], z["Fd"], H, fan_in=z["Fd"]))
        else:
            lp.update(
                router=w(ks[4], H, z["E"], fan_in=H),
                router_bias=0.1 * jax.random.normal(
                    ks[5], (z["E"],), jnp.float32),
                w_gate=w(ks[6], z["held"], H, z["F"], fan_in=H),
                w_up=w(ks[7], z["held"], H, z["F"], fan_in=H),
                w_down=w(ks[8], z["held"], z["F"], H, fan_in=z["F"]))
        out.append(lp)
    return {"layers": out, "norm_f": jnp.ones((H,), jnp.float32),
            "head": w(keys[0], z["V"], H, fan_in=H)}


def rotary(cfg: dict, T: int) -> tuple:
    """cos, sin [T, head_dim]: angle(t, i) = t * theta ** (-2i / head_dim)
    for i < head_dim / 2, laid out twice (dimension i turns with
    i + head_dim / 2)."""
    rp = cfg["rope_parameters"]
    if rp["rope_type"] != "default":
        raise ValueError("the reference has no scaled rotary code")
    d = cfg["head_dim"]
    inv = float(rp["rope_theta"]) ** (
        -2.0 * np.arange(d // 2, dtype=np.float64) / d)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=1)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def turn(x, cos, sin):
    """x [T, heads, head_dim] turned by rotate-half."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def swiglu(ops, h, w_gate, w_up, w_down):
    return ops.dot(jax.nn.silu(ops.dot(h, w_gate)) * ops.dot(h, w_up), w_down)


def conv_mixer(cfg: dict, ops, lp: dict, h):
    """The gated short convolution of one sequence, h [T, hidden]."""
    K, T = cfg["conv_L_cache"], h.shape[0]
    if cfg["conv_bias"]:
        raise ValueError("the reference's convolution has no bias")
    b, c, u = jnp.split(ops.dot(h, lp["conv_in"]), 3, axis=-1)
    z = b * u
    t = jnp.arange(T)[:, None]
    y = jnp.zeros_like(z)
    for j in range(K):  # tap j weighs the token K - 1 - j steps back
        back = K - 1 - j
        y = y + lp["conv_w"][j] * jnp.where(
            t >= back, jnp.roll(z, back, axis=0), 0.0)
    return ops.dot(c * y, lp["conv_out"])


def attention(cfg: dict, ops, lp: dict, h):
    z = sizes(cfg)
    T, nq, nkv, d = h.shape[0], z["nq"], z["nkv"], z["d"]
    eps = cfg["norm_eps"]
    cos, sin = rotary(cfg, T)
    q = ops.dot(h, lp["wq"]).reshape(T, nq, d)
    k = ops.dot(h, lp["wk"]).reshape(T, nkv, d)
    v = ops.dot(h, lp["wv"]).reshape(T, nkv, d)
    q = turn(rms_norm(q, lp["q_norm"], eps), cos, sin)
    k = turn(rms_norm(k, lp["k_norm"], eps), cos, sin)
    # every query head beside the key-value head it reads
    k, v = (jnp.repeat(a, nq // nkv, axis=1) for a in (k, v))
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    @jax.checkpoint
    def head(qkv):  # one head: three [T, 64]
        qh, kh, vh = qkv
        s = ops.einsum("qd,kd->qk", qh, kh) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return ops.einsum("qk,kd->qd", p, vh)

    out = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return ops.dot(out.transpose(1, 0, 2).reshape(T, nq * d), lp["wo"])


def routed(cfg: dict, ops, lp: dict, h):
    """The held experts' part of the routed sum."""
    z = sizes(cfg)
    if not cfg["use_expert_bias"] or not cfg["norm_topk_prob"]:
        raise ValueError("the reference chooses by sigmoid scores + a bias "
                         "and renormalises the chosen")
    s = jax.nn.sigmoid(ops.dot(h, lp["router"]))
    _, top_e = jax.lax.top_k(s + lp["router_bias"], z["k"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    top_w = cfg["routed_scaling_factor"] * top_s / (
        top_s.sum(axis=-1, keepdims=True) + 1e-20)

    @jax.checkpoint
    def expert(h, w, w_gate, w_up, w_down):
        return w[:, None] * swiglu(ops, h, w_gate, w_up, w_down)

    y = jnp.zeros_like(h)
    for e in range(z["held"]):  # experts 0 .. held-1 live here
        w = jnp.where(top_e == e, top_w, 0.0).sum(axis=-1)
        y = y + expert(h, w, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
    return y


def sequence_loss(cfg: dict, ops, params: dict, x, target):
    """x [T, hidden]; target [T]: the next token's class, -1 where none.
    Returns (sum of cross-entropies, how many)."""
    eps = cfg["norm_eps"]

    def layer(lp, x, op, dense):
        h = rms_norm(x, lp["n1"], eps)
        x = x + (conv_mixer(cfg, ops, lp, h) if op == "conv"
                 else attention(cfg, ops, lp, h))
        h = rms_norm(x, lp["n2"], eps)
        if dense:
            return x + swiglu(ops, h, lp["mlp_gate"], lp["mlp_up"],
                              lp["mlp_down"])
        return x + routed(cfg, ops, lp, h)

    for lp, (op, dense) in zip(params["layers"], layers(cfg)):
        x = jax.checkpoint(layer, static_argnums=(2, 3))(lp, x, op, dense)
    scored = target >= 0

    @jax.checkpoint
    def block(xt):  # LOGIT_ROWS rows of logits at a time
        xb, tb, sb = xt
        logits = ops.dot(rms_norm(xb, params["norm_f"], eps),
                         params["head"].T)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.where(sb, tb, 0)[:, None], axis=1)[:, 0]
        return -(picked * sb).sum()

    T = x.shape[0]
    rows = math.gcd(T, LOGIT_ROWS)
    sums = jax.lax.map(block, tuple(
        a.reshape(T // rows, rows, *a.shape[1:]) for a in (x, target, scored)))
    return sums.sum(), scored.sum()


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
