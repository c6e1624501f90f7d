"""Kimi-Linear-48B-A3B's decoder, plainly, as one chip's share of a layout
in which 32 chips share each layer: the reference of the ``kimi_linear``
model name.

From the model's published ``config.json`` (``model_type`` kimi_linear; the
configuration's file holds it whole; ``cfg`` below is that file).  ``n`` =
RMSNorm, eps ``rms_norm_eps`` 1e-5, learned scale; no biases but
``dt_bias``; x [T, hidden].  The layers held here are ``layers_held``,
numbered from 1 as ``linear_attn_config`` numbers them: layer l's operator
is KDA where l is in ``kda_layers`` and latent attention where it is in
``full_attn_layers``, its feed-forward dense where l <=
``first_k_dense_replace``.  Each layer is ``x += op(n1 x); x += ffn(n2 x)``:

  op, KDA (a gated delta rule with a decay a channel), h = n1(x), 32 heads
  of d = 128:
      q~, k~, v~ = h W_q, h W_k, h W_v            ([2304, 4096] each)
      each through a causal depthwise convolution over time of
      short_conv_kernel_size = 4 taps, one weight a channel and tap, zeros
      before the sequence's first token (w_3 weighs the token itself), then
      SiLU
      per head: q_t = q~_t / sqrt(|q~_t|^2 + 1e-6) / sqrt(d),
                k_t = k~_t / sqrt(|k~_t|^2 + 1e-6), v_t = v~_t
      g_t = -exp(A_log_h) * softplus((h W_fa) W_fb + dt_bias)   <= 0: the
            logarithm of the decay, one number a CHANNEL (128 a head)
      beta_t = sigmoid(h W_beta)                                 one a head
      state S [128, 128] (key x value) a head, S_0 = 0, token by token:
          S~_t = diag(exp g_t) S_{t-1}
          S_t  = S~_t + beta_t k_t (v_t - S~_t^T k_t)^T
          o_t  = S_t^T q_t
      x += (n_o(o_t) * sigmoid((h W_ga) W_gb)) W_o: n_o an RMSNorm with a
      learned scale over the 128 floats of each head
  op, latent attention (MLA) with NO positional code (mla_use_nope):
      q = h Wq -> [T, 32, 192]; h Wkv_a -> [T, 576], split the latent c
      (512) | k_r (64: ONE per token, shared by all 32 heads)
      n_kv(c) Wkv_b -> [T, 32, 256], split k_nope (128) | v (128)
      k = [k_nope | k_r for every head]; nothing is turned
      x += softmax(causal(q k^T / sqrt(192))) v  Wo      ([T, 32 * 128])
  ffn, h = n2(x):
      dense (l <= first_k_dense_replace 1):
          x += Wdown(silu(Wgate h) * Wup h), width 9,216
      sparse:
          s = sigmoid(h Wr) over all 256 experts; the 8 with the largest
          s + b (b the selection bias; num_expert_group = topk_group = 1,
          so the grouped choice is a plain top-8); w_e = 2.446 * s_e /
          (sum of the chosen s + 1e-20) (moe_renormalize): b is in the
          choice and nowhere else
          x += sum over e chosen and HELD HERE (0 .. num_experts_held - 1)
               of w_e * Wdown_e(silu(Wgate_e h) * Wup_e h)  (width 1,024)
             + shared(h): one SwiGLU of width 1 * 1,024, unweighted
          What the absent experts would add is left out, here as in the
          program, and that partial sum goes on; the shared expert is what
          every share computes alike.
  logits = n_f(x) Whead^T over the vocab_size classes held here; loss =
  mean over the positions that have a next token of the softmax
  cross-entropy against that token's class (its key's rank among the
  table's sorted keys: ``key_rank[inv]`` of the next occurrence).

Written to fit beside the four copies of 555 M parameters a step holds
(common.make_step donates its state: 16 bytes a parameter): one sequence
at a time (``lax.map``; a step of one sequence, the cell's, runs it
without the loop, whose backward pass would carry a second copy of the
gradient), every layer rematerialised (``jax.checkpoint``),
the recurrence a ``lax.scan`` over tokens in blocks of ``STATE_BLOCK``,
each block rematerialised -- the backward pass keeps the state at every
64th token (128 x 2.1 MB a layer at 8,192 tokens) and 64 states of the
block it is in, not 8,192 --, attention one head at a time (one [T, T]
block of scores alive), each held expert and each block of ``LOGIT_ROWS``
rows of logits rematerialised.  The arithmetic is the plain one: the state
updated token by token, a [T, T] mask from positions, every held expert on
every token, the convolution as slices of its input behind K - 1 rows
of zeros.  Every product goes through ``ops``: the projections, the state's
read ``S^T k``, its rank-one update and its output ``S^T q``; decays,
gates, norms and taps are elementwise float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LOGIT_ROWS = 1024
STATE_BLOCK = 64


def sizes(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    return {
        "H": cfg["hidden_size"], "nh": cfg["num_attention_heads"],
        "rank": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
        "kh": lin["num_heads"], "kd": lin["head_dim"],
        "K": lin["short_conv_kernel_size"], "R": cfg["kda_gate_rank"],
        "Fd": cfg["intermediate_size"], "F": cfg["moe_intermediate_size"],
        "Fs": cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        "E": cfg["num_experts"], "held": cfg["num_experts_held"],
        "k": cfg["num_experts_per_token"], "V": cfg["vocab_size"],
        "T": cfg["feed"]["max_seq_len"],
    }


def layers(cfg: dict) -> list:
    """(operator kind, whether the feed-forward is dense) of each layer
    held here, by its published number (from 1)."""
    lin = cfg["linear_attn_config"]
    if len(cfg["layers_held"]) != cfg["num_hidden_layers"]:
        raise ValueError("layers_held does not list num_hidden_layers layers")
    out = []
    for l in cfg["layers_held"]:
        if (l in lin["kda_layers"]) == (l in lin["full_attn_layers"]):
            raise ValueError(f"layer {l} is not one of KDA and full attention")
        out.append(("kda" if l in lin["kda_layers"] else "mla",
                    l <= cfg["first_k_dense_replace"]))
    return out


def init_params(cfg: dict, key) -> dict:
    """The program's tree (models/decoder_lm.py ``init``) for this
    description: normal weights scaled by 1/sqrt(fan-in), the taps by
    1/sqrt(4), norm scales 1, the selection bias normal * 0.1 (wide enough
    to change some choices), and the decays as the family seeds them: A
    uniform in [1, 16) (``A_log`` its logarithm) and ``dt_bias`` the
    inverse softplus of a log-uniform draw from [1e-3, 1e-1), so that the
    seeded decays span weak to strong."""
    z = sizes(cfg)
    H, nh, W = z["H"], z["nh"], z["kh"] * z["kd"]

    def w(k, *shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)

    kinds = layers(cfg)
    keys = jax.random.split(key, len(kinds) + 1)
    out = []
    for (op, dense), lk in zip(kinds, keys[1:]):
        n_op = 15 if op == "kda" else 4
        ks = jax.random.split(lk, n_op + (3 if dense else 8))
        lp = {"n1": jnp.ones((H,), jnp.float32),
              "n2": jnp.ones((H,), jnp.float32)}
        if op == "kda":
            dt = jnp.exp(jax.random.uniform(
                ks[9], (W,), jnp.float32, np.log(1e-3), np.log(1e-1)))
            lp.update(
                kda_q=w(ks[0], H, W, fan_in=H), kda_k=w(ks[1], H, W, fan_in=H),
                kda_v=w(ks[2], H, W, fan_in=H),
                kda_conv_q=w(ks[3], z["K"], W, fan_in=z["K"]),
                kda_conv_k=w(ks[4], z["K"], W, fan_in=z["K"]),
                kda_conv_v=w(ks[5], z["K"], W, fan_in=z["K"]),
                kda_fa=w(ks[6], H, z["R"], fan_in=H),
                kda_fb=w(ks[7], z["R"], W, fan_in=z["R"]),
                kda_A_log=jnp.log(jax.random.uniform(
                    ks[8], (z["kh"],), jnp.float32, 1.0, 16.0)),
                kda_dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                kda_beta=w(ks[10], H, z["kh"], fan_in=H),
                kda_ga=w(ks[11], H, z["R"], fan_in=H),
                kda_gb=w(ks[12], z["R"], W, fan_in=z["R"]),
                kda_o_norm=jnp.ones((z["kd"],), jnp.float32),
                kda_o=w(ks[14], W, H, fan_in=W))
        else:
            lp.update(
                n_kv=jnp.ones((z["rank"],), jnp.float32),
                wq=w(ks[0], H, nh * (z["nope"] + z["rope"]), fan_in=H),
                wkv_a=w(ks[1], H, z["rank"] + z["rope"], fan_in=H),
                wkv_b=w(ks[2], z["rank"], nh * (z["nope"] + z["dv"]),
                        fan_in=z["rank"]),
                wo=w(ks[3], nh * z["dv"], H, fan_in=nh * z["dv"]))
        ks = ks[n_op:]
        if dense:
            lp.update(mlp_gate=w(ks[0], H, z["Fd"], fan_in=H),
                      mlp_up=w(ks[1], H, z["Fd"], fan_in=H),
                      mlp_down=w(ks[2], z["Fd"], H, fan_in=z["Fd"]))
        else:
            lp.update(
                router=w(ks[0], H, z["E"], fan_in=H),
                router_bias=0.1 * jax.random.normal(
                    ks[1], (z["E"],), jnp.float32),
                w_gate=w(ks[2], z["held"], H, z["F"], fan_in=H),
                w_up=w(ks[3], z["held"], H, z["F"], fan_in=H),
                w_down=w(ks[4], z["held"], z["F"], H, fan_in=z["F"]),
                shared_gate=w(ks[5], H, z["Fs"], fan_in=H),
                shared_up=w(ks[6], H, z["Fs"], fan_in=H),
                shared_down=w(ks[7], z["Fs"], H, fan_in=z["Fs"]))
        out.append(lp)
    return {"layers": out, "norm_f": jnp.ones((H,), jnp.float32),
            "head": w(keys[0], z["V"], H, fan_in=H)}


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def swiglu(ops, h, w_gate, w_up, w_down):
    return ops.dot(jax.nn.silu(ops.dot(h, w_gate)) * ops.dot(h, w_up), w_down)


def short_conv(cfg: dict, lp_w, u):
    """u [T, W] through the causal depthwise convolution (zeros before the
    first token), then SiLU: K - 1 rows of zeros in front, and tap j reads
    the rows j .. j + T - 1 of that, which are the tokens K - 1 - j steps
    back.  (Not ``jnp.roll`` and a mask: in one program with the product
    before it the TPU's compiler rolls 8,192 rows in blocks of 1,024, and
    rows 1,024 k, k >= 1, read the wrong tokens -- PERF.md section 6,
    PR 39.)"""
    K, T = cfg["linear_attn_config"]["short_conv_kernel_size"], u.shape[0]
    z = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    return jax.nn.silu(sum(lp_w[j] * z[j:j + T] for j in range(K)))


def delta_rule(ops, q, k, v, g, beta):
    """The recurrence as written above, token by token.  q, k, g [T, nh,
    d], v [T, nh, d], beta [T, nh]; returns o [T, nh, d]."""
    T, nh, d = q.shape

    def token(S, x):  # S [nh, d(key), d(value)]
        q, k, v, g, b = x
        S = jnp.exp(g)[:, :, None] * S
        seen = ops.einsum("hkv,hk->hv", S, k)
        S = S + ops.einsum("hk,hv->hkv", b[:, None] * k, v - seen)
        return S, ops.einsum("hkv,hk->hv", S, q)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    rows = math.gcd(T, STATE_BLOCK)
    _, o = jax.lax.scan(
        block, jnp.zeros((nh, d, v.shape[-1]), jnp.float32),
        tuple(a.reshape(T // rows, rows, *a.shape[1:])
              for a in (q, k, v, g, beta)))
    return o.reshape(T, nh, -1)


def kda(cfg: dict, ops, lp: dict, h):
    """The KDA operator of one sequence, h [T, hidden] = n1(x)."""
    z = sizes(cfg)
    T, nh, d = h.shape[0], z["kh"], z["kd"]

    def heads(a):
        return a.reshape(T, nh, d)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    q = heads(short_conv(cfg, lp["kda_conv_q"], ops.dot(h, lp["kda_q"])))
    k = heads(short_conv(cfg, lp["kda_conv_k"], ops.dot(h, lp["kda_k"])))
    v = heads(short_conv(cfg, lp["kda_conv_v"], ops.dot(h, lp["kda_v"])))
    g = -jnp.exp(lp["kda_A_log"])[:, None] * jax.nn.softplus(heads(
        ops.dot(ops.dot(h, lp["kda_fa"]), lp["kda_fb"]) + lp["kda_dt_bias"]))
    beta = jax.nn.sigmoid(ops.dot(h, lp["kda_beta"]))
    o = delta_rule(ops, unit(q) / math.sqrt(d), unit(k), v, g, beta)
    gate = jax.nn.sigmoid(heads(
        ops.dot(ops.dot(h, lp["kda_ga"]), lp["kda_gb"])))
    o = rms_norm(o, lp["kda_o_norm"], cfg["rms_norm_eps"]) * gate
    return ops.dot(o.reshape(T, nh * d), lp["kda_o"])


def attention(cfg: dict, ops, lp: dict, h):
    """Latent attention with no positional code, h [T, hidden] = n1(x)."""
    z = sizes(cfg)
    T, nh, nope, rank = h.shape[0], z["nh"], z["nope"], z["rank"]
    if not cfg["mla_use_nope"] or cfg["q_lora_rank"] is not None:
        raise ValueError("the reference's latent attention has no "
                         "positional code and no low-rank query")
    q = ops.dot(h, lp["wq"]).reshape(T, nh, nope + z["rope"])
    kv_a = ops.dot(h, lp["wkv_a"])
    kv = ops.dot(rms_norm(kv_a[:, :rank], lp["n_kv"], cfg["rms_norm_eps"]),
                 lp["wkv_b"]).reshape(T, nh, nope + z["dv"])
    k = jnp.concatenate(  # the 64-wide slice: one for all heads, unturned
        [kv[..., :nope],
         jnp.broadcast_to(kv_a[:, None, rank:], (T, nh, z["rope"]))], axis=-1)
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    @jax.checkpoint
    def head(qkv):  # one head: [T, 192], [T, 192], [T, 128]
        qh, kh, vh = qkv
        s = ops.einsum("qd,kd->qk", qh, kh) / math.sqrt(qh.shape[-1])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return ops.einsum("qk,kd->qd", p, vh)

    out = jax.lax.map(head, tuple(
        a.transpose(1, 0, 2) for a in (q, k, kv[..., nope:])))
    return ops.dot(out.transpose(1, 0, 2).reshape(T, nh * z["dv"]), lp["wo"])


def routed(cfg: dict, ops, lp: dict, h):
    """The held experts' part of the routed sum, and the shared expert."""
    z = sizes(cfg)
    if cfg["moe_router_activation_func"] != "sigmoid" or cfg[
            "num_expert_group"] != 1 or cfg["topk_group"] != 1 or not cfg[
            "moe_renormalize"]:
        raise ValueError("the reference routes by sigmoid scores, one "
                         "group, the chosen renormalised")
    s = jax.nn.sigmoid(ops.dot(h, lp["router"]))
    _, top_e = jax.lax.top_k(s + lp["router_bias"], z["k"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    top_w = cfg["routed_scaling_factor"] * top_s / (
        top_s.sum(axis=-1, keepdims=True) + 1e-20)

    @jax.checkpoint
    def expert(h, w, w_gate, w_up, w_down):
        return w[:, None] * swiglu(ops, h, w_gate, w_up, w_down)

    y = swiglu(ops, h, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    for e in range(z["held"]):  # experts 0 .. held-1 live here
        w = jnp.where(top_e == e, top_w, 0.0).sum(axis=-1)
        y = y + expert(h, w, lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])
    return y


def sequence_loss(cfg: dict, ops, params: dict, x, target):
    """x [T, hidden]; target [T]: the next token's class, -1 where none.
    Returns (sum of cross-entropies, how many)."""
    eps = cfg["rms_norm_eps"]

    def layer(lp, x, op, dense):
        h = rms_norm(x, lp["n1"], eps)
        x = x + (kda if op == "kda" else attention)(cfg, ops, lp, h)
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
    x = x.reshape(B, T, -1)
    if B == 1:  # no loop: its backward pass would carry a second gradient
        total, count = sequence_loss(cfg, ops, params, x[0], target[0])
        return total / jnp.maximum(count, 1)
    sums, counts = jax.lax.map(
        lambda a: sequence_loss(cfg, ops, params, a[0], a[1]), (x, target))
    return sums.sum() / jnp.maximum(counts.sum(), 1)
