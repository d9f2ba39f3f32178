"""Plain reference: the LFM2 sparse-expert decoder's forward pass in
straightforward jax.numpy float32 — no kernels, no grouping, independent of
paddle_tpu.  Follows the HF `modeling_lfm2_moe.py` of LiquidAI/LFM2-8B-A1B,
in the keys of its config.json (`layer_types` has one entry a layer).

With x the residual stream and every Linear without bias:

  block   h = x + Op(RMSNorm(x)); x = h + FFN(RMSNorm(h)); after the last
          layer one RMSNorm, then logits through the embedding matrix
          transposed (the head is tied).  RMSNorm: x * rsqrt(mean(x^2) +
          norm_eps) * w.
  conv    [B, C, X] = split3(W_in u); z = B * X; c_t = sum_{j<L} w[:, j] *
          z_{t-(L-1)+j} (depthwise, causal, zero history, L = conv_L_cache
          taps, no bias, NO activation); W_out (C * c).
  attn    q = W_q u as [heads, D], k, v as [kv heads, D], D = hidden_size /
          heads; q, k = RMSNorm_D(q), RMSNorm_D(k) per head (one weight of
          D each); rotary over the whole head, rotate-half pairing
          (channel i with i + D/2), angle = position * rope_theta^(-2i/D),
          position = index in the sequence; causal softmax(q k^T /
          sqrt(D)) v with query head h reading KV head h // (heads / kv
          heads), a block of queries at a time; W_o.
  FFN     the first `num_dense_layers` layers w2(silu(w1 x) * w3 x) at
          `intermediate_size`; the rest s = sigmoid(W_r x) over all
          published experts, the `num_experts_per_tok` largest of s + b
          (b the expert bias), w = s[chosen] / sum(s[chosen]) *
          routed_scaling_factor, y = sum_e w_e E_e(x), every E_e a SwiGLU at
          `moe_intermediate_size` — each held expert a dense pass over
          every token, masked by the routing.  No shared expert.

Departures, each noted:
- THE CHIP'S SHARE.  `num_experts` counts the experts held here, experts
  `experts_held_start` .. + `num_experts` of `num_experts_published`; the
  router keeps the published width and the sum runs over the held experts
  only — what the absent ones would have added is left out, as in the
  program.  `vocab_size` is the rows of the vocabulary held here.
- the expert bias b is zero (the harness seeds parameters only; a
  pre-training job moves b outside the gradient); `forward` takes another
  under `params` where a test wants one.
- the renormalisation's denominator carries no epsilon (the published code
  adds one under 1e-6 of the sum; `assumed` in the configuration file).

Every matmul runs under jax.default_matmul_precision("highest").
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
CONV, ATTN = "conv", "full_attention"


def layer_kinds(cfg):
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    if len(kinds) != cfg["num_hidden_layers"] \
            or set(kinds) - {CONV, ATTN}:
        raise ValueError(f"layer_types {kinds}")
    return kinds


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def param_shapes(cfg):
    """name -> shape, in the program's parameter names."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    H, G, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        _head_dim(cfg)
    f, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    shapes = {"embed_tokens.weight": (v, d)}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"layers.{i}."
        shapes[p + "input_norm.weight"] = (d,)
        shapes[p + "post_norm.weight"] = (d,)
        m = p + "mixer."
        if kind == CONV:
            shapes.update({m + "in_proj.weight": (d, 3 * d),
                           m + "conv.weight": (d, cfg["conv_L_cache"]),
                           m + "out_proj.weight": (d, d)})
        else:
            shapes.update({m + "q_proj.weight": (d, H * D),
                           m + "k_proj.weight": (d, G * D),
                           m + "v_proj.weight": (d, G * D),
                           m + "q_norm.weight": (D,),
                           m + "k_norm.weight": (D,),
                           m + "o_proj.weight": (H * D, d)})
        if i < cfg["num_dense_layers"]:
            width = cfg["intermediate_size"]
            shapes.update({p + "ffn.gate_proj.weight": (d, width),
                           p + "ffn.up_proj.weight": (d, width),
                           p + "ffn.down_proj.weight": (width, d)})
        else:
            shapes.update({
                p + "ffn.router.weight": (d, cfg["num_experts_published"]),
                p + "ffn.experts_gate": (e, d, f),
                p + "ffn.experts_up": (e, d, f),
                p + "ffn.experts_down": (e, f, d)})
    shapes["norm.weight"] = (d,)
    return shapes


def n_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def max_positions(cfg):
    """The positions a check may use: the configuration's training length
    (`training.positions`), not the published 128,000-token context."""
    return cfg["training"]["positions"]


def attention_shape(cfg, mesh=None):
    """None: the one-head-count shape the accepted flash metrics take does
    not describe this model (32 query heads over 8 KV heads); see
    `mixer_shapes`."""
    return None


def mixer_shapes(cfg):
    """What the attention layers' kernels see on this chip, for the shape
    functions of harness/flops_hybrid.py (which count K and V at
    `kv_heads`), and how many layers are of each kind."""
    kinds = layer_kinds(cfg)
    return {"gqa": {"heads": cfg["num_attention_heads"],
                    "kv_heads": cfg["num_key_value_heads"],
                    "dk": _head_dim(cfg), "dv": _head_dim(cfg),
                    "layers": kinds.count(ATTN)},
            "conv": {"taps": cfg["conv_L_cache"],
                     "layers": kinds.count(CONV)}}


def _matmul_params_per_token(cfg):
    """Parameters that multiply a token on this chip: a held routed expert
    by the share of tokens it expects (experts per token / published), the
    tied embedding once — as the head (the lookup is a gather)."""
    k, pub = cfg["num_experts_per_tok"], cfg["num_experts_published"]
    total = 0.0
    for name, shape in param_shapes(cfg).items():
        if len(shape) < 2 or name.endswith("conv.weight"):
            continue                    # norms; the taps are elementwise
        n = math.prod(shape)
        if ".ffn.experts_" in name:
            n *= k / pub
        total += n
    return total


def _mixing_flops_per_token(cfg, seq_len):
    """Forward operations per token of the sequence mixing itself: causal
    attention's QK^T and PV, halved by the mask; the convolution's taps
    (a multiply and an add each) and its two gates."""
    m = mixer_shapes(cfg)
    g, c = m["gqa"], m["conv"]
    attn = g["layers"] * g["heads"] * 2 * seq_len * (g["dk"] + g["dv"]) * 0.5
    conv = c["layers"] * cfg["hidden_size"] * (2 * c["taps"] + 2)
    return attn + conv


def train_flops_per_token(cfg, seq_len):
    """Operations the forward and backward passes need per token ON THIS
    CHIP: 6 x the parameters that multiply it (a held expert by its
    expected 4 x 8 / 32 = 1 expert a token, the head over the held rows of
    the vocabulary) plus 3 x the mixing's forward operations.  Recomputed
    layers are not counted."""
    return 6 * _matmul_params_per_token(cfg) \
        + 3 * _mixing_flops_per_token(cfg, seq_len)


def forward_flops(cfg, rows, tokens, attn_pairs):
    """Forward operations for `rows` positions through the blocks, `tokens`
    of them through the head, `attn_pairs` (query, key) pairs in each
    attention layer."""
    m = mixer_shapes(cfg)
    g, c = m["gqa"], m["conv"]
    head = cfg["vocab_size"] * cfg["hidden_size"]
    return 2 * (_matmul_params_per_token(cfg) - head) * rows \
        + 2 * head * tokens \
        + 2 * (g["dk"] + g["dv"]) * g["heads"] * g["layers"] * attn_pairs \
        + c["layers"] * cfg["hidden_size"] * (2 * c["taps"] + 2) * rows


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def short_conv(z, w):
    """Causal depthwise convolution over [S, C] with w [C, taps], the last
    tap on the current token, zero history, no activation."""
    taps, s = w.shape[1], z.shape[0]
    padded = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    return sum(padded[i:i + s] * w[:, i] for i in range(taps))


def _conv_mixer(u, p, n, cfg):
    d = cfg["hidden_size"]
    bcx = u @ p[n + "in_proj.weight"]
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    return (c * short_conv(b * x, p[n + "conv.weight"])) \
        @ p[n + "out_proj.weight"]


def rotary(x, theta):
    """x [S, heads, D] turned by its position: channel i pairs with
    i + D/2, angle = position * theta^(-2i/D)."""
    s, D = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + turned * sin


def attention(q, k, v):
    """Causal softmax(q k^T / sqrt(D)) v; q [S, H, D], k and v [S, G, D]
    with G dividing H: query head h reads KV head h // (H / G).  A block
    of queries at a time, explicit softmax."""
    s, H, D = q.shape
    G = k.shape[1]
    qg = q.reshape(s, G, H // G, D)
    block = math.gcd(s, QUERY_BLOCK)
    keys = jnp.arange(s)

    def attend(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, block, 0)
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k) / math.sqrt(D)
        rows = start + jnp.arange(block)
        scores = jnp.where(keys[None, None, None, :]
                           <= rows[None, None, :, None], scores, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, -1), v)

    return jax.lax.map(attend, jnp.arange(0, s, block)).reshape(s, H, D)


def _attn_mixer(u, p, n, cfg):
    H, G, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        _head_dim(cfg)
    s, eps, theta = u.shape[0], cfg["norm_eps"], cfg["rope_theta"]
    q = _rms_norm((u @ p[n + "q_proj.weight"]).reshape(s, H, D),
                  p[n + "q_norm.weight"], eps)
    k = _rms_norm((u @ p[n + "k_proj.weight"]).reshape(s, G, D),
                  p[n + "k_norm.weight"], eps)
    v = (u @ p[n + "v_proj.weight"]).reshape(s, G, D)
    o = attention(rotary(q, theta), rotary(k, theta), v)
    return o.reshape(s, H * D) @ p[n + "o_proj.weight"]


def routing(x, router_w, bias, cfg):
    """(chosen experts [S, k], their weights [S, k]) over the published
    experts."""
    s = jax.nn.sigmoid(x @ router_w)
    _, idx = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w * cfg["routed_scaling_factor"]


def expert_layer(x, p, n, cfg, bias=None, chosen=None):
    """The held experts' part of the routed sum (dense passes masked by
    the routing); no shared expert.  `chosen` = (idx, w) replaces the
    layer's own routing (a check that forces one model's routing into
    another's)."""
    pub = cfg["num_experts_published"]
    bias = jnp.zeros((pub,), jnp.float32) if bias is None else bias
    idx, w = chosen or routing(x, p[n + "router.weight"], bias, cfg)
    start = cfg.get("experts_held_start", 0)
    y = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):
        w_e = jnp.sum(jnp.where(idx == start + e, w, 0.0), -1)
        y = y + w_e[:, None] * _swiglu(
            x, p[n + "experts_gate"][e], p[n + "experts_up"][e],
            p[n + "experts_down"][e])
    return y


def forward(params, ids, cfg):
    """ids [S] int -> logits [S, vocab_size] float32, one sequence.  A
    key `layers.<i>.ffn.correction_bias` in `params` gives that layer's b."""
    eps = cfg["norm_eps"]
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        x = p["embed_tokens.weight"][ids]
        for i, kind in enumerate(layer_kinds(cfg)):
            n = f"layers.{i}."
            u = _rms_norm(x, p[n + "input_norm.weight"], eps)
            x = x + (_conv_mixer if kind == CONV else _attn_mixer)(
                u, p, n + "mixer.", cfg)
            h = _rms_norm(x, p[n + "post_norm.weight"], eps)
            if i < cfg["num_dense_layers"]:
                x = x + _swiglu(h, p[n + "ffn.gate_proj.weight"],
                                p[n + "ffn.up_proj.weight"],
                                p[n + "ffn.down_proj.weight"])
            else:
                x = x + expert_layer(h, p, n + "ffn.", cfg,
                                     p.get(n + "ffn.correction_bias"))
        return _rms_norm(x, p["norm.weight"], eps) @ p["embed_tokens.weight"].T


def sequence_loss(params, ids, labels, cfg):
    """Mean next-token cross-entropy over one sequence."""
    logp = jax.nn.log_softmax(forward(params, ids, cfg), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))
