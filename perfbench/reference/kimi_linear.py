"""Plain reference: the Kimi-Linear decoder's forward pass in straightforward
jax.numpy float32 — no kernels, no chunking, no grouping, independent of
paddle_tpu.  Follows Kimi Team 2025 ("Kimi Linear") / the HF
`modeling_kimi.py` of moonshotai/Kimi-Linear-48B-A3B-Instruct, in the keys
of its config.json (layer numbers there are 1-based).

With x the residual stream and every Linear without bias:

  block   x += mixer(RMSNorm(x)); x += ffn(RMSNorm(x)); final RMSNorm; an
          untied head.
  KDA     q, k, v = SiLU(conv4(W x)) (causal depthwise convolution over the
          sequence, one filter a channel); q, k L2-normalised per head, q
          times head_dim^-0.5; log decay g = -exp(A_log[h]) * softplus(
          W_f2 W_f1 x + dt_bias) per channel; beta = sigmoid(W_b x) per
          head; per head S' = diag(exp(g_t)) S; u = beta_t (v_t - S'^T k_t);
          S = S' + k_t u^T; o_t = S^T q_t — TOKEN BY TOKEN (lax.scan);
          W_o(RMSNorm_head(o) * sigmoid(W_g2 W_g1 x)).
  MLA     q = W_q x as [heads, nope + rope]; [c, k_pe] = W_kva x;
          [k_nope, v] = W_kvb RMSNorm(c); k_h = [k_nope_h, k_pe]; causal
          softmax(q k^T / sqrt(nope + rope)) v by explicit softmax, a block
          of queries at a time; W_o.  `mla_use_nope`: no rotation anywhere.
  FFN     the first `first_k_dense_replace` layers a SwiGLU at
          `intermediate_size`; the rest s = sigmoid(W_r x) over all
          published experts, the `num_experts_per_token` largest of s + b,
          w = s[chosen] / sum(s[chosen]) * routed_scaling_factor,
          y = shared(x) + sum_e w_e E_e(x), every E_e a SwiGLU at
          `moe_intermediate_size` — each held expert a dense pass over
          every token, masked by the routing.

Departures, each noted:
- THE CHIP'S SHARE.  `num_experts` counts the experts held here, experts
  `experts_held_start` .. + `num_experts` of `num_experts_published`; the
  router keeps the published width and the sum runs over the held experts
  only — what the absent ones would have added is left out, as in the
  program.  `vocab_size` is the rows of the vocabulary held here.
- the correction bias b is zero (the harness seeds parameters only; a
  pre-training job moves b outside the gradient); `forward` takes another
  under `params` where a test wants one.
- the two low-rank gate pairs have rank `gate_low_rank_dim` (not in the
  published config; `assumed` in the configuration file).

Every matmul runs under jax.default_matmul_precision("highest").
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
L2_EPS = 1e-6


def layer_kinds(cfg):
    """"kda" or "mla" for each of the configuration's layers, from the
    1-based tables of `linear_attn_config`."""
    la = cfg["linear_attn_config"]
    kinds = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if i in la["kda_layers"]:
            kinds.append("kda")
        elif i in la["full_attn_layers"]:
            kinds.append("mla")
        else:
            raise ValueError(f"layer {i} is in neither table")
    return kinds


def _gate_rank(cfg):
    return cfg.get("gate_low_rank_dim") or cfg["linear_attn_config"]["head_dim"]


def param_shapes(cfg):
    """name -> shape, in the program's parameter names."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    la = cfg["linear_attn_config"]
    kw = la["num_heads"] * la["head_dim"]
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank, gr = cfg["v_head_dim"], cfg["kv_lora_rank"], _gate_rank(cfg)
    f, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    shapes = {"embed_tokens.weight": (v, d)}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"layers.{i}."
        shapes[p + "input_norm.weight"] = (d,)
        shapes[p + "post_norm.weight"] = (d,)
        m = p + "mixer."
        if kind == "kda":
            for n in "qkv":
                shapes[m + f"{n}_proj.weight"] = (d, kw)
                shapes[m + f"{n}_conv.weight"] = (
                    kw, la["short_conv_kernel_size"])
            for n in "fg":
                shapes[m + f"{n}_a_proj.weight"] = (d, gr)
                shapes[m + f"{n}_b_proj.weight"] = (gr, kw)
            shapes.update({
                m + "b_proj.weight": (d, la["num_heads"]),
                m + "A_log": (la["num_heads"],), m + "dt_bias": (kw,),
                m + "o_norm.weight": (la["head_dim"],),
                m + "o_proj.weight": (kw, d)})
        else:
            shapes.update({
                m + "q_proj.weight": (d, heads * (nope + rope)),
                m + "kv_a_proj.weight": (d, rank + rope),
                m + "kv_a_norm.weight": (rank,),
                m + "kv_b_proj.weight": (rank, heads * (nope + vd)),
                m + "o_proj.weight": (heads * vd, d)})
        if i < cfg["first_k_dense_replace"]:
            ffn, width = p + "ffn.", cfg["intermediate_size"]
        else:
            ffn, width = p + "ffn.shared.", f
            shapes.update({
                p + "ffn.router.weight": (d, cfg["num_experts_published"]),
                p + "ffn.experts_gate": (e, d, f),
                p + "ffn.experts_up": (e, d, f),
                p + "ffn.experts_down": (e, f, d)})
        shapes.update({ffn + "gate_proj.weight": (d, width),
                       ffn + "up_proj.weight": (d, width),
                       ffn + "down_proj.weight": (width, d)})
    shapes.update({"norm.weight": (d,), "lm_head.weight": (d, v)})
    return shapes


def n_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def max_positions(cfg):
    return cfg["model_max_length"]


def attention_shape(cfg, mesh=None):
    """None: the one-head-size shape the accepted flash metrics take does
    not describe this model (q/k 192, v 128); see `mixer_shapes`."""
    return None


def mixer_shapes(cfg):
    """What each kind of mixer's kernel sees on this chip, for the shape
    functions of harness/flops_hybrid.py: heads, head sizes, and how many
    layers call it."""
    kinds = layer_kinds(cfg)
    la = cfg["linear_attn_config"]
    return {
        "kda": {"heads": la["num_heads"], "dk": la["head_dim"],
                "dv": la["head_dim"], "layers": kinds.count("kda")},
        "mla": {"heads": cfg["num_attention_heads"],
                "dk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                "dv": cfg["v_head_dim"], "layers": kinds.count("mla")}}


def _matmul_params_per_token(cfg):
    """Parameters that multiply a token on this chip, a held routed expert
    by the share of tokens it expects: experts per token x held /
    published."""
    shapes = param_shapes(cfg)
    k, pub = cfg["num_experts_per_token"], cfg["num_experts_published"]
    total = 0.0
    for name, shape in shapes.items():
        if len(shape) < 2 or name == "embed_tokens.weight":
            continue                    # norms, A_log, dt_bias; a gather
        n = math.prod(shape)
        if ".ffn.experts_" in name:
            n *= k / pub                # each held expert: k / published
        total += n
    return total


def _mixing_flops_per_token(cfg, seq_len):
    """Forward operations per token of the two sequence mixers: causal
    attention's QK^T (q/k head size) and PV (v head size), halved by the
    mask; the delta rule by its recurrence — per head S'^T k, k u^T and
    S^T q at 2 Dk Dv each and the decay's Dk Dv."""
    m = mixer_shapes(cfg)
    mla, kda = m["mla"], m["kda"]
    attn = mla["layers"] * mla["heads"] * 2 * seq_len * (
        mla["dk"] + mla["dv"]) * 0.5
    scan = kda["layers"] * kda["heads"] * 7 * kda["dk"] * kda["dv"]
    return attn + scan


def train_flops_per_token(cfg, seq_len):
    """Operations the forward and backward passes need per token ON THIS
    CHIP: 6 x the parameters that multiply it (a held expert by its
    expected 8 x 8 / 256 of the tokens, the head over the held rows of the
    vocabulary) plus 3 x the mixers' forward operations.  Recomputed layers
    are not counted."""
    return 6 * _matmul_params_per_token(cfg) \
        + 3 * _mixing_flops_per_token(cfg, seq_len)


def forward_flops(cfg, rows, tokens, attn_pairs):
    """Forward operations for `rows` positions through the blocks, `tokens`
    of them through the head, `attn_pairs` (query, key) pairs in each
    attention layer."""
    m = mixer_shapes(cfg)
    head = cfg["vocab_size"] * cfg["hidden_size"]
    return 2 * (_matmul_params_per_token(cfg) - head) * rows \
        + 2 * head * tokens \
        + 2 * (m["mla"]["dk"] + m["mla"]["dv"]) * m["mla"]["heads"] \
        * m["mla"]["layers"] * attn_pairs \
        + m["kda"]["layers"] * m["kda"]["heads"] * 7 * m["kda"]["dk"] \
        * m["kda"]["dv"] * rows


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _short_conv(x, w):
    """Causal depthwise convolution over [S, C] with w [C, taps] (the last
    tap on the current token), then SiLU."""
    taps, s = w.shape[1], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[i:i + s] * w[:, i] for i in range(taps)))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, one token a step: q, k, g [S, H, Dk],
    v [S, H, Dv], beta [S, H] -> o [S, H, Dv]."""
    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None] * S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    H, Dk, Dv = q.shape[1], q.shape[2], v.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((H, Dk, Dv), jnp.float32),
                        (q, k, v, g, beta))
    return o


def _kda(x, p, n, cfg):
    la = cfg["linear_attn_config"]
    H, D, s = la["num_heads"], la["head_dim"], x.shape[0]

    def branch(name):
        y = _short_conv(x @ p[n + f"{name}_proj.weight"],
                        p[n + f"{name}_conv.weight"])
        return y.reshape(s, H, D)

    def l2(a):
        return a * jax.lax.rsqrt(jnp.sum(jnp.square(a), -1, keepdims=True)
                                 + L2_EPS)

    q, k, v = l2(branch("q")) * D ** -0.5, l2(branch("k")), branch("v")
    f = x @ p[n + "f_a_proj.weight"] @ p[n + "f_b_proj.weight"]
    g = -jnp.exp(p[n + "A_log"])[None, :, None] * jax.nn.softplus(
        (f + p[n + "dt_bias"]).reshape(s, H, D))
    beta = jax.nn.sigmoid(x @ p[n + "b_proj.weight"])
    o = delta_rule(q, k, v, g, beta)
    gate = (x @ p[n + "g_a_proj.weight"] @ p[n + "g_b_proj.weight"]
            ).reshape(s, H, D)
    o = _rms_norm(o, p[n + "o_norm.weight"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(gate)
    return o.reshape(s, H * D) @ p[n + "o_proj.weight"]


def _mla(x, p, n, cfg):
    H, s = cfg["num_attention_heads"], x.shape[0]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = (x @ p[n + "q_proj.weight"]).reshape(s, H, nope + rope)
    latent = x @ p[n + "kv_a_proj.weight"]
    c = _rms_norm(latent[:, :rank], p[n + "kv_a_norm.weight"],
                  cfg["rms_norm_eps"])
    kv = (c @ p[n + "kv_b_proj.weight"]).reshape(s, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        latent[:, None, rank:], (s, H, rope))], -1)
    v = kv[..., nope:]
    block = math.gcd(s, QUERY_BLOCK)
    keys = jnp.arange(s)

    def attend(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(nope + rope)
        rows = start + jnp.arange(block)
        scores = jnp.where(keys[None, None, :] <= rows[None, :, None],
                           scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    o = jax.lax.map(attend, jnp.arange(0, s, block)).reshape(s, H * vd)
    return o @ p[n + "o_proj.weight"]


def routing(x, router_w, bias, cfg):
    """(chosen experts [S, k], their weights [S, k]) over the published
    experts."""
    s = jax.nn.sigmoid(x @ router_w)
    _, idx = jax.lax.top_k(s + bias, cfg["num_experts_per_token"])
    w = jnp.take_along_axis(s, idx, -1)
    if cfg.get("moe_renormalize", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w * cfg["routed_scaling_factor"]


def expert_layer(x, p, n, cfg, bias=None):
    """The held experts' part of the routed sum (dense passes masked by
    the routing) plus the shared expert."""
    pub = cfg["num_experts_published"]
    bias = jnp.zeros((pub,), jnp.float32) if bias is None else bias
    idx, w = routing(x, p[n + "router.weight"], bias, cfg)
    y = _swiglu(x, p[n + "shared.gate_proj.weight"],
                p[n + "shared.up_proj.weight"],
                p[n + "shared.down_proj.weight"])
    start = cfg.get("experts_held_start", 0)
    for e in range(cfg["num_experts"]):
        w_e = jnp.sum(jnp.where(idx == start + e, w, 0.0), -1)
        y = y + w_e[:, None] * _swiglu(
            x, p[n + "experts_gate"][e], p[n + "experts_up"][e],
            p[n + "experts_down"][e])
    return y


def forward(params, ids, cfg):
    """ids [S] int -> logits [S, vocab_size] float32, one sequence.  A
    key `layers.<i>.ffn.correction_bias` in `params` gives that layer's b."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        x = p["embed_tokens.weight"][ids]
        for i, kind in enumerate(layer_kinds(cfg)):
            n = f"layers.{i}."
            h = _rms_norm(x, p[n + "input_norm.weight"], eps)
            x = x + (_kda if kind == "kda" else _mla)(h, p, n + "mixer.", cfg)
            h = _rms_norm(x, p[n + "post_norm.weight"], eps)
            if i < cfg["first_k_dense_replace"]:
                x = x + _swiglu(h, p[n + "ffn.gate_proj.weight"],
                                p[n + "ffn.up_proj.weight"],
                                p[n + "ffn.down_proj.weight"])
            else:
                x = x + expert_layer(h, p, n + "ffn.", cfg,
                                     p.get(n + "ffn.correction_bias"))
        return _rms_norm(x, p["norm.weight"], eps) @ p["lm_head.weight"]


def sequence_loss(params, ids, labels, cfg):
    """Mean next-token cross-entropy over one sequence."""
    logp = jax.nn.log_softmax(forward(params, ids, cfg), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))
