"""Plain reference: the GPT-2 forward pass and loss in straightforward
jax.numpy float32, no kernels, no cache, no batching tricks, independent of
paddle_tpu.  Follows Radford et al. 2019 / HF `modeling_gpt2.py`: learned
token and position embeddings, pre-LayerNorm blocks (LN eps 1e-5), causal
multi-head attention scaled by 1/sqrt(head size), a GELU MLP, a final
LayerNorm and a head tied to the token embedding.

Departures, each noted:
- q, k, v are three [hidden, hidden] matrices (HF fuses them into one
  c_attn of [hidden, 3*hidden]); the arithmetic is the same.
- the activation is what the configuration file states under
  `activation_function`: "gelu" is the exact erf form, "gelu_new" the tanh
  approximation the published checkpoints use.

Every matmul runs under jax.default_matmul_precision("highest"): on a TPU a
float32 matmul otherwise rounds its operands to bfloat16.

Weights arrive as one flat dict; NAMES below is the whole interface.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def param_shapes(cfg):
    """name -> shape for a configuration (HF key names of config.json)."""
    h, v = cfg["n_embd"], cfg["vocab_size"]
    ffn = cfg.get("n_inner") or 4 * h
    shapes = {"wte.weight": (v, h), "wpe.weight": (cfg["n_positions"], h)}
    for i in range(cfg["n_layer"]):
        p = f"layers.{i}."
        shapes.update({
            p + "ln1.weight": (h,), p + "ln1.bias": (h,),
            p + "attn.q_proj.weight": (h, h), p + "attn.q_proj.bias": (h,),
            p + "attn.k_proj.weight": (h, h), p + "attn.k_proj.bias": (h,),
            p + "attn.v_proj.weight": (h, h), p + "attn.v_proj.bias": (h,),
            p + "attn.out_proj.weight": (h, h),
            p + "attn.out_proj.bias": (h,),
            p + "ln2.weight": (h,), p + "ln2.bias": (h,),
            p + "fc1.weight": (h, ffn), p + "fc1.bias": (ffn,),
            p + "fc2.weight": (ffn, h), p + "fc2.bias": (h,),
        })
    shapes.update({"ln_f.weight": (h,), "ln_f.bias": (h,)})
    return shapes


def n_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _act(x, kind):
    if kind == "gelu":
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    if kind == "gelu_new":
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(f"unknown activation_function {kind!r}")


def forward(params, ids, cfg):
    """ids [S] int -> logits [S, vocab] float32, one sequence."""
    heads = cfg["n_head"]
    eps = cfg.get("layer_norm_epsilon", 1e-5)
    act = cfg.get("activation_function", "gelu_new")
    s = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        x = p["wte.weight"][ids] + p["wpe.weight"][:s]
        d = x.shape[-1] // heads
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(cfg["n_layer"]):
            n = f"layers.{i}."
            h = _layer_norm(x, p[n + "ln1.weight"], p[n + "ln1.bias"], eps)

            def proj(name, h=h, n=n):
                y = h @ p[n + f"attn.{name}.weight"] \
                    + p[n + f"attn.{name}.bias"]
                return y.reshape(s, heads, d).transpose(1, 0, 2)

            q, k, v = proj("q_proj"), proj("k_proj"), proj("v_proj")
            scores = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(d)
            scores = jnp.where(causal[None], scores, -jnp.inf)
            att = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("hqk,hkd->hqd", att, v)
            ctx = ctx.transpose(1, 0, 2).reshape(s, heads * d)
            x = x + ctx @ p[n + "attn.out_proj.weight"] \
                + p[n + "attn.out_proj.bias"]
            h = _layer_norm(x, p[n + "ln2.weight"], p[n + "ln2.bias"], eps)
            h = _act(h @ p[n + "fc1.weight"] + p[n + "fc1.bias"], act)
            x = x + h @ p[n + "fc2.weight"] + p[n + "fc2.bias"]
        x = _layer_norm(x, p["ln_f.weight"], p["ln_f.bias"], eps)
        return x @ p["wte.weight"].T


def sequence_loss(params, ids, labels, cfg):
    """(mean next-token cross-entropy over one sequence, argmax [S])."""
    logits = forward(params, ids, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll), jnp.argmax(logits, axis=-1)


def batch_loss(params, ids, labels, cfg):
    """Mean cross-entropy over a batch [B, S], one sequence at a time so
    the reference holds one sequence's activations whatever B is."""
    losses, _ = jax.lax.map(
        lambda xy: sequence_loss(params, xy[0], xy[1], cfg), (ids, labels))
    return jnp.mean(losses)
