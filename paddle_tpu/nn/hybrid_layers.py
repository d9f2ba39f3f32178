"""Layers of hybrid sequence-mixer / sparse-expert decoders (the
Kimi-Linear and LFM2 families): RMSNorm plain and sigmoid-gated, a SwiGLU
FFN, a causal depthwise short convolution, the gated-delta-rule mixer
(KDA), NoPE latent attention (MLA), the gated short-convolution mixer,
grouped-query attention with per-head QK-norm and rotary positions, and
one chip's share of a sparse-expert layer.

Every Linear is without bias.  Norms and gates compute in float32 and
return their input's dtype; the matmuls follow `amp.auto_cast`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.dispatch import apply
from ..tensor import Tensor
from . import functional as F
from . import initializer as init
from .common_layers import Linear
from .layer import Layer


def _rms(v, eps):
    v = v.astype(jnp.float32)
    return v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True) + eps)


class RMSNorm(Layer):
    """x / sqrt(mean(x^2) + eps) * weight over the last axis."""

    def __init__(self, size, epsilon=1e-5):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            shape=[size], default_initializer=init.Constant(1.0))

    def forward(self, x):
        return apply("rms_norm", lambda v, w: (
            _rms(v, self._epsilon) * w.astype(jnp.float32)).astype(v.dtype),
            x, self.weight)


class GatedRMSNorm(RMSNorm):
    """RMSNorm(x) * weight * sigmoid(gate): the output norm of a
    linear-attention head."""

    def forward(self, x, gate):
        return apply("gated_rms_norm", lambda v, g, w: (
            _rms(v, self._epsilon) * w.astype(jnp.float32)
            * jax.nn.sigmoid(g.astype(jnp.float32))).astype(v.dtype),
            x, gate, self.weight)


class SwiGLU(Layer):
    """down(SiLU(gate(x)) * up(x))."""

    def __init__(self, hidden_size, intermediate_size):
        super().__init__()
        self.gate_proj = Linear(hidden_size, intermediate_size,
                                bias_attr=False)
        self.up_proj = Linear(hidden_size, intermediate_size, bias_attr=False)
        self.down_proj = Linear(intermediate_size, hidden_size,
                                bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class ShortConv1D(Layer):
    """Causal depthwise convolution over the sequence axis of [B, T, C],
    one filter of `kernel_size` taps a channel (weight [C, kernel_size],
    the last tap on the current token, zero history), then `activation`:
    "silu" (the default, as the KDA mixer's branches take it) or None for
    the taps alone (the gated short-convolution mixer gates outside)."""

    def __init__(self, channels, kernel_size=4, activation="silu"):
        super().__init__()
        if activation not in ("silu", None):
            raise ValueError(f"activation {activation!r}: 'silu' or None")
        self._activation = activation
        self.weight = self.create_parameter(shape=[channels, kernel_size])

    def forward(self, x):
        silu = self._activation == "silu"

        def conv(v, w):
            taps, T = w.shape[1], v.shape[1]
            padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
            y = sum(padded[:, i:i + T].astype(jnp.float32)
                    * w[:, i].astype(jnp.float32) for i in range(taps))
            return (jax.nn.silu(y) if silu else y).astype(v.dtype)

        return apply("short_conv1d", conv, x, self.weight)


def _l2_normalize(v, scale=1.0, eps=1e-6):
    v = v.astype(jnp.float32)
    return v * (scale * jax.lax.rsqrt(
        jnp.sum(jnp.square(v), -1, keepdims=True) + eps))


class KimiDeltaAttention(Layer):
    """The KDA mixer: q, k, v through a short convolution and SiLU, q and
    k L2-normalised per head, a per-channel log decay from a low-rank
    gate, beta one per head, the gated delta rule (ops/linear_attention),
    and a sigmoid-gated RMSNorm per head before the output projection."""

    def __init__(self, hidden_size, num_heads, head_dim, conv_size=4,
                 gate_rank=None, epsilon=1e-5):
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        width = num_heads * head_dim
        rank = gate_rank or head_dim
        lin = lambda i, o: Linear(i, o, bias_attr=False)
        self.q_proj, self.k_proj, self.v_proj = (
            lin(hidden_size, width) for _ in range(3))
        self.q_conv, self.k_conv, self.v_conv = (
            ShortConv1D(width, conv_size) for _ in range(3))
        self.f_a_proj, self.f_b_proj = lin(hidden_size, rank), lin(rank, width)
        self.g_a_proj, self.g_b_proj = lin(hidden_size, rank), lin(rank, width)
        self.b_proj = lin(hidden_size, num_heads)
        self.A_log = self.create_parameter(
            shape=[num_heads], default_initializer=init.Constant(0.0))
        self.dt_bias = self.create_parameter(
            shape=[width], default_initializer=init.Constant(0.0))
        self.o_norm = GatedRMSNorm(head_dim, epsilon)
        self.o_proj = lin(width, hidden_size)

    def forward(self, x):
        from ..ops.linear_attention import gated_delta_rule

        B, T, _ = x.shape
        H, D = self.num_heads, self.head_dim
        heads = lambda t: t.reshape([B, T, H, D])
        q = heads(self.q_conv(self.q_proj(x)))
        k = heads(self.k_conv(self.k_proj(x)))
        v = heads(self.v_conv(self.v_proj(x)))
        q = apply("l2_normalize", lambda a: _l2_normalize(
            a, D ** -0.5).astype(a.dtype), q)
        k = apply("l2_normalize", lambda a: _l2_normalize(a).astype(a.dtype),
                  k)
        g = apply("kda_log_decay", lambda f, a_log, dt: (
            -jnp.exp(a_log.astype(jnp.float32))[:, None] * jax.nn.softplus(
                (f.astype(jnp.float32) + dt.astype(jnp.float32)
                 ).reshape(B, T, H, D))),
            self.f_b_proj(self.f_a_proj(x)), self.A_log, self.dt_bias)
        beta = apply("sigmoid", lambda b: jax.nn.sigmoid(
            b.astype(jnp.float32)), self.b_proj(x))
        o = gated_delta_rule(q, k, v, g, beta)
        o = self.o_norm(o, heads(self.g_b_proj(self.g_a_proj(x))))
        return self.o_proj(o.reshape([B, T, H * D]))


class LatentAttention(Layer):
    """Multi-head latent attention without a position rotation (NoPE): K
    and V come from a normed low-rank latent; each head's key is its own
    `qk_nope_head_dim` channels beside `qk_rope_head_dim` channels all
    heads share; causal softmax(q k^T / sqrt(q/k head size)) v through the
    attention op (the flash kernels take v's own head size)."""

    def __init__(self, hidden_size, num_heads, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 epsilon=1e-5):
        super().__init__()
        self.num_heads = num_heads
        self.nope, self.shared, self.v_dim = (
            qk_nope_head_dim, qk_rope_head_dim, v_head_dim)
        self.rank = kv_lora_rank
        lin = lambda i, o: Linear(i, o, bias_attr=False)
        self.q_proj = lin(hidden_size,
                          num_heads * (qk_nope_head_dim + qk_rope_head_dim))
        self.kv_a_proj = lin(hidden_size, kv_lora_rank + qk_rope_head_dim)
        self.kv_a_norm = RMSNorm(kv_lora_rank, epsilon)
        self.kv_b_proj = lin(kv_lora_rank,
                             num_heads * (qk_nope_head_dim + v_head_dim))
        self.o_proj = lin(num_heads * v_head_dim, hidden_size)

    def forward(self, x):
        B, T, _ = x.shape
        H = self.num_heads
        q = self.q_proj(x).reshape([B, T, H, self.nope + self.shared])
        latent = self.kv_a_proj(x)
        kv = self.kv_b_proj(self.kv_a_norm(latent[:, :, :self.rank])
                            ).reshape([B, T, H, self.nope + self.v_dim])
        k = apply("mla_keys", lambda own, pe: jnp.concatenate(
            [own, jnp.broadcast_to(pe[:, :, None, :].astype(own.dtype),
                                   own.shape[:3] + pe.shape[-1:])], -1),
            kv[:, :, :, :self.nope], latent[:, :, self.rank:])
        o = F.scaled_dot_product_attention(q, k, kv[:, :, :, self.nope:],
                                           is_causal=True)
        return self.o_proj(o.reshape([B, T, H * self.v_dim]))


class GatedShortConv(Layer):
    """The gated short-convolution mixer of the LFM2 family: one
    projection to three streams [B, C, X]; z = B * X; a causal depthwise
    convolution of `kernel_size` taps over z with no activation
    (`ShortConv1D(..., activation=None)`); out_proj(C * conv(z)).  Its
    whole state is the last `kernel_size - 1` tokens of z."""

    def __init__(self, hidden_size, kernel_size=3):
        super().__init__()
        self.in_proj = Linear(hidden_size, 3 * hidden_size, bias_attr=False)
        self.conv = ShortConv1D(hidden_size, kernel_size, activation=None)
        self.out_proj = Linear(hidden_size, hidden_size, bias_attr=False)

    def forward(self, x):
        d = x.shape[-1]
        bcx = self.in_proj(x)
        gate_in, gate_out, v = (bcx[:, :, i * d:(i + 1) * d]
                                for i in range(3))
        return self.out_proj(gate_out * self.conv(gate_in * v))


class GroupedQueryAttention(Layer):
    """Causal self-attention with `num_kv_heads` K/V heads shared by
    `num_heads` query heads (query head h reads KV head
    h // (num_heads / num_kv_heads)), RMSNorm over each head of q and k
    (one weight of the head size, hidden_size / num_heads, each), rotary
    positions over the whole head (rotate-half, base `rope_theta`,
    position = index in the sequence; ops/rotary), softmax(q k^T /
    sqrt(head size)) v through the attention op — the flash kernels read
    the shared K/V heads in place."""

    def __init__(self, hidden_size, num_heads, num_kv_heads,
                 rope_theta=10000.0, epsilon=1e-5):
        super().__init__()
        if num_heads % num_kv_heads or hidden_size % num_heads:
            raise ValueError(
                f"{num_kv_heads} KV heads must divide {num_heads} query "
                f"heads, and those the hidden size {hidden_size}")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim = hidden_size // num_heads
        self.rope_theta = float(rope_theta)
        lin = lambda i, o: Linear(i, o, bias_attr=False)
        self.q_proj = lin(hidden_size, num_heads * self.head_dim)
        self.k_proj = lin(hidden_size, num_kv_heads * self.head_dim)
        self.v_proj = lin(hidden_size, num_kv_heads * self.head_dim)
        self.q_norm = RMSNorm(self.head_dim, epsilon)
        self.k_norm = RMSNorm(self.head_dim, epsilon)
        self.o_proj = lin(num_heads * self.head_dim, hidden_size)

    def forward(self, x):
        from ..ops.rotary import rotary_embedding

        B, T, _ = x.shape
        H, G, D = self.num_heads, self.num_kv_heads, self.head_dim
        q = self.q_norm(self.q_proj(x).reshape([B, T, H, D]))
        k = self.k_norm(self.k_proj(x).reshape([B, T, G, D]))
        v = self.v_proj(x).reshape([B, T, G, D])
        q = rotary_embedding(q, self.rope_theta)
        k = rotary_embedding(k, self.rope_theta)
        o = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(o.reshape([B, T, H * D]))


class SparseExpertShare(Layer):
    """One chip's share of a sparse-expert FFN under expert parallelism.

    `experts_held` = (start, count): of the `num_experts_published`
    experts the router scores, experts start .. start + count live here.
    Every token is routed over all published experts (sigmoid scores in
    float32, top `experts_per_token` of score + `correction_bias`,
    renormalised, times `routed_scale`); only the chosen experts held here
    are computed, grouped over the tokens routed to them, none dropped;
    the shared expert, which every chip computes alike, is added (the
    routed part alone is `ops.moe.sparse_expert_share`, and the layer's
    whole result with `shared_expert=False`, for a model that has none:
    no `shared` sublayer is built then; the default builds and adds it).
    The exchange
    with the chips that hold the other experts is not implemented: what
    they would have added is left out of the result.

    forward returns (y, counts): counts [count + 1] float32 are the
    assignments routed to each held expert, then those to absent ones.
    `correction_bias` is a buffer (a pre-training job moves it outside the
    gradient); it starts at zero."""

    def __init__(self, hidden_size, expert_size, num_experts_published,
                 experts_held, experts_per_token, routed_scale=1.0,
                 renormalize=True, shared_expert=True):
        super().__init__()
        self.start, held = experts_held
        assert 0 <= self.start and self.start + held <= num_experts_published
        self.k, self.scale = experts_per_token, routed_scale
        self.renormalize = renormalize
        self.router = Linear(hidden_size, num_experts_published,
                             bias_attr=False)
        self.register_buffer("correction_bias", Tensor(
            jnp.zeros((num_experts_published,), jnp.float32)))
        self.experts_gate = self.create_parameter(
            shape=[held, hidden_size, expert_size])
        self.experts_up = self.create_parameter(
            shape=[held, hidden_size, expert_size])
        self.experts_down = self.create_parameter(
            shape=[held, expert_size, hidden_size])
        self.shared = SwiGLU(hidden_size, expert_size) \
            if shared_expert else None

    def forward(self, x):
        from ..ops.moe import sparse_expert_share

        y, counts = sparse_expert_share(
            x, self.router.weight, self.correction_bias, self.experts_gate,
            self.experts_up, self.experts_down, self.start, self.k,
            self.scale, self.renormalize)
        if self.shared is not None:
            y = y + self.shared(x)
        return y, counts
