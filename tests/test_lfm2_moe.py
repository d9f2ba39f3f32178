"""The LFM2 sparse-expert architecture on the CPU at a tiny size, seeded
weights: the program against perfbench/reference/lfm2_moe.py (logits, loss,
every leaf's gradient), the expert shares against the uncut layer (no
shared expert), the flash kernels with fewer KV heads than query heads,
the rotary op against cos/sin by hand, the gated short convolution against
its token-by-token recurrence, the tied head, and the counts of the
published and the cut configuration."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness.manifest import Manifest  # noqa: E402
from perfbench.harness.weights import make_weights  # noqa: E402

M = Manifest(ROOT)
NAME = "lfm2-8b-a1b-train-ep4"
CELL = "train-lfm2-moe-s8192"
CFG = M.config(NAME)
REF = M.reference(CFG)
PUBLISHED_EXPERTS, SHARES = 16, 4
HELD = PUBLISHED_EXPERTS // SHARES


def tiny(**over):
    """The cell's configuration with every width shrunk (4 query heads of
    8 over 2 KV heads), 4 shares of 16 experts, this chip share 1."""
    cfg = dict(CFG, hidden_size=32, intermediate_size=48,
               moe_intermediate_size=16, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=64, num_experts=HELD,
               num_experts_published=PUBLISHED_EXPERTS, experts_held_start=4)
    cfg.update(over)
    return cfg


def biases(cfg, scale=0.2):
    """A non-zero expert bias for every expert layer."""
    return {f"layers.{i}.ffn.correction_bias": scale * jax.random.normal(
        jax.random.PRNGKey(i), (cfg["num_experts_published"],))
        for i in range(cfg["num_dense_layers"], cfg["num_hidden_layers"])}


@pytest.fixture(scope="module")
def program_and_reference():
    """(loss, logits, gradients) of the program's model and of the
    reference on one sequence, with a non-zero expert bias and every layer
    recomputed."""
    from paddle_tpu.jit.functional import functional_call, get_state

    cfg = tiny()
    model = M.model(cfg).construct(cfg)
    shapes = REF.param_shapes(cfg)
    assert {n: tuple(p.shape) for n, p in model.named_parameters()} \
        == {n: tuple(s) for n, s in shapes.items()}
    weights = make_weights(shapes, 7, std=0.3)
    bias = biases(cfg)
    ids = np.random.default_rng(0).integers(0, 64, size=151).astype(np.int32)
    x, y = jnp.asarray(ids[:-1]), jnp.asarray(ids[1:])
    model.train()
    assert model.recompute
    _, buffers = get_state(model)
    buffers = dict(buffers, **bias)

    def program(w):
        out, bufs = functional_call(model, w, buffers, (x[None],),
                                    training=True)
        logp = jax.nn.log_softmax(out[0].astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1)), \
            (out[0], bufs["moe_routed_tokens"])

    (loss, (logits, routed)), grads = jax.value_and_grad(
        program, has_aux=True)(weights)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda w: REF.sequence_loss(dict(w, **bias), x, y, cfg))(weights)
    ref_logits = REF.forward(dict(weights, **bias), x, cfg)
    return {"loss": (loss, ref_loss), "logits": (logits, ref_logits),
            "grads": (grads, ref_grads), "routed": np.asarray(routed),
            "cfg": cfg}


def test_logits_and_loss_match_the_reference(program_and_reference):
    logits, ref_logits = program_and_reference["logits"]
    scale = float(jnp.max(jnp.abs(ref_logits)))
    assert scale > 1.0
    assert float(jnp.max(jnp.abs(logits - ref_logits))) < 1e-3 * scale
    loss, ref_loss = program_and_reference["loss"]
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)


@pytest.mark.parametrize("name", sorted(REF.param_shapes(tiny())))
def test_every_leafs_gradient_matches_the_reference(program_and_reference,
                                                    name):
    grads, ref_grads = program_and_reference["grads"]
    scale = float(jnp.max(jnp.abs(ref_grads[name])))
    assert scale > 0
    assert float(jnp.max(jnp.abs(grads[name] - ref_grads[name]))) \
        < 1e-3 * scale


def test_the_step_counts_what_it_routes(program_and_reference):
    routed, cfg = program_and_reference["routed"], program_and_reference["cfg"]
    assert routed.shape == (4, cfg["num_experts"] + 1)
    # every (token, slot) assignment is counted once, here or as absent
    assert (routed.sum(axis=1) == 150 * cfg["num_experts_per_tok"]).all()
    assert (routed[:, :-1].sum(axis=1) > 0).all()


def test_the_tied_head_is_one_leaf_whose_gradient_sums_both_uses(
        program_and_reference):
    """No `lm_head` leaf; the embedding's gradient (held to the reference's
    by the per-leaf test) is the lookup's rows PLUS the head's dense part:
    with the embedding used for the lookup alone (the head through a
    stopped copy) and for the head alone, the two gradients add up to the
    leaf's, and rows no id ever looked up get theirs from the head."""
    from paddle_tpu.jit.functional import functional_call, get_state

    grads, _ = program_and_reference["grads"]
    cfg = program_and_reference["cfg"]
    assert not any("lm_head" in n for n in grads)
    assert not any("lm_head" in n for n in REF.param_shapes(cfg))
    model = M.model(cfg).construct(cfg)
    assert model.lm_head is None
    model.eval()
    _, buffers = get_state(model)
    w = make_weights(REF.param_shapes(cfg), 7, std=0.3)
    ids = np.random.default_rng(0).integers(0, 64, size=151)[:-1]
    x = jnp.asarray(ids.astype(np.int32))
    probe = jax.random.normal(jax.random.PRNGKey(1), (150, 64))
    embed = w["embed_tokens.weight"]

    def logits_of(e):
        out, _ = functional_call(model, dict(w, **{
            "embed_tokens.weight": e}), buffers, (x[None],), training=False)
        return out[0]

    both = jax.grad(lambda e: jnp.sum(logits_of(e) * probe))(embed)
    # the head's use alone: logits = h @ E^T with h, the final norm's
    # output, held fixed (E [64, 32] has full column rank: h is exact)
    h = jnp.linalg.lstsq(embed, logits_of(embed).T)[0].T    # [150, 32]
    head_part = jax.grad(lambda e: jnp.sum((h @ e.T) * probe))(embed)
    lookup_part = both - head_part
    unseen = sorted(set(range(64)) - set(ids.tolist()))
    seen = sorted(set(ids.tolist()))
    scale = float(jnp.max(jnp.abs(both)))
    assert float(jnp.max(jnp.abs(head_part))) > 0.01 * scale
    assert float(jnp.max(jnp.abs(lookup_part[jnp.asarray(seen)]))) \
        > 0.01 * scale
    if unseen:
        # no lookup ever touched these rows: all they have is the head's
        assert float(jnp.max(jnp.abs(lookup_part[jnp.asarray(unseen)]))) \
            < 1e-3 * scale
        assert float(jnp.max(jnp.abs(both[jnp.asarray(unseen)]))) > 0


# --- rotary ------------------------------------------------------------------
def test_rotary_is_the_closed_form_and_position_0_is_the_identity():
    import paddle_tpu as paddle
    from paddle_tpu.ops.rotary import rotary_embedding

    B, T, H, D, theta = 2, 11, 3, 8, 1e6
    x = np.random.default_rng(3).standard_normal((B, T, H, D)).astype(
        np.float32)
    out = np.asarray(rotary_embedding(paddle.to_tensor(x), theta)._value)
    want = np.empty_like(x)
    for t in range(T):
        for i in range(D // 2):
            a = t * theta ** (-2.0 * i / D)
            c, s = np.cos(a), np.sin(a)
            want[:, t, :, i] = x[:, t, :, i] * c - x[:, t, :, i + D // 2] * s
            want[:, t, :, i + D // 2] = x[:, t, :, i + D // 2] * c \
                + x[:, t, :, i] * s
    assert np.abs(out - want).max() < 1e-5
    assert np.array_equal(out[:, 0], x[:, 0])
    # a rotation: norms are kept, and q.k depends on the offset alone
    assert np.allclose(np.linalg.norm(out, axis=-1),
                       np.linalg.norm(x, axis=-1), rtol=1e-5)
    same = np.broadcast_to(x[:, :1], x.shape).copy()
    r = np.asarray(rotary_embedding(paddle.to_tensor(same), theta)._value)
    dots = np.einsum("bthd,bshd->bhts", r, r)
    assert np.allclose(dots[:, :, 2, 5], dots[:, :, 6, 9], atol=1e-4)


def test_rotary_angles_are_float32_whatever_the_input_is():
    """bf16 q in, bf16 out, and the angle, cos and sin never leave
    float32: at base 1e6 and 8,192 positions a bf16 angle is off by whole
    turns."""
    from paddle_tpu.ops import rotary

    x = jnp.ones((1, 8192, 2, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda v: rotary.rotate_half(v, 1e6))(x)
    trig = [e for e in jaxpr.eqns if e.primitive.name in ("cos", "sin")]
    assert len(trig) == 2
    assert all(e.invars[0].aval.dtype == jnp.float32 for e in trig)
    out = rotary.rotate_half(x, 1e6)
    assert out.dtype == jnp.bfloat16
    f32 = rotary.rotate_half(x.astype(jnp.float32), 1e6)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - f32))) < 2 ** -7


# --- the gated short convolution ---------------------------------------------
def test_the_conv_mixer_is_its_recurrence_over_a_two_token_history():
    """Token by token with a state of the last two z = B * X: the mixer's
    whole state is conv_L_cache - 1 tokens."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    d, T = 16, 23
    layer = nn.GatedShortConv(d, 3)
    rng = np.random.default_rng(5)
    for p in layer.parameters():
        p._value = jnp.asarray(rng.standard_normal(p.shape).astype(
            np.float32) * 0.5)
    u = rng.standard_normal((2, T, d)).astype(np.float32)
    out = np.asarray(layer(paddle.to_tensor(u))._value)
    w_in = np.asarray(layer.in_proj.weight._value)
    taps = np.asarray(layer.conv.weight._value)
    w_out = np.asarray(layer.out_proj.weight._value)
    assert taps.shape == (d, 3)
    want = np.empty_like(out)
    for b in range(2):
        history = np.zeros((2, d), np.float32)         # z_{t-2}, z_{t-1}
        for t in range(T):
            bcx = u[b, t] @ w_in
            gate_in, gate_out, x = bcx[:d], bcx[d:2 * d], bcx[2 * d:]
            z = gate_in * x
            c = taps[:, 0] * history[0] + taps[:, 1] * history[1] \
                + taps[:, 2] * z
            want[b, t] = (gate_out * c) @ w_out
            history = np.stack([history[1], z])
    assert np.abs(out - want).max() < 1e-4 * max(1.0, np.abs(want).max())


def test_short_conv_keeps_silu_by_default_and_drops_it_on_request():
    import paddle_tpu as paddle
    from paddle_tpu import nn

    x = paddle.to_tensor(np.random.default_rng(1).standard_normal(
        (1, 9, 4)).astype(np.float32))
    with_silu, plain = nn.ShortConv1D(4, 4), nn.ShortConv1D(4, 4, None)
    plain.weight._value = with_silu.weight._value
    a, b = with_silu(x)._value, plain(x)._value
    assert float(jnp.max(jnp.abs(a - jax.nn.silu(b)))) < 1e-6
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3
    with pytest.raises(ValueError):
        nn.ShortConv1D(4, 4, "relu")


# --- grouped-query flash ------------------------------------------------------
def _explicit_gqa(q, k, v):
    """[B, S, H, D] x [B, S, G, D]: explicit softmax, query head h on KV
    head h // (H / G)."""
    B, S, H, D = q.shape
    G = k.shape[2]
    qg = q.reshape(B, S, G, H // G, D)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k) / D ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, -1),
                      v).reshape(B, S, H, D)


@pytest.mark.parametrize("group", [4, 1])
@pytest.mark.parametrize("interpret_kernel", [False, True])
def test_attention_with_fewer_kv_heads(group, interpret_kernel, monkeypatch):
    """8 query heads over 8 / group KV heads: the op's XLA route and the
    three flash kernels (interpret mode, 2 x 2 blocks so the causal skip
    and the walk over the group's heads both run) against explicit
    softmax, forward and the gradients of q, k and v."""
    import paddle_tpu as paddle
    from paddle_tpu.ops import attention
    from paddle_tpu.ops.pallas_ops import flash_attention as fa

    if interpret_kernel:
        monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH", "1")
        monkeypatch.setattr(fa, "DEFAULT_BLOCK_Q", 128)
        monkeypatch.setattr(fa, "DEFAULT_BLOCK_K", 128)
    ks = jax.random.split(jax.random.PRNGKey(group), 4)
    B, S, H, D = 2, 200, 8, 16
    G = H // group
    q = jax.random.normal(ks[0], (B, S, H, D))
    k, v = (jax.random.normal(ks[i], (B, S, G, D)) for i in (1, 2))
    w = jax.random.normal(ks[3], (B, S, H, D))
    before = dict(attention.ROUTE_STATS)
    tq, tk, tv = (paddle.to_tensor(np.asarray(a), stop_gradient=False)
                  for a in (q, k, v))
    out = attention.scaled_dot_product_attention(tq, tk, tv, is_causal=True)
    route = "pallas" if interpret_kernel else "xla"
    assert attention.ROUTE_STATS[route] == before[route] + 1
    assert tuple(out.shape) == (B, S, H, D)
    assert float(jnp.max(jnp.abs(out._value - _explicit_gqa(q, k, v)))) < 1e-5
    (out * paddle.to_tensor(np.asarray(w))).sum().backward()
    want = jax.grad(lambda *a: jnp.sum(_explicit_gqa(*a) * w),
                    (0, 1, 2))(q, k, v)
    for got, ref in zip((tq, tk, tv), want):
        assert tuple(got.grad.shape) == ref.shape
        assert float(jnp.max(jnp.abs(got.grad._value - ref))) < 1e-4


def test_grouped_flash_dropout_draws_per_query_head(monkeypatch):
    """The backward kernels regenerate the forward's dropout bits: with
    the group's heads walked inside one grid axis, the hash still takes
    the QUERY head's index — dk/dv of the kernels equal autodiff through
    the forward kernel's own output only if they agree."""
    from paddle_tpu.ops.pallas_ops import flash_attention as fa

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    B, S, H, G, D = 1, 256, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, D))
    k, v = (jax.random.normal(ks[i], (B, S, G, D)) for i in (1, 2))

    def grouped(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True, dropout_p=0.3,
                                       seed=5, block_q=128, block_k=128)

    def repeated(q, k, v):
        return fa.flash_attention_bshd(
            q, jnp.repeat(k, H // G, 2), jnp.repeat(v, H // G, 2),
            causal=True, dropout_p=0.3, seed=5, block_q=128, block_k=128)

    assert float(jnp.max(jnp.abs(grouped(q, k, v) - repeated(q, k, v)))) \
        < 1e-5
    w = jax.random.normal(ks[3], (B, S, H, D))
    got = jax.grad(lambda *a: jnp.sum(grouped(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(repeated(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_kv_heads_must_divide_the_query_heads():
    from paddle_tpu import nn
    from paddle_tpu.ops.pallas_ops import flash_attention as fa

    with pytest.raises(ValueError):
        nn.GroupedQueryAttention(32, 4, 3)
    q, k = jnp.ones((1, 128, 4, 64)), jnp.ones((1, 128, 3, 64))
    with pytest.raises(ValueError):
        fa.flash_attention_bshd(q, k, k, causal=True)


# --- the expert shares ---------------------------------------------------------
def _expert_layer_weights(cfg, seed=3):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E = cfg["num_experts_published"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = lambda i, *s: 0.4 * jax.random.normal(ks[i], s)
    return {"router.weight": n(0, d, E), "experts_gate": n(1, E, d, f),
            "experts_up": n(2, E, d, f), "experts_down": n(3, E, f, d)}, \
        n(4, 37, d)


def test_the_four_shares_add_up_to_the_uncut_expert_layer():
    """THE SHARE TEST.  The routed parts the program's layer gives at
    starts 0, 4, 8, 12 of 16 experts add up to the uncut reference's whole
    expert layer — there is no shared expert to count once — and each
    share equals the reference given the same share."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    cfg = tiny()
    whole, x = _expert_layer_weights(cfg)
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(11),
                                   (PUBLISHED_EXPERTS,))
    uncut = dict(cfg, num_experts=PUBLISHED_EXPERTS, experts_held_start=0)
    with jax.default_matmul_precision("highest"):
        want = REF.expert_layer(x, whole, "", uncut, bias)
    total, routed_here = 0.0, 0.0
    names = ("experts_gate", "experts_up", "experts_down")
    for lo in range(0, PUBLISHED_EXPERTS, HELD):
        layer = nn.SparseExpertShare(
            cfg["hidden_size"], cfg["moe_intermediate_size"],
            PUBLISHED_EXPERTS, (lo, HELD), cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["norm_topk_prob"],
            shared_expert=False)
        assert layer.shared is None
        assert not any("shared" in n for n, _ in layer.named_parameters())
        layer.router.weight._value = whole["router.weight"]
        layer.correction_bias._value = bias
        for n in names:
            getattr(layer, n)._value = whole[n][lo:lo + HELD]
        y, counts = layer(paddle.to_tensor(np.asarray(x)))
        routed_here += float(counts._value[:-1].sum())
        with jax.default_matmul_precision("highest"):
            same_share = REF.expert_layer(
                x, {**whole, **{n: whole[n][lo:lo + HELD] for n in names}},
                "", dict(cfg, experts_held_start=lo), bias)
        assert float(jnp.max(jnp.abs(y._value - same_share))) < 1e-4
        total = total + y._value
    assert routed_here == 37 * cfg["num_experts_per_tok"]
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert float(jnp.max(jnp.abs(total - want))) < 1e-4


def test_the_shared_expert_is_still_built_and_added_by_default():
    from paddle_tpu import nn

    layer = nn.SparseExpertShare(8, 4, 8, (0, 2), 2)
    assert isinstance(layer.shared, nn.SwiGLU)
    assert sum("shared" in n for n, _ in layer.named_parameters()) == 3


def test_no_token_is_dropped_when_every_token_picks_one_held_expert():
    """Imbalance: a bias that sends every token to held expert 0 first;
    a quarter of the rows live is the ordinary case, all of one expert's
    the worst."""
    from paddle_tpu.ops.moe import expert_share

    cfg = tiny()
    whole, x = _expert_layer_weights(cfg)
    bias = jnp.zeros((PUBLISHED_EXPERTS,)).at[4].set(10.0)
    sl = lambda n: whole[n][4:8]
    y, counts = expert_share(
        x, whole["router.weight"], bias, sl("experts_gate"),
        sl("experts_up"), sl("experts_down"), start=4,
        k=cfg["num_experts_per_tok"], scale=cfg["routed_scaling_factor"])
    assert counts[0] == x.shape[0]
    with jax.default_matmul_precision("highest"):
        want = REF.expert_layer(
            x, {**whole, **{n: sl(n) for n in (
                "experts_gate", "experts_up", "experts_down")}}, "", cfg,
            bias)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-4


def test_the_reference_takes_a_forced_routing():
    """`chosen` replaces the layer's own top-k: the builder's gradient
    comparison on the chip forces one model's routing into the other."""
    cfg = tiny()
    whole, x = _expert_layer_weights(cfg)
    sl = {n: whole[n][4:8] for n in ("experts_gate", "experts_up",
                                     "experts_down")}
    p = {**whole, **sl}
    bias = jnp.zeros((PUBLISHED_EXPERTS,))
    idx, w = REF.routing(x, whole["router.weight"], bias, cfg)
    assert float(jnp.max(jnp.abs(
        REF.expert_layer(x, p, "", cfg, bias, chosen=(idx, w))
        - REF.expert_layer(x, p, "", cfg, bias)))) == 0.0
    other = (idx + 1) % PUBLISHED_EXPERTS
    assert float(jnp.max(jnp.abs(
        REF.expert_layer(x, p, "", cfg, bias, chosen=(other, w))
        - REF.expert_layer(x, p, "", cfg, bias)))) > 1e-3


# --- the configuration ---------------------------------------------------------
def test_a_sliced_vocabulary_is_the_first_rows_of_the_whole_embedding():
    import paddle_tpu as paddle

    three = dict(num_hidden_layers=3,
                 layer_types=["conv", "conv", "full_attention"])
    cfg_whole = tiny(vocab_size=128, **three)
    cfg_slice = tiny(vocab_size=64, **three)
    w_whole = make_weights(REF.param_shapes(cfg_whole), 5, std=0.3)
    w_slice = dict(w_whole)
    w_slice["embed_tokens.weight"] = w_whole["embed_tokens.weight"][:64]
    ids = np.random.default_rng(1).integers(0, 64, size=70).astype(np.int32)
    model = M.model(cfg_slice).construct(cfg_slice)
    for n, p in model.named_parameters():
        p._value = w_slice[n]
    model.eval()
    sliced = model(paddle.to_tensor(ids[None]))._value[0]
    whole = REF.forward(w_whole, jnp.asarray(ids), cfg_whole)
    assert tuple(sliced.shape) == (70, 64)
    assert float(jnp.max(jnp.abs(sliced - whole[:, :64]))) \
        < 1e-3 * float(jnp.max(jnp.abs(whole)))


def test_parameter_counts_of_the_published_model_and_of_the_cut():
    published = dict(M.published(NAME), num_experts_published=32,
                     training=CFG["training"])
    assert 8.30e9 <= REF.n_params(published) <= 8.38e9      # 8.34 B, tied
    untied = REF.n_params(published) \
        + published["vocab_size"] * published["hidden_size"]
    assert 8.45e9 <= untied <= 8.49e9
    assert REF.n_params(CFG) == 568_647_808
    assert round(REF.n_params(CFG) * 16 / 1e9, 2) == 9.10
    assert REF.layer_kinds(CFG) == ["conv", "conv", "full_attention",
                                    "conv", "conv", "conv"]
    kinds = REF.layer_kinds(published)
    assert (kinds.count("conv"), kinds.count("full_attention")) == (18, 6)
    # the issue's table, part by part
    d = CFG["hidden_size"]
    conv = 3 * d * d + d * d + 3 * d + 2 * d
    attn = 2 * d * d + 2 * d * (d // 4) + 2 * 64 + 2 * d
    dense = 3 * d * CFG["intermediate_size"]
    experts = 8 * 3 * d * CFG["moe_intermediate_size"] + d * 32
    assert (conv, attn, dense, experts) == (
        16_787_456, 10_489_984, 44_040_192, 88_145_920)
    assert 2 * (conv + dense) + (attn + experts) + 3 * (conv + experts) \
        + 16384 * d + d == 568_647_808


def test_train_flops_count_what_this_chip_computes():
    per_token = REF.train_flops_per_token(CFG, 8192)
    assert 1.6e9 < per_token < 1.75e9
    # a held expert by the share of tokens it expects: 4 x 8 / 32 = 1
    more = REF.train_flops_per_token(dict(CFG, num_experts_per_tok=8), 8192)
    expert = 3 * CFG["hidden_size"] * CFG["moe_intermediate_size"]
    assert more - per_token == pytest.approx(
        6 * 4 * 8 * expert * 4 / 32, rel=1e-9)
    # the tied matrix multiplies a token once (the head); the lookup is a
    # gather
    wider = REF.train_flops_per_token(dict(CFG, vocab_size=32768), 8192)
    assert wider - per_token == 6 * 16384 * CFG["hidden_size"]
    m = REF.mixer_shapes(CFG)
    assert m["gqa"] == {"heads": 32, "kv_heads": 8, "dk": 64, "dv": 64,
                        "layers": 1}
    assert m["conv"] == {"taps": 3, "layers": 5}
    assert REF.attention_shape(CFG) is None
    assert REF.max_positions(CFG) == 8192 != CFG["max_position_embeddings"]
    fwd = REF.forward_flops(CFG, 8192, 8192, 8192 * 8193 // 2)
    assert fwd == pytest.approx(8192 * per_token / 3, rel=1e-3)


def test_the_configuration_keeps_every_published_width():
    published = M.published(NAME)
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_experts", "vocab_size"]
    for key, value in published.items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    assert CFG["layer_types"] == published["layer_types"][:6]
    assert CFG["num_experts_published"] == published["num_experts"] == 32
    assert (CFG["num_experts"], CFG["experts_held_start"]) == (8, 0)
    assert CFG["num_experts_per_tok"] == 4
    assert CFG["vocab_size"] * 4 == published["vocab_size"]
    assert CFG["num_dense_layers"] == 2 and CFG["conv_L_cache"] == 3
    assert CFG["training"]["recompute"] is True
    assert CFG["model_type"] == "lfm2_moe"
    for text in ("EP4", "share 0", "layers 0-5", "4 slices", "bends"):
        assert text in CFG["deployment"], text
    cell = M.cell(CELL)
    assert (cell["config"], cell["chips"]) == (NAME, 1)
    traffic = M.traffic(cell["traffic"])
    assert (traffic["seq_len"], traffic["sequences_per_replica"]) \
        == (CFG["training"]["positions"], 1)


def test_the_cell_reports_the_metrics_the_issue_names():
    names = {m["name"] for m in M.metrics_of(CELL, "per_layer")}
    assert {"gqa_flash_fwd_roofline.train", "gqa_flash_bwd_roofline.train",
            "moe_grouped_matmul_time_share.train", "train_mfu",
            "step_device_ms_p50.train", "compiles_in_window.train",
            "device_idle_share.train", "hbm_peak_gb.train",
            "idle_dispatch_share.train", "idle_fetch_share.train",
            "idle_outside_step_share.train",
            "moe_expert_load_max_over_mean.train",
            "moe_routed_here_share.train"} <= names
    assert not {n for n in names if n.startswith(("kda_", "mla_", "flash_"))}
    assert {m["name"] for m in M.metrics_of(CELL, "end_to_end")} \
        == {"train_tok_s", "setup_s"}


def test_the_new_readers_read_nothing_without_a_trace_or_shapes():
    import types

    for name in ("gqa_flash_fwd_roofline.train",
                 "gqa_flash_bwd_roofline.train"):
        spec = M.layer_metric(name)
        reducer = M.reducer(spec["reducer"])
        assert reducer.reduce({"trace": None}, **spec["args"]) is None
        # another architecture's reference has no such mixer
        trace = types.SimpleNamespace(devices={}, window=lambda: (0.0, 1.0))
        ctx = {"trace": trace,
               "values": {"sequences_per_chip": 1, "seq_len": 64},
               "job": types.SimpleNamespace(
                   manifest=M, config=M.config("gpt2-medium-train"))}
        assert reducer.reduce(ctx, **spec["args"]) is None
    spec = M.layer_metric("moe_grouped_matmul_time_share.train")
    assert M.reducer(spec["reducer"]).reduce({"trace": None},
                                             **spec["args"]) is None


def test_the_flash_patterns_tell_the_three_grouped_kernels_apart():
    """The labels the trace gives the three Mosaic calls at 32 / 8 heads of
    64 (the scope jax traced them in, then their result shapes;
    harness/trace.py::op_label): forward by its lse, the two backward
    kernels by theirs, a grouped matmul by neither — and NOT the
    zero-time layout `custom-call`s XLA leaves beside them, whose label
    starts with the opcode (on the chip they made the backward share read
    53.9% where the kernels' own time gives 21.6%)."""
    import re

    fwd = re.compile(M.layer_metric("gqa_flash_fwd_roofline.train")[
        "args"]["pattern"])
    bwd = re.compile(M.layer_metric("gqa_flash_bwd_roofline.train")[
        "args"]["pattern"])
    rag = re.compile(M.layer_metric("moe_grouped_matmul_time_share.train")[
        "args"]["pattern"])
    labels = {
        "fwd": "forward_ custom-call bf16[32,8192,64] f32[32,8192,1]",
        "fwd again": "rematted_computation custom-call bf16[32,8192,64] "
                     "f32[32,8192,1]",
        "dkv": "checkpoint custom-call bf16[8,8192,64] bf16[8,8192,64]",
        "dq": "checkpoint custom-call bf16[32,8192,64]",
        "layout q": "custom-call bf16[32,8192,64]",
        "layout k": "custom-call bf16[8,8192,64]",
        "layout": "custom-call bf16[8192,8,64]",
        "ragged": "ragged-dot-none custom-call bf16[32768,1792]"}
    assert [k for k, v in labels.items() if fwd.search(v)] \
        == ["fwd", "fwd again"]
    assert [k for k, v in labels.items() if bwd.search(v)] == ["dkv", "dq"]
    assert [k for k, v in labels.items() if rag.search(v)] == ["ragged"]


@pytest.mark.parametrize("guarded", [False, True])
def test_train_batch_publishes_the_routing_counter_each_step(guarded):
    """Through `paddle.Model.train_batch` under bf16 autocast as the cell
    runs it, plain and under the anomaly guard: after every step the
    registry holds the model's float32 counter, all assignments of every
    step so far in it."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.monitor import stat_registry

    cfg = tiny(num_hidden_layers=4, layer_types=CFG["layer_types"][:4])
    net = M.model(cfg).construct(cfg)
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=net.parameters()),
        paddle.nn.CrossEntropyLoss())
    model._anomaly_guard = guarded
    ids = np.random.default_rng(2).integers(0, 64, size=(1, 33))
    for step in (1, 2):
        with paddle.amp.auto_cast(dtype="bfloat16"):
            loss = model.train_batch([ids[:, :-1]], [ids[:, 1:]])[0]
        assert np.isfinite(loss)
        counts = stat_registry.held("moe.routed_tokens")
        assert counts.dtype == np.float32 and counts.shape == (2, HELD + 1)
        assert counts.sum() == step * 2 * 32 * cfg["num_experts_per_tok"]


def test_the_models_spans_are_recorded():
    """`text/lfm2_moe/build` with the layer counts and the experts held,
    `text/lfm2_moe/forward` with the tokens — in the tracer the repo has."""
    import paddle_tpu as paddle
    from paddle_tpu.profiler.tracer import tracer

    cfg = tiny()
    tracer.enable(clear=True)
    try:
        net = M.model(cfg).construct(cfg)
        net.eval()
        net(paddle.to_tensor(np.zeros((1, 12), np.int32)))
        spans = {s.name: s.args for s in tracer.get_spans()}
    finally:
        tracer.disable()
        tracer.clear()
    assert spans["text/lfm2_moe/build"] == {
        "layers": 6, "conv_layers": 5, "attn_layers": 1, "experts_held": HELD,
        "experts_published": PUBLISHED_EXPERTS}
    assert spans["text/lfm2_moe/forward"] == {"tokens": 12, "layers": 6}
