"""The Kimi-Linear architecture on the CPU at a tiny size, seeded weights:
the program against perfbench/reference/kimi_linear.py (logits, loss, every
leaf's gradient), the chunked delta rule against the token-by-token
recurrence, flash attention with a v head size other than q/k's, the expert
share against the uncut layer, the sliced vocabulary, and the counts of the
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
NAME = "kimi-linear-48b-a3b-train-ep32"
CFG = M.config(NAME)
REF = M.reference(CFG)
PUBLISHED_EXPERTS, SHARES = 16, 4


def tiny(**over):
    """The cell's configuration with every width shrunk, 4 shares of 16
    experts, this chip share 1."""
    cfg = dict(
        CFG, hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
        kv_lora_rank=8, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_attention_heads=2, vocab_size=64,
        num_experts=PUBLISHED_EXPERTS // SHARES,
        num_experts_published=PUBLISHED_EXPERTS, experts_held_start=4,
        num_experts_per_token=4, gate_low_rank_dim=8,
        linear_attn_config=dict(CFG["linear_attn_config"], head_dim=16,
                                num_heads=2))
    cfg.update(over)
    return cfg


def biases(cfg, scale=0.2):
    """A non-zero correction bias for every expert layer."""
    return {f"layers.{i}.ffn.correction_bias": scale * jax.random.normal(
        jax.random.PRNGKey(i), (cfg["num_experts_published"],))
        for i in range(cfg["first_k_dense_replace"],
                       cfg["num_hidden_layers"])}


@pytest.fixture(scope="module")
def program_and_reference():
    """(loss, logits, gradients) of the program's model and of the
    reference on one sequence whose length is no multiple of a chunk, with
    a non-zero correction bias and every layer recomputed."""
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
    assert (routed.sum(axis=1) == 150 * cfg["num_experts_per_token"]).all()
    assert (routed[:, :-1].sum(axis=1) > 0).all()


def _delta_inputs(T, low, high, seed=0, B=2, H=3, Dk=32, Dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, Dk))) * Dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, Dk)))
    v = jax.random.normal(ks[2], (B, T, H, Dv))
    g = jnp.log(jax.random.uniform(ks[3], (B, T, H, Dk), minval=low,
                                   maxval=high))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("T,low,high", [
    (300, 0.2, 0.999), (64, 0.2, 0.3), (257, 0.95, 0.999), (100, 0.5, 0.5)])
def test_chunked_delta_rule_is_the_recurrence(T, low, high):
    """Forward and every gradient, at lengths that are no multiple of a
    chunk and decays from 0.999 down to 0.2 a token (exp(-G) would
    overflow float32 inside one chunk at 0.2)."""
    from paddle_tpu.ops.linear_attention import (
        gated_delta_rule_chunked, gated_delta_rule_recurrent)

    args = _delta_inputs(T, low, high)
    ref = gated_delta_rule_recurrent(*args)
    out = gated_delta_rule_chunked(*args)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
    w = jax.random.normal(jax.random.PRNGKey(9), ref.shape)
    grads = jax.grad(lambda *a: jnp.sum(gated_delta_rule_chunked(*a) * w),
                     argnums=(0, 1, 2, 3, 4))(*args)
    ref_grads = jax.grad(
        lambda *a: jnp.sum(gated_delta_rule_recurrent(*a) * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    for got, want in zip(grads, ref_grads):
        assert float(jnp.max(jnp.abs(got - want))) \
            < 1e-4 * max(1.0, float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("case", [
    (300, 0.2, 0.999), (64, 0.2, 0.3), (257, 0.95, 0.999), (100, 0.5, 0.5),
    "bf16", "route"], ids=["T300", "T64-fast-decay", "T257-slow-decay",
                           "T100", "bf16-products", "route-counter"])
def test_the_delta_rule_kernels_are_the_recurrence(case):
    """The Pallas route (ops/pallas_ops/delta_rule.py), interpreted on the
    CPU at head size 128: forward and every gradient against the
    token-by-token recurrence at the tolerances the XLA form is held to;
    with bfloat16 operands in the large products, against the XLA form
    given the same; and the route `gated_delta_rule` takes here."""
    from paddle_tpu.ops import linear_attention as la
    from paddle_tpu.ops.pallas_ops.delta_rule import gated_delta_rule_kernel

    kernel = lambda *a, **kw: gated_delta_rule_kernel(*a, interpret=True,
                                                      **kw)
    grads_of = lambda fn, w: jax.grad(
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))
    if case == "route":
        before = dict(la.ROUTE_STATS)
        for dk, dv in ((128, 128), (32, 16)):
            out = la.gated_delta_rule(*_delta_inputs(
                70, 0.5, 0.9, B=1, H=1, Dk=dk, Dv=dv))
            assert out.shape == [1, 70, 1, dv]
        assert la.ROUTE_STATS == {"pallas": before["pallas"],
                                  "xla": before["xla"] + 2}
        return
    if case == "bf16":
        args = _delta_inputs(200, 0.3, 0.999, B=1, H=2, Dk=128, Dv=128)
        twin = lambda *a: la.gated_delta_rule_chunked(
            *a, mm_dtype=jnp.bfloat16)
        mine = lambda *a: kernel(*a, mm_dtype=jnp.bfloat16)
        # the forward rounds the same operands; the backward's cotangents
        # are rounded too, where autodiff of the twin keeps them float32
        want, tol, grad_tol = twin(*args), 1e-4, 1e-2
    else:
        args = _delta_inputs(*case, B=1, H=2, Dk=128, Dv=128)
        twin = mine = None
        want, tol, grad_tol = la.gated_delta_rule_recurrent(*args), 1e-5, 1e-4
    out = (mine or kernel)(*args)
    assert bool(jnp.all(jnp.isfinite(out)))
    scale = lambda a: max(1.0, float(jnp.max(jnp.abs(a))))
    assert float(jnp.max(jnp.abs(out - want))) < tol
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = grads_of(mine or kernel, w)(*args)
    wants = grads_of(twin or la.gated_delta_rule_recurrent, w)(*args)
    for got, ref in zip(grads, wants):
        assert float(jnp.max(jnp.abs(got - ref))) \
            < grad_tol * scale(ref)


def test_the_delta_rule_never_steps_over_single_tokens():
    """The normal path's loops run over groups of chunks, forward and
    backward: no loop in the lowered program has as many steps as
    tokens."""
    import re

    from paddle_tpu.ops import linear_attention as la

    args = _delta_inputs(1024, 0.5, 0.9, B=1, H=1)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(
        la.gated_delta_rule_chunked(*a)), argnums=(0, 1, 2, 3, 4))
    ).lower(*args).as_text()
    # every loop's state carries its slices by group: 1024 tokens are 4
    # groups of GROUP x CHUNK, and no tensor is laid out one token a step
    assert f"tensor<{1024 // (la.CHUNK * la.GROUP)}x" in text
    assert not re.search(r"tensor<1024x1x1x(32|16)xf32>", text)
    assert "stablehlo.while" in text


@pytest.mark.parametrize("interpret_kernel", [False, True])
def test_attention_with_a_v_head_size_of_its_own(interpret_kernel,
                                                 monkeypatch):
    """q/k 24 wide, v 16: the op's XLA route and the flash kernels
    (interpret mode) against explicit softmax, forward and gradients."""
    import paddle_tpu as paddle
    from paddle_tpu.ops import attention

    if interpret_kernel:
        monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH", "1")
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    B, S, H = 1, 200, 2
    q, k = (jax.random.normal(ks[i], (B, S, H, 24)) for i in (0, 1))
    v = jax.random.normal(ks[2], (B, S, H, 16))
    w = jax.random.normal(ks[3], (B, S, H, 16))

    def explicit(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 24 ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    before = dict(attention.ROUTE_STATS)
    tq, tk, tv = (paddle.to_tensor(np.asarray(a), stop_gradient=False)
                  for a in (q, k, v))
    out = attention.scaled_dot_product_attention(tq, tk, tv, is_causal=True)
    route = "pallas" if interpret_kernel else "xla"
    assert attention.ROUTE_STATS[route] == before[route] + 1
    assert tuple(out.shape) == (B, S, H, 16)
    assert float(jnp.max(jnp.abs(out._value - explicit(q, k, v)))) < 1e-5
    (out * paddle.to_tensor(np.asarray(w))).sum().backward()
    want = jax.grad(lambda *a: jnp.sum(explicit(*a) * w), (0, 1, 2))(q, k, v)
    for got, ref in zip((tq, tk, tv), want):
        assert float(jnp.max(jnp.abs(got.grad._value - ref))) < 1e-4


def _expert_layer_weights(cfg, seed=3):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E = cfg["num_experts_published"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda i, *s: 0.4 * jax.random.normal(ks[i], s)
    return {"router.weight": n(0, d, E), "experts_gate": n(1, E, d, f),
            "experts_up": n(2, E, d, f), "experts_down": n(3, E, f, d),
            "shared.gate_proj.weight": n(4, d, f),
            "shared.up_proj.weight": n(5, d, f),
            "shared.down_proj.weight": n(6, f, d)}, n(7, 37, d)


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """THE SHARE TEST.  Over all shares (4 of 16 experts), the routed
    parts the program's layer gives, plus the shared expert counted once,
    equal the uncut reference's expert layer; and each share equals the
    reference given the same share."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    cfg = tiny()
    whole, x = _expert_layer_weights(cfg)
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(11),
                                   (PUBLISHED_EXPERTS,))
    uncut = dict(cfg, num_experts=PUBLISHED_EXPERTS, experts_held_start=0)
    with jax.default_matmul_precision("highest"):
        want = REF.expert_layer(x, whole, "", uncut, bias)
        shared_once = REF.expert_layer(
            x, whole, "", dict(uncut, num_experts=0), bias)
    held = PUBLISHED_EXPERTS // SHARES
    total = shared_once
    routed_here = 0
    for share in range(SHARES):
        lo = share * held
        layer = nn.SparseExpertShare(
            cfg["hidden_size"], cfg["moe_intermediate_size"],
            PUBLISHED_EXPERTS, (lo, held), cfg["num_experts_per_token"],
            cfg["routed_scaling_factor"])
        layer.router.weight._value = whole["router.weight"]
        layer.correction_bias._value = bias
        for n in ("experts_gate", "experts_up", "experts_down"):
            getattr(layer, n)._value = whole[n][lo:lo + held]
        for n in ("gate_proj", "up_proj", "down_proj"):
            getattr(layer.shared, n).weight._value = \
                whole[f"shared.{n}.weight"]
        tx = paddle.to_tensor(np.asarray(x))
        y, counts = layer(tx)
        routed_here += float(counts._value[:-1].sum())
        with jax.default_matmul_precision("highest"):
            same_share = REF.expert_layer(
                x, {**whole, **{n: whole[n][lo:lo + held] for n in (
                    "experts_gate", "experts_up", "experts_down")}}, "",
                dict(cfg, experts_held_start=lo), bias)
        assert float(jnp.max(jnp.abs(y._value - same_share))) < 1e-4
        total = total + y._value - layer.shared(tx)._value
    assert routed_here == 37 * cfg["num_experts_per_token"]
    assert float(jnp.max(jnp.abs(want))) > 0.1
    assert float(jnp.max(jnp.abs(total - want))) < 1e-4


def test_no_token_is_dropped_when_every_token_picks_one_held_expert():
    """Imbalance: a bias that sends every token to held expert 0 first."""
    from paddle_tpu.ops.moe import expert_share

    cfg = tiny()
    whole, x = _expert_layer_weights(cfg)
    bias = jnp.zeros((PUBLISHED_EXPERTS,)).at[4].set(10.0)
    sl = lambda n: whole[n][4:8]
    y, counts = expert_share(
        x, whole["router.weight"], bias, sl("experts_gate"),
        sl("experts_up"), sl("experts_down"), start=4,
        k=cfg["num_experts_per_token"], scale=cfg["routed_scaling_factor"])
    assert counts[0] == x.shape[0]
    with jax.default_matmul_precision("highest"):
        want = REF.expert_layer(
            x, {**whole, **{n: sl(n) for n in (
                "experts_gate", "experts_up", "experts_down")}}, "", cfg,
            bias)
        want = want - REF.expert_layer(
            x, whole, "", dict(cfg, num_experts=0), bias)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-4


def test_rows_the_grouped_matmul_leaves_unwritten_never_reach_a_result(
        monkeypatch):
    """On the chip `ragged_dot` leaves the rows past its groups unwritten,
    forward and in the gradient of its rows (PR 28: the first step's loss
    was right and the second NaN).  Here those rows are poisoned with NaN
    in both directions: the layer's result and every gradient stay what
    they were."""
    from paddle_tpu.ops import moe

    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        live = (jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None]
        return jnp.where(live, real(lhs, rhs, sizes), jnp.nan)

    def fwd(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        live = (jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None]
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)[1](
            jnp.where(live, g, 0))
        return jnp.where(live, d_lhs, jnp.nan), d_rhs, None

    poisoned.defvjp(fwd, bwd)
    cfg = tiny()
    whole, x = _expert_layer_weights(cfg)
    sl = lambda n: whole[n][4:8]
    names = ("experts_gate", "experts_up", "experts_down")

    def loss(x, rw, wg, wu, wd):
        y, _ = moe.expert_share(
            x, rw, jnp.zeros((PUBLISHED_EXPERTS,)), wg, wu, wd, start=4,
            k=cfg["num_experts_per_token"],
            scale=cfg["routed_scaling_factor"])
        return jnp.sum(jnp.square(y))

    args = (x, whole["router.weight"]) + tuple(sl(n) for n in names)
    want = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5 * max(
            1.0, float(jnp.max(jnp.abs(b))))


def test_the_router_scores_in_float32_whatever_the_activations_are():
    """bf16 activations and weights in, the router's matmul still takes
    float32 operands at the highest precision (on the chip the loss limit
    cannot tell a bf16 router from a float32 one: PERF.md section 7)."""
    from paddle_tpu.ops import moe

    bf = jnp.bfloat16
    jaxpr = jax.make_jaxpr(lambda x, w, b: moe.route(x, w, b, 4, 2.0))(
        jnp.ones((8, 32), bf), jnp.ones((32, 16), bf), jnp.zeros((16,), bf))
    dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 1
    assert all(v.aval.dtype == jnp.float32 for v in dots[0].invars)
    assert "HIGHEST" in str(dots[0].params["precision"])
    idx, w = moe.route(jnp.ones((8, 32), bf), jnp.ones((32, 16), bf),
                       jnp.zeros((16,), bf), 4, 2.0)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32


def test_a_sliced_vocabulary_is_the_first_rows_of_the_whole_head():
    two = dict(num_hidden_layers=2, linear_attn_config=dict(
        tiny()["linear_attn_config"], kda_layers=[1], full_attn_layers=[2]))
    cfg_whole = tiny(vocab_size=128, **two)
    cfg_slice = tiny(vocab_size=64, **two)
    w_whole = make_weights(REF.param_shapes(cfg_whole), 5, std=0.3)
    w_slice = dict(w_whole)
    w_slice["embed_tokens.weight"] = w_whole["embed_tokens.weight"][:64]
    w_slice["lm_head.weight"] = w_whole["lm_head.weight"][:, :64]
    ids = np.random.default_rng(1).integers(0, 64, size=70).astype(np.int32)
    model = M.model(cfg_slice).construct(cfg_slice)
    for n, p in model.named_parameters():
        p._value = w_slice[n]
    model.eval()
    import paddle_tpu as paddle

    sliced = model(paddle.to_tensor(ids[None]))._value[0]
    whole = REF.forward(w_whole, jnp.asarray(ids), cfg_whole)
    assert tuple(sliced.shape) == (70, 64)
    assert float(jnp.max(jnp.abs(sliced - whole[:, :64]))) \
        < 1e-3 * float(jnp.max(jnp.abs(whole)))


def test_parameter_counts_of_the_published_model_and_of_the_cut():
    published = dict(M.published(NAME), num_experts_published=256)
    assert 48e9 * 0.99 <= REF.n_params(published) <= 49e9 * 1.01
    assert REF.n_params(CFG) == 602_433_408
    assert round(REF.n_params(CFG) * 16 / 1e9, 2) == 9.64
    assert REF.layer_kinds(CFG) == ["kda", "kda", "kda", "mla", "kda"]
    kinds = REF.layer_kinds(published)
    assert (kinds.count("kda"), kinds.count("mla")) == (20, 7)


def test_train_flops_count_what_this_chip_computes():
    per_token = REF.train_flops_per_token(CFG, 8192)
    assert 2.2e9 < per_token < 2.5e9
    # a held expert by the share of tokens it expects: 8 x 8 / 256
    more = REF.train_flops_per_token(dict(CFG, num_experts_per_token=16),
                                     8192)
    expert = 3 * CFG["hidden_size"] * CFG["moe_intermediate_size"]
    assert more - per_token == pytest.approx(
        6 * 4 * 8 * expert * 8 / 256, rel=1e-9)
    m = REF.mixer_shapes(CFG)
    assert (m["mla"]["dk"], m["mla"]["dv"], m["mla"]["layers"]) == (192, 128, 1)
    assert (m["kda"]["dk"], m["kda"]["heads"], m["kda"]["layers"]) \
        == (128, 32, 4)
    assert REF.attention_shape(CFG) is None
    assert REF.max_positions(CFG) == 1048576


def test_the_configuration_keeps_every_published_width():
    published = M.published(NAME)
    for key, value in published.items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
    la, pla = CFG["linear_attn_config"], published["linear_attn_config"]
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert la[key] == pla[key]
    assert CFG["num_experts_published"] == published["num_experts"] == 256
    assert CFG["vocab_size"] * 8 == published["vocab_size"]
    assert CFG["training"]["recompute"] is True
    traffic = M.traffic(M.cell("train-kimi-linear-s8192")["traffic"])
    assert (traffic["seq_len"], traffic["sequences_per_replica"]) == (8192, 1)


def test_an_unknown_model_type_still_names_the_files_to_add():
    """tests/perfbench's own case took `kimi_linear` for its unknown
    architecture; this PR made it a known one (see tests/perfbench/
    conftest.py), so the rule is held here with a name nothing has."""
    for lookup in (M.model, M.reference, M.tolerance):
        with pytest.raises(FileNotFoundError) as e:
            lookup({"model_type": "no_such_architecture"})
        for path in ("perfbench/models/no_such_architecture.py",
                     "perfbench/reference/no_such_architecture.py",
                     "perfbench/reference/no_such_architecture.tolerance.json"):
            assert path in str(e.value)
    with pytest.raises(FileNotFoundError):
        M.reference({"vocab_size": 8})


def test_the_counter_readers_read_a_held_counter_and_nothing_else():
    from paddle_tpu.framework.monitor import stat_registry

    reducer = M.reducer("held_counter")
    stat_registry.hold("test.kimi.counts", jnp.asarray(
        [[30, 10, 20, 0, 940], [10, 10, 10, 10, 960]], jnp.float32))
    ctx = {}
    assert reducer.reduce(ctx, "test.kimi.counts", "counted_share") \
        == pytest.approx(100.0 * 100 / 2000)
    assert reducer.reduce(ctx, "test.kimi.counts", "max_over_mean") \
        == pytest.approx((30 / 15 + 1.0) / 2)
    assert reducer.reduce(ctx, "test.kimi.nothing", "counted_share") is None


def test_the_mixer_roofline_reads_nothing_without_a_trace_or_shapes():
    import types

    reducer = M.reducer("mixer_roofline")
    args = dict(pattern="^while", shape_fn="delta_rule_train", mixer="kda")
    assert reducer.reduce({"trace": None}, **args) is None
    gpt2 = M.config("gpt2-medium-train")
    trace = types.SimpleNamespace(devices={}, window=lambda: (0.0, 1.0))
    ctx = {"trace": trace, "values": {"sequences_per_chip": 1,
                                      "seq_len": 64},
           "job": types.SimpleNamespace(manifest=M, config=gpt2)}
    assert reducer.reduce(ctx, **args) is None


def test_hybrid_shape_functions():
    from perfbench.harness import flops_hybrid as H

    flops, nbytes = H.attn_fwd(1, 8192, 32, 192, 128)
    assert flops == 2 * 8192 * 8192 * 320 * 32 * 0.5
    assert nbytes == 2 * 320 * 32 * 8192 * 2
    bwd, _ = H.attn_bwd(1, 8192, 32, 192, 128)
    assert bwd == 2 * 8192 * 8192 * (3 * 192 + 2 * 128) * 32 * 0.5
    fwd, _ = H.delta_rule_fwd(1, 8192, 32, 128, 128)
    assert fwd == 7 * 128 * 128 * 32 * 8192
    assert H.delta_rule_train(1, 8192, 32, 128, 128)[0] == 3 * fwd


@pytest.mark.parametrize("guarded", [False, True])
def test_train_batch_publishes_the_routing_counter_each_step(guarded):
    """Through `paddle.Model.train_batch` under bf16 autocast as the cell
    runs it, plain and under the anomaly guard: after every step the
    registry holds the model's float32 counter, all assignments of every
    step so far in it."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.monitor import stat_registry

    cfg = tiny(num_hidden_layers=2, linear_attn_config=dict(
        tiny()["linear_attn_config"], kda_layers=[1], full_attn_layers=[2]))
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
        assert counts.dtype == np.float32 and counts.shape == (1, 4 + 1)
        assert counts.sum() == step * 32 * cfg["num_experts_per_token"]
