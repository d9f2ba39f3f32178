"""The plain reference against a hand-checked tiny case and an independent
scalar-loop GPT-2; the seeded weights."""
import math

import numpy as np
import pytest

import preset_tree  # noqa: F401 — puts the repo root on sys.path
from perfbench.harness import weights as W
from perfbench.harness.model import reference

ref = reference()

HAND = {"vocab_size": 3, "n_positions": 2, "n_embd": 2, "n_layer": 1,
        "n_head": 1, "n_inner": None, "activation_function": "gelu",
        "layer_norm_epsilon": 1e-5}


def _zero_block(cfg):
    p = {n: np.zeros(s, np.float32)
         for n, s in ref.param_shapes(cfg).items()}
    for n in p:
        if n.endswith(("ln1.weight", "ln2.weight", "ln_f.weight")):
            p[n] = np.ones_like(p[n])
    return p


def test_hand_checked_case():
    """All block weights zero: x = wte[id] + wpe[pos] passes the residual
    stream untouched; ln_f((3, 1)) = (1, -1) / sqrt(1 + 1e-5); the tied head
    gives logits = ln_f(x) . wte rows."""
    p = _zero_block(HAND)
    p["wte.weight"] = np.array([[2, 0], [0, 2], [1, 1]], np.float32)
    p["wpe.weight"] = np.array([[1, 1], [0, 3]], np.float32)
    logits = np.asarray(ref.forward(p, np.array([0, 1]), HAND))
    k = 1.0 / math.sqrt(1.0 + 1e-5)
    # position 0: x = (2,0)+(1,1) = (3,1) -> (k,-k); position 1: x =
    # (0,2)+(0,3) = (0,5): mean 2.5, var 6.25 -> (-1, 1) / sqrt(1 + 1e-5/6.25)
    k1 = 1.0 / math.sqrt(1.0 + 1e-5 / 6.25)
    want = np.array([[2 * k, -2 * k, 0.0], [-2 * k1, 2 * k1, 0.0]])
    np.testing.assert_allclose(logits, want, atol=1e-6)
    loss, top = ref.sequence_loss(p, np.array([0, 1]), np.array([0, 1]),
                                  HAND)
    nll = [math.log(math.exp(2 * k) + math.exp(-2 * k) + 1) - 2 * k,
           math.log(math.exp(2 * k1) + math.exp(-2 * k1) + 1) - 2 * k1]
    assert float(loss) == pytest.approx(sum(nll) / 2, abs=1e-6)
    assert top.tolist() == [0, 1]


def _loop_gpt2(p, ids, cfg):
    """GPT-2 in scalar loops over positions and heads (float64)."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    h, heads = cfg["n_embd"], cfg["n_head"]
    d = h // heads

    def ln(x, w, b):
        return (x - x.mean()) / math.sqrt(x.var() + 1e-5) * w + b

    def gelu(x):
        return np.array([0.5 * v * (1 + math.erf(v / math.sqrt(2)))
                         for v in x])

    xs = [p["wte.weight"][t] + p["wpe.weight"][i] for i, t in enumerate(ids)]
    for layer in range(cfg["n_layer"]):
        n = f"layers.{layer}."
        hs = [ln(x, p[n + "ln1.weight"], p[n + "ln1.bias"]) for x in xs]
        q = [v @ p[n + "attn.q_proj.weight"] + p[n + "attn.q_proj.bias"]
             for v in hs]
        k = [v @ p[n + "attn.k_proj.weight"] + p[n + "attn.k_proj.bias"]
             for v in hs]
        v_ = [v @ p[n + "attn.v_proj.weight"] + p[n + "attn.v_proj.bias"]
              for v in hs]
        new = []
        for i, x in enumerate(xs):
            ctx = np.zeros(h)
            for a in range(heads):
                sl = slice(a * d, (a + 1) * d)
                s = np.array([q[i][sl] @ k[j][sl] / math.sqrt(d)
                              for j in range(i + 1)])
                w = np.exp(s - s.max())
                w /= w.sum()
                ctx[sl] = sum(w[j] * v_[j][sl] for j in range(i + 1))
            x = x + ctx @ p[n + "attn.out_proj.weight"] \
                + p[n + "attn.out_proj.bias"]
            m = ln(x, p[n + "ln2.weight"], p[n + "ln2.bias"])
            m = gelu(m @ p[n + "fc1.weight"] + p[n + "fc1.bias"])
            new.append(x + m @ p[n + "fc2.weight"] + p[n + "fc2.bias"])
        xs = new
    return np.stack([ln(x, p["ln_f.weight"], p["ln_f.bias"])
                     @ p["wte.weight"].T for x in xs])


TINY = {"vocab_size": 37, "n_positions": 12, "n_embd": 16, "n_layer": 2,
        "n_head": 4, "n_inner": None, "activation_function": "gelu",
        "layer_norm_epsilon": 1e-5}


@pytest.fixture(scope="module")
def tiny_weights():
    w = W.make_weights(ref.param_shapes(TINY), seed=2 ** 31 + 3, std=0.3)
    return {k: np.asarray(v) for k, v in w.items()}


def test_reference_matches_the_scalar_loops(tiny_weights):
    ids = np.array([3, 1, 4, 1, 5, 9, 2, 6, 5, 3])
    got = np.asarray(ref.forward(tiny_weights, ids, TINY))
    np.testing.assert_allclose(got, _loop_gpt2(tiny_weights, ids, TINY),
                               atol=2e-4)


def test_reference_is_causal(tiny_weights):
    a = np.array([3, 1, 4, 1, 5, 9])
    b = a.copy()
    b[4:] = [7, 8]
    la = np.asarray(ref.forward(tiny_weights, a, TINY))
    lb = np.asarray(ref.forward(tiny_weights, b, TINY))
    np.testing.assert_allclose(la[:4], lb[:4], atol=1e-6)
    assert np.abs(la[4:] - lb[4:]).max() > 1e-3


def test_batch_loss_is_the_mean_of_sequence_losses(tiny_weights):
    x = np.array([[3, 1, 4, 1], [5, 9, 2, 6]])
    y = np.array([[1, 4, 1, 5], [9, 2, 6, 5]])
    each = [float(ref.sequence_loss(tiny_weights, x[i], y[i], TINY)[0])
            for i in range(2)]
    assert float(ref.batch_loss(tiny_weights, x, y, TINY)) \
        == pytest.approx(sum(each) / 2, rel=1e-6)


def test_gelu_new_is_the_tanh_form():
    cfg = dict(TINY, activation_function="gelu_new")
    assert float(ref._act(np.float32(1.0), "gelu_new")) \
        == pytest.approx(0.841192, abs=1e-5)
    assert float(ref._act(np.float32(1.0), "gelu")) \
        == pytest.approx(0.841345, abs=1e-5)
    with pytest.raises(ValueError):
        ref._act(np.float32(1.0), "relu6")
    assert cfg["activation_function"] == "gelu_new"


@pytest.mark.parametrize("name,count", [
    ("configs/gpt2-small-serve", 124_439_808),
    ("configs/gpt2-medium-train", 354_823_168),
    ("unproven/gpt2-large-train-dp2mp2", 774_030_080)])
def test_published_parameter_counts(name, count):
    import json
    import os

    with open(os.path.join(preset_tree.ROOT, "perfbench",
                           name + ".json")) as f:
        assert ref.n_params(json.load(f)) == count


def test_weights_follow_the_seed_and_nothing_else():
    shapes = ref.param_shapes(TINY)
    a = W.make_weights(shapes, 5)
    b = W.make_weights(shapes, 5)
    c = W.make_weights(shapes, 2 ** 31 + 5)
    assert set(a) == set(shapes)
    for n, s in shapes.items():
        assert tuple(a[n].shape) == tuple(s) and str(a[n].dtype) == "float32"
        np.testing.assert_array_equal(np.asarray(a[n]), np.asarray(b[n]))
    assert np.abs(np.asarray(a["wte.weight"])
                  - np.asarray(c["wte.weight"])).max() > 0
    # gains sit around 1, everything else around 0; no two layers alike
    assert abs(float(np.asarray(a["ln_f.weight"]).mean()) - 1) < 0.05
    assert abs(float(np.asarray(a["layers.0.fc1.weight"]).mean())) < 0.01
    assert np.abs(np.asarray(a["layers.0.fc1.weight"])
                  - np.asarray(a["layers.1.fc1.weight"])).max() > 0
