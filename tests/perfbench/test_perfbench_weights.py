"""The seeded weights: every configuration the benchmark has drawn whole
in one program, the float32 arrays it has always had, bit for bit; a
larger one group by group, classes over the bound split and still
deterministic; bfloat16 as the rounded float32 draw; `build` installing
each group before the next is drawn and refusing a model of another type."""
import math
import types

import numpy as np
import pytest

import preset_tree  # noqa: F401 — puts the repo root on sys.path
from perfbench.harness import model as model_mod
from perfbench.harness import weights as W
from perfbench.harness.manifest import Manifest

M = Manifest(preset_tree.ROOT)

# One chip's share of a bfloat16 serving cut of several billion parameters
# (ISSUE 35; no configuration of the benchmark): five layers of width 6,144
# with latent attention — one dense layer with a selection indexer, three
# sparse-expert layers without, one with — 16 experts of 2,048 stacked a
# layer beside a shared one and a 256-way router, 19,360 rows of embedding
# and of head.  Drawn alone on the chip once (PERF.md section 6, PR 35).
LARGE_PARTS = {
    "attn": {"q_a.weight": (6144, 2048), "q_a_norm.weight": (2048,),
             "q_b.weight": (2048, 16384), "kv_a.weight": (6144, 576),
             "kv_a_norm.weight": (512,), "kv_b.weight": (512, 28672),
             "o.weight": (16384, 6144)},
    "norms": {"input_norm.weight": (6144,), "post_attn_norm.weight": (6144,)},
    "indexer": {"wq_b.weight": (2048, 4096), "wk.weight": (6144, 128),
                "k_norm.weight": (128,), "k_norm.bias": (128,),
                "weights_proj.weight": (6144, 32)},
    "dense_ffn": {"gate.weight": (6144, 12288), "up.weight": (6144, 12288),
                  "down.weight": (12288, 6144)},
    "shared_expert": {"gate.weight": (6144, 2048), "up.weight": (6144, 2048),
                      "down.weight": (2048, 6144)},
    "router": {"weight": (256, 6144), "bias": (256,)},
    "experts": {"gate": (16, 6144, 2048), "up": (16, 6144, 2048),
                "down": (16, 2048, 6144)},
}
SPARSE = ("attn", "norms", "shared_expert", "router", "experts")
LARGE_LAYERS = [("attn", "norms", "indexer", "dense_ffn"), SPARSE, SPARSE,
                SPARSE, SPARSE + ("indexer",)]
LARGE_TOP = {"embed.weight": (19360, 6144), "head.weight": (19360, 6144),
             "final_norm.weight": (6144,)}
LARGE_ELEMENTS = 3_881_517_056


def large_shapes():
    shapes = dict(LARGE_TOP)
    for i, parts in enumerate(LARGE_LAYERS):
        for part in parts:
            for leaf, shape in LARGE_PARTS[part].items():
                shapes[f"layers.{i}.{part}.{leaf}"] = shape
    return shapes


def parent_make_weights(shapes, seed, std=W.INIT_STD):
    """make_weights as it stood before ISSUE 35 — every class in one
    jitted call, float32: the oracle for "the arrays they get today"."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)
    classes = {}
    for n in names:
        classes.setdefault(tuple(shapes[n]), []).append(n)
    class_list = sorted(classes.items())

    def init(kd):
        key = jax.random.wrap_key_data(kd, impl="threefry2x32")
        out = {}
        for i, (shape, members) in enumerate(class_list):
            block = std * jax.random.normal(
                jax.random.fold_in(key, i), (len(members),) + shape,
                jnp.float32)
            for j, n in enumerate(members):
                gain = n.endswith(".weight") and len(shape) == 1
                out[n] = block[j] + 1.0 if gain else block[j]
        return out

    return jax.jit(init)(jnp.asarray(W.key_data(seed, 0)))


def bits(x):
    a = np.asarray(x)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def toy_shapes(config_name):
    cfg = M.config(config_name)
    ref = M.reference(cfg)
    small = {"gpt2": {"n_embd": 16, "n_head": 2, "n_layer": 3,
                      "vocab_size": 97, "n_positions": 8}}
    return ref.param_shapes(dict(cfg, **small[cfg["model_type"]]))


@pytest.mark.parametrize("seed", [0, 5, 2 ** 31 + 7])
def test_float32_classes_below_the_bound_are_the_parents_bit_for_bit(seed):
    shapes = toy_shapes("gpt2-medium-train")
    old, new = parent_make_weights(shapes, seed), W.make_weights(shapes, seed)
    assert set(old) == set(new) == set(shapes)
    for n in shapes:
        assert new[n].dtype == np.float32 and new[n].shape == shapes[n]
        assert np.array_equal(bits(old[n]), bits(new[n])), n


def block_bytes(group):
    return sum(4 * math.prod(shape) * len(members)
               for _, _, shape, members in group)


@pytest.mark.parametrize("config", [c["name"] for c in M.data["configs"]])
def test_every_configuration_is_drawn_whole_in_one_program(config,
                                                           monkeypatch):
    """As before groups existed: one program, each class one block under
    fold_in(key, i) — the arrays and the set-up the cells have had.  Were
    one to outgrow WHOLE_BYTES, its classes are all within the groups'
    bound, so the groups would still draw the same arrays."""
    cfg = M.config(config)
    assert W.weights_dtype(cfg) == "float32"
    shapes = M.reference(cfg).param_shapes(cfg)
    plan = W.plan_groups(shapes)
    assert len(plan) == 1
    assert sorted(n for _, _, _, m in plan[0] for n in m) == sorted(shapes)
    assert all(g is None for _, g, _, _ in plan[0])
    assert block_bytes(plan[0]) <= W.WHOLE_BYTES <= 4 << 30
    monkeypatch.setattr(W, "WHOLE_BYTES", 0)
    plan = W.plan_groups(shapes)
    assert all(g is None for group in plan for _, g, _, _ in group)
    assert max(block_bytes(group) for group in plan) <= W.GROUP_BYTES
    assert 0.875 * 2 ** 30 <= W.GROUP_BYTES <= 2 << 30
    total = 4 * sum(math.prod(s) for s in shapes.values())
    assert 1 <= len(plan) <= 2 * total // W.GROUP_BYTES + 1 <= 5


def test_whole_or_in_groups_the_classes_within_the_bound_are_the_same_bits(
        monkeypatch):
    shapes = toy_shapes("gpt2-medium-train")
    whole = W.make_weights(shapes, 13)
    # the bound is the largest class's block: none is split, some share
    largest = max(block_bytes([part]) for part in W.plan_groups(shapes)[0])
    monkeypatch.setattr(W, "WHOLE_BYTES", 0)
    monkeypatch.setattr(W, "GROUP_BYTES", largest)
    plan = W.plan_groups(shapes)
    assert len(plan) > 1 and any(len(group) > 1 for group in plan)
    assert all(g is None for group in plan for _, g, _, _ in group)
    packed = W.make_weights(shapes, 13)
    assert all(np.array_equal(bits(whole[n]), bits(packed[n]))
               for n in shapes)


def test_the_large_cut_is_the_stated_table_and_splits_as_planned():
    shapes = large_shapes()
    assert sum(math.prod(s) for s in shapes.values()) == LARGE_ELEMENTS
    plan = W.plan_groups(shapes)
    names = [n for group in plan for _, _, _, members in group
             for n in members]
    assert sorted(names) == sorted(shapes)          # each leaf once
    # no leaf of this table is over the bound, so no group is
    assert max(block_bytes(group) for group in plan) <= W.GROUP_BYTES
    split = {shape for group in plan for _, g, shape, _ in group
             if g is not None}
    assert split == {(16, 6144, 2048), (16, 2048, 6144), (16384, 6144)}
    # a run of a split class is a group of its own
    assert all(len(group) == 1 for group in plan
               if group[0][1] is not None)


def test_a_class_over_the_bound_is_split_and_deterministic(monkeypatch):
    shapes = toy_shapes("gpt2-medium-train")
    monkeypatch.setattr(W, "WHOLE_BYTES", 0)
    monkeypatch.setattr(W, "GROUP_BYTES", 4 * 16 * 16 * 5)
    plan = W.plan_groups(shapes)
    parts = [part for group in plan for part in group]
    assert {len(m) for _, g, s, m in parts if s == (16, 16)} == {5, 2}
    # a single leaf over the bound is a group of its own
    assert [(g, len(m)) for _, g, s, m in parts if s == (97, 16)] == [(0, 1)]
    assert [g for _, g, s, _ in parts if s == (16,)] == [None]
    # whole classes share a program while they fit the bound together
    assert [s for _, _, s, _ in plan[0]] == [(8, 16), (16,)]
    assert max(block_bytes(g) for g in plan if g[0][1] is None) \
        <= W.GROUP_BYTES
    whole = {n for _, g, _, m in parts if g is None for n in m}
    groups = list(W.weight_groups(shapes, 5))
    a = {n: v for g in groups for n, v in g.items()}
    b = {n: v for g in W.weight_groups(shapes, 5) for n, v in g.items()}
    c = {n: v for g in W.weight_groups(shapes, 6) for n, v in g.items()}
    assert len(groups) == len(plan) == 12
    assert set(a) == set(shapes)
    assert all(np.array_equal(bits(a[n]), bits(b[n])) for n in shapes)
    assert not any(np.array_equal(bits(a[n]), bits(c[n])) for n in shapes)
    # members of one class drawn in different groups are different draws
    two = [n for n in sorted(shapes) if shapes[n] == (16, 16)]
    assert not np.array_equal(bits(a[two[0]]), bits(a[two[5]]))
    # a class still under the bound is what the parent drew
    old = parent_make_weights(shapes, 5)
    for n in shapes:
        assert (n in whole) == bool(
            np.array_equal(bits(old[n]), bits(a[n]))), n


def test_bfloat16_leaves_are_the_float32_draw_rounded_once():
    import jax.numpy as jnp

    shapes = toy_shapes("gpt2-medium-train")
    full, half = W.make_weights(shapes, 9), W.make_weights(shapes, 9,
                                                           "bfloat16")
    for n in shapes:
        assert half[n].dtype == jnp.bfloat16 and half[n].shape == shapes[n]
        assert np.array_equal(bits(full[n].astype(jnp.bfloat16)),
                              bits(half[n])), n
    gain = np.asarray(half["ln_f.weight"].astype(jnp.float32))
    assert abs(gain.mean() - 1.0) < 0.05        # gains are 1 + the draw


@pytest.mark.parametrize("name", ["float16", "int8", "fp8", ""])
def test_an_unknown_weights_dtype_is_refused_by_name(capsys, name):
    with pytest.raises(SystemExit) as e:
        W.weights_dtype({"weights_dtype": name})
    assert e.value.code == 4
    assert repr(name) in capsys.readouterr().err
    assert W.weights_dtype({}) == "float32"
    assert W.weights_dtype({"weights_dtype": "bfloat16"}) == "bfloat16"


def test_the_reference_upcasts_bfloat16_leaves():
    """The convention of weights.py: the reference is handed the stored
    arrays and computes in float32 from them — the same logits as from
    their float32 copies, in float32."""
    import jax.numpy as jnp

    cfg = dict(M.config("gpt2-small-serve"), n_embd=16, n_head=2, n_layer=2,
               vocab_size=37, n_positions=8)
    ref = M.reference(cfg)
    half = W.make_weights(ref.param_shapes(cfg), 3, "bfloat16", std=0.3)
    ids = np.arange(8) % 37
    got = ref.forward(half, ids, cfg)
    want = ref.forward({n: v.astype(jnp.float32) for n, v in half.items()},
                       ids, cfg)
    assert got.dtype == jnp.float32
    assert np.array_equal(bits(got), bits(want))


# -- build: in place, a group at a time ---------------------------------------
class StubParameter:
    def __init__(self, name, value, log):
        self.name, self._v, self.log = name, value, log
        self.shape = list(value.shape)

    @property
    def _value(self):
        return self._v

    @_value.setter
    def _value(self, new):
        self.log.append(("install", self.name))
        self._v = new


def stub_manifest(shapes, model_dtype, log):
    """A manifest whose reference states `shapes` and whose model holds a
    zero leaf of `model_dtype` for each."""
    import jax.numpy as jnp

    def construct(config):
        params = [StubParameter(n, jnp.zeros(s, model_dtype), log)
                  for n, s in shapes.items()]
        return types.SimpleNamespace(
            named_parameters=lambda: [(p.name, p) for p in params])

    return types.SimpleNamespace(
        reference=lambda cfg: types.SimpleNamespace(
            param_shapes=lambda c: shapes),
        model=lambda cfg: types.SimpleNamespace(construct=construct))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_build_installs_each_group_before_the_next_is_drawn(monkeypatch,
                                                            dtype):
    shapes = toy_shapes("gpt2-medium-train")
    monkeypatch.setattr(W, "WHOLE_BYTES", 0)
    monkeypatch.setattr(W, "GROUP_BYTES", 4 * 16 * 16 * 5)
    log = []
    drawer = W._drawer()

    def counting(*args):
        leaves = drawer(*args)
        log.append(("draw", len(leaves)))
        return leaves

    monkeypatch.setattr(W, "_drawer", lambda: counting)
    model, weights = model_mod.build(
        stub_manifest(shapes, dtype, log), {"weights_dtype": dtype}, 11)
    # between two draws every leaf of the first was installed: no group
    # is ever alive uninstalled beside the next
    pending, draws = 0, 0
    for what, n in log:
        if what == "draw":
            assert pending == 0, log
            pending, draws = n, draws + 1
        else:
            pending -= 1
    assert pending == 0 and draws == len(W.plan_groups(shapes)) == 12
    # `weights` names the installed buffers themselves, in the stated type
    want = W.make_weights(shapes, 11, dtype)
    for n, p in model.named_parameters():
        assert p._value is weights[n]
        assert np.array_equal(bits(p._value), bits(want[n])), n


@pytest.mark.parametrize("configured,held", [("bfloat16", "float32"),
                                             ("float32", "bfloat16")])
def test_build_refuses_a_model_of_another_type(capsys, configured, held):
    shapes = {"a.weight": (4, 4), "b.weight": (4,)}
    with pytest.raises(SystemExit) as e:
        model_mod.build(stub_manifest(shapes, held, []),
                        {"weights_dtype": configured}, 1)
    assert e.value.code == 4
    err = capsys.readouterr().err
    assert "a.weight" in err and held in err and configured in err


def test_build_still_refuses_other_shapes(capsys):
    manifest = stub_manifest({"a.weight": (4, 4)}, "float32", [])
    manifest.reference = lambda cfg: types.SimpleNamespace(
        param_shapes=lambda c: {"a.weight": (4, 5)})
    with pytest.raises(SystemExit) as e:
        model_mod.build(manifest, {}, 1)
    assert e.value.code == 4 and "a.weight" in capsys.readouterr().err
