"""One GPT block and one paged serving core (ISSUE 30).

``text/generation.py`` holds ONE GPT-2 layer (``_gpt_block``) and ONE
paged core builder (``_make_gpt_paged_core``): the dense decode step,
the one-chip paged programs and the mesh-sharded program all run the
same block, each behind its own ``attend`` (block against cache).  The
mesh form is the core's body under ``shard_map``; the one-chip form is
that body called directly.  These tests hold the seams:

- on one chip (no layout, or a layout of size 1) nothing of the mesh is
  traced — no ``shard_map``, no collective, no ``axis_index``;
- under a mesh each collective is traced exactly where its static degree
  asks for it (``all_gather`` iff tp > 1, ``pmax``/``psum`` iff sp > 1);
- the dense step and the paged core compute the same logits;
- the engine takes the model's geometry from ``generation.py``;
- the block's source occurs once.

Byte-identity of whole streams across the forms is the job of
test_serving_mesh.py / test_serving.py / test_quant_serving.py.
"""
import inspect

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.serving import ServingEngine, engine as engine_mod
from paddle_tpu.text import generation
from paddle_tpu.text.generation import (ServingMeshLayout,
                                        make_gpt_decode_step,
                                        make_gpt_paged_ragged_step)

VOCAB = 50
PAGE, M, PAGES = 4, 16, 40            # 16 pages of 4 = the model's 64 positions
MESH_ONLY = {"shard_map", "all_gather", "psum", "pmax", "axis_index"}


@pytest.fixture(scope="module")
def gpt(shared_gpt_small):
    return shared_gpt_small


@pytest.fixture(scope="module")
def quant(gpt):
    from paddle_tpu.slim import export_serving_quant

    rng = np.random.RandomState(3)
    return export_serving_quant(
        gpt, calib_prompts=rng.randint(1, VOCAB, (4, 12)).astype(np.int32))


def _qkw(mode, quant):
    """Builder keywords of one KV/weight mode."""
    if mode == "native":
        return {}
    kw = dict(kv_cache_dtype="int8", weight_quant=quant["weights"])
    if mode == "int8_static":
        kw["kv_scales"] = quant["kv_scales"]
    return kw


def _primitives(jaxpr):
    """Names of every primitive of a jaxpr, nested jaxprs included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub)
    return names


def _traced_primitives(ragged_fn, init_pages, lanes, rows):
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)     # noqa: E731
    closed = jax.make_jaxpr(ragged_fn)(
        i32(lanes), i32(lanes), i32(lanes, M), i32(lanes, rows),
        i32(lanes, rows), i32(lanes, rows), i32(lanes), init_pages(PAGES))
    return _primitives(closed.jaxpr)


@pytest.mark.parametrize("rows", [1, 8], ids=["Q1", "Q8"])
@pytest.mark.parametrize("mode", ["native", "int8_static", "int8_dynamic"])
def test_one_chip_core_traces_no_mesh_op(gpt, quant, mode, rows):
    """No layout, and a layout of size 1: the body is called directly."""
    for layout in (None, ServingMeshLayout(tp=1, sp=1)):
        fn, init_pages = make_gpt_paged_ragged_step(
            gpt, PAGE, M, mesh_layout=layout, **_qkw(mode, quant))
        pools = jax.tree_util.tree_leaves(init_pages(PAGES))
        assert all(len(a.sharding.device_set) == 1 for a in pools)
        prims = _traced_primitives(fn, init_pages, 4, rows)
        assert not prims & MESH_ONLY, sorted(prims & MESH_ONLY)


@pytest.mark.parametrize("tp,sp", [(2, 1), (1, 2), (2, 2)],
                         ids=["tp2", "sp2", "tp2xsp2"])
def test_mesh_core_collectives(gpt, tp, sp):
    """The same body under shard_map: every mesh-only step sits behind
    the static degree that needs it."""
    fn, init_pages = make_gpt_paged_ragged_step(
        gpt, PAGE, M, mesh_layout=ServingMeshLayout(tp=tp, sp=sp))
    pool = init_pages(PAGES)["k"][0]
    assert len(pool.sharding.device_set) == tp * sp
    prims = _traced_primitives(fn, init_pages, 4, 8)
    assert "shard_map" in prims
    assert ("all_gather" in prims) == (tp > 1)
    for name in ("pmax", "psum", "axis_index"):
        assert (name in prims) == (sp > 1), name


@pytest.mark.parametrize("mode", ["native", "int8_static"])
def test_dense_and_paged_steps_share_the_block(gpt, quant, mode,
                                               monkeypatch):
    """The dense ring step and the paged core at Q = 1 over a 20-token
    prompt: one block (counted), two caches, the same logits."""
    calls = []
    block = generation._gpt_block
    monkeypatch.setattr(
        generation, "_gpt_block",
        lambda *a, **kw: calls.append(a[2]) or block(*a, **kw))
    kw = _qkw(mode, quant)
    prompt = np.random.RandomState(5).randint(1, VOCAB, (20,)).astype(np.int32)
    layers = len(gpt.layers)

    dense_step, init_state = make_gpt_decode_step(gpt, 24, **kw)
    state, dense = init_state(1), []
    for tok in prompt:
        logits, state = dense_step(jnp.asarray([tok]), state)
        dense.append(np.asarray(logits[0]))
    assert calls == list(range(layers)) * len(prompt)

    del calls[:]
    core, init_pages = generation._make_gpt_paged_core(gpt, PAGE, M, **kw)
    core = jax.jit(core, static_argnames=("qgroup",))
    kv = init_pages(PAGES)
    table = jnp.arange(1, M + 1, dtype=jnp.int32)[None, :]
    for t, tok in enumerate(prompt):
        logits, kv = core(jnp.asarray([tok]), jnp.asarray([t], jnp.int32),
                          table, kv, qgroup=1)
        np.testing.assert_allclose(np.asarray(logits[0]), dense[t],
                                   rtol=2e-5, atol=2e-5)
        assert int(np.argmax(logits[0])) == int(np.argmax(dense[t]))
    assert calls == list(range(layers))          # one trace, L layers


def test_engine_reads_geometry_from_the_core(monkeypatch):
    """``ServingEngine`` takes the position-table length and the head
    count from ``generation._gpt_geometry``, not from the model's
    attributes."""
    import paddle_tpu
    from paddle_tpu.text.models import GPTModel

    paddle_tpu.seed(11)
    model = GPTModel(vocab_size=VOCAB, hidden_size=32, num_layers=1,
                     num_heads=2, ffn_size=64, max_seq_len=64, dropout=0.0)
    model.eval()
    assert generation._gpt_geometry(model) == (1, 2, 16, 32, 64, VOCAB)
    assert ServingEngine(model, page_size=PAGE).max_seq_len == 64
    source = inspect.getsource(engine_mod)
    assert "model.wpe" not in source
    assert "model.layers[0].attn" not in source

    monkeypatch.setattr(generation, "_gpt_geometry",
                        lambda m: (1, 3, 16, 48, 32, VOCAB))
    assert ServingEngine(model, page_size=PAGE).max_seq_len == 32
    with pytest.raises(InvalidArgumentError, match="position table"):
        ServingEngine(model, page_size=PAGE, max_seq_len=64)
    with pytest.raises(InvalidArgumentError, match=r"num_heads \(3\)"):
        ServingEngine(model, page_size=PAGE, mesh_axes={"tp": 2})


def test_the_block_is_written_once():
    """The layer's feed-forward, its first norm and the paged core's
    builder each occur once in generation.py; the mesh twin is gone."""
    source = inspect.getsource(generation)
    assert source.count("_gelu(mm(") == 1
    assert source.count("ln1.weight") == 1
    assert source.count("def _make_gpt_paged_") == 1
    assert not hasattr(generation, "_make_gpt_paged_sharded_core")
