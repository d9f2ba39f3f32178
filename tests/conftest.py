"""Test configuration.

Forces an 8-device virtual CPU platform (SURVEY §4: reference distributed
tests run multi-process on localhost; here multi-device single-process on a
virtual mesh — --xla_force_host_platform_device_count).
"""
import os

# tests run on the CPU whatever the host has: set BEFORE jax initializes its
# backends (the chip is exercised by chip_smoke.py, one process per chip).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP) — register the marker so
    # the long Poisson/failover load tests deselect cleanly
    config.addinivalue_line(
        "markers",
        "slow: long-running load test, excluded from the tier-1 run "
        "(-m 'not slow')")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu

    paddle_tpu.seed(102)
    yield


@pytest.fixture(scope="session")
def greedy_ref_memo():
    """SESSION-scoped ``generate()`` reference memo (ISSUE 14 suite
    health, extending test_numeric_guards' module-level memo of ISSUE
    13 to every serving byte-identity module).  Each ``generate()``
    call builds — and XLA-compiles — a fresh dense decode closure, so
    every repeated (model, prompt, budget, end_id) reference costs a
    full compile; the serving modules re-derive the same greedy refs
    across tests (and, via ``shared_gpt_small``, across modules).  The
    memo pays each distinct reference ONCE per suite.

    Returns ``ref(model, input_ids, max_new_tokens, end_id=0,
    quant=None, quant_key=None)`` -> the UNTRUNCATED [T] (1-D input)
    or [B, T] token array, a defensive copy.  EOS truncation stays at
    the call sites (it is per-consumer policy, not part of the
    reference).  ``quant=`` references must pass a stable
    ``quant_key`` naming the export; keys are scoped per MODEL via a
    WeakKeyDictionary, so id-reuse of a collected private model can
    never alias another model's streams."""
    import weakref

    from paddle_tpu.text.generation import generate

    caches = weakref.WeakKeyDictionary()

    def ref(model, input_ids, max_new_tokens, end_id=0, quant=None,
            quant_key=None):
        ids = np.asarray(input_ids, np.int32)
        squeeze = ids.ndim == 1
        if squeeze:
            ids = ids[None, :]
        if quant is not None and quant_key is None:
            raise ValueError(
                "quant= references need a stable quant_key to memoize")
        cache = caches.setdefault(model, {})
        key = (ids.shape, ids.tobytes(), int(max_new_tokens),
               int(end_id), quant_key)
        if key not in cache:
            out, _ = generate(model, ids,
                              max_new_tokens=max_new_tokens,
                              end_id=end_id, quant=quant)
            cache[key] = np.asarray(out._value)
        out = cache[key]
        return out[0].copy() if squeeze else out.copy()

    return ref


@pytest.fixture(scope="session")
def shared_gpt_small():
    """ONE tiny GPT for the serving-stack test modules (ISSUE 11 suite
    health).  Seven modules (serving / async / abort / frontend /
    resilience / prefix_cache / quant_serving) each built the IDENTICAL
    model — seed 11, vocab 50, hid 32, 2 layers / 2 heads, ffn 64,
    seq 64 — so each module recompiled the same serving XLA programs.
    The engine's shared-program cache is keyed per MODEL OBJECT: one
    session-scoped instance compiles each program once for the whole
    suite.  Weights are identical to what every module built before
    (same seed at construction), so every byte-identity reference is
    unchanged.  Eval-only by contract — serving tests never train it.
    test_jit_ledger deliberately keeps its own private models: its
    compile-count pins need a cold program cache."""
    import paddle_tpu
    from paddle_tpu.text.models import GPTModel

    paddle_tpu.seed(11)
    m = GPTModel(vocab_size=50, hidden_size=32, num_layers=2,
                 num_heads=2, ffn_size=64, max_seq_len=64, dropout=0.0)
    m.eval()
    return m
