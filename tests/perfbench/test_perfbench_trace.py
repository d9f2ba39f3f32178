"""The reduction from a profiler trace to numbers: interval arithmetic and
gap attribution on hand-made traces, then the whole path on a small trace
recorded on the v5e (sample.xplane.pb, by record_sample_trace.py)."""
import os

import pytest

from preset_tree import ROOT  # noqa: F401 — puts the repo root on sys.path
from perfbench.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
SAMPLE = os.path.join(HERE, "sample.xplane.pb")


def test_merge_clips_and_unites():
    got = T.merge([(0, 2), (1, 3), (5, 6), (9, 12), (2.5, 2.8)], 0.5, 10)
    assert got == [(0.5, 3), (5, 6), (9, 10)]
    assert T.merge([(3, 3), (4, 2)], 0, 10) == []


def test_gaps_are_the_complement():
    merged = [(0.5, 3), (5, 6), (9, 10)]
    assert T.gaps(merged, 0, 10) == [(0, 0.5), (3, 5), (6, 9)]
    assert T.gaps([], 1, 2) == [(1, 2)]
    assert T.gaps([(1, 2)], 1, 2) == []


def _toy():
    ops = [("fusion", 0.0, 1.0), ("copy f32[8]", 1.0, 1.0),
           ("fusion", 4.0, 2.0), ("fwd tpu_custom_call", 8.0, 1.0)]
    host = [("perfbench/window", "python3", 0.0, 10.0),
            ("np.asarray(jax.Array)", "python3", 1.9, 2.2),
            ("whole_loop", "python3", 0.0, 10.0),
            ("PjitFunction(step_fn)", "python3", 6.0, 1.0),
            ("idle_poll", "worker", 6.9, 1.2)]
    return T.Trace({0: {"ops": ops, "modules": [("jit_step_fn(1)", 0.0, 2.0),
                                                ("jit_step_fn(1)", 4.0, 5.0),
                                                ("jit_other", 4.0, 1.0)]}},
                   host)


def test_window_is_the_benchmarks_own_annotation():
    assert _toy().window() == (0.0, 9.0)     # to the last recorded op
    bare = T.Trace({0: {"ops": [("a", 2.0, 1.0), ("b", 5.0, 2.0)],
                        "modules": []}}, [])
    assert bare.window() == (2.0, 7.0)


def test_busy_idle_and_per_op_time():
    tr = _toy()
    busy, span = T.busy_seconds(tr)
    assert (busy, span) == (5.0, 9.0)
    assert T.idle_share(tr) == pytest.approx(4.0 / 9.0)
    assert T.op_seconds(tr) == {"fusion": 3.0, "copy f32[8]": 1.0,
                                "fwd tpu_custom_call": 1.0}
    assert T.op_calls(tr, "^fwd .*tpu_custom_call") == (1.0, 1.0)
    assert T.op_calls(tr, "fusion") == (3.0, 2.0)
    assert sorted(T.module_durations(tr, "step_fn")) == [2.0, 5.0]


def test_busy_is_averaged_over_the_chips():
    tr = T.Trace({0: {"ops": [("a", 0.0, 4.0)], "modules": []},
                  1: {"ops": [("a", 0.0, 2.0)], "modules": []}},
                 [("perfbench/window", "python3", 0.0, 4.0)])
    assert T.busy_seconds(tr) == (3.0, 4.0)
    assert T.op_seconds(tr) == {"a": 3.0}


def test_each_gap_goes_to_the_most_specific_host_event():
    got = T.gap_attribution(_toy())
    # (2, 4): np.asarray covers it whole and is shorter than whole_loop;
    # (6, 8): PjitFunction and idle_poll each cover about half, the former
    # is the shorter
    assert got == {"np.asarray(jax.Array) (python3)": pytest.approx(2.0),
                   "PjitFunction(step_fn) (python3)": pytest.approx(2.0)}
    only_loop = T.Trace({0: {"ops": [("a", 0.0, 1.0), ("a", 2.0, 1.0)],
                             "modules": []}},
                        [("whole_loop", "python3", 0.0, 3.0)])
    assert T.gap_attribution(only_loop) == {
        "whole_loop (python3)": pytest.approx(1.0)}
    none = T.Trace({0: {"ops": [("a", 0.0, 1.0), ("a", 2.0, 1.0)],
                        "modules": []}}, [])
    assert T.gap_attribution(none) == {T.NO_HOST_MARK: pytest.approx(1.0)}


def test_breakdown_lists_are_short_and_sorted():
    b = T.breakdown(_toy(), top=2)
    assert [k for k, _ in b["device_ops"]] == ["fusion", "copy f32[8]"] \
        or [k for k, _ in b["device_ops"]][0] == "fusion"
    assert len(b["device_ops"]) == 2 and len(b["idle_gaps"]) == 2
    assert b["idle_gaps"][0][1] >= b["idle_gaps"][1][1]


@pytest.mark.parametrize("name,want", [
    ("%fusion.123", "fusion"),
    ("%copy.4 = f32[3073,16,12,64]{3,2,1,0:T(8,128)} "
     "copy(f32[3073,16,12,64]{3,2,1,0} %p.1)", "copy f32[3073,16,12,64]"),
    ("%multiply_add_fusion.7.1 = (f32[1024]{0:T(1024)}, f32[4,1024]{1,0}) "
     "fusion(f32[4,1024]{1,0} %a), kind=kLoop, calls=%fused.1",
     "multiply_add_fusion f32[1024] f32[4,1024]"),
    ("%transpose_jvp___.74 = (bf16[64,1024,64]{2,1,0:T(8,128)(2,1)}, "
     "bf16[64,1024,64]{2,1,0}) custom-call(s32[1]{0} %x), "
     "custom_call_target=\"tpu_custom_call\"",
     "transpose_jvp___ custom-call bf16[64,1024,64] bf16[64,1024,64]"),
    ("%jvp__.3 = (bf16[64,1024,64]{2,1,0}, f32[64,1024,1]{2,1,0}) "
     "custom-call(s32[1]{0} %x)",
     "jvp__ custom-call bf16[64,1024,64] f32[64,1024,1]"),
    ("%ragged_fn.23 = f32[48,16,64,128]{3,2,1,0:T(8,128)S(1)} "
     "custom-call(s32[48,64]{1,0:T(8,128)S(1)} %copy-done.108)",
     "ragged_fn custom-call f32[48,16,64,128]"),
])
def test_op_labels(name, want):
    assert T.op_label(name) == want


def test_the_layer_metric_patterns_tell_the_kernels_apart():
    import re

    from perfbench.harness.manifest import Manifest

    m = Manifest(ROOT)
    fwd = m.layer_metric("flash_fwd_roofline.train")["args"]["pattern"]
    bwd = m.layer_metric("flash_bwd_roofline.train")["args"]["pattern"]
    rag = m.layer_metric("ragged_attn_time_share.chat")["args"]["pattern"]
    f = "jvp__ custom-call bf16[64,1024,64] f32[64,1024,1]"
    b = "transpose_jvp___ custom-call bf16[64,1024,64]"
    r = "ragged_fn custom-call f32[48,16,64,128]"
    assert re.search(fwd, f) and not re.search(fwd, b)
    assert re.search(bwd, b) and not re.search(bwd, f)
    assert re.search(rag, r) and not re.search(rag, "copy f32[3073,16]")


def test_the_window_ends_at_the_last_recorded_op():
    tr = T.Trace({0: {"ops": [("a", 1.0, 1.0), ("a", 3.0, 1.0)],
                      "modules": []}},
                 [("perfbench/window", "python3", 0.5, 9.0)])
    assert tr.window() == (0.5, 4.0)
    assert T.idle_share(tr) == pytest.approx(1.5 / 3.5)


needs_sample = pytest.mark.skipif(not os.path.isfile(SAMPLE),
                                  reason="sample.xplane.pb not recorded")


@pytest.fixture(scope="module")
def sample():
    return T.load(SAMPLE)


@needs_sample
def test_sample_is_small_and_has_one_tpu(sample):
    assert os.path.getsize(SAMPLE) < 1024 * 1024
    assert sorted(sample.devices) == [0]
    assert sample.devices[0]["ops"] and sample.devices[0]["modules"]
    assert any(n == T.WINDOW_MARK for n, _, _, _ in sample.host)


@needs_sample
def test_sample_steps_and_idle_share(sample):
    """record_sample_trace.py: four jitted steps of ~90 us, the window
    around the last three, a 2-ms host pause before each."""
    lo, hi = sample.window()
    inside = T.module_durations(sample, "sample_step")
    assert len(sample.devices[0]["modules"]) == 4 and len(inside) == 3
    assert all(80e-6 < d < 100e-6 for d in inside)
    busy, span = T.busy_seconds(sample)
    assert span == pytest.approx(hi - lo) and 0.005 < span < 0.02
    assert busy == pytest.approx(270.6e-6, rel=0.01)
    assert T.idle_share(sample) == pytest.approx(1 - busy / span)
    assert 0.95 < T.idle_share(sample) < 0.99


@needs_sample
def test_sample_per_op_time_and_gap_attribution(sample):
    ops = T.op_seconds(sample)
    assert max(ops, key=ops.get) == "fusion bf16[]"
    assert sum(ops.values()) == pytest.approx(T.busy_seconds(sample)[0],
                                              rel=0.01)
    secs, calls = T.op_calls(sample, "^fusion")
    assert calls == 3 and secs == pytest.approx(ops["fusion bf16[]"])
    named = T.gap_attribution(sample)
    busy, span = T.busy_seconds(sample)
    assert sum(named.values()) == pytest.approx(span - busy, rel=1e-6)
    assert named["sample/pause (python3)"] > 0.0005
    b = T.breakdown(sample)
    assert b["device_ops"][0][0] == "fusion bf16[]"
    assert {k for k, _ in b["idle_gaps"]} == set(named)
