"""tools/analyze AST lint suite (ISSUE 7) — planted-violation fixtures
per checker, live-repo cleanliness, and the CLI exit-code contract
(in-process `main(argv)` plus one stdlib-only
subprocess proving `python -m tools.analyze`).
"""
import os
import subprocess
import sys
import textwrap
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.analyze import main as analyze_main  # noqa: E402
from tools.analyze import run_checks  # noqa: E402
from tools.analyze import core as analyze_core  # noqa: E402
from tools.analyze.core import (AnalysisContext, Finding,  # noqa: E402
                                load_baseline, new_findings)
from tools.analyze.metrics_coverage import collect_table_names  # noqa: E402
from tools.analyze.metrics_drift import collect_doc_names  # noqa: E402


def make_tree(tmp_path, files):
    for rel, content in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(content))
    return str(tmp_path)


# =============================================================================
# lock-discipline
# =============================================================================
class TestLockDiscipline:
    def test_planted_violations_and_exemptions(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/serving/bad.py": '''
            import threading
            import time


            class F:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cond = threading.Condition()

                def bad_sleep(self):
                    with self._lock:
                        time.sleep(0.1)

                def bad_foreign_wait(self, other):
                    with self._lock:
                        other.wait()

                def bad_engine_step(self, eng):
                    with self._lock:
                        eng.step()

                def bad_rpc(self):
                    with self._lock:
                        self.table.pull([1])

                def ok_condvar_wait(self):
                    with self._cond:
                        self._cond.wait_for(lambda: True)

                def ok_nested_def_runs_later(self):
                    with self._lock:
                        def later():
                            time.sleep(1)
                        return later

                def ok_suppressed(self):
                    with self._lock:
                        time.sleep(0)  # analyze: allow[lock-discipline] test

                def ok_not_under_lock(self):
                    time.sleep(0.1)
            '''})
        found = run_checks(root=root, checks=["lock-discipline"])
        msgs = sorted(f.message for f in found)
        assert len(found) == 4, msgs
        assert all(f.code == "LD001" for f in found)
        assert any("time.sleep" in m for m in msgs)
        assert any("wait on 'other'" in m for m in msgs)
        assert any("engine step" in m for m in msgs)
        assert any("backing-table" in m for m in msgs)

    def test_live_repo_clean(self):
        assert run_checks(root=ROOT, checks=["lock-discipline"]) == []


# =============================================================================
# jit-hazard
# =============================================================================
class TestJitHazard:
    def test_planted_violations_by_all_three_detections(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/ops/badjit.py": '''
            import jax
            import numpy as np


            @jax.jit
            def decorated(x):
                return np.asarray(x)


            def _wrapped(x):
                return x.item()


            w = jax.jit(_wrapped)


            def marked(x):  # analyze: jit-path
                return x.tolist()


            def plain_host_helper(x):
                return np.asarray(x)


            class Executor:
                def run(self, x):
                    # same NAME as a jitted closure elsewhere must not
                    # be flagged: class scopes are not in the lexical
                    # lookup chain
                    return np.asarray(x)


            def outer():
                def run(x):
                    return x + 1
                return jax.jit(run)
            '''})
        found = run_checks(root=root, checks=["jit-hazard"])
        assert all(f.code == "JH001" for f in found)
        flagged_fns = sorted({f.message.split("'")[1] for f in found})
        assert flagged_fns == ["_wrapped", "decorated", "marked"]

    def test_live_repo_clean(self):
        assert run_checks(root=ROOT, checks=["jit-hazard"]) == []


# =============================================================================
# retrace-hazard
# =============================================================================
class TestRetraceHazard:
    def test_rh001_loop_varying_scalar(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/serving/bad.py": '''
            import jax


            @jax.jit
            def step(x):
                return x + 1


            class Engine:
                def drive(self, xs):
                    out = []
                    for i in range(8):
                        out.append(step(i))            # RH001
                        out.append(step(xs[i]))        # ok: array row
                        out.append(self._decode_jit(i))  # RH001 (_jit attr)
                    for j, x in enumerate(xs):
                        out.append(step(j + 1))        # RH001 (arith)
                        out.append(step(x))            # ok: the element
                    for s in xs:                       # not range/enumerate
                        out.append(step(s))            # ok
                    return out

                def comp(self, fn):
                    g = jax.jit(fn)
                    return [g((i, 2)) for i in range(4)]   # RH001
            '''})
        found = run_checks(root=root, checks=["retrace-hazard"])
        assert [f.code for f in found] == ["RH001"] * 4
        assert {f.line for f in found} == {14, 16, 18, 26}

    def test_rh002_rh003_def_side(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/serving/bad.py": '''
            import jax
            from functools import partial


            @jax.jit
            def bad_default(x, flag=True, mode="fast"):   # RH002 x2
                return x


            @partial(jax.jit, static_argnames=("mode",))
            def ok_static(x, mode="fast"):                # covered
                return x


            @jax.jit
            def bad_mutable(x, cache=[]):                 # RH003
                return x


            def traced_inline_helper(x, with_head=True):  # analyze: jit-path
                # marker mode: invoked as plain Python by its builder —
                # call-site/static-argnames rules do not apply
                return x
            '''})
        found = run_checks(root=root, checks=["retrace-hazard"])
        codes = sorted(f.code for f in found)
        assert codes == ["RH002", "RH002", "RH003"]
        msgs = " ".join(f.message for f in found)
        assert "'flag'" in msgs and "'mode'" in msgs and "'cache'" in msgs

    def test_rh004_bool_str_leaves(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/serving/bad.py": '''
            import jax


            def go(fn, x):
                w = jax.jit(fn)
                w(x, True)                    # RH004
                w(x, "greedy")                # RH004
                ws = jax.jit(fn, static_argnums=(1,))
                ws(x, True)                   # covered by static_argnums
                return jax.jit(fn)(x, False)  # RH004 (immediate invoke)
            '''})
        found = run_checks(root=root, checks=["retrace-hazard"])
        assert [f.code for f in found] == ["RH004"] * 3

    def test_rh005_mutable_closure_state(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/serving/bad.py": '''
            import jax

            _EVENTS = []


            @jax.jit
            def side_effect(x):
                _EVENTS.append(1)             # RH005: trace-time mutation
                return x


            def build():
                table = [1, 2, 3]

                @jax.jit
                def stale(x):
                    return x + table[0]       # RH005: hot mutable capture

                table.append(4)
                return stale


            def ok_build():
                cfg = [1, 2]                  # never mutated: fine

                @jax.jit
                def inner(x):
                    out = dict(a=1)
                    out["b"] = 2              # local: fine
                    return x + cfg[0]

                return inner
            '''})
        found = run_checks(root=root, checks=["retrace-hazard"])
        assert [f.code for f in found] == ["RH005"] * 2
        msgs = " ".join(f.message for f in found)
        assert "_EVENTS" in msgs and "'table'" in msgs

    def test_live_repo_clean(self):
        assert run_checks(root=ROOT, checks=["retrace-hazard"]) == []


# =============================================================================
# pallas-contract
# =============================================================================
_BAD_CONTRACTS = '''
    LANE = 128


    class BlockDecl:
        pass


    class KernelContract:
        pass


    MISALIGNED = KernelContract(
        name="misaligned",
        module="paddle_tpu/ops/pallas_ops/fake_kernel.py",
        grid=("i",),
        dims={"bq": 104, "d": 96},
        blocks=(
            BlockDecl("q", "in", (1, "bq", "d"), "float32"),       # PC001
            BlockDecl("w", "in", (8, LANE), "int8"),               # PC002
            BlockDecl("ok", "out", (1, 4, LANE), "float32",
                      waivers=("sublane: tested waiver",)),
        ),
        shape_buckets={"bq": (100, 250)},                          # PC003
    )


    HOG = KernelContract(
        name="vmem_hog",
        module="paddle_tpu/ops/pallas_ops/fake_kernel.py",
        grid=("i",),
        dims={"b": 1024},
        blocks=(
            BlockDecl("x", "in", ("b", "b"), "float32"),
            BlockDecl("y", "in", ("b", "b"), "float32"),
            BlockDecl("o", "out", ("b", "b"), "float32"),
        ),                                                         # PC004
    )


    OPAQUE = KernelContract(
        name="opaque",
        module="paddle_tpu/ops/pallas_ops/fake_kernel.py",
        grid=("i",),
        dims=make_dims(),                                          # PC005
        blocks=(),
    )
    '''

_DRIFTY_KERNEL = '''
    DEFAULT_BLOCK_Q = 512                     # PC005: raw literal


    def kern(x, *, block_m=128):              # PC005: raw default
        return x
    '''


class TestPallasContract:
    def _tree(self, tmp_path, kernel=_DRIFTY_KERNEL):
        return make_tree(tmp_path, {
            "paddle_tpu/ops/pallas_ops/contracts.py": _BAD_CONTRACTS,
            "paddle_tpu/ops/pallas_ops/fake_kernel.py": kernel,
        })

    def test_planted_violations_every_code(self, tmp_path):
        found = run_checks(root=self._tree(tmp_path),
                           checks=["pallas-contract"])
        by_code = {}
        for f in found:
            by_code.setdefault(f.code, []).append(f.message)
        assert len(by_code["PC001"]) == 1          # bq=100 lanes
        assert "96" in by_code["PC001"][0]
        assert len(by_code["PC002"]) == 1          # int8 sublane 8 < 32
        assert len(by_code["PC003"]) == 2          # 100, 250 vs bq=100
        assert len(by_code["PC004"]) == 1          # 3 x 4MB blocks x2
        # PC005: opaque contract + missing-import + 2 raw literals
        assert len(by_code["PC005"]) == 4
        pc5 = " ".join(by_code["PC005"])
        assert "pure literal" in pc5
        assert "does not import the contracts module" in pc5

    def test_waiver_suppresses_with_reason_on_record(self, tmp_path):
        """The 'ok' block's sublane dim (bq=100 % 8 != 0) is waived
        in-contract; no PC002 fires for it (the misaligned 'w' block
        still does)."""
        found = run_checks(root=self._tree(tmp_path),
                           checks=["pallas-contract"])
        pc2 = [f for f in found if f.code == "PC002"]
        assert len(pc2) == 1 and "'w'" in pc2[0].message

    def test_clean_kernel_module_passes_drift(self, tmp_path):
        clean = '''
            from .contracts import MISALIGNED as _C

            DEFAULT_BLOCK_Q = _C.dim("bq")

            def kern(x, *, block_m=_C.dim("bq")):
                return x
            '''
        found = run_checks(root=self._tree(tmp_path, kernel=clean),
                           checks=["pallas-contract"])
        assert not any("fake_kernel" in f.file for f in found)

    def test_live_repo_clean(self):
        assert run_checks(root=ROOT, checks=["pallas-contract"]) == []


# =============================================================================
# metrics-drift
# =============================================================================
class TestMetricsDrift:
    def test_planted_drift_both_directions(self, tmp_path):
        root = make_tree(tmp_path, {
            "paddle_tpu/m.py": '''
                from paddle_tpu.framework.monitor import stat_registry
                from paddle_tpu.profiler.jit_cost import profiled_jit


                def f():
                    stat_registry.get("serving.documented").add(1)
                    stat_registry.get("serving.undocumented").add(1)
                    prog = profiled_jit("serving.attribution_name", f)
                    return prog
                ''',
            "docs/OBSERVABILITY.md": """
                The engine emits `serving.documented` and promises
                `serving.orphan_metric`; `serving.attribution_name` is a
                jit-cost attribution name, exempt from the emitted set.
                """})
        found = run_checks(root=root, checks=["metrics-drift"])
        by_code = {}
        for f in found:
            by_code.setdefault(f.code, []).append(f.message)
        assert len(by_code.get("MD001", [])) == 1
        assert "serving.undocumented" in by_code["MD001"][0]
        assert len(by_code.get("MD002", [])) == 1
        assert "serving.orphan_metric" in by_code["MD002"][0]

    def test_doc_shorthand_expansion(self, tmp_path):
        root = make_tree(tmp_path, {"docs/OBSERVABILITY.md": """
            counters: `serving.frontend.submitted`, `.completed` and
            `.rejects`; resilience adds `serving.{snapshots,restores}`.
            Wildcards like `serving.frontend.*` and class references
            like `serving.FrontendMetrics` are ignored.
            """})
        names = collect_doc_names(AnalysisContext(root))
        assert set(names) == {
            "serving.frontend.submitted", "serving.frontend.completed",
            "serving.frontend.rejects", "serving.snapshots",
            "serving.restores"}

    def test_live_repo_clean(self):
        assert run_checks(root=ROOT, checks=["metrics-drift"]) == []


# =============================================================================
# metrics-coverage (ISSUE 17 — serving.* names <-> doc metric TABLES)
# =============================================================================
class TestMetricsCoverage:
    CODE = '''
        from paddle_tpu.framework.monitor import stat_registry


        def f():
            stat_registry.get("serving.tabled").add(1)
            stat_registry.get("serving.prose_only").add(1)
            stat_registry.windowed("serving.window.tabled_ms").observe(1)
        '''

    def test_planted_drift_both_directions(self, tmp_path):
        root = make_tree(tmp_path, {
            "paddle_tpu/m.py": self.CODE,
            "docs/OBSERVABILITY.md": """
                Prose mentions `serving.prose_only` (satisfies
                metrics-drift, NOT metrics-coverage).

                | metric | meaning |
                |---|---|
                | `serving.tabled` | documented in a table row |
                | `serving.window.tabled_ms` | windowed family row |
                | `serving.table_orphan` | nothing emits this |
                """})
        found = run_checks(root=root, checks=["metrics-coverage"])
        by_code = {}
        for f in found:
            by_code.setdefault(f.code, []).append(f.message)
        assert len(by_code.get("MC001", [])) == 1
        assert "serving.prose_only" in by_code["MC001"][0]
        assert len(by_code.get("MC002", [])) == 1
        assert "serving.table_orphan" in by_code["MC002"][0]

    def test_table_shorthands_and_prose_isolation(self, tmp_path):
        root = make_tree(tmp_path, {"docs/OBSERVABILITY.md": """
            Prose names `serving.not_in_table` and sets up a dangling
            prefix with `serving.frontend.submitted` — continuations
            must NOT leak into the table below.

            | metric | meaning |
            |---|---|
            | `serving.a.one`, `.two` | continuation inside a table row |
            | `serving.{snapshots,restores}` | brace expansion |
            | `serving.frontend.*` | wildcards ignored |
            """})
        names = collect_table_names(AnalysisContext(root))
        assert set(names) == {
            "serving.a.one", "serving.a.two", "serving.snapshots",
            "serving.restores"}

    def test_live_repo_clean(self):
        assert run_checks(root=ROOT, checks=["metrics-coverage"]) == []


# =============================================================================
# error-taxonomy
# =============================================================================
class TestErrorTaxonomy:
    def test_planted_violations(self, tmp_path):
        root = make_tree(tmp_path, {
            "paddle_tpu/framework/errors.py": '''
                class EnforceNotMet(RuntimeError):
                    pass


                class GoodError(EnforceNotMet):
                    pass


                class OrphanError(RuntimeError):
                    pass


                ERROR_HTTP_STATUS = {EnforceNotMet: 500}
                ''',
            "paddle_tpu/serving/s.py": '''
                from ..framework.errors import GoodError


                def f(x):
                    if x:
                        raise GoodError("fine")
                    raise ValueError("ad hoc")


                def g(e):
                    raise e


                def h():
                    try:
                        f(0)
                    except GoodError:
                        raise
                '''})
        found = run_checks(root=root, checks=["error-taxonomy"])
        pairs = [(f.code, f.message) for f in found]
        assert any(c == "ET001" and "ValueError" in m for c, m in pairs)
        assert any(c == "ET002" and "OrphanError" in m for c, m in pairs)
        assert len(found) == 2      # GoodError / bare / `raise e` exempt

    def test_live_repo_clean(self):
        assert run_checks(root=ROOT, checks=["error-taxonomy"]) == []


# =============================================================================
# determinism (ISSUE 15)
# =============================================================================
class TestDeterminism:
    def test_dt001_ambient_rng_fire_and_exemptions(self, tmp_path):
        root = make_tree(tmp_path, {
            "paddle_tpu/io/bad.py": '''
                import random

                import numpy as np


                def draw():
                    a = np.random.rand(3)                  # DT001
                    np.random.seed(7)                      # DT001
                    b = random.uniform(0.0, 1.0)           # DT001
                    ok1 = np.random.RandomState(0).rand(2)
                    ok2 = np.random.default_rng(0).random()
                    ok3 = random.Random(0).random()
                    state = np.random.get_state()          # snapshot ok
                    waived = np.random.rand(1)  # analyze: allow[determinism] test
                    return a, b, ok1, ok2, ok3, state, waived
                ''',
            "paddle_tpu/testing/fixture_gen.py": '''
                import numpy as np


                def soak_entropy():
                    # testing/ is excluded: fixtures are allowed entropy
                    return np.random.rand(4)
                '''})
        found = run_checks(root=root, checks=["determinism"])
        assert [f.code for f in found] == ["DT001"] * 3
        msgs = " ".join(f.message for f in found)
        assert "np.random.rand" in msgs and "np.random.seed" in msgs \
            and "random.uniform" in msgs
        assert all(f.file == "paddle_tpu/io/bad.py" for f in found)

    def test_dt002_wall_clock_control_flow(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/serving/bad.py": '''
            import time


            def loop(deadline):
                while time.monotonic() < deadline:         # DT002
                    pass
                now = time.time()
                if now > deadline:                         # DT002 (name)
                    return 1
                t0 = time.perf_counter()
                work = 2 + 2
                elapsed = time.perf_counter() - t0         # metric: ok
                record(elapsed)
                return work


            def state_dict():
                return {"created": time.time()}            # DT002 persisted


            def regular():
                return {"created": time.time()}            # not a boundary


            def record(x):
                pass
            '''})
        found = run_checks(root=root, checks=["determinism"])
        assert [f.code for f in found] == ["DT002"] * 3
        assert {f.line for f in found} == {6, 9, 19}

    def test_dt003_unsorted_listings(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/io/bad.py": '''
            import glob
            import os


            def pick(d):
                names = os.listdir(d)                      # DT003
                pats = glob.glob("*.ckpt")                 # DT003
                ok1 = sorted(os.listdir(d))
                ok2 = sorted(e.name for e in os.scandir(d))
                ok3 = len(os.listdir(d))                   # aggregation
                return names, pats, ok1, ok2, ok3
            '''})
        found = run_checks(root=root, checks=["determinism"])
        assert [f.code for f in found] == ["DT003"] * 2
        assert {f.line for f in found} == {7, 8}

    def test_dt004_set_iteration(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/serving/bad.py": '''
            def dispatch(a, b, mapping):
                for x in set(a):                           # DT004
                    emit(x)
                live = set(a) - set(b)
                for x in live:                             # DT004 (name)
                    emit(x)
                got = [x for x in set(a) | set(b)]         # DT004 (comp)
                for x in sorted(set(a)):                   # ok
                    emit(x)
                for k in mapping:                          # dict: ordered
                    emit(k)
                return got


            def emit(x):
                pass
            '''})
        found = run_checks(root=root, checks=["determinism"])
        assert [f.code for f in found] == ["DT004"] * 3
        assert {f.line for f in found} == {3, 6, 8}

    def test_dt005_id_keys_on_replay_boundaries(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/serving/bad.py": '''
            def state_dict(params, store):
                return {p: store[id(p)] for p in params}   # DT005


            def snapshot_meta(objs):
                return {id(o): o.name for o in objs}       # DT005 (key)


            def describe(cache, obj):
                return cache.get(id(obj))                  # DT005 (.get)


            def in_process_dedup(objs):
                seen = {}
                for o in objs:
                    seen[id(o)] = o                        # not a boundary
                return list(seen.values())
            '''})
        found = run_checks(root=root, checks=["determinism"])
        assert [f.code for f in found] == ["DT005"] * 3
        assert {f.line for f in found} == {3, 7, 11}

    def test_live_repo_clean(self):
        assert run_checks(root=ROOT, checks=["determinism"]) == []


# =============================================================================
# host-sync (ISSUE 15)
# =============================================================================
class TestHostSync:
    def test_hs001_hs002_coercions_and_transfers_in_loops(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/ops/bad.py": '''
            import jax
            import numpy as np


            def _decode(x):
                return x


            w = jax.jit(_decode)


            def drive(xs, host_rows):
                out = []
                for x in xs:
                    y = w(x)
                    out.append(int(y))                 # HS001
                    out.append(y.item())               # HS001
                    out.append(np.asarray(y))          # HS002
                    got = jax.device_get(y)            # HS002
                    out.append(int(host_rows[0]))      # non-jit: ok
                z = w(xs)
                hoisted = np.asarray(z)                # outside loop: ok
                return out, int(hoisted[0]), got
            '''})
        found = run_checks(root=root, checks=["host-sync"])
        codes = sorted(f.code for f in found)
        assert codes == ["HS001", "HS001", "HS002", "HS002"]
        assert {f.line for f in found} == {17, 18, 19, 20}

    def test_hs001_engine_jit_attr_idiom(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/ops/bad.py": '''
            class Engine:
                def drive(self, xs):
                    toks = []
                    for x in xs:
                        out = self._decode_jit(x)
                        toks.append(int(out))          # HS001 (_jit attr)
                    return toks
            '''})
        found = run_checks(root=root, checks=["host-sync"])
        assert [f.code for f in found] == ["HS001"]
        assert "'out'" in found[0].message

    def test_hs003_implicit_truthiness(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/ops/bad.py": '''
            import jax


            def go(fn, x, flag):
                y = jax.jit(fn)(x)
                if y:                                  # HS003
                    return 1
                while not y:                           # HS003
                    break
                if flag and y:                         # HS003
                    return 2
                if flag:                               # host bool: ok
                    return 3
                done = bool(y)                         # not a test: HS-free
                return done
            '''})
        found = run_checks(root=root, checks=["host-sync"])
        assert [f.code for f in found] == ["HS003"] * 3
        assert {f.line for f in found} == {7, 9, 11}

    def test_hs004_hot_module_roundtrips_and_waiver(self, tmp_path):
        code = '''
            import jax


            def pump(handles):
                for h in handles:
                    jax.device_get(h)                  # HS004 (hot only)
                    h.block_until_ready()              # HS004 (hot only)
                snap = jax.device_get(handles)         # off-loop: ok
                return snap


            def drain(handles):
                for h in handles:
                    jax.device_get(h)  # analyze: allow[host-sync] test
            '''
        hot = make_tree(tmp_path / "hot",
                        {"paddle_tpu/serving/engine.py": code})
        cold = make_tree(tmp_path / "cold",
                         {"paddle_tpu/ops/helper.py": code})
        found = run_checks(root=hot, checks=["host-sync"])
        assert [f.code for f in found] == ["HS004"] * 2
        assert {f.line for f in found} == {7, 8}
        # the same code outside engine/scheduler/frontend: operand is
        # unresolvable, so no finding — HS004 is the hot-path ratchet
        assert run_checks(root=cold, checks=["host-sync"]) == []

    def test_live_repo_clean(self):
        assert run_checks(root=ROOT, checks=["host-sync"]) == []


# =============================================================================
# chaos-coverage (ISSUE 15)
# =============================================================================
class TestChaosCoverage:
    def _tree(self, tmp_path):
        return make_tree(tmp_path, {
            "paddle_tpu/serving/sites.py": '''
                from ..testing.chaos import chaos_site


                def a():
                    chaos_site("a.site", key="k")


                def b():
                    chaos_site("b.site")


                def c():
                    chaos_site("c.site")
                ''',
            "paddle_tpu/testing/chaos.py": '''
                """Chaos harness.

                Instrumented sites
                ------------------
                ``a.site``       the documented, drilled site
                ``d.gone``       documented but no longer instrumented

                Actions like ``deny`` or ``kill`` in prose are not
                site rows; neither is an indented ``x.y``   mention.
                """


                def chaos_site(site, key=None):
                    return None
                ''',
            "tests/test_drill.py": '''
                from paddle_tpu.testing.chaos import Fault


                def test_drills():
                    plan = [Fault("a.site", at=1, action="deny"),
                            Fault("b.site", at=2, action="raise")]
                    return plan
                '''})

    def test_all_three_drift_directions(self, tmp_path):
        found = run_checks(root=self._tree(tmp_path),
                           checks=["chaos-coverage"])
        by_code = {}
        for f in found:
            by_code.setdefault(f.code, []).append(f)
        # b.site + c.site instrumented but undocumented
        assert sorted(f.message.split("'")[1]
                      for f in by_code["CC001"]) == ["b.site", "c.site"]
        assert all(f.file == "paddle_tpu/serving/sites.py"
                   for f in by_code["CC001"])
        # d.gone documented but gone from code
        assert len(by_code["CC002"]) == 1
        assert "d.gone" in by_code["CC002"][0].message
        assert by_code["CC002"][0].file == "paddle_tpu/testing/chaos.py"
        # c.site never scheduled by any test Fault
        assert len(by_code["CC003"]) == 1
        assert "c.site" in by_code["CC003"][0].message
        assert len(found) == 4

    def test_doc_table_parser_ignores_prose_backticks(self, tmp_path):
        from tools.analyze.chaos_coverage import collect_doc_sites

        doc = collect_doc_sites(AnalysisContext(self._tree(tmp_path)))
        assert set(doc) == {"a.site", "d.gone"}

    def test_live_repo_every_site_documented_and_drilled(self):
        """The ISSUE 15 acceptance pin: every chaos_site() in the live
        repo is in the chaos.py site table AND scheduled by at least
        one test — and the table promises nothing the code lacks."""
        from tools.analyze.chaos_coverage import (collect_code_sites,
                                                  collect_doc_sites,
                                                  collect_scheduled_sites)

        ctx = AnalysisContext(ROOT)
        code = set(collect_code_sites(ctx))
        doc = set(collect_doc_sites(ctx))
        drilled = collect_scheduled_sites(ctx)
        assert code, "site collector found nothing — collector broken?"
        assert code == doc
        assert code <= drilled
        assert run_checks(root=ROOT, checks=["chaos-coverage"]) == []


# =============================================================================
# --changed-only (ISSUE 15)
# =============================================================================
class TestChangedOnly:
    _FILES = {
        "paddle_tpu/io/one.py": '''
            import os


            def pick(d):
                return os.listdir(d)                       # DT003
            ''',
        "paddle_tpu/io/two.py": '''
            import numpy as np


            def draw():
                return np.random.rand(2)                   # DT001
            ''',
    }

    def test_restricted_run_agrees_with_full_run(self, tmp_path):
        """The agreement pin: per-file checkers over only=<all files>
        produce byte-for-byte the findings of the unrestricted run."""
        root = make_tree(tmp_path, self._FILES)
        full = run_checks(root=root, checks=["determinism"])
        agree = run_checks(root=root, checks=["determinism"],
                           only=sorted(self._FILES))
        assert [f.key() for f in agree] == [f.key() for f in full]
        assert len(full) == 2

    def test_restriction_drops_other_files_findings(self, tmp_path):
        root = make_tree(tmp_path, self._FILES)
        got = run_checks(root=root, checks=["determinism"],
                         only=["paddle_tpu/io/one.py"])
        assert [f.code for f in got] == ["DT003"]
        assert got[0].file == "paddle_tpu/io/one.py"

    def test_cross_file_checkers_ignore_restriction(self, tmp_path):
        """chaos-coverage must see the full tree even under
        --changed-only: a restricted view would misreport every
        unchanged site as missing."""
        root = TestChaosCoverage()._tree(tmp_path)
        full = run_checks(root=root, checks=["chaos-coverage"])
        restricted = run_checks(root=root, checks=["chaos-coverage"],
                                only=["paddle_tpu/serving/sites.py"])
        assert [f.key() for f in restricted] == [f.key() for f in full]

    def test_baseline_forces_full_run(self, tmp_path, monkeypatch,
                                      capsys):
        """--baseline + --changed-only must not write a baseline from a
        restricted run (it would drop every grandfathered finding in
        unchanged files): the combination forces the full tree."""
        monkeypatch.setattr(analyze_core, "baseline_path",
                            lambda: str(tmp_path / "baseline.txt"))
        root = make_tree(tmp_path, self._FILES)
        args = ["--root", root, "--check", "determinism"]
        assert analyze_main(args + ["--changed-only", "--baseline"]) == 0
        out = capsys.readouterr().out
        assert "ignored with --baseline" in out
        # both files' findings were grandfathered, not just a diff's
        assert "wrote 2 finding(s)" in out
        assert analyze_main(args) == 0

    def test_cli_changed_only_against_git_worktree(self, tmp_path):
        """End-to-end: an untracked file with a planted finding is
        linted under --changed-only; a clean tree falls back to the
        full run (never silently lints nothing)."""
        from tools.analyze.__main__ import changed_files

        root = make_tree(tmp_path, self._FILES)
        git = lambda *a: subprocess.run(  # noqa: E731
            ["git", *a], cwd=root, capture_output=True, text=True,
            timeout=60)
        if git("init", "-q").returncode != 0:
            pytest.skip("git unavailable")
        assert sorted(changed_files(root)) == sorted(self._FILES)
        assert analyze_main(["--root", root, "--changed-only",
                             "--check", "determinism"]) == 1
        git("add", "-A")
        git("-c", "user.email=t@t", "-c", "user.name=t",
            "commit", "-qm", "x")
        # clean tree -> changed_files None -> full-run fallback still
        # sees the committed findings
        assert changed_files(root) is None
        assert analyze_main(["--root", root, "--changed-only",
                             "--check", "determinism"]) == 1


# =============================================================================
# runner / baseline / CLI contract
# =============================================================================
class TestRunnerAndCLI:
    def test_live_repo_analyzer_clean_and_baseline_empty(self):
        """The ISSUE 7 acceptance pin: zero non-baselined findings AND a
        baseline with zero grandfathered entries — the repo is
        analyzer-clean outright, not clean-modulo-debt."""
        findings = run_checks(root=ROOT)
        assert new_findings(findings, load_baseline()) == []
        assert sum(load_baseline().values()) == 0
        assert findings == []

    def test_new_findings_multiset_subtraction(self):
        f = Finding("a.py", 3, "XX001", "x", "msg")
        g = Finding("a.py", 9, "XX001", "x", "msg")   # same key, new line
        base = Counter({f.key(): 1})
        assert new_findings([f], base) == []
        assert new_findings([f, g], base) == [g]      # one allowed, one new
        assert new_findings([f], Counter()) == [f]

    def test_cli_exit_codes(self, tmp_path, capsys):
        # exit-code semantics only — the all-checkers live-repo clean
        # pin is test_live_repo_analyzer_clean_and_baseline_empty; one
        # single-check live run covers the rc=0 path ~5s cheaper
        assert analyze_main(["--root", ROOT,
                             "--check", "error-taxonomy"]) == 0
        assert analyze_main(["--check", "bogus"]) == 2
        root = make_tree(tmp_path, {"paddle_tpu/serving/bad.py": '''
            def f():
                raise ValueError("x")
            '''})
        assert analyze_main(["--root", root,
                             "--check", "error-taxonomy"]) == 1
        out = capsys.readouterr().out
        assert "ET001" in out and "bad.py:3" in out

    def test_cli_baseline_roundtrip(self, tmp_path, capsys,
                                    monkeypatch):
        """--baseline grandfathers the current findings; the next run
        exits 0 (and a NEW finding still fails)."""
        monkeypatch.setattr(analyze_core, "baseline_path",
                            lambda: str(tmp_path / "baseline.txt"))
        root = make_tree(tmp_path, {"paddle_tpu/serving/bad.py": '''
            def f():
                raise ValueError("x")
            '''})
        args = ["--root", root, "--check", "error-taxonomy"]
        assert analyze_main(args) == 1
        assert analyze_main(args + ["--baseline"]) == 0
        assert analyze_main(args) == 0
        (tmp_path / "paddle_tpu/serving/worse.py").write_text(
            "def g():\n    raise KeyError('y')\n")
        assert analyze_main(args) == 1

    def test_module_cli_subprocess(self):
        """`python -m tools.analyze --list` works from the repo root —
        the real invocation CI uses (stdlib-only import, fast)."""
        res = subprocess.run(
            [sys.executable, "-m", "tools.analyze", "--list"],
            cwd=ROOT, capture_output=True, text=True, timeout=60)
        assert res.returncode == 0, res.stderr
        names = res.stdout.split()
        assert names == sorted(["error-taxonomy", "jit-hazard",
                                "lock-discipline", "metrics-coverage",
                                "metrics-drift", "pallas-contract",
                                "retrace-hazard", "determinism",
                                "host-sync", "chaos-coverage"])

    def test_suppression_requires_matching_check_name(self, tmp_path):
        root = make_tree(tmp_path, {"paddle_tpu/serving/bad.py": '''
            def f():
                raise ValueError("x")  # analyze: allow[lock-discipline]
            '''})
        # wrong check name in the marker: the finding survives
        found = run_checks(root=root, checks=["error-taxonomy"])
        assert len(found) == 1
