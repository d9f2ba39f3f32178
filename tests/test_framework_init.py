"""Process init + signal handlers (reference platform/init.cc — r3
component #5 'partial: seeding only')."""
import os
import signal
import subprocess
import sys

import pytest

import paddle_tpu.framework.init as finit


class TestInit:
    def test_init_devices_idempotent(self):
        d1 = finit.init_devices()
        d2 = finit.init_devices()
        assert d1 is d2 and len(d1) >= 1
        assert finit.is_initialized()
        assert finit.get_platform() in ("cpu", "tpu")

    def test_faulthandler_enabled(self):
        import faulthandler

        finit.init_signal_handlers()
        assert faulthandler.is_enabled()

    def test_sigterm_runs_shutdown_hooks(self, tmp_path):
        """A TERM'd trainer (launcher watchdog kill) flushes registered
        state before dying."""
        marker = str(tmp_path / "flushed")
        code = f"""
import os, signal, sys, time
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
import paddle_tpu.framework.init as finit
finit.init_signal_handlers()
finit.register_shutdown_hook(lambda: open({marker!r}, "w").write("ok"))
os.kill(os.getpid(), signal.SIGTERM)
time.sleep(10)
"""
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=60)
        assert r.returncode != 0          # died by TERM
        assert os.path.exists(marker)     # ...after flushing


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestImportTakesNoChip:
    """One process per chip (ISSUE 21): a process that has initialised a
    jax backend holds the chip, so importing the package — which the
    launcher parent does — must initialise none."""

    def test_import_initialises_no_backend(self):
        code = f"""
import os, sys
sys.path.insert(0, {_REPO!r})
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
from jax._src import xla_bridge
import jax
import paddle_tpu
assert not xla_bridge._backends, ("import paddle_tpu", xla_bridge._backends)
import paddle_tpu.distributed.launch
import paddle_tpu.distributed.spawn
assert not xla_bridge._backends, ("launch/spawn", xla_bridge._backends)
# a CPU-held process gets no repo-placed compile cache ...
import paddle_tpu.framework.init as finit
assert jax.config.jax_compilation_cache_dir is None
# ... any other gets a fixed path in the checkout, derived from the
# package's own location (still no backend: only jax.config is touched)
jax.config.update("jax_platforms", "")
want = os.path.join({_REPO!r}, ".jax_cache")
assert finit.configure_compile_cache() == want
assert jax.config.jax_compilation_cache_dir == want
assert not xla_bridge._backends
jax.config.update("jax_platforms", "cpu")
# using the generator is what brings the backend up
paddle_tpu.framework.random.default_generator.split_key()
assert xla_bridge._backends
print("ok")
"""
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=120,
                           env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.strip().endswith("ok")

    def test_operator_placed_compile_cache_wins(self, monkeypatch):
        import jax

        prev = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere")
        assert finit.configure_compile_cache() is None   # nothing set in code
        assert jax.config.jax_compilation_cache_dir == prev

    def test_launcher_binds_each_child_to_its_chip(self):
        from paddle_tpu.distributed.env import chip_binding_env

        env = chip_binding_env(3)
        assert env["FLAGS_selected_tpus"] == "3" == env["TPU_VISIBLE_CHIPS"]
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" \
            == env["TPU_PROCESS_BOUNDS"]


class TestPlaceLookup:
    """A Place names a platform, not a position in the default backend's
    device list: on a TPU host `jax.devices()` lists TPUs only, and
    CPUPlace must still find the host's CPU device."""

    def test_cpu_place_when_default_backend_is_not_cpu(self, monkeypatch):
        import jax

        import paddle_tpu as paddle
        from paddle_tpu.framework.place import (CPUPlace, CUDAPinnedPlace,
                                                TPUPlace)

        real = jax.devices
        cpu = real("cpu")[0]

        class FakeTpu:
            platform = "tpu"

        # what a TPU host reports: the default list holds no CPU device
        monkeypatch.setattr(
            jax, "devices",
            lambda backend=None: real(backend) if backend else [FakeTpu()])
        assert CPUPlace().jax_device == cpu
        assert CUDAPinnedPlace().jax_device == cpu
        t = paddle.to_tensor([1.0, 2.0], place=CPUPlace())
        assert t._value.devices() == {cpu}
        # an absent backend is a ValueError naming the place, and an id
        # past the end is not wrapped
        with pytest.raises(ValueError, match=r"TPUPlace\(0\).*no 'tpu'"):
            TPUPlace(0).jax_device
        monkeypatch.setattr(jax, "devices", real)
        with pytest.raises(ValueError, match="no device with that id"):
            paddle.CUDAPlace(len(real()) + 1).jax_device


class TestLazyGeneratorKey:
    """The default generator builds its key on first use; seeding
    semantics are what they were when it was built eagerly."""

    def test_seed_and_state_dict_unchanged_by_lazy_key(self):
        import jax
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu.framework.random import Generator

        def data(k):
            return np.asarray(jax.random.key_data(k))

        g = Generator(5)
        assert g._key is None                       # nothing built yet
        np.testing.assert_array_equal(g.state_dict()["key_data"],
                                      data(jax.random.key(5)))
        # the split sequence is the eager generator's
        k = jax.random.key(5)
        for _ in range(3):
            k, sub = jax.random.split(k)
            np.testing.assert_array_equal(data(g.split_key()), data(sub))
        # manual_seed drops the old stream; get_state rebuilds from seed
        g.manual_seed(9)
        assert g._key is None and g.initial_seed == 9
        np.testing.assert_array_equal(data(g.get_state()),
                                      data(jax.random.key(9)))
        # state_dict round trip restores the exact stream position
        g.split_key()
        snap = g.state_dict()
        nxt = data(g.split_key())
        np.testing.assert_array_equal(
            data(Generator(0).set_state_dict(snap).split_key()), nxt)
        # paddle.seed drives the same path
        gen = paddle.seed(1234)
        np.testing.assert_array_equal(gen.state_dict()["key_data"],
                                      data(jax.random.key(1234)))
        assert gen.state_dict()["seed"] == 1234


class TestSplitAhead:
    """split_ahead() computes the next split early and is otherwise
    invisible: same stream, same state, dropped when the key changes."""

    @staticmethod
    def data(k):
        import jax
        import numpy as np

        return np.asarray(jax.random.key_data(k))

    def stream(self, seed, n):
        import jax

        k, out = jax.random.key(seed), []
        for _ in range(n):
            k, sub = jax.random.split(k)
            out.append(self.data(sub))
        return out

    @pytest.mark.parametrize("ahead_before", [(), (0,), (0, 1, 2), (1,)])
    def test_the_stream_is_the_same_wherever_it_is_called(self, ahead_before):
        import numpy as np

        from paddle_tpu.framework.random import Generator

        g = Generator(7)
        for i, want in enumerate(self.stream(7, 3)):
            if i in ahead_before:
                before = g.state_dict()["key_data"]
                g.split_ahead()
                g.split_ahead()                      # twice is once
                np.testing.assert_array_equal(
                    g.state_dict()["key_data"], before)
            np.testing.assert_array_equal(self.data(g.split_key()), want)

    @pytest.mark.parametrize("change", ["manual_seed", "set_state",
                                        "set_state_dict"])
    def test_a_new_key_in_between_drops_what_was_computed(self, change):
        import jax
        import numpy as np

        from paddle_tpu.framework.random import Generator

        g = Generator(7)
        g.split_key()
        g.split_ahead()
        if change == "manual_seed":
            g.manual_seed(11)
        elif change == "set_state":
            g.set_state(jax.random.key(11))
        else:
            g.set_state_dict(Generator(11).state_dict())
        np.testing.assert_array_equal(self.data(g.split_key()),
                                      self.stream(11, 1)[0])

    def test_train_batch_takes_host_arrays_and_keeps_the_key_stream(self):
        """Two steps from numpy batches: the generator ends where two
        split_key() calls end, and the losses are those of device-array
        batches (the jitted step moves host arrays itself)."""
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu import nn, optimizer
        from paddle_tpu.framework.random import default_generator

        def run(as_device):
            paddle.seed(21)
            net = nn.Sequential(nn.Linear(4, 8), nn.Dropout(0.5),
                                nn.Linear(8, 3))
            m = paddle.Model(net)
            m.prepare(optimizer.SGD(0.1, parameters=net.parameters()),
                      nn.CrossEntropyLoss())
            rng = np.random.default_rng(0)
            out = []
            for _ in range(2):
                x = rng.normal(size=(6, 4)).astype(np.float32)
                y = rng.integers(0, 3, size=(6,))          # int64 labels
                if as_device:
                    x, y = paddle.to_tensor(x), paddle.to_tensor(y)
                out.append(m.train_batch([x], [y])[0])
            return out, default_generator.state_dict()["key_data"]

        host, key_host = run(False)
        dev, key_dev = run(True)
        assert host == dev
        np.testing.assert_array_equal(key_host, key_dev)
        # ... and where the same draws plus one split_key() a step end
        paddle.seed(21)
        nn.Sequential(nn.Linear(4, 8), nn.Dropout(0.5), nn.Linear(8, 3))
        default_generator.split_key()
        default_generator.split_key()
        np.testing.assert_array_equal(
            default_generator.state_dict()["key_data"], key_host)
