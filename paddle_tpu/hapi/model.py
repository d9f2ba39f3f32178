"""hapi Model (reference: python/paddle/hapi/model.py — Model :810, fit :1299,
DynamicGraphAdapter :609).

TPU-native: Model.prepare builds ONE jitted train step (forward + loss +
grad + optimizer update, donated arrays) over the functional layer state —
the whole-step XLA program is the performance path the reference approximates
with per-op kernels.  `accelerate=False` falls back to eager (tape) stepping
for debugging parity.
"""
from __future__ import annotations

import time as _time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.monitor import histogram_observe, stat_registry
from ..framework.random import default_generator, py_random, rng_scope
from ..jit.functional import functional_call, get_state
from ..metric.metrics import Metric
from ..tensor import Tensor
from ..utils.profiler import RecordEvent
from .callbacks import CallbackList, ProgBarLogger


def _to_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _batch_size_of(x):
    try:
        return int(x.shape[0])
    except Exception:
        return 1


class StaticGraphAdapter:
    """Static-graph execution path (reference hapi/model.py:224
    StaticGraphAdapter): records train/eval/predict Programs from the
    network + loss + optimizer.minimize and drives them through the
    static Executor — Model.fit/evaluate/predict run on the SAME loops,
    only the per-batch engine differs.

    Selected when ``paddle.enable_static()`` is active at prepare() time;
    requires Model(inputs=[InputSpec...], labels=[InputSpec...]) like the
    reference."""

    def __init__(self, model: "Model"):
        from .. import static as _static

        self.model = model
        if not model._inputs:
            raise ValueError(
                "static mode requires Model(network, inputs=[InputSpec], "
                "labels=[InputSpec]) so the feed layout is known at "
                "program-build time (reference hapi/model.py:224)")
        self._static = _static
        self._exe = _static.Executor()
        self._progs = {}
        self._fetches = {}

    def _spec_shape(self, spec):
        return [d if d is not None else -1 for d in spec.shape]

    def _build(self, mode):
        """Record the program for `mode` once (reference _make_program)."""
        if mode in self._progs:
            return
        _st = self._static
        model = self.model
        prog = _st.Program()
        with _st.program_guard(prog):
            ins = [_st.data(s.name or f"input_{i}",
                            self._spec_shape(s), str(s.dtype))
                   for i, s in enumerate(_to_list(model._inputs))]
            outs = model.network(*ins)
            outs_l = _to_list(outs)
            fetches = list(outs_l)
            if mode != "predict" and model._loss is not None:
                labels = [_st.data(s.name or f"label_{i}",
                                   self._spec_shape(s), str(s.dtype))
                          for i, s in enumerate(_to_list(model._labels))]
                loss = model._loss(*outs_l, *labels)
                fetches = [loss] + fetches
                if mode == "train":
                    model._optimizer.minimize(loss)
        self._progs[mode] = prog
        self._fetches[mode] = fetches

    def _feed_dict(self, inputs, labels, mode):
        model = self.model
        feed = {}
        for i, (spec, v) in enumerate(zip(_to_list(model._inputs),
                                          inputs)):
            feed[spec.name or f"input_{i}"] = np.asarray(
                v.numpy() if isinstance(v, Tensor) else v)
        if mode != "predict":
            for i, (spec, v) in enumerate(zip(_to_list(model._labels),
                                              labels)):
                feed[spec.name or f"label_{i}"] = np.asarray(
                    v.numpy() if isinstance(v, Tensor) else v)
        return feed

    def train_batch(self, inputs, labels=None, update=True):
        if not update:
            raise ValueError(
                "update=False (gradient accumulation) is not supported in "
                "static mode — the train program records the optimizer "
                "update; use gradient_merge in the strategy, or dygraph "
                "mode")
        self.model.network.train()
        self._build("train")
        res = self._exe.run(self._progs["train"],
                            feed=self._feed_dict(inputs, labels, "train"),
                            fetch_list=self._fetches["train"])
        loss, outs = res[0], res[1:]
        yv = labels[0]
        yv = yv.numpy() if isinstance(yv, Tensor) else np.asarray(yv)
        metrics_out = self.model._update_metrics(
            jnp.asarray(outs[0]), jnp.asarray(yv))
        return [float(np.asarray(loss))] + metrics_out

    def eval_batch(self, inputs, labels=None):
        self.model.network.eval()
        labeled = bool(labels) and labels[0] is not None
        mode = "eval" if (labeled and self.model._loss is not None) \
            else "predict"
        self._build(mode)
        res = self._exe.run(self._progs[mode],
                            feed=self._feed_dict(inputs, labels, mode),
                            fetch_list=self._fetches[mode])
        out = []
        if labeled:
            yv = labels[0]
            yv = yv.numpy() if isinstance(yv, Tensor) else np.asarray(yv)
            net_out = res[1] if mode == "eval" else res[0]
            if mode == "eval":
                out.append(float(np.asarray(res[0])))
            # metrics update for ANY labeled batch, loss or not — same
            # contract as the dynamic path
            out += self.model._update_metrics(jnp.asarray(net_out),
                                              jnp.asarray(yv))
        return out

    def predict_batch(self, inputs):
        self.model.network.eval()
        self._build("predict")
        res = self._exe.run(self._progs["predict"],
                            feed=self._feed_dict(inputs, None, "predict"),
                            fetch_list=self._fetches["predict"])
        return [np.asarray(res[0])]


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self._accelerate = True
        self._train_step = None
        self._eval_fn = None
        self._state = None
        self._adapter = None       # StaticGraphAdapter when static mode
        self.stop_training = False
        # numerical self-healing (ISSUE 13, docs/CHECKPOINT.md): with
        # fit(anomaly=) active the train step is built GUARDED — it
        # additionally returns isfinite(loss) & isfinite(global grad
        # norm) (read on host with the loss, zero extra syncs) and
        # keeps the pre-step state handle alive so a poisoned update
        # can be discarded by a pointer swap
        self._anomaly_guard = False
        self._train_step_guarded = False
        self._last_guard = None    # {"ok", "loss", "grad_norm"} | None
        self._prev_state = None    # pre-step state (guard mode only)

    # --- prepare -----------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None,
                accelerate=True):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _to_list(metrics)
        self._accelerate = accelerate
        self._train_step = None
        self._eval_fn = None
        from .. import in_dynamic_mode

        self._adapter = None if in_dynamic_mode() else \
            StaticGraphAdapter(self)
        return self

    # --- state sync: functional state <-> layer tensors ---------------------
    def _ensure_state(self):
        if self._state is None:
            params, buffers = get_state(self.network)
            opt = (self._optimizer.init_opt_state(params)
                   if self._optimizer is not None else {})
            self._state = {"params": params, "buffers": buffers, "opt": opt,
                           "step": jnp.zeros((), jnp.int32)}

    def _writeback_state(self):
        """Push functional state back into layer tensors (so state_dict etc.
        observe trained weights)."""
        if self._state is None:
            return
        for n, p in self.network.named_parameters():
            if n in self._state["params"]:
                p._value = self._state["params"][n]
        for n, b in self.network.named_buffers():
            if n in self._state["buffers"]:
                b._value = self._state["buffers"][n]

    def _build_train_step(self, guarded: bool = False):
        network, loss_fn, optimizer = self.network, self._loss, self._optimizer

        def step_fn(state, key, x, y):
            def loss_of(params):
                with rng_scope(key):
                    out, new_bufs = functional_call(
                        network, params, state["buffers"], (x,), training=True)
                out_t = jax.tree_util.tree_map(
                    lambda v: Tensor(v) if isinstance(v, jax.Array) else v, out)
                if not isinstance(out_t, (list, tuple)):
                    out_t = [out_t]
                loss = loss_fn(*out_t, Tensor(y))
                return loss._value.astype(jnp.float32), (new_bufs, out)

            (loss, (new_bufs, out)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(state["params"])
            count = state["step"] + 1
            new_params, new_opt = optimizer.fused_step(
                state["params"], grads, state["opt"], count)
            new_state = {"params": new_params, "buffers": new_bufs,
                         "opt": new_opt, "step": count}
            if guarded:
                # device-side numeric guard folded into the step's own
                # outputs (ISSUE 13): one f32 reduction over the grads
                # XLA fuses into the update it is already computing —
                # the host learns ok/grad_norm at the same sync point
                # it reads the loss, zero extra transfers
                gn = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads)))
                ok = jnp.isfinite(loss) & jnp.isfinite(gn)
                return new_state, loss, out, gn, ok
            return new_state, loss, out

        # guard mode keeps the pre-step buffers alive (no donation) so
        # SKIP-STEP can discard a poisoned update with a host pointer
        # swap — the measured cost of that trade is the bench's
        # detail.numerical_resilience guard-overhead number
        return jax.jit(step_fn,
                       donate_argnums=() if guarded else (0,))

    def _build_eval_fn(self):
        network = self.network

        def eval_fn(params, buffers, x):
            out, _ = functional_call(network, params, buffers, (x,),
                                     training=False)
            return out

        return jax.jit(eval_fn)

    # --- single-batch API ----------------------------------------------------
    def train_batch(self, inputs, labels=None, update=True):
        # one span per step whoever drives it (fit's loop or a caller of
        # its own); the children name the host's phases of a jitted step
        with RecordEvent("hapi/train_batch"):
            return self._train_batch(_to_list(inputs), _to_list(labels),
                                     update)

    def _train_batch(self, inputs, labels, update):
        if self._adapter is not None:
            return self._adapter.train_batch(inputs, labels, update)
        x = inputs[0]
        y = labels[0] if labels else None
        with RecordEvent("hapi/train_batch/inputs"):
            # host arrays stay host arrays: the jitted step moves them with
            # its own dispatch (same avals), a put of their own is one more
            # round of the runtime between two steps
            xv = x._value if isinstance(x, Tensor) else np.asarray(x)
            yv = y._value if isinstance(y, Tensor) else np.asarray(y)

        if self._accelerate:
            self._ensure_state()
            if self._train_step is None \
                    or self._train_step_guarded != self._anomaly_guard:
                self._train_step = self._build_train_step(
                    self._anomaly_guard)
                self._train_step_guarded = self._anomaly_guard
                # buffers the network counts in, step by step on the device
                # ({stat name: buffer name}); see _publish_counters
                self._step_counters = tuple(getattr(
                    self.network, "step_counters", {}).items())
            key = default_generator.split_key()
            if self._anomaly_guard:
                prev = self._state
                with RecordEvent("hapi/train_batch/dispatch"):
                    (self._state, loss, out,
                     gn, ok) = self._train_step(self._state, key, xv, yv)
                    self._publish_counters()
                    default_generator.split_ahead()
                with RecordEvent("hapi/train_batch/fetch_loss"):
                    lossf = float(np.asarray(loss))
                    okb = bool(np.asarray(ok))
                    self._last_guard = {"ok": okb, "loss": lossf,
                                        "grad_norm": float(np.asarray(gn))}
                self._prev_state = prev
                if not okb:
                    # poisoned step: never feed NaN outputs into the
                    # metrics; the anomaly runtime decides skip/rollback
                    return [lossf]
                with RecordEvent("hapi/train_batch/metrics"):
                    metrics_out = self._update_metrics(out, yv)
                return [lossf] + metrics_out
            self._last_guard = None
            with RecordEvent("hapi/train_batch/dispatch"):
                self._state, loss, out = self._train_step(
                    self._state, key, xv, yv)
                self._publish_counters()
                # the next step's key, while this one runs
                default_generator.split_ahead()
            with RecordEvent("hapi/train_batch/metrics"):
                metrics_out = self._update_metrics(out, yv)
            with RecordEvent("hapi/train_batch/fetch_loss"):
                return [float(np.asarray(loss))] + metrics_out

        # eager path
        self.network.train()
        outputs = self.network(Tensor(xv))
        outs = _to_list(outputs)
        loss = self._loss(*outs, Tensor(yv))
        loss.backward()
        if self._anomaly_guard:
            lossf = float(np.asarray(loss._value))
            gn_sq = 0.0
            for p in self.network.parameters():
                g = getattr(p, "grad", None)
                if g is None:
                    continue
                garr = np.asarray(g._value if hasattr(g, "_value") else g,
                                  np.float64)
                gn_sq += float(np.sum(np.square(garr)))
            gnf = float(np.sqrt(gn_sq))
            okb = bool(np.isfinite(lossf) and np.isfinite(gnf))
            self._last_guard = {"ok": okb, "loss": lossf,
                                "grad_norm": gnf}
            if not okb:
                # eager SKIP-STEP: the optimizer never runs, so the
                # params are untouched by construction
                self._optimizer.clear_grad()
                return [lossf]
        else:
            self._last_guard = None
        if update:
            self._optimizer.step()
            self._optimizer.clear_grad()
        metrics_out = self._update_metrics(outs[0]._value, yv)
        return [float(np.asarray(loss._value))] + metrics_out

    def _publish_counters(self):
        """Hand the registry the step's counter buffers as they stand on
        the device: a pointer each, no transfer, no sync — whoever reads
        `stat_registry.held(name)` pays for the copy."""
        for stat, buf in self._step_counters:
            stat_registry.hold(stat, self._state["buffers"][buf])

    def _update_metrics(self, out, yv):
        res = []
        first = out[0] if isinstance(out, (list, tuple)) else out
        for m in self._metrics:
            c = m.compute(Tensor(first), Tensor(yv))
            r = m.update(c)
            res.append(r)
        return res

    def eval_batch(self, inputs, labels=None):
        inputs = _to_list(inputs)
        labels = _to_list(labels)
        if self._adapter is not None:
            return self._adapter.eval_batch(inputs, labels)
        x = inputs[0]
        y = labels[0] if labels else None
        xv = x._value if isinstance(x, Tensor) else jnp.asarray(np.asarray(x))
        if self._accelerate:
            self._ensure_state()
            if self._eval_fn is None:
                self._eval_fn = self._build_eval_fn()
            out = self._eval_fn(self._state["params"], self._state["buffers"], xv)
        else:
            self.network.eval()
            out = self.network(Tensor(xv))._value
        outs = out if isinstance(out, (list, tuple)) else [out]
        res = []
        if y is not None:
            yv = y._value if isinstance(y, Tensor) else jnp.asarray(np.asarray(y))
            if self._loss is not None:
                loss = self._loss(Tensor(outs[0]), Tensor(yv))
                res.append(float(np.asarray(loss._value)))
            res += self._update_metrics(outs[0], yv)
        return res

    def predict_batch(self, inputs):
        inputs = _to_list(inputs)
        if self._adapter is not None:
            return self._adapter.predict_batch(inputs)
        x = inputs[0]
        xv = x._value if isinstance(x, Tensor) else jnp.asarray(np.asarray(x))
        if self._accelerate:
            self._ensure_state()
            if self._eval_fn is None:
                self._eval_fn = self._build_eval_fn()
            out = self._eval_fn(self._state["params"], self._state["buffers"], xv)
            return [np.asarray(out)]
        self.network.eval()
        return [self.network(Tensor(xv)).numpy()]

    # --- loops ---------------------------------------------------------------
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None,
            checkpoint_dir=None, checkpoint_interval=None,
            checkpoint_async=True, keep_checkpoints=3, resume=False,
            step_retries=0, step_retry_backoff_s=0.05, anomaly=None):
        """Train loop.  Crash-consistency knobs (ISSUE 9 — contracts in
        docs/CHECKPOINT.md):

        - ``checkpoint_dir`` + ``checkpoint_interval``: commit an atomic
          train-state checkpoint (params, optimizer slots, LR scheduler,
          PRNG streams, loader position, step counter) every K steps
          through an :class:`~paddle_tpu.io.checkpoint.CheckpointStore`;
          ``checkpoint_async`` overlaps serialization with the next
          steps (``keep_checkpoints`` = keep-last-K retention).
        - ``resume``: ``True`` resumes from ``checkpoint_dir``'s newest
          VALID checkpoint (torn/corrupt ones are skipped); a path or
          CheckpointStore resumes from there instead.  A resumed run is
          bit-identical to the uninterrupted one — at most the steps
          since the last commit are recomputed.  An empty/absent store
          starts from scratch.
        - ``step_retries`` + ``step_retry_backoff_s``: transient
          batch-fetch / train-step failures are retried with bounded
          exponential backoff (PRNG state restored per attempt, so a
          retried step consumes the same keys).  ``FatalError`` (e.g. a
          ``train.step`` chaos ``kill``) is never retried — it models
          process death.
        - ``anomaly``: ``True`` or an
          :class:`~paddle_tpu.hapi.anomaly.AnomalyPolicy` turns on
          numerical self-healing (ISSUE 13 — docs/CHECKPOINT.md
          "Numerical self-healing"): the jitted train step grows a
          device-side ``isfinite(loss) & isfinite(global_grad_norm)``
          guard, a non-finite step is SKIPPED (state, optimizer, LR and
          PRNG streams untouched, batch discarded), a rolling
          median/MAD loss-spike detector skips or tolerates divergence
          bursts, repeated damage ROLLS BACK to the newest verified
          checkpoint (requires ``checkpoint_dir`` when rollback is
          armed), and a periodic SDC audit sweeps the live parameters
          for corruption, naming the exact leaf.  A rollback budget
          bounds the healing loop; exhausting it raises ``FatalError``
          with a postmortem bundle.
        """
        from ..framework.errors import FatalError, InvalidArgumentError
        from ..framework.monitor import stat_add
        from ..io import DataLoader
        from ..io.dataset import Dataset
        from ..testing.chaos import KILL, chaos_site

        if isinstance(train_data, Dataset):
            train_loader = DataLoader(train_data, batch_size=batch_size,
                                      shuffle=shuffle, drop_last=drop_last,
                                      num_workers=num_workers)
        else:
            train_loader = train_data
        if eval_data is not None and isinstance(eval_data, Dataset):
            eval_loader = DataLoader(eval_data, batch_size=batch_size,
                                     num_workers=num_workers)
        else:
            eval_loader = eval_data

        ckpt = None
        if checkpoint_dir is not None:
            from .checkpoint import TrainCheckpointer

            ckpt = TrainCheckpointer(
                checkpoint_dir,
                interval=(1 if checkpoint_interval is None
                          else checkpoint_interval),
                async_write=checkpoint_async, keep_last=keep_checkpoints)
        resume_pos = None
        if resume:
            from .checkpoint import TrainCheckpointer

            if resume is True:
                if ckpt is None:
                    raise InvalidArgumentError(
                        "resume=True needs checkpoint_dir= (or pass the "
                        "store/path to resume from as resume=)")
                resume_pos = ckpt.resume(self)
            else:
                resume_pos = TrainCheckpointer(
                    resume, async_write=False).resume(self)

        # --- numerical self-healing (ISSUE 13) ---------------------------
        anomaly_rt = None
        if anomaly:
            from .anomaly import AnomalyPolicy, AnomalyRuntime

            if anomaly is not True and not isinstance(anomaly,
                                                      AnomalyPolicy):
                # the watchdog=/brownout= discipline: a truthy config
                # object must not silently become the defaults
                raise InvalidArgumentError(
                    f"anomaly must be True or an AnomalyPolicy, "
                    f"got {anomaly!r}")
            policy = (anomaly if isinstance(anomaly, AnomalyPolicy)
                      else AnomalyPolicy())
            if self._adapter is not None:
                raise InvalidArgumentError(
                    "anomaly= is not supported in static-graph mode — "
                    "the guard rides the jitted dynamic train step")
            if policy.rollback_after is not None and ckpt is None:
                raise InvalidArgumentError(
                    "AnomalyPolicy with rollback armed "
                    "(rollback_after is not None) needs checkpoint_dir= "
                    "— rollback restores from the TrainCheckpointer's "
                    "store; pass AnomalyPolicy(rollback_after=None) for "
                    "skip-only operation")
            if not self._accelerate and policy.spike_window > 0 \
                    and policy.spike_action == "skip":
                # the eager optimizer update is already applied when
                # the spike is detected — "skip" cannot be honored, and
                # silently tolerating would violate the configured
                # policy (non-finite eager steps still skip exactly:
                # their update never runs)
                raise InvalidArgumentError(
                    "spike_action='skip' needs the accelerated (jitted)"
                    " train path; with accelerate=False use "
                    "spike_action='tolerate' or spike_window=0")
            anomaly_rt = AnomalyRuntime(policy, checkpointer=ckpt)
            self._anomaly_guard = True
        else:
            self._anomaly_guard = False
        from .anomaly import _RollbackRequested

        steps = None
        try:
            steps = len(train_loader)
        except TypeError:
            pass
        cbks = CallbackList(callbacks, model=self, verbose=verbose,
                            metrics=["loss"] + self._metric_names(),
                            epochs=epochs, steps=steps, log_freq=log_freq)
        cbks.on_begin("train")
        self.stop_training = False
        global_step = 0 if resume_pos is None else resume_pos["global_step"]
        start_epoch = 0 if resume_pos is None else resume_pos["epoch"]
        trained_any = False
        logs = {}
        try:
            epoch = 0
            while epoch < epochs:
                if epoch < start_epoch:
                    epoch += 1
                    continue            # fully covered by the checkpoint
                skip_batches = 0
                np_resume_mid = None
                py_resume_mid = None
                if resume_pos is not None and epoch == start_epoch:
                    # replay the SAME epoch permutation the killed run
                    # drew, skip the batches it already trained, then
                    # rejoin its exact numpy-RNG stream (and the
                    # sanctioned stdlib stream the vision transforms
                    # draw from — absent in pre-ISSUE-15 checkpoints)
                    np.random.set_state(
                        resume_pos["np_state_epoch_start"])
                    if resume_pos.get("py_state_epoch_start") is not None:
                        py_random.setstate(
                            resume_pos["py_state_epoch_start"])
                    skip_batches = resume_pos["next_batch"]
                    np_resume_mid = resume_pos["np_random"]
                    py_resume_mid = resume_pos.get("py_random")
                try:
                    # one span per epoch; per-batch spans + a latency
                    # histogram nest inside it (fit > epoch > train_batch)
                    with RecordEvent("hapi/fit.epoch", epoch=epoch):
                        cbks.on_epoch_begin(epoch)
                        for m in self._metrics:
                            m.reset()
                        logs = {}
                        # captured BEFORE the loader draws the
                        # permutation: the snapshot leaf a mid-epoch
                        # resume replays from
                        np_epoch_start = np.random.get_state()
                        py_epoch_start = py_random.getstate()
                        it = iter(train_loader)
                        step = 0
                        while True:
                            if num_iters is not None \
                                    and step >= num_iters:
                                break
                            if step >= skip_batches \
                                    and np_resume_mid is not None:
                                # rejoin the checkpoint's exact numpy
                                # stream BEFORE fetching the first
                                # non-replayed batch: the capture
                                # happened after training batch k-1 and
                                # before fetching batch k, so a dataset
                                # whose __getitem__ consumes np.random
                                # must see the restored state at fetch
                                # time — restoring after the fetch (the
                                # PR-9 ordering) fed batch k the replay
                                # stream, which lacks the training-time
                                # RNG consumption and diverges from the
                                # uninterrupted run
                                np.random.set_state(np_resume_mid)
                                np_resume_mid = None
                                if py_resume_mid is not None:
                                    py_random.setstate(py_resume_mid)
                                    py_resume_mid = None
                            # -- fetch (chaos-instrumented, retried) --
                            batch = self._fetch_with_retry(
                                it, step_retries, step_retry_backoff_s,
                                chaos_site, stat_add)
                            if batch is None:
                                break       # epoch exhausted
                            if step < skip_batches:
                                step += 1   # resume replay: trained
                                continue
                            if anomaly_rt is not None \
                                    and (epoch, step) in anomaly_rt.poisoned:
                                # post-rollback replay: the batch whose
                                # damage triggered the rollback is
                                # discarded for good — training it
                                # again would deterministically poison
                                # the restored trajectory.  No RNG is
                                # consumed (the skip that recorded it
                                # rewound the streams), so the replay
                                # continues bit-exact past it.
                                step += 1
                                continue
                            cbks.on_batch_begin("train", step, logs)
                            x = batch[0]
                            y = batch[1] if len(batch) > 1 else None
                            t0 = _time.perf_counter()
                            outs = self._step_with_retry(
                                x, y, step_retries,
                                step_retry_backoff_s, chaos_site,
                                stat_add, KILL, FatalError,
                                runtime=anomaly_rt, epoch=epoch,
                                batch=step, global_step=global_step)
                            histogram_observe(
                                "hapi.train_batch_ms",
                                (_time.perf_counter() - t0) * 1e3)
                            if outs is None:
                                # anomaly SKIP-STEP: batch discarded,
                                # state/optimizer/PRNG untouched — the
                                # step never happened.  The SDC audit
                                # still ticks: persistent parameter
                                # corruption makes EVERY step skip, and
                                # exactly then the audit (not the skip
                                # machinery) must name the leaf and
                                # trigger the rollback.  Callbacks keep
                                # their begin/end pairing (a consumer
                                # pairing timers/counters must not see
                                # an unmatched begin); logs are the
                                # previous batch's — the skipped step
                                # contributed nothing.
                                cbks.on_batch_end("train", step, logs)
                                anomaly_rt.maybe_audit(
                                    self, global_step=global_step,
                                    epoch=epoch, batch=step)
                                step += 1
                                continue
                            global_step += 1
                            trained_any = True
                            logs = {"loss": outs[0],
                                    "batch_size": _batch_size_of(x)}
                            for name, val in zip(self._metric_names(),
                                                 outs[1:]):
                                logs[name] = val
                            cbks.on_batch_end("train", step, logs)
                            snapped = False
                            if ckpt is not None:
                                ckpt.note_step(global_step)
                                snapped = ckpt.maybe_snapshot(
                                    self, global_step=global_step,
                                    epoch=epoch, next_batch=step + 1,
                                    np_state_epoch_start=np_epoch_start,
                                    py_state_epoch_start=py_epoch_start)
                            if anomaly_rt is not None:
                                # SDC audit cadence: every N trained
                                # steps, plus right after a committed
                                # checkpoint
                                anomaly_rt.maybe_audit(
                                    self, global_step=global_step,
                                    epoch=epoch, batch=step,
                                    force=snapped)
                            step += 1
                            if self.stop_training:
                                break
                        if eval_loader is not None \
                                and (epoch + 1) % eval_freq == 0:
                            eval_logs = self.evaluate(
                                eval_loader, verbose=0, _inside_fit=True)
                            logs.update({f"eval_{k}": v
                                         for k, v in eval_logs.items()})
                        cbks.on_epoch_end(epoch, logs)
                except _RollbackRequested as rb:
                    # numerical damage crossed the policy threshold (or
                    # the audit named a corrupt leaf): restore the
                    # newest verified checkpoint and re-enter the loop
                    # at its position — the resume machinery replays
                    # the epoch permutation, skips the already-covered
                    # batches and rejoins the checkpoint's RNG streams,
                    # while the poisoned set fast-forwards past the
                    # damaged batches
                    resume_pos = anomaly_rt.perform_rollback(
                        self, rb.reason)
                    global_step = resume_pos["global_step"]
                    start_epoch = resume_pos["epoch"]
                    epoch = start_epoch
                    continue
                if save_dir and (epoch + 1) % save_freq == 0:
                    self.save(f"{save_dir}/{epoch}")
                if self.stop_training:
                    break
                epoch += 1
            if ckpt is not None and (trained_any or resume_pos is None):
                # terminal checkpoint at position (epochs, 0): resuming
                # with the same epoch budget is a no-op, a larger one
                # continues exactly where training ended.  A no-op
                # resume (every epoch already covered) must NOT rewrite
                # it: this process's numpy state is unrelated to the
                # true end-of-training state the existing terminal
                # checkpoint carries
                ckpt.snapshot(self, global_step=global_step,
                              epoch=epochs, next_batch=0,
                              np_state_epoch_start=np.random.get_state(),
                              py_state_epoch_start=py_random.getstate())
        finally:
            # guard mode is a per-fit property: leaving it armed would
            # make later standalone train_batch calls run guarded with
            # no runtime to act on the verdict (a poisoned update kept,
            # a 1-element return breaking the [loss, *metrics]
            # contract), and _prev_state would pin a full extra
            # params+optimizer copy for the model's lifetime
            self._anomaly_guard = False
            self._prev_state = None
            self._last_guard = None
            if ckpt is not None:
                import sys as _sys

                in_flight = _sys.exc_info()[0] is not None
                try:
                    ckpt.close()
                except Exception:  # noqa: BLE001 — see re-raise below
                    # a close failure (flush timeout on a hung disk,
                    # deferred write error) must never MASK a training
                    # exception already propagating — FatalError is the
                    # crash cause resume tooling keys on.  With no
                    # exception in flight the close failure IS the
                    # error and propagates as before.
                    if not in_flight:
                        raise
        cbks.on_end("train", logs)
        if save_dir:
            self.save(f"{save_dir}/final")
        return self

    def _fetch_with_retry(self, it, retries, backoff_s, chaos_site,
                          stat_add):
        """Next batch through the ``loader.next`` chaos site with
        bounded-backoff retry; None = epoch exhausted.  ONLY the
        pre-fetch site faults are retried: the actual ``next()`` may
        already have consumed a sampler index when it fails, so
        retrying it would silently skip a batch — a real loader
        failure propagates instead."""
        from ..profiler.flight_recorder import recorder as _flight

        attempt = 0
        while True:
            try:
                chaos_site("loader.next")
                break
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                attempt += 1
                if attempt > retries:
                    raise
                stat_add("train.step_retries", 1)
                _flight.on_transition("train.retry", "loader.next",
                                      f"{type(e).__name__}: {e}")
                _time.sleep(backoff_s * (2 ** (attempt - 1)))
        try:
            return next(it)
        except StopIteration:
            return None

    def _step_with_retry(self, x, y, retries, backoff_s, chaos_site,
                         stat_add, KILL, FatalError, runtime=None,
                         epoch=0, batch=0, global_step=0):
        """One train step through the ``train.step`` chaos site.
        Transient failures retry with exponential backoff after
        restoring BOTH PRNG streams captured before the attempt — a
        retried step consumes the same keys, so a run with transient
        faults stays bit-identical to a clean one.  A chaos ``kill``
        raises FatalError (never retried: it models process death; so
        does a real crash after the jitted update already donated the
        previous state).  Retries and fatals land in the flight
        recorder — a FatalError additionally triggers a postmortem
        bundle (when a bundle_dir is armed), so a training crash
        leaves the same black box a replica death does.

        Numeric chaos (ISSUE 13): ``nan_loss``/``nan_grad`` poison the
        batch before the step, ``corrupt_param`` flips a named param
        leaf's element non-finite on device.  With ``runtime`` (an
        AnomalyRuntime) the step's guard verdict is applied here:
        SKIP-STEP returns None after rewinding BOTH PRNG streams to the
        pre-attempt capture — the poisoned batch never happened."""
        from ..profiler.flight_recorder import recorder as _flight
        from ..testing.chaos import CORRUPT_PARAM, NAN_GRAD, NAN_LOSS

        attempt = 0
        while True:
            key_state = default_generator.get_state()
            np_state = np.random.get_state()
            py_state = py_random.getstate()
            xin = x
            try:
                fault = chaos_site("train.step")
                if fault is not None:
                    if fault.action == KILL:
                        raise FatalError(fault.message)
                    if fault.action in (NAN_LOSS, NAN_GRAD):
                        xin = self._poison_batch(fault.action, x,
                                                 NAN_LOSS)
                    elif fault.action == CORRUPT_PARAM:
                        self._corrupt_param(fault)
                outs = self.train_batch([xin], [y])
            except (KeyboardInterrupt, SystemExit):
                raise
            except FatalError as e:
                _flight.on_transition("train.fatal", "train.step",
                                      str(e))
                _flight.auto_dump(f"train step fatal: {e}")
                raise
            except Exception as e:
                attempt += 1
                default_generator.set_state(key_state)
                np.random.set_state(np_state)
                py_random.setstate(py_state)
                if attempt > retries:
                    raise
                stat_add("train.step_retries", 1)
                _flight.on_transition("train.retry", "train.step",
                                      f"{type(e).__name__}: {e}")
                _time.sleep(backoff_s * (2 ** (attempt - 1)))
                continue
            # success path: apply the anomaly policy OUTSIDE the retry
            # try-block — a rollback signal must propagate, never be
            # swallowed into the transient-retry loop
            if runtime is None or self._last_guard is None:
                return outs
            verdict = runtime.on_step_outcome(
                self, outs, epoch=epoch, batch=batch,
                global_step=global_step)
            if verdict == "skip":
                # the batch is discarded: rewind all three PRNG streams
                # so the next batch consumes exactly the keys it would
                # have consumed had this batch never been drawn
                default_generator.set_state(key_state)
                np.random.set_state(np_state)
                py_random.setstate(py_state)
                return None
            return outs

    def _poison_batch(self, action, x, NAN_LOSS):
        """Chaos ``nan_loss``/``nan_grad``: return a poisoned copy of
        the batch inputs — NaN drives the loss non-finite, an
        overflow-scale magnitude blows up the gradient norm (both trip
        the combined device guard; they differ in which side of
        ``isfinite(loss) & isfinite(grad_norm)`` carries the damage)."""
        from ..framework.errors import InvalidArgumentError

        arr = np.array(x.numpy() if hasattr(x, "numpy") else x)
        if not np.issubdtype(arr.dtype, np.floating):
            raise InvalidArgumentError(
                f"chaos {action} needs a floating-point input batch to "
                f"poison, got dtype {arr.dtype}")
        arr[...] = np.nan if action == NAN_LOSS \
            else np.finfo(arr.dtype).max
        return arr

    def _corrupt_param(self, fault):
        """Chaos ``corrupt_param``: flip one deterministically chosen
        element of the named parameter leaf to a non-finite bit
        pattern on device — the simulated SDC event the ISSUE 13 audit
        exists to catch.  The flip persists until a rollback restores a
        clean checkpoint (SKIP-STEP deliberately does not heal it: the
        pre-step state it restores is already corrupted)."""
        from ..framework.errors import InvalidArgumentError
        from ..profiler.flight_recorder import recorder as _flight

        leaf = fault.leaf
        if self._state is not None:
            params = self._state["params"]
            if leaf not in params:
                raise InvalidArgumentError(
                    f"corrupt_param leaf {leaf!r} not in the model's "
                    f"params (have e.g. {sorted(params)[:4]})")
            arr = params[leaf]
            idx = fault.element_index(int(np.prod(arr.shape)) or 1)
            flat = arr.reshape(-1).at[idx].set(jnp.nan)
            self._state = {**self._state,
                           "params": {**params,
                                      leaf: flat.reshape(arr.shape)}}
        else:
            target = dict(self.network.named_parameters()).get(leaf)
            if target is None:
                raise InvalidArgumentError(
                    f"corrupt_param leaf {leaf!r} not found among the "
                    "network's named parameters")
            arr = target._value
            idx = fault.element_index(int(np.prod(arr.shape)) or 1)
            target._value = arr.reshape(-1).at[idx].set(
                jnp.nan).reshape(arr.shape)
        _flight.on_transition("chaos.corrupt_param", leaf,
                              f"element {idx} set non-finite")

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_iters=None, _inside_fit=False):
        from ..io import DataLoader
        from ..io.dataset import Dataset

        if isinstance(eval_data, Dataset):
            loader = DataLoader(eval_data, batch_size=batch_size,
                                num_workers=num_workers)
        else:
            loader = eval_data
        for m in self._metrics:
            m.reset()
        losses = []
        for step, batch in enumerate(loader):
            if num_iters is not None and step >= num_iters:
                break
            x, y = batch[0], batch[1] if len(batch) > 1 else None
            outs = self.eval_batch([x], [y])
            if outs:
                losses.append(outs[0])
        logs = {}
        if losses:
            logs["loss"] = float(np.mean(losses))
        for m in self._metrics:
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = m.accumulate()
            vals = vals if isinstance(vals, list) else [vals]
            for n, v in zip(names, vals):
                logs[n] = v
        if verbose and not _inside_fit:
            print("Eval:", logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0, stack_outputs=False,
                callbacks=None, verbose=1):
        from ..io import DataLoader
        from ..io.dataset import Dataset

        if isinstance(test_data, Dataset):
            loader = DataLoader(test_data, batch_size=batch_size,
                                num_workers=num_workers)
        else:
            loader = test_data
        outputs = []
        for batch in loader:
            x = batch[0] if isinstance(batch, (list, tuple)) else batch
            outputs.append(self.predict_batch([x])[0])
        if stack_outputs:
            return [np.concatenate(outputs, axis=0)]
        return [outputs]

    # --- persistence ---------------------------------------------------------
    def save(self, path, training=True):
        from ..framework_io import save as _save

        self._writeback_state()
        _save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            _save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework_io import load as _load

        state = _load(path + ".pdparams")
        self.network.set_state_dict(state)
        self._state = None  # rebuild functional state from layer tensors
        self._train_step = None
        self._eval_fn = None
        import os

        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(path + ".pdopt"):
            self._optimizer.set_state_dict(_load(path + ".pdopt"))
        return self

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def state_dict(self):
        self._writeback_state()
        return self.network.state_dict()

    def _metric_names(self):
        names = []
        for m in self._metrics:
            n = m.name()
            names += n if isinstance(n, list) else [n]
        return names

    def summary(self, input_size=None, dtype=None):
        from .summary import summary as _summary

        return _summary(self.network, input_size, dtype)
