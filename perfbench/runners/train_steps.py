"""Runner for traffic of kind `train_steps`: a training job that takes one
fresh seeded batch per step from a host generator running beside it.

The configuration's `training.path` picks the program's entry point:
`hapi` is paddle.Model.prepare/train_batch on one chip, `sharded` is
distributed.make_sharded_train_step on the mesh the configuration states.
A step is complete when the host holds its loss.
"""
from __future__ import annotations

import math
import os
import queue
import threading
import time

import numpy as np

from perfbench.harness import device
from perfbench.harness import trace as trace_mod
from perfbench.harness.model import build


def lm_loss_fn(vocab):
    def lm_loss(out, y):
        import paddle_tpu.nn.functional as F

        return F.cross_entropy(out.reshape([-1, vocab]), y.reshape([-1]))

    return lm_loss


def make_optimizer(spec, model):
    from paddle_tpu import optimizer

    return getattr(optimizer, spec["name"])(
        learning_rate=spec["learning_rate"],
        weight_decay=spec["weight_decay"], parameters=model.parameters())


class BatchSource:
    """A host thread that draws [sequences, seq_len + 1] token ids per
    step from --seed and keeps two batches ahead of the trainer."""

    def __init__(self, seed, sequences, seq_len, vocab):
        self.rng = np.random.default_rng([int(seed), 31])
        self.shape = (sequences, seq_len + 1)
        self.vocab = vocab
        self.q = queue.Queue(maxsize=2)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True,
                                        name="perfbench-batches")
        self._thread.start()

    def draw(self):
        toks = self.rng.integers(0, self.vocab, size=self.shape,
                                 dtype=np.int32)
        return toks[:, :-1], toks[:, 1:]

    def _fill(self):
        while not self._stop.is_set():
            batch = self.draw()
            while not self._stop.is_set():
                try:
                    self.q.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    pass

    def next(self):
        return self.q.get()

    def close(self):
        self._stop.set()
        self._thread.join()


def make_stepper(job, model):
    """step(x, y) -> float loss, through the configured entry point."""
    import jax

    import paddle_tpu as paddle

    training = job.config["training"]
    loss_fn = lm_loss_fn(job.config["vocab_size"])
    autocast = training["autocast"]
    model.train()
    if training["path"] == "hapi":
        trainer = paddle.Model(model)
        trainer.prepare(optimizer=make_optimizer(training["optimizer"],
                                                 model), loss=loss_fn)

        def step(x, y):
            with paddle.amp.auto_cast(dtype=autocast):
                return float(trainer.train_batch([x], [y])[0])

        return step
    if training["path"] == "sharded":
        import warnings

        from paddle_tpu.distributed import init_mesh
        from paddle_tpu.distributed.parallel import make_sharded_train_step

        axes = training["mesh"]
        mesh = init_mesh(dict(axes), devices=job.devices)
        with warnings.catch_warnings():
            # the published vocabulary is odd: the step says that
            # wte.weight stays whole on every device
            warnings.simplefilter("ignore")
            run, state = make_sharded_train_step(
                model, loss_fn, make_optimizer(training["optimizer"], model),
                mesh=mesh)
        # the model's own unsharded arrays are not needed again: let the
        # parameters point at the placed copies so device 0 holds one set
        for n, p in model.named_parameters():
            p._value = state["params"][n]
        box = [state]

        def step(x, y):
            with paddle.amp.auto_cast(dtype=autocast):
                box[0], loss = run(box[0], x, y)
            return float(jax.device_get(loss))

        return step
    raise ValueError(f"unknown training.path {training['path']!r}")


def teacher_batch(job, weights, x):
    """The check batch's labels and the reference's loss on them: labels
    are the reference's own argmax at every position (see the reference's
    tolerance file for why), so the loss is lse - max logit."""
    import jax
    import jax.numpy as jnp

    cfg = job.config
    ref = job.manifest.reference(cfg)

    def teach(w, batch):
        def one(ids):
            logits = ref.forward(w, ids, cfg)
            top = jnp.max(logits, axis=-1)
            lse = jax.nn.logsumexp(logits, axis=-1)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), \
                jnp.mean(lse - top)

        return jax.lax.map(one, batch)

    # the weights are an argument: closed over, they would be constants of
    # the executable (gigabytes, and a new cache key for every seed)
    labels, losses = jax.jit(teach)(weights, jnp.asarray(x))
    return np.asarray(labels), float(jnp.mean(losses))


def run(job):
    traffic, cfg = job.traffic, job.config
    seq_len = int(traffic["seq_len"])
    model, weights = build(job.manifest, cfg, job.seed)
    job.clock.phase("model + weights")
    rtol = job.manifest.tolerance(cfg)["train_loss_rtol"]

    mesh = cfg["training"].get("mesh", {})
    sequences = int(traffic["sequences_per_replica"]) * int(mesh.get("dp", 1))
    source = BatchSource(job.seed, sequences, seq_len, cfg["vocab_size"])
    try:
        # the check batch, judged by the reference on the initial weights
        x0, _ = source.next()
        y0, ref_loss = teacher_batch(job, weights, x0)
        del weights
        job.clock.phase("reference check batch")
        step = make_stepper(job, model)
        loss0 = step(x0, y0)
        job.clock.phase("first step (compiles)")
        losses = [loss0]
        for _ in range(int(traffic.get("warm_steps", 3))):
            losses.append(step(*source.next()))
        job.clock.phase("warm steps")

        tokens_per_step = sequences * seq_len
        span = min(float(traffic.get("trace_s", 3.0)), job.seconds / 2)
        compiles = job.counter.mark()
        obs = {}
        stop_trace = None
        tracing = False
        t_start = time.perf_counter()
        t_end = t_start + job.seconds
        t_trace = t_start + (job.seconds - span) / 2 if job.trace else None
        steps = 0
        rate_steps, rate_end = None, None
        while True:
            x, y = source.next()
            loss = step(x, y)
            now = time.perf_counter()
            steps += 1
            losses.append(loss)
            if now >= t_end:
                # the window closes with the step in flight at its end, so
                # the rate is whole steps over their own time and does not
                # jump by one step's worth from run to run
                t_end = now
                break
            if t_trace is not None and stop_trace is None and now >= t_trace:
                # starting and stopping the profiler stalls the loop: a
                # traced run takes its rate from the steps before it
                rate_steps, rate_end = steps, now
                stop_trace = trace_mod.capture(os.path.join(
                    job.manifest.out_dir(job.cell["name"]), "trace"))
                tracing = True
                t_trace = time.perf_counter()
            elif tracing and now >= t_trace + span:
                tracing = False
                obs["trace_path"] = stop_trace()
        if tracing:
            obs["trace_path"] = stop_trace()
        obs["compiles_in_window"] = job.counter.since(compiles)
        obs["memory_peak_bytes"] = device.peak_bytes(job.devices)
        obs["window_start_perf"] = t_start
    finally:
        source.close()

    gap = abs(loss0 - ref_loss) / abs(ref_loss)
    nonfinite = sum(not math.isfinite(v) for v in losses)
    if rate_steps is None:
        rate_steps, rate_end = steps, t_end
    train_tok_s = rate_steps * tokens_per_step / (rate_end - t_start)
    return {
        "correct": bool(gap <= rtol and not nonfinite and steps > 0),
        "attempted": steps, "failed": 1 if nonfinite else 0,
        "end_to_end": {"train_tok_s": train_tok_s},
        "values": {"train_tok_s": train_tok_s,
                   "tokens_per_step": tokens_per_step,
                   "seq_len": seq_len, "sequences": sequences,
                   "sequences_per_chip": int(traffic["sequences_per_replica"])},
        "obs": obs,
        "checks": {"loss_gap_rel": {"value": gap, "limit": rtol},
                   "nonfinite_losses": {"value": nonfinite, "limit": 0}},
        "detail": {"first_loss": loss0, "reference_loss": ref_loss,
                   "loss_gap_rel": gap, "loss_rtol": rtol, "steps": steps,
                   "last_loss": losses[-1]},
    }
