#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU.  It drives the system's main path once through the
entry points a user calls, on GPT-2-small at its published width (12
layers, hidden 768, 12 heads x 64, FFN 3072, vocab 50257; random weights
from a seed), and checks what comes out by the repo's own means:

  places   CPUPlace and TPUPlace(0) each reach a device of their platform.
  serve    Config.enable_serving -> create_serving_frontend ->
           start_http_server; six POST /generate requests (in concurrent
           pairs) over the default dispatch (unified ragged step,
           native KV); every engine token checked against the dense
           forward's LOGITS.  Then the KV pools' layout: the bytes they
           hold on the device against their logical bytes, and the
           unified step's optimised program, which may hold no pad, copy
           or transpose of a whole pool and must update each in place
           (the check a builder runs before the benchmark does).
  train    three AdamW steps at seq 2048, batch 4, bf16 autocast through
           paddle.Model.prepare/train_batch on one repeated batch.
  kernels  every entry of contracts.CONTRACTS compiled on the chip (never
           interpreted) at (H=12, D=64) and (H=16, D=128), page 16,
           against its XLA twin; then the hybrid models' mixers (PR 28):
           flash forward and backward at q/k 192, v 128 and the gated
           delta rule's two kernels (PR 34), forward and gradients,
           against the token-by-token recurrence.
  mesh     only with >= 4 devices visible: the same model and requests
           on ServingEngine(mesh_axes={"tp": 2, "sp": 2}), and the train
           phase's own job (same batch, s2048 b4 bf16, three steps)
           through make_sharded_train_step at dp=2 x mp=2, its losses
           held against the one-chip train phase's.

Every phase is a hard failure: nothing is caught and reported while the
run exits 0.  Without a TPU it exits non-zero and prints no result; it
has no interpret-mode and no XLA-reference route, and never sets
JAX_PLATFORMS.  Times printed here are information, not metrics.  The
last line of stdout is one JSON object naming the device as jax reports
it.
"""
from __future__ import annotations

import http.client
import json
import sys
import threading
import time

# GPT-2-small as published (Radford et al. 2019; HF `gpt2` config.json).
# `positions` is the one departure: the published table has 1024 rows; it
# is 2048 here so the repo's s2048 training job runs on the SAME weights
# the server just used — the engine itself is capped at the published
# 1024 (SERVE_MAX_SEQ_LEN).
GPT2_SMALL = dict(vocab_size=50257, hidden_size=768, num_layers=12,
                  num_heads=12, ffn_size=3072, max_seq_len=2048)
SERVE_MAX_SEQ_LEN = 1024
PAGE_SIZE = 16
PREFILL_CHUNK = 64
# (prompt length, new tokens): one short (< 16), several past the
# prefill chunk (several chunk rows), one >= 512; posted as three
# concurrent pairs.  The lengths leave chunk tails of 8 rows, so the
# engine compiles few (lane bucket, row bucket) programs.
REQUESTS = ((9, 16), (73, 24), (521, 32), (200, 16), (41, 32), (328, 24))
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 2048, 4, 3
KERNEL_SHAPES = ((12, 64), (16, 128))

# --- tolerances, each beside its reason ------------------------------------
# Engine token vs dense forward: both paths hold f32 operands, and at the
# MXU's DEFAULT precision an f32 matmul rounds its operands to bf16 (8
# mantissa bits, relative step 2^-8) before an f32 accumulate.  The two
# paths order the work differently (incremental paged attention vs one
# full causal forward), so their logits differ by a few such steps of the
# logit scale, and where the engine's argmax is not the dense argmax the
# dense logit of the engine's token falls short of the dense maximum by
# at most twice that.  2^-5 of the position's largest |logit| is eight
# steps; a token picked from wrong attention misses by the logit scale
# itself (dozens of times more).
LOGIT_MARGIN_REL = 2.0 ** -5
# Kernel vs XLA twin on f32 inputs, twin at HIGHEST precision: what is
# left is the kernel's own MXU rounding — Mosaic, too, feeds f32 operands
# to the MXU as bf16 (relative step 2^-8).  Judged against the twin's
# largest magnitude; 2^-6 is four such steps.  Measured on the v5e: 0.03%
# to 0.5% of that scale across the kernels (chip runs, PR 21).
KERNEL_RTOL = 2.0 ** -6
# Sharded (dp2 x mp2) vs one-chip training loss, step by step, on the same
# weights and batch: both run bf16 autocast, but the mp-split projections
# round their partial sums before the all-reduce and the dp halves reduce
# the batch in another order, and AdamW's normalised update carries that
# into the later steps.  Measured on the four-chip v5e host: 1e-5 of the
# loss at the third step (chip run, PR 21).  2^-10 of the loss (0.01 at
# 10.8) is a hundred times that and an eightieth of the 0.86 the loss
# falls over the three steps: a sharded step with wrong attention or wrong
# gradients does not follow the one-chip curve that closely.
SHARDED_LOSS_RTOL = 2.0 ** -10


class SmokeFailure(Exception):
    pass


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


class CompileClock:
    """Seconds jax spent in backend compilation (a persistent-cache read
    counts as its — much shorter — compile), and persistent-cache
    entries read / written (jax writes only compiles above its
    min-compile-time threshold), per phase.  Information for the
    cold-vs-cached comparison."""

    def __init__(self):
        import jax.monitoring as mon

        self.phase = "startup"
        self.seconds = {}
        self.hits = {}
        self.misses = {}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) + secs

    def _on_event(self, event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            self.hits[self.phase] = self.hits.get(self.phase, 0) + 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.misses[self.phase] = self.misses.get(self.phase, 0) + 1

    def report(self, phase, wall_s):
        print(f"[{phase}] info: wall {wall_s:.1f} s, of which compile "
              f"{self.seconds.get(phase, 0.0):.1f} s "
              f"(persistent cache: {self.hits.get(phase, 0)} read, "
              f"{self.misses.get(phase, 0)} written)", flush=True)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------
def build_model(seed=0):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTModel

    paddle.seed(seed)
    model = GPTModel(dropout=0.0, **GPT2_SMALL)
    model.eval()
    return model


def places_check():
    """A Place names a platform: on this host jax.devices() lists the TPU
    only, and CPUPlace must still reach the host's CPU device."""
    import paddle_tpu as paddle

    for place, platform in ((paddle.CPUPlace(), "cpu"),
                            (paddle.TPUPlace(0), "tpu")):
        held = {d.platform
                for d in paddle.to_tensor([1.0], place=place)._value.devices()}
        check(held == {platform},
              f"to_tensor(place={place!r}) landed on {held}")
    print("[places] PASS: CPUPlace -> cpu, TPUPlace(0) -> tpu", flush=True)


def make_prompts():
    import numpy as np

    rng = np.random.RandomState(21)
    return [rng.randint(1, GPT2_SMALL["vocab_size"], (n,)).astype(int).tolist()
            for n, _ in REQUESTS]


def make_dense_forward(model):
    """The dense full forward of the same model (GPTModel.forward: one
    causal pass, flash kernel at S >= 128), jitted once at a fixed padded
    length — trailing padding cannot reach earlier positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.jit.functional import functional_call, get_state

    params, buffers = get_state(model)
    pad_to = -(-max(p + n for p, n in REQUESTS) // 128) * 128

    @jax.jit
    def fwd(params, ids):
        out, _ = functional_call(model, params, buffers, (ids,),
                                 training=False)
        return out

    def dense_logits(tokens):
        ids = np.zeros((1, pad_to), np.int32)
        ids[0, :len(tokens)] = tokens
        return np.asarray(fwd(params, jnp.asarray(ids))[0, :len(tokens)],
                          np.float32)

    return dense_logits


def check_stream_against_dense(label, dense_logits, prompt, generated):
    """Teacher-force prompt + stream through the dense forward; at every
    generated position the engine's token must be within
    LOGIT_MARGIN_REL of the dense maximum.  Returns (worst shortfall /
    scale, positions where the argmax itself differed)."""
    import numpy as np

    check(np.all(np.asarray(generated) >= 0),
          f"{label}: negative token id in the stream")
    seq = list(prompt) + [int(t) for t in generated]
    logits = dense_logits(seq[:-1])
    check(np.all(np.isfinite(logits)), f"{label}: dense logits not finite")
    worst, flips = 0.0, 0
    for j, tok in enumerate(generated):
        row = logits[len(prompt) - 1 + j]
        scale = float(np.max(np.abs(row)))
        short = float(np.max(row) - row[int(tok)]) / scale
        flips += int(np.argmax(row) != int(tok))
        worst = max(worst, short)
        check(short <= LOGIT_MARGIN_REL,
              f"{label}: token {j} (id {int(tok)}) is {short:.4f} of the "
              f"logit scale below the dense maximum — margin "
              f"{LOGIT_MARGIN_REL:.4f}")
    return worst, flips


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def post_generate(port, prompt, max_new_tokens, out, key):
    """POST /generate, read the NDJSON stream to its terminal line."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", "/generate", body=json.dumps(
            {"prompt": prompt, "max_new_tokens": max_new_tokens}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        tokens, final = [], None
        for line in resp.read().decode().splitlines():
            ev = json.loads(line)
            if ev.get("restart"):
                tokens = []
            elif "token" in ev:
                tokens.append(ev["token"])
            elif ev.get("done"):
                final = ev
        out[key] = (resp.status, tokens, final)
    except Exception as e:  # noqa: BLE001 — re-raised by the caller's check
        out[key] = (None, [], {"error": f"{type(e).__name__}: {e}"})
    finally:
        conn.close()


def _bytes_in_use(device):
    return device.memory_stats()["bytes_in_use"]


def pool_layout_check(tag, engine, allocated, lanes, shards=(1, 1)):
    """The pools are stored in the layout the ragged kernel reads: they
    hold their logical bytes on the device (``allocated``: what building
    the engine added to the device, pools and lane state; None for a
    mesh engine, whose construction also places the weights; ``shards``
    = its (sp, tp)), and the unified step program, as this chip's
    compiler optimised it, rewrites no pool whole — no pad, copy or
    transpose of pool shape, every pool aliased to its output."""
    import jax

    from paddle_tpu.serving.engine import (aliased_arguments,
                                           whole_pool_relayouts)

    sp, tp = shards
    pools = jax.tree_util.tree_leaves(engine._kv)
    logical = engine.kv_cache_bytes() // (sp * tp)
    held = "not measured"
    if allocated is not None:
        check(allocated <= 1.02 * logical + 2 ** 24,
              f"{tag}: building the engine took {allocated} B of the "
              f"device for pools of {logical} logical B — the pools are "
              f"stored padded")
        held = f"{allocated} B (x{allocated / logical:.3f})"
    compiled = engine.lower_ragged_step(PREFILL_CHUNK, lanes=lanes).compile()
    text = compiled.as_text()
    found = whole_pool_relayouts(text, engine.cache.num_pages // sp,
                                 PAGE_SIZE)
    check(not found, f"{tag}: the unified step program rewrites whole KV "
          f"pools: {sorted(set(found))} x{len(found)}")
    aliased = aliased_arguments(text)
    check(aliased == len(pools), f"{tag}: {aliased} of {len(pools)} pool "
          f"arguments are aliased to an output")
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"[{tag}] PASS: {len(pools)} pools of {tuple(pools[0].shape)} "
          f"{pools[0].dtype}, {logical} logical B a device, held on the "
          f"device: {held}; unified step at "
          f"{lanes} x {PREFILL_CHUNK} rows: no pad/copy/transpose of "
          f"pool shape, {aliased}/{len(pools)} pools updated in place, "
          f"{temp} B of temporaries (info: the logits among them)",
          flush=True)


def serve_phase(model, dense_logits):
    import jax

    from paddle_tpu.inference import Config
    from paddle_tpu.ops.pallas_ops.paged_attention import PAGED_ROUTE_STATS
    from paddle_tpu.serving import (create_serving_frontend,
                                    start_http_server)

    prompts = make_prompts()
    routes0 = dict(PAGED_ROUTE_STATS)
    cfg = Config()
    # eos_id=-1: random weights may emit any id — every request must run
    # its full budget
    cfg.enable_serving(max_batch_size=8, page_size=PAGE_SIZE,
                       max_seq_len=SERVE_MAX_SEQ_LEN, eos_id=-1,
                       prefill_chunk=PREFILL_CHUNK, replicas=1)
    device = jax.devices()[0]
    before = _bytes_in_use(device)
    frontend = create_serving_frontend(model, cfg)
    allocated = _bytes_in_use(device) - before
    server = start_http_server(frontend, port=0)
    results = {}
    try:
        for first in range(0, len(REQUESTS), 2):
            pair = [threading.Thread(target=post_generate,
                                     args=(server.port, prompts[i],
                                           REQUESTS[i][1], results, i))
                    for i in (first, first + 1)]
            for t in pair:
                t.start()
            for t in pair:
                t.join()

        # see through the frontend's crash containment: a killed replica
        # fails the smoke with the reason it carries
        for rep in frontend._replicas:
            check(not rep.dead_reason,
                  f"replica {rep.id} was killed: {rep.dead_reason}")
        for i, (plen, budget) in enumerate(REQUESTS):
            status, tokens, final = results[i]
            check(status == 200 and final is not None
                  and final.get("status") == "completed",
                  f"request {i} (prompt {plen}): HTTP {status}, {final}")
            check(len(tokens) == budget == final["num_tokens"],
                  f"request {i}: {len(tokens)} tokens of a budget of "
                  f"{budget}")
        health = frontend.health()
        stats = frontend.stats()
        fe, eng = stats["frontend"], stats["engines"]
        check(health["healthy_replicas"] == 1 == len(frontend._replicas),
              f"healthy_replicas {health['healthy_replicas']} of 1")
        check(fe["retries"] == 0 and fe["failures"] == 0,
              f"frontend retries {fe['retries']}, failures "
              f"{fe['failures']}")
        check(eng["restores"] == 0 and eng["watchdog_trips"] == 0,
              f"failover restores {eng['restores']}, watchdog trips "
              f"{eng['watchdog_trips']}")
        engine = frontend._replicas[0].engine
        check(engine.cache.pages_in_use == 0,
              f"{engine.cache.pages_in_use} KV pages leaked")
        pallas = PAGED_ROUTE_STATS["pallas"] - routes0["pallas"]
        xla = PAGED_ROUTE_STATS["xla"] - routes0["xla"]
        check(pallas > 0 and xla == 0,
              f"paged attention routes: pallas {pallas}, xla {xla}")
        ragged = engine.stats()["pipeline"]["ragged"]
        check(ragged is True, "the engine did not run the unified step")
        # requests ran in pairs: the two-lane bucket is the one compiled
        pool_layout_check("serve", engine, allocated, lanes=2)
    finally:
        server.stop()
        frontend.close()

    streams = {}
    worst, flips, total = 0.0, 0, 0
    for i in range(len(REQUESTS)):
        streams[i] = results[i][1]
        w, f = check_stream_against_dense(
            f"serve request {i}", dense_logits, prompts[i], streams[i])
        worst, flips, total = max(worst, w), flips + f, total + len(streams[i])
    print(f"[serve] PASS: {len(REQUESTS)} requests completed with full "
          f"budgets ({total} tokens), 1/1 replicas healthy, 0 retries / "
          f"failovers / watchdog trips, 0 pages in use, paged routes "
          f"pallas={pallas} xla={xla}", flush=True)
    print(f"[serve] PASS: every token within {LOGIT_MARGIN_REL:.4f} of the "
          f"logit scale of the dense maximum (worst {worst:.5f}; "
          f"{flips}/{total} positions where the argmax itself differed)",
          flush=True)
    return prompts, streams


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def make_train_batch():
    import numpy as np

    rng = np.random.RandomState(0)
    toks = rng.randint(0, GPT2_SMALL["vocab_size"],
                       (TRAIN_BATCH, TRAIN_SEQ + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def lm_loss(out, y):
    import paddle_tpu.nn.functional as F

    return F.cross_entropy(out.reshape([-1, GPT2_SMALL["vocab_size"]]),
                           y.reshape([-1]))


def make_adamw(model):
    from paddle_tpu import optimizer

    return optimizer.AdamW(learning_rate=6e-4, weight_decay=0.1,
                           parameters=model.parameters())


def train_phase(model, sharded_losses=None):
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.ops import attention as attn_mod

    layers = GPT2_SMALL["num_layers"]
    model.train()
    trainer = paddle.Model(model)
    trainer.prepare(optimizer=make_adamw(model), loss=lm_loss)
    x, y = make_train_batch()

    routes0 = dict(attn_mod.ROUTE_STATS)
    losses, step_s = [], []
    for _ in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        # the autocast region is read while the step traces (first call)
        with paddle.amp.auto_cast(dtype="bfloat16"):
            loss = trainer.train_batch([x], [y])[0]
        # train_batch fetched the loss; close the step on the updated
        # train state as well
        jax.block_until_ready(trainer._state)
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    pallas = attn_mod.ROUTE_STATS["pallas"] - routes0["pallas"]
    xla = attn_mod.ROUTE_STATS["xla"] - routes0["xla"]
    check(pallas >= layers and xla == 0,
          f"attention routes per trace: pallas {pallas}, xla {xla} "
          f"(need >= {layers} flash hits and no XLA attention)")
    check(all(np.isfinite(v) for v in losses), f"losses {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    print(f"[train] PASS: {TRAIN_STEPS} steps b{TRAIN_BATCH} s{TRAIN_SEQ} "
          f"bf16 AdamW, losses {[round(v, 4) for v in losses]}, flash "
          f"route hits per trace {pallas}, XLA attention {xla}", flush=True)
    print(f"[train] info: step wall seconds (first includes compile) "
          f"{[round(s, 3) for s in step_s]}", flush=True)
    if sharded_losses is not None:
        gaps = [abs(a - b) / abs(b) for a, b in zip(sharded_losses, losses)]
        check(max(gaps) <= SHARDED_LOSS_RTOL,
              f"dp2 x mp2 losses {sharded_losses} leave the one-chip "
              f"losses {losses} by {max(gaps):.5f} of the loss — margin "
              f"{SHARDED_LOSS_RTOL:.5f}")
        print(f"[train] PASS: the dp=2 x mp=2 losses follow these within "
              f"{SHARDED_LOSS_RTOL:.5f} of the loss (per step "
              f"{[round(g, 6) for g in gaps]})", flush=True)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _first_line(exc):
    text = str(exc).strip() or type(exc).__name__
    return text.splitlines()[0][:200]


def _err_and_scale(got, want):
    """(max |got - want|, max |want|) over the outputs.  The stats
    form's lse marks rows with nothing visible with the NEG_INF sentinel:
    those must be equal exactly and stay out of the scale."""
    import jax
    import numpy as np

    err = scale = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        sentinel = w < -1e29
        if not np.array_equal(g[sentinel], w[sentinel]):
            return float("inf"), 1.0
        err = max(err, float(np.max(np.abs(g - w)[~sentinel])))
        scale = max(scale, float(np.max(np.abs(w[~sentinel]))))
    return err, scale


def kernels_phase():
    import jax

    from paddle_tpu.ops.pallas_ops.cases import (grouped_cases,
                                                 kernel_cases, mixer_cases,
                                                 serve_cell_case)

    lines, failed = 0, []

    def line(where, label, kernel, twin, args):
        head = f"[kernels] {where} {label:<32}"
        # the twin is the reference: full f32 precision
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(twin)(*args))
        t0 = time.perf_counter()
        try:
            got = jax.block_until_ready(jax.jit(kernel)(*args))
        except Exception as e:  # noqa: BLE001 — verdict line, judged below
            print(f"{head} REFUSED  {_first_line(e)}", flush=True)
            failed.append(f"{label} ({where}) refused")
            return
        err, scale = _err_and_scale(got, want)
        ok = err <= KERNEL_RTOL * scale
        print(f"{head} compiled {'matches' if ok else 'MISMATCH'} XLA "
              f"twin: max abs err {err:.3e} = {err / scale:.2e} of its "
              f"scale (tol {KERNEL_RTOL:.2e}); info: compile+run "
              f"{time.perf_counter() - t0:.2f} s", flush=True)
        if not ok:
            failed.append(f"{label} ({where}) mismatch")

    for H, D in KERNEL_SHAPES:
        # every CONTRACTS entry, each form it governs (the table refuses
        # to build while a contract has no case); kernels are called
        # with interpret=False or decide from jax.default_backend(),
        # which main() has already required to be "tpu"
        for _, label, kernel, twin, args in kernel_cases(H, D):
            lines += 1
            line(f"H={H:<2} D={D:<3}", label, kernel, twin, args)
    # the ragged kernel as the long-prompt serve cell dispatches it: 48
    # lanes x 64 rows over 64-page tables, 39 of them one-row decode lanes
    _, label, kernel, twin, args = serve_cell_case()
    lines += 1
    line("H=12 D=64 ", label, kernel, twin, args)
    # the hybrid models' mixers at their own head sizes: flash at q/k
    # 192, v 128 and the delta rule's kernels at 128
    for _, label, kernel, twin, args in mixer_cases():
        lines += 1
        line("mixers    ", label, kernel, twin, args)
    # the three flash kernels with 32 query heads over 8 KV heads of 64
    # (grouped-query attention): K and V are read in place, dk/dv summed
    # over each group inside the kernel
    for _, label, kernel, twin, args in grouped_cases():
        lines += 1
        line("grouped   ", label, kernel, twin, args)
    # a kernel may be left refused only while the option selecting it is
    # refused at engine construction; this tree leaves none, so any
    # refusal or mismatch fails the run
    check(not failed, f"kernels failed: {failed}")
    print(f"[kernels] PASS: {lines} kernel/shape lines compiled on the "
          f"chip and match their XLA twins", flush=True)


# ---------------------------------------------------------------------------
# mesh (four devices)
# ---------------------------------------------------------------------------
def _assert_spread(label, array, n_devices):
    shards = array.addressable_shards
    devices = {s.device for s in shards}
    check(len(devices) == n_devices,
          f"{label}: lives on {len(devices)} device(s), wanted {n_devices}")
    per = [int(s.data.size) for s in shards]
    check(max(per) < array.size,
          f"{label}: every device holds the whole array (not sharded)")
    return per


def mesh_serve_phase(model, dense_logits, prompts, one_chip_streams):
    import jax
    import numpy as np

    from paddle_tpu.inference import Config
    from paddle_tpu.ops.pallas_ops.paged_attention import PAGED_ROUTE_STATS
    from paddle_tpu.serving import create_serving_engine

    devices = jax.devices()[:4]
    routes0 = dict(PAGED_ROUTE_STATS)
    cfg = Config()
    cfg.enable_serving(max_batch_size=8, page_size=PAGE_SIZE,
                       max_seq_len=SERVE_MAX_SEQ_LEN, eos_id=-1,
                       prefill_chunk=PREFILL_CHUNK)
    engine = create_serving_engine(model, cfg,
                                   mesh_axes={"tp": 2, "sp": 2})
    rids = [engine.add_request(np.asarray(p, np.int32),
                               max_new_tokens=REQUESTS[i][1])
            for i, p in enumerate(prompts)]
    outs = engine.drain()
    check(engine.cache.pages_in_use == 0,
          f"mesh engine leaked {engine.cache.pages_in_use} pages")
    pallas = PAGED_ROUTE_STATS["pallas"] - routes0["pallas"]
    xla = PAGED_ROUTE_STATS["xla"] - routes0["xla"]
    check(pallas > 0 and xla == 0,
          f"mesh paged routes: pallas {pallas}, xla {xla}")
    worst, same, total = 0.0, 0, 0
    for i, rid in enumerate(rids):
        toks = [int(t) for t in outs[rid]]
        check(len(toks) == REQUESTS[i][1],
              f"mesh request {i}: {len(toks)} of {REQUESTS[i][1]} tokens")
        w, _ = check_stream_against_dense(
            f"mesh request {i}", dense_logits, prompts[i], toks)
        worst = max(worst, w)
        same += sum(a == b for a, b in zip(toks, one_chip_streams[i]))
        total += len(toks)
    pool = _assert_spread("KV pool k[0]", engine._kv["k"][0], 4)
    mem = [_bytes_in_use(d) for d in devices]
    check(all(m > 0 for m in mem), f"device memory in use: {mem}")
    print(f"[mesh] PASS: tp=2 x sp=2 engine served {len(rids)} requests, "
          f"every token within {LOGIT_MARGIN_REL:.4f} of the dense maximum "
          f"(worst {worst:.5f}); {same}/{total} tokens equal the one-chip "
          f"streams; paged routes pallas={pallas} xla={xla}", flush=True)
    print(f"[mesh] PASS: KV pool shards (elements per device) {pool}; "
          f"bytes in use per device {mem}", flush=True)
    # all six requests were admitted at once: the eight-lane bucket
    pool_layout_check("mesh", engine, None, lanes=8, shards=(2, 2))


def mesh_train_phase(model):
    """The train phase's own job — same weights, batch, length, autocast
    and optimizer — as GSPMD steps at dp=2 x mp=2: parameters carry their
    'mp' partition specs, the batch is split over 'dp', and the flash
    kernel is split over both under shard_map (XLA cannot partition a
    Mosaic call itself).  Returns the losses; train_phase holds them
    against the one-chip run's."""
    import warnings

    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import init_mesh
    from paddle_tpu.distributed.parallel import make_sharded_train_step
    from paddle_tpu.ops import attention as attn_mod

    layers = GPT2_SMALL["num_layers"]
    mesh = init_mesh({"dp": 2, "mp": 2}, devices=jax.devices()[:4])
    model.train()
    # donate=False: device_put may alias the model's own buffers into the
    # sharded state, and [train] still needs them
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        step, state = make_sharded_train_step(model, lm_loss,
                                              make_adamw(model), mesh=mesh,
                                              donate=False)
    # the published vocabulary (50257) is odd: mp=2 cannot row-split the
    # embedding, and the step says so instead of failing or padding it
    whole = [str(w.message) for w in caught if "wte.weight" in str(w.message)]
    check(len(whole) == 1, f"expected one warning that wte.weight stays "
          f"whole, got {[str(w.message) for w in caught]}")
    x, y = make_train_batch()
    routes0 = dict(attn_mod.ROUTE_STATS)
    losses, step_s = [], []
    for _ in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        with paddle.amp.auto_cast(dtype="bfloat16"):
            state, loss = step(state, x, y)
        jax.block_until_ready(state)
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    pallas = attn_mod.ROUTE_STATS["pallas"] - routes0["pallas"]
    xla = attn_mod.ROUTE_STATS["xla"] - routes0["xla"]
    check(pallas >= layers and xla == 0,
          f"sharded step attention routes per trace: pallas {pallas}, xla "
          f"{xla} (need >= {layers} flash hits and no XLA attention)")
    check(all(np.isfinite(v) for v in losses), f"sharded losses {losses}")
    check(losses[-1] < losses[0],
          f"sharded loss did not fall on a repeated batch: {losses}")
    weight = _assert_spread("layers.0.fc1.weight",
                            state["params"]["layers.0.fc1.weight"], 4)
    wte = state["params"]["wte.weight"]
    check(len(wte.addressable_shards) == 4
          and all(s.data.size == wte.size for s in wte.addressable_shards),
          "wte.weight is not held whole on each of the four devices")
    print(f"[mesh] PASS: make_sharded_train_step dp=2 x mp=2 took "
          f"{TRAIN_STEPS} steps b{TRAIN_BATCH} s{TRAIN_SEQ} bf16 AdamW, "
          f"losses {[round(v, 4) for v in losses]}, flash route hits per "
          f"trace {pallas}, XLA attention {xla}; fc1.weight shards "
          f"(elements per device) {weight}; wte.weight whole on each "
          f"device ({whole[0]})", flush=True)
    print(f"[mesh] info: sharded step wall seconds (first includes "
          f"compile) {[round(s, 3) for s in step_s]}", flush=True)
    return losses


# ---------------------------------------------------------------------------
def main():
    import jax

    import paddle_tpu  # noqa: F401 — places the compile cache, no backend yet

    clock = CompileClock()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}; "
          f"compile cache at "
          f"{jax.config.jax_compilation_cache_dir!r}", flush=True)
    if jax.default_backend() != "tpu":
        print("chip_smoke: no TPU found — this check runs on the chip only "
              "(tests/ cover the CPU)", file=sys.stderr)
        return 1

    def run(phase, fn, *args):
        clock.phase = phase
        t0 = time.perf_counter()
        out = fn(*args)
        clock.report(phase, time.perf_counter() - t0)
        return out

    places_check()
    model = build_model()
    dense_logits = make_dense_forward(model)
    prompts, streams = run("serve", serve_phase, model, dense_logits)
    sharded_losses = None
    if len(devices) >= 4:
        run("mesh-serve", mesh_serve_phase, model, dense_logits, prompts,
            streams)
        sharded_losses = run("mesh-train", mesh_train_phase, model)
    else:
        print(f"[mesh] not run: {len(devices)} device(s) visible, the "
              f"tp=2 x sp=2 and dp=2 x mp=2 phases need 4", flush=True)
    run("train", train_phase, model, sharded_losses)
    run("kernels", kernels_phase)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        sys.exit(1)
