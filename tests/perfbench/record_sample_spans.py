#!/usr/bin/env python3
"""Records the second small trace the reduction's tests read
(tests/perfbench/sample_spans.xplane.pb): a small GPT behind the program's
own ServingEngine for a dozen RecordEvent-wrapped steps (admissions,
prefill chunks beside decode rows, a request that waits for a lane), then
three paddle.Model.train_batch steps of a small MLP — all inside the
benchmark's window annotation, every program compiled before the trace
starts.  Run once on the chip:

    chiprun -- python3 tests/perfbench/record_sample_spans.py chiprun_out/sample
"""
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

# the sample's model, as the tests hand it to the reducers
CONFIG = {"n_embd": 128, "n_head": 2, "n_layer": 2, "vocab_size": 512,
          "n_positions": 256,
          "serving": {"enable_serving": {
              "max_batch_size": 4, "page_size": 16, "prefill_chunk": 16,
              "eos_id": -1}}}
PROMPTS = (40, 21, 9, 33, 12)       # five requests on four lanes
NEW_TOKENS = 6
TRAIN_STEPS = 3


def serve_round(engine, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    for i, n in enumerate(PROMPTS):
        engine.add_request(
            rng.integers(1, CONFIG["vocab_size"], size=n).astype(np.int32),
            max_new_tokens=NEW_TOKENS)
        if i == 2:
            engine.step()
            engine.step()
    return engine.drain()


def record(out_dir):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models import GPTModel

    from perfbench.harness import trace as T

    paddle.seed(11)
    gpt = GPTModel(vocab_size=CONFIG["vocab_size"],
                   hidden_size=CONFIG["n_embd"],
                   num_layers=CONFIG["n_layer"], num_heads=CONFIG["n_head"],
                   ffn_size=4 * CONFIG["n_embd"],
                   max_seq_len=CONFIG["n_positions"], dropout=0.0)
    gpt.eval()
    engine = ServingEngine(gpt, bucket_sizes=[4],
                           **CONFIG["serving"]["enable_serving"])
    net = nn.Sequential(nn.Linear(256, 256), nn.ReLU(), nn.Linear(256, 8))
    trainer = paddle.Model(net)
    trainer.prepare(optimizer.SGD(0.01, parameters=net.parameters()),
                    nn.MSELoss())
    x = np.ones((64, 256), np.float32)
    y = np.zeros((64, 8), np.float32)

    # every program compiles here: the same shapes in the same order
    serve_round(engine, 1)
    for _ in range(2):
        trainer.train_batch([x], [y])

    log = os.path.join(out_dir, "log")
    stop = T.capture(log)
    outs = serve_round(engine, 2)
    for _ in range(TRAIN_STEPS):
        trainer.train_batch([x], [y])
    path = stop()
    assert len(outs) == len(PROMPTS) and engine.cache.pages_in_use == 0
    dest = os.path.join(out_dir, "sample_spans.xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(log)
    return dest


def main(out_dir):
    import jax

    assert jax.devices()[0].platform == "tpu", "records on the chip only"
    dest = record(out_dir)
    print(dest, os.path.getsize(dest))


if __name__ == "__main__":
    main(sys.argv[1])
