"""The program's GPTModel built from a configuration file and given the
benchmark's seeded weights — the same arrays the reference gets."""
from __future__ import annotations

import os
import sys

from .manifest import BENCH_DIR, load_module
from .weights import make_weights


def reference():
    return load_module(os.path.join(BENCH_DIR, "reference"), "gpt2")


def weight_seed(config, seed):
    """The configuration's fixed `weight_seed` where it states one (see
    the serve configuration for why), else --seed."""
    fixed = config.get("weight_seed")
    return int(seed) if fixed is None else int(fixed)


def build(config, seed):
    """(model, weights): paddle_tpu's GPTModel at the configuration's
    sizes, its parameters replaced by make_weights(seed)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTModel

    shapes = reference().param_shapes(config)
    paddle.seed(int(seed) % (2 ** 31 - 1))
    model = GPTModel(vocab_size=config["vocab_size"],
                     hidden_size=config["n_embd"],
                     num_layers=config["n_layer"], num_heads=config["n_head"],
                     ffn_size=config.get("n_inner") or 4 * config["n_embd"],
                     max_seq_len=config["n_positions"],
                     dropout=config.get("training", {}).get(
                         "dropout", config["resid_pdrop"]))
    have = {n: tuple(p.shape) for n, p in model.named_parameters()}
    if have != {n: tuple(s) for n, s in shapes.items()}:
        odd = sorted(set(have.items()) ^ set(
            (n, tuple(s)) for n, s in shapes.items()))[:6]
        print(f"perfbench: the program's model is not the configured "
              f"architecture: {odd}", file=sys.stderr)
        raise SystemExit(4)
    weights = make_weights(shapes, weight_seed(config, seed))
    for n, p in model.named_parameters():
        p._value = weights[n]
    return model, weights
