"""The program's model built from a configuration file and given the
benchmark's seeded weights — the same arrays the reference gets.  Which
model and which reference is the configuration's `model_type`
(manifest.py); nothing here knows an architecture."""
from __future__ import annotations

import sys

from .weights import weight_groups, weights_dtype


def weight_seed(config, seed):
    """The configuration's fixed `weight_seed` where it states one (see
    the serve configuration for why), else --seed."""
    fixed = config.get("weight_seed")
    return int(seed) if fixed is None else int(fixed)


def _refuse(what, odd):
    print(f"perfbench: the program's model is not the configured "
          f"architecture ({what}): {sorted(odd)[:6]}", file=sys.stderr)
    raise SystemExit(4)


def build(manifest, config, seed):
    """(model, weights): the program's model at the configuration's sizes
    and in its `weights_dtype`, every parameter replaced in place by the
    seeded array of the shape the reference states.  The weights land a
    group at a time and each leaf is installed as its group lands, which
    releases the constructor's leaf: the peak is the model plus one group,
    never two copies.  `weights` names the installed buffers themselves."""
    import jax.numpy as jnp
    import paddle_tpu as paddle

    dtype = weights_dtype(config)
    shapes = {n: tuple(s) for n, s in
              manifest.reference(config).param_shapes(config).items()}
    paddle.seed(int(seed) % (2 ** 31 - 1))
    model = manifest.model(config).construct(config)
    params = dict(model.named_parameters())
    have = {n: tuple(p.shape) for n, p in params.items()}
    if have != shapes:
        _refuse("shapes", set(have.items()) ^ set(shapes.items()))
    held = {n: jnp.dtype(p._value.dtype).name for n, p in params.items()}
    odd = {(n, d) for n, d in held.items() if d != dtype}
    if odd:
        _refuse(f"weights_dtype is {dtype}", odd)
    weights = {}
    for group in weight_groups(shapes, weight_seed(config, seed), dtype):
        for n, leaf in group.items():
            params[n]._value = leaf
        weights.update(group)
    return model, weights
