"""Weights made on the device from a seed, in one jitted call.

One `jax.random.normal` per distinct shape (all leaves of that shape in
one array), not one per leaf and nothing on the host: a GPT-2 has ~10
shape classes whatever its depth, so the program is small, compiles once
and is served from the persistent cache afterwards.  Every leaf is
N(0, 0.02) — LayerNorm gains are 1 + that, so no parameter is a constant
the check could not see — in float32, the type both the server and the
trainer hold them in.
"""
from __future__ import annotations

import numpy as np

INIT_STD = 0.02


def key_data(seed, stream):
    """Two uint32 words from any whole-number seed (the driver's are
    larger than 2**31) and a stream id."""
    return np.random.SeedSequence([int(seed), int(stream)]
                                  ).generate_state(2).astype(np.uint32)


def make_weights(shapes, seed, std=INIT_STD):
    """name -> float32 device array for `shapes` (name -> shape)."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)
    classes = {}
    for n in names:
        classes.setdefault(tuple(shapes[n]), []).append(n)
    class_list = sorted(classes.items())

    def init(kd):
        key = jax.random.wrap_key_data(kd, impl="threefry2x32")
        out = {}
        for i, (shape, members) in enumerate(class_list):
            block = std * jax.random.normal(
                jax.random.fold_in(key, i), (len(members),) + shape,
                jnp.float32)
            for j, n in enumerate(members):
                gain = n.endswith(".weight") and len(shape) == 1
                out[n] = block[j] + 1.0 if gain else block[j]
        return out

    return jax.jit(init)(jnp.asarray(key_data(seed, 0)))
