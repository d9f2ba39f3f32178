"""Weights made on the device from a seed, group by group.

One `jax.random.normal` per distinct shape (all leaves of that shape in
one array), not one per leaf and nothing on the host: a GPT-2 has ~10
shape classes whatever its depth, so the programs are few and small,
compile once and are served from the persistent cache afterwards.  Every
leaf is N(0, 0.02) — LayerNorm gains are 1 + that, so no parameter is a
constant the check could not see.

A group is what one jitted call draws.  A configuration whose whole
float32 draw is within WHOLE_BYTES — every configuration the benchmark
has — is ONE group, one program, as it was before groups existed: a
program costs half a second of set-up from the cache and seconds to
compile whatever it draws.  Only a larger one is drawn in bounded groups:
as many whole shape classes, taken in order, as fit GROUP_BYTES of
float32 together, or a run of the members of one class that is over the
bound (a leaf over the bound is a group of its own).  Whoever installs
the weights takes them a group at a time (`weight_groups`), so no more
than one group is ever alive beside the model that receives them.

The type is the configuration's `weights_dtype`: float32 where it states
none — the type the trainer's masters and the float32 server hold — or
bfloat16.  Values are drawn in float32 either way and rounded once inside
the call that draws them.  **The plain reference is handed these stored
arrays and computes in float32 at `highest` from them** (every reference
upcasts each leaf on entry), so the program and the reference start from
the same numbers whatever the stored type.
"""
from __future__ import annotations

import math
import sys

import numpy as np

INIT_STD = 0.02
# float32 bytes of the largest block one call draws: no lower than the
# largest shape class of any configuration the benchmark has (0.875 GiB),
# so each of their classes is still one block drawn as it always was
GROUP_BYTES = 1 << 30
# float32 bytes up to which a configuration is drawn whole, in one program
# (the largest the benchmark has draws 2.41 GB; the model's own copy and a
# whole float32 draw of this size are 8.6 GB together on a 16-GB chip)
WHOLE_BYTES = 4 << 30
DTYPES = ("float32", "bfloat16")


def key_data(seed, stream):
    """Two uint32 words from any whole-number seed (the driver's are
    larger than 2**31) and a stream id."""
    return np.random.SeedSequence([int(seed), int(stream)]
                                  ).generate_state(2).astype(np.uint32)


def weights_dtype(config):
    """The configuration's `weights_dtype` (float32 where absent); any
    other than DTYPES is refused by name."""
    name = config.get("weights_dtype", "float32")
    if name not in DTYPES:
        print(f"perfbench: weights_dtype {name!r} is not one of {DTYPES}",
              file=sys.stderr)
        raise SystemExit(4)
    return name


def plan_groups(shapes):
    """The groups, each a list of parts (class index, run index or None,
    shape, member names): the shape classes in sorted order, consecutive
    ones packed while their float32 blocks fit the bound together; a class
    over the bound split along its sorted members, each run a group of its
    own.  The bound is WHOLE_BYTES where the whole draw fits it — one
    group, then — else GROUP_BYTES.  None marks a class drawn whole."""
    classes = {}
    for n in sorted(shapes):
        classes.setdefault(tuple(shapes[n]), []).append(n)
    classes = sorted(classes.items())
    total = 4 * sum(math.prod(s) * len(m) for s, m in classes)
    bound = WHOLE_BYTES if total <= WHOLE_BYTES else GROUP_BYTES
    plan, room = [], 0
    for i, (shape, members) in enumerate(classes):
        leaf = 4 * math.prod(shape)
        if leaf * len(members) > bound:
            per = max(1, bound // leaf)
            plan.extend([(i, g, shape, members[at:at + per])] for g, at in
                        enumerate(range(0, len(members), per)))
            room = 0
            continue
        if leaf * len(members) > room:
            plan.append([])
            room = bound
        plan[-1].append((i, None, shape, members))
        room -= leaf * len(members)
    return plan


def _drawer():
    """The jitted draw of one group: `index` holds (class, run) of each
    part, `parts` their static (shape, gains, split)."""
    import jax
    import jax.numpy as jnp

    def draw(kd, index, parts, dtype, std):
        root = jax.random.wrap_key_data(kd, impl="threefry2x32")
        out = []
        for k, (shape, gains, split) in enumerate(parts):
            key = jax.random.fold_in(root, index[k, 0])
            if split:
                key = jax.random.fold_in(key, index[k, 1])
            block = std * jax.random.normal(key, (len(gains),) + shape,
                                            jnp.float32)
            out.extend((block[j] + 1.0 if gain else block[j]).astype(dtype)
                       for j, gain in enumerate(gains))
        return out

    return jax.jit(draw, static_argnums=(2, 3, 4))


def weight_groups(shapes, seed, dtype="float32", std=INIT_STD):
    """Yields {name: device array} a group at a time, each drawn only
    when asked for.  The jitted function lives as long as this generator,
    and its programs leave the device with it."""
    import jax.numpy as jnp

    kd = jnp.asarray(key_data(seed, 0))
    draw = _drawer()
    for group in plan_groups(shapes):
        parts = tuple(
            (shape, tuple(n.endswith(".weight") and len(shape) == 1
                          for n in members), g is not None)
            for _, g, shape, members in group)
        index = np.array([(i, g or 0) for i, g, _, _ in group], np.int32)
        names = [n for _, _, _, members in group for n in members]
        yield dict(zip(names, draw(kd, index, parts, dtype, float(std))))


def make_weights(shapes, seed, dtype="float32", std=INIT_STD):
    """name -> device array of `dtype` for `shapes` (name -> shape), all
    groups together: for tests (those of the two hybrid models outside
    `tests/perfbench/` import it); `build` takes the groups."""
    out = {}
    for group in weight_groups(shapes, seed, dtype, std):
        out.update(group)
    return out
