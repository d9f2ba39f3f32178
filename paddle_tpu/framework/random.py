"""Random state management.

The reference threads per-device curand generators through DeviceContext; the
TPU-native design is a functional PRNG (jax.random) with a convenience
stateful facade:

* Eager mode: a global ``Generator`` splits a fresh subkey per request.
* Traced/jit mode: a ``rng_scope(key)`` context supplies the step key as a
  traced value; each consumption site folds in a Python-level counter that is
  fixed at trace time, so one traced step consumes deterministic, distinct
  subkeys derived from the per-step key argument (the idiomatic jax pattern —
  no traced global state).
"""
from __future__ import annotations

import random as _stdlib_random
import threading
from typing import Optional

import jax
import numpy as np


@jax.jit
def _split_pair(key):
    # one dispatch for (next key, subkey); unpacking jax.random.split's
    # result on the host costs two more
    nxt, sub = jax.random.split(key)
    return nxt, sub


class Generator:
    """The key is built from the seed on FIRST USE, not at construction:
    ``jax.random.key`` initialises the backend, and ``import paddle_tpu``
    constructs the module-level ``default_generator`` — an import must
    not take the chip (a launcher parent that imports the package would
    otherwise hold it before any child starts)."""

    def __init__(self, seed: int = 0):
        self._seed = seed
        self._key = None
        self._ahead = None      # (key, its split) — see split_ahead
        self._lock = threading.Lock()

    def _live_key(self):
        if self._key is None:
            self._key = jax.random.key(self._seed)
        return self._key

    def manual_seed(self, seed: int):
        self._seed = seed
        self._key = None
        return self

    def get_state(self):
        return self._live_key()

    def set_state(self, key):
        self._key = key

    def _split_of(self, key):
        """(key, next key, subkey): the split of `key`, from split_ahead
        if that already ran on this very key object."""
        ahead, self._ahead = self._ahead, None
        if ahead is None or ahead[0] is not key:
            ahead = (key, *_split_pair(key))
        return ahead

    def split_key(self):
        with self._lock:
            _, self._key, sub = self._split_of(self._live_key())
            return sub

    def split_ahead(self):
        """Dispatch now the split the next ``split_key()`` will hand out,
        and change nothing: the state stays the key it was, and the
        result is kept only for that key object (a reseed, ``set_state``
        or ``set_state_dict`` in between drops it).  A caller about to
        wait on the device (a train step's loss) takes the next step's
        split off the host's path between two steps this way."""
        with self._lock:
            self._ahead = self._split_of(self._live_key())

    def state_dict(self):
        """Serializable snapshot of the generator (exact-resume leaf:
        io.checkpoint / hapi train checkpoints persist this so a resumed
        run splits the SAME subkey sequence the killed run would have)."""
        with self._lock:
            return {"seed": int(self._seed),
                    "key_data": np.asarray(
                        jax.random.key_data(self._live_key()))}

    def set_state_dict(self, state):
        with self._lock:
            self._seed = int(state["seed"])
            self._key = jax.random.wrap_key_data(
                jax.numpy.asarray(np.asarray(state["key_data"])))
        return self

    @property
    def initial_seed(self):
        return self._seed


# the one sanctioned entropy source: the process-startup seed itself
# must be fresh; every draw after this point rides the seeded generators
default_generator = Generator(
    np.random.randint(0, 2**31 - 1))  # analyze: allow[determinism] startup seed entropy

# explicit stdlib generator for host-side data augmentation (vision
# transforms): ``paddle_tpu.seed()`` reseeds it, so stdlib-random
# augmentation replays — ambient ``random.*`` module draws never would
# (the module-level stream is invisible to seed() and to checkpoints)
py_random = _stdlib_random.Random()


def seed(value: int):
    """paddle.seed parity: seeds the global generator (and the numpy +
    stdlib data-augmentation generators)."""
    default_generator.manual_seed(int(value))
    # seeding the ambient numpy stream IS the sanctioned data-order
    # source: samplers draw from it and hapi checkpoints snapshot/
    # restore it for exact resume
    np.random.seed(int(value) % (2**32))  # analyze: allow[determinism] the seeding facade itself
    py_random.seed(int(value))
    return default_generator


class _RngScope(threading.local):
    def __init__(self):
        self.key = None
        self.counter = 0


_scope = _RngScope()


class rng_scope:
    """Provide the PRNG key for a traced step: ``with rng_scope(key): ...``."""

    def __init__(self, key):
        self._key = key
        self._prev = None
        self._prev_counter = 0

    def __enter__(self):
        self._prev, self._prev_counter = _scope.key, _scope.counter
        _scope.key, _scope.counter = self._key, 0
        return self

    def __exit__(self, *exc):
        _scope.key, _scope.counter = self._prev, self._prev_counter
        return False


def next_rng_key() -> jax.Array:
    """Next PRNG key: from the active rng_scope if any (trace-safe), else the
    global eager generator."""
    if _scope.key is not None:
        site = _scope.counter
        _scope.counter += 1
        return jax.random.fold_in(_scope.key, site)
    return default_generator.split_key()


def get_rng_state():
    return default_generator.get_state()


def set_rng_state(state):
    default_generator.set_state(state)
