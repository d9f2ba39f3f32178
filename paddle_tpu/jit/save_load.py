"""jit.save / jit.load — inference model export.

Reference analog: paddle.jit.save (fluid/dygraph/jit.py; dygraph/io.py
TranslatedLayer): saves a traced program + params reloadable WITHOUT the
original Python class.

TPU-native: the traced computation is serialized with jax.export (StableHLO
bytes — the XLA-world ProgramDesc analog) next to a pickled state dict.
``jit.load`` rebuilds a TranslatedLayer whose forward invokes the deserialized
StableHLO executable.
"""
from __future__ import annotations

import os
import pickle
from typing import List, Optional

import jax
import numpy as np

import jax.export
from ..nn.layer import Layer
from ..tensor import Tensor
from .functional import functional_call, get_state

_PDMODEL_SUFFIX = ".pdmodel"  # StableHLO bytes
_PDPARAMS_SUFFIX = ".pdiparams"  # pickled numpy state dict


def save(layer, path, input_spec=None, **configs):
    """Export layer for inference. input_spec: list of InputSpec or Tensors."""
    from .to_static import InputSpec, StaticFunction

    if isinstance(getattr(layer, "forward", None), StaticFunction):
        fwd = layer.forward._fn
    else:
        fwd = None

    if input_spec is None:
        raise ValueError("jit.save requires input_spec (shapes are static on TPU)")
    args = []
    for spec in input_spec:
        if isinstance(spec, Tensor):
            args.append(jax.ShapeDtypeStruct(tuple(spec.shape), spec.dtype))
        elif isinstance(spec, InputSpec):
            args.append(jax.ShapeDtypeStruct(spec.shape, spec.dtype))
        else:
            raise TypeError(f"bad input spec {spec!r}")

    params, buffers = get_state(layer)

    def infer_fn(*arr_args):
        out, _ = functional_call(layer, params, buffers, arr_args, training=False)
        return out

    exported = jax.export.export(jax.jit(infer_fn))(*args)
    blob = exported.serialize()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path + _PDMODEL_SUFFIX, "wb") as f:
        f.write(blob)
    state = {n: np.asarray(v) for n, v in {**params, **buffers}.items()}
    with open(path + _PDPARAMS_SUFFIX, "wb") as f:
        pickle.dump(state, f, protocol=4)
    # named input/output meta for the serving predictor
    # (paddle_tpu.inference.create_predictor)
    input_names = []
    for i, spec in enumerate(input_spec):
        name = getattr(spec, "name", None)
        input_names.append(name if name else f"x{i}")
    output_names = [f"out_{i}" for i in range(len(exported.out_avals))]
    from ..framework.op_version import op_version_registry

    with open(path + ".pdmeta", "wb") as f:
        pickle.dump({"input_names": input_names,
                     "output_names": output_names,
                     "op_version_map": op_version_registry.version_map()},
                    f, protocol=4)


class TranslatedLayer(Layer):
    """Reloaded inference program (reference: fluid/dygraph/io.py:TranslatedLayer)."""

    def __init__(self, exported, state, output_indices=None):
        super().__init__()
        self._exported = exported
        self._state = state
        self._output_indices = output_indices

    def forward(self, *args):
        arr_args = [a._value if isinstance(a, Tensor) else np.asarray(a) for a in args]
        out = self._exported.call(*arr_args)
        if not isinstance(out, (list, tuple)):
            return Tensor(out)
        if self._output_indices is not None:
            # onnx.export output_spec pruning (meta output_indices)
            out = [out[i] for i in self._output_indices]
            if len(out) == 1:
                return Tensor(out[0])
        return type(out)(Tensor(o) for o in out)

    def program(self):
        return self._exported.mlir_module()


def load(path, **configs):
    with open(path + _PDMODEL_SUFFIX, "rb") as f:
        blob = f.read()
    exported = jax.export.deserialize(blob)
    with open(path + _PDPARAMS_SUFFIX, "rb") as f:
        state = pickle.load(f)
    indices = None
    try:
        with open(path + ".pdmeta", "rb") as f:
            meta = pickle.load(f)
        indices = meta.get("output_indices")
        saved_versions = meta.get("op_version_map")
        if saved_versions is not None:
            from ..framework.op_version import op_version_registry

            for msg in op_version_registry.check_compat(saved_versions):
                import warnings

                warnings.warn(f"loaded program compat: {msg}", stacklevel=2)
    except OSError:
        pass
    return TranslatedLayer(exported, state, output_indices=indices)
