"""Predictor (reference AnalysisPredictor, analysis_predictor.cc).

create_predictor(config) loads a ``jit.save`` artifact in a fresh process —
no model class needed — and serves named inputs/outputs:

    config = Config("model_prefix")
    predictor = create_predictor(config)
    h = predictor.get_input_handle(predictor.get_input_names()[0])
    h.copy_from_cpu(batch_np)
    predictor.run()
    out = predictor.get_output_handle(predictor.get_output_names()[0])
    result = out.copy_to_cpu()

Batch-size buckets: the exported artifact has a static batch B0; a smaller
feed batch is padded up to B0 (rows repeated) and the fetch sliced back —
one compiled executable serves every batch size ≤ B0 (reference predictors
re-run the IR pipeline per shape; XLA would recompile, so padding is the
TPU-native bucket).
"""
from __future__ import annotations

import pickle
from typing import Dict, List, Optional

import jax
import numpy as np

import jax.export
from .config import Config


class PredictorTensor:
    """Named feed/fetch handle (reference PaddleTensor / ZeroCopyTensor)."""

    def __init__(self, name, shape=None, dtype=None):
        self.name = name
        self._shape = shape
        self._dtype = dtype
        self._value: Optional[np.ndarray] = None

    def copy_from_cpu(self, arr):
        self._value = np.asarray(arr)

    def copy_to_cpu(self):
        return self._value

    def reshape(self, shape):
        self._shape = tuple(shape)

    @property
    def shape(self):
        return (tuple(self._value.shape) if self._value is not None
                else self._shape)

    @property
    def dtype(self):
        return self._dtype


class Predictor:
    def __init__(self, config: Config):
        self._config = config
        with open(config.prog_file(), "rb") as f:
            self._exported = jax.export.deserialize(f.read())
        try:
            with open(config.params_file(), "rb") as f:
                self._state = pickle.load(f)
        except FileNotFoundError:
            self._state = {}
        meta = {}
        try:
            with open(config.prog_file()[: -len(".pdmodel")] + ".pdmeta",
                      "rb") as f:
                meta = pickle.load(f)
        except FileNotFoundError:
            pass
        in_specs = list(self._exported.in_avals)
        self._input_names = meta.get(
            "input_names", [f"x{i}" for i in range(len(in_specs))])
        self._in_specs = in_specs
        n_out = len(self._exported.out_avals)
        self._output_names = meta.get(
            "output_names", [f"out_{i}" for i in range(n_out)])
        # optional pruning: serve only these exported-output positions
        # (paddle.onnx.export output_spec analog)
        self._output_indices = meta.get("output_indices")
        self._inputs: Dict[str, PredictorTensor] = {
            n: PredictorTensor(n, tuple(s.shape), s.dtype)
            for n, s in zip(self._input_names, in_specs)}
        self._outputs: Dict[str, PredictorTensor] = {
            n: PredictorTensor(n) for n in self._output_names}
        if config._warmup:
            self._warmup_call()

    def _warmup_call(self):
        """AOT-compile once at load (analysis_predictor.cc:231
        OptimizeInferenceProgram analog — here XLA compilation)."""
        feeds = [np.zeros(tuple(s.shape), s.dtype) for s in self._in_specs]
        try:
            self._exported.call(*feeds)
        except Exception as e:
            # best-effort (e.g. zero int ids may be out of an embedding's
            # bounds) — but say so instead of hiding a broken artifact
            import warnings

            warnings.warn(f"Predictor warmup call failed ({e!r}); first "
                          "real run will compile instead", stacklevel=2)

    # --- reference API ------------------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_output_names(self) -> List[str]:
        return list(self._output_names)

    def get_input_handle(self, name) -> PredictorTensor:
        return self._inputs[name]

    def get_output_handle(self, name) -> PredictorTensor:
        return self._outputs[name]

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        """Execute. inputs: optional positional feeds (else the values set on
        the input handles).  Feed batches smaller than the exported bucket
        are padded + sliced; LARGER batches are chunked over multiple calls
        and re-concatenated (analysis_predictor Run loop analog)."""
        if inputs is not None:
            for n, a in zip(self._input_names, inputs):
                self._inputs[n].copy_from_cpu(a)
        vals = []
        for n in self._input_names:
            v = self._inputs[n]._value
            if v is None:
                raise ValueError(f"input {n!r} not set (copy_from_cpu first)")
            vals.append(v)

        exported_b = (self._in_specs[0].shape[0]
                      if len(self._in_specs[0].shape) else None)
        actual_b = (vals[0].shape[0] if vals and hasattr(vals[0], "shape")
                    and np.ndim(vals[0]) else None)
        # an input is "batched" iff its exported spec shares the leading
        # batch dim; static side inputs (tables, masks with other leading
        # dims) are passed through unsliced
        batched = [len(s.shape) >= 1 and s.shape[0] == exported_b
                   for s in self._in_specs]
        if (exported_b and actual_b and actual_b > exported_b
                and any(batched)
                and all((np.ndim(v) and v.shape[0] == actual_b) if b
                        else v.shape == tuple(s.shape)
                        for v, b, s in zip(vals, batched, self._in_specs))):
            # chunk an oversized batch through the fixed-size executable
            chunks = []
            chunk_sizes = []
            for lo in range(0, actual_b, exported_b):
                part = [v[lo:lo + exported_b] if b else v
                        for v, b in zip(vals, batched)]
                chunk_sizes.append(min(exported_b, actual_b - lo))
                chunks.append(self._run_once(part))
            merged = []
            for i in range(len(self._output_names)):
                outs_i = [c[i] for c in chunks]
                if all(o.ndim >= 1 and o.shape[0] == cs
                       for o, cs in zip(outs_i, chunk_sizes)):
                    merged.append(np.concatenate(outs_i, axis=0))
                else:
                    # non-batched output (scalar/reduced): per-chunk values
                    # cannot be concatenated meaningfully — return the
                    # chunk results stacked so nothing is silently dropped
                    merged.append(np.stack(outs_i, axis=0))
            for n, arr in zip(self._output_names, merged):
                self._outputs[n].copy_from_cpu(arr)
            return [self._outputs[n].copy_to_cpu()
                    for n in self._output_names]

        outs = self._run_once(vals)
        for n, arr in zip(self._output_names, outs):
            self._outputs[n].copy_from_cpu(arr)
        return [self._outputs[n].copy_to_cpu() for n in self._output_names]

    def _run_once(self, vals):
        """One executable call with bucket padding; returns np outputs
        sliced back to the fed batch."""
        feeds = []
        batch = None
        for n, spec, v in zip(self._input_names, self._in_specs, vals):
            want = tuple(spec.shape)
            if v.shape != want:
                if (len(v.shape) == len(want) and v.shape[1:] == want[1:]
                        and v.shape[0] < want[0]):
                    # batch bucket: pad rows up to the exported batch
                    batch = v.shape[0] if batch is None else batch
                    pad = np.repeat(v[-1:], want[0] - v.shape[0], axis=0)
                    v = np.concatenate([v, pad], axis=0)
                else:
                    raise ValueError(
                        f"input {n!r} shape {v.shape} incompatible with "
                        f"exported {want}")
            feeds.append(v.astype(spec.dtype))
        outs = self._exported.call(*feeds)
        if not isinstance(outs, (list, tuple)):
            outs = (outs,)
        if self._output_indices is not None:
            outs = [outs[i] for i in self._output_indices]
        result = []
        for o in outs:
            arr = np.asarray(o)
            if batch is not None and arr.ndim >= 1 \
                    and arr.shape[0] == self._in_specs[0].shape[0]:
                arr = arr[:batch]
            result.append(arr)
        return result

    def clear_intermediate_tensor(self):
        pass

    def try_shrink_memory(self):
        pass


def create_predictor(config: Config) -> Predictor:
    """reference CreatePaddlePredictor (analysis_predictor.cc:602)."""
    return Predictor(config)
