"""onnx.export-shaped entry (reference python/paddle/onnx/export.py:21)."""
from __future__ import annotations


def export(layer, path, input_spec=None, opset_version=None, **configs):
    """Export ``layer`` as a StableHLO inference artifact.

    Signature-compatible with the reference ``paddle.onnx.export``: the
    same (layer, path, input_spec, **configs) contract; ``opset_version``
    is accepted and ignored (StableHLO carries its own versioning).
    ``configs['output_spec']`` prunes outputs the same way the reference
    does.

    Writes ``<path>.pdmodel`` (StableHLO bytes), ``<path>.pdiparams``
    (weights) and ``<path>.pdmeta`` (named IO) — loadable by
    ``paddle_tpu.jit.load`` and ``paddle_tpu.inference.create_predictor``.
    Returns the artifact prefix.
    """
    from .. import jit

    if path.endswith(".onnx"):
        path = path[: -len(".onnx")]
    output_spec = configs.pop("output_spec", None)
    jit.save(layer, path, input_spec=input_spec, **configs)
    if output_spec is not None:
        _prune_outputs(path, output_spec)
    return path


def _prune_outputs(path, output_spec):
    """Keep only the requested outputs (reference export.py output_spec
    semantics).  Entries may be integer positions, exported output names
    ('out_2'), or objects with a matching ``.name``; the Predictor serves
    exactly the selected positions via meta['output_indices']."""
    import pickle

    with open(path + ".pdmeta", "rb") as f:
        meta = pickle.load(f)
    names = meta["output_names"]
    indices = []
    for spec in output_spec:
        if isinstance(spec, int):
            idx = spec
        else:
            name = spec if isinstance(spec, str) else getattr(spec, "name",
                                                              None)
            if name not in names:
                raise ValueError(
                    f"output_spec entry {spec!r} does not match any exported "
                    f"output {names}")
            idx = names.index(name)
        if not 0 <= idx < len(names):
            raise ValueError(f"output_spec index {idx} out of range "
                             f"(model has {len(names)} outputs)")
        indices.append(idx)
    meta["output_indices"] = indices
    meta["output_names"] = [names[i] for i in indices]
    with open(path + ".pdmeta", "wb") as f:
        pickle.dump(meta, f, protocol=4)
