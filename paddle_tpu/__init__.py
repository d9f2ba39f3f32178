"""paddle_tpu — a TPU-native deep-learning framework.

A ground-up re-design of the reference framework's capabilities
(/root/reference: PaddlePaddle ~v2.0) for TPU: jax/XLA is the compiler and
runtime for all device compute, Pallas provides custom kernels, pjit/shard_map
over device meshes provide distribution, and this package provides the
imperative (dygraph) + declarative (static/jit) programming model, the layer
and optimizer libraries, data pipelines, and the distributed strategy stack.

Public surface mirrors `import paddle` (python/paddle/__init__.py in the
reference) so users of the reference can switch with an import change.
"""
from __future__ import annotations

from .framework.init import configure_compile_cache as _configure_compile_cache

_configure_compile_cache()

# framework primitives
from .framework import (  # noqa: F401
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    TPUPlace,
    XPUPlace,
    bfloat16,
    bool_,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    get_default_dtype,
    get_device,
    get_flags,
    int8,
    int16,
    int32,
    int64,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    is_compiled_with_xpu,
    seed,
    set_default_dtype,
    set_device,
    set_flags,
    uint8,
)
from .framework import random as _random_state  # noqa: F401
from .framework.random import get_rng_state, set_rng_state  # noqa: F401
from .tensor import Parameter, Tensor  # noqa: F401

# autograd
from .autograd import enable_grad, grad, is_grad_enabled, no_grad, set_grad_enabled  # noqa: F401

# ops — flat namespace like paddle.*
from .ops.creation import *  # noqa: F401,F403
from .ops.math import *  # noqa: F401,F403
from .ops.manipulation import *  # noqa: F401,F403
from .ops.logic import *  # noqa: F401,F403
from .ops.search import *  # noqa: F401,F403
from .ops.linalg import *  # noqa: F401,F403
from .ops.random_ops import *  # noqa: F401,F403
from .ops import linalg  # noqa: F401

# saving / loading
from .framework_io import load, save  # noqa: F401

# subpackages
from . import amp  # noqa: F401
from . import autograd  # noqa: F401
from . import distributed  # noqa: F401
from . import hapi as _hapi  # noqa: F401
from . import incubate  # noqa: F401
from . import inference  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import distribution  # noqa: F401
from . import metric  # noqa: F401
from . import nn  # noqa: F401
from . import onnx  # noqa: F401
from . import optimizer  # noqa: F401
from . import profiler  # noqa: F401
from . import slim  # noqa: F401
from . import static  # noqa: F401
from . import text  # noqa: F401
from . import utils  # noqa: F401
from . import vision  # noqa: F401
from .hapi import Model, summary  # noqa: F401
from .hapi import callbacks  # noqa: F401
from .param_attr import ParamAttr  # noqa: F401
from .distributed import DataParallel  # noqa: F401
from .static import data  # noqa: F401
from .compat import (  # noqa: F401
    LoDTensor, LoDTensorArray, VarBase, addmm, cast, create_global_var,
    crop_tensor, disable_dygraph, elementwise_add, elementwise_div,
    elementwise_floordiv, elementwise_mod, elementwise_pow,
    elementwise_sub, enable_dygraph, fill_constant, flops,
    get_cuda_rng_state, get_cudnn_version,
    get_tensor_from_selected_rows, has_inf, has_nan,
    in_dygraph_mode, monkey_patch_math_varbase, monkey_patch_variable,
    mv, rank, reduce_max, reduce_mean, reduce_min, reduce_prod,
    reduce_sum, scatter_, set_cuda_rng_state, set_printoptions, shape,
    tanh_)
from .nn.functional.extension import (  # noqa: F401
    array_length, array_read, array_write, create_array)
from .compat import elementwise_mul  # noqa: F401
from .jit import to_static  # noqa: F401

__version__ = "0.1.0"

# dygraph-mode toggles: eager is the default and only "imperative" mode;
# enable_static flips the default into graph-capture mode (static.Program).
from .static import _mode as _static_mode  # noqa: E402


def in_dynamic_mode() -> bool:
    return not _static_mode.static_mode_enabled()


def enable_static():
    _static_mode.enable_static()


def disable_static():
    _static_mode.disable_static()


def is_grad_enabled_():  # private alias
    return is_grad_enabled()


def _patch_tensor_methods():
    """Attach functional ops as Tensor methods (reference analog:
    fluid/dygraph/math_op_patch.py monkey-patching VarBase)."""
    from .ops import linalg, logic, manipulation, math, search
    from .ops import creation as _creation
    from .ops import random_ops as _random

    method_sources = [math, manipulation, logic, search, linalg]
    skip = {"cond", "is_tensor", "broadcast_shape", "builtins_sum", "jax_topk",
            "slice_builtin"}
    for mod in method_sources:
        for name in dir(mod):
            if name.startswith("_") or name in skip:
                continue
            fn = getattr(mod, name)
            if not callable(fn):
                continue
            if not hasattr(Tensor, name):
                setattr(Tensor, name, fn)
    # extra aliases
    Tensor.add_ = lambda self, y: self._replace_from(math.add(self, y))
    Tensor.subtract_ = lambda self, y: self._replace_from(math.subtract(self, y))
    Tensor.multiply_ = lambda self, y: self._replace_from(math.multiply(self, y))
    Tensor.scale_ = lambda self, *a, **k: self._replace_from(math.scale(self, *a, **k))
    Tensor.clip_ = lambda self, *a, **k: self._replace_from(math.clip(self, *a, **k))
    Tensor.zero_ = lambda self: self.set_value(
        __import__("jax.numpy", fromlist=["zeros"]).zeros_like(self._value))
    Tensor.fill_ = lambda self, v: self.set_value(
        __import__("jax.numpy", fromlist=["full"]).full_like(self._value, v))
    Tensor.uniform_ = _random.uniform_
    Tensor.normal_ = _random.normal_
    Tensor.exponential_ = _random.exponential_
    Tensor.mm = linalg.mm
    Tensor.matmul = linalg.matmul
    Tensor.dot = linalg.dot
    Tensor.norm = linalg.norm


_patch_tensor_methods()
del _patch_tensor_methods

# hapi namespace parity: paddle.Model
Model = Model
summary = summary
