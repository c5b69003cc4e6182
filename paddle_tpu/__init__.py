"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new framework with the capability surface of the PaddlePaddle
reference (see SURVEY.md): eager autograd + jit compilation, full nn/optim/io
stacks, and hybrid-parallel training (DP/TP/PP/SP/EP/ZeRO) — built
TPU-first on JAX/XLA/Pallas: ops are pure-jax functions XLA fuses onto the
MXU, autograd is jax.vjp over those functions, distribution is GSPMD over a
jax.sharding.Mesh, and the hot kernels (flash attention, MoE dispatch) are
Pallas.
"""

from __future__ import annotations

import os as _os

# Multi-process rendezvous must happen before ANY backend-initializing jax
# call (jax.distributed.initialize's own requirement), and code that
# imports this package goes on to touch the backend — so when the launch
# CLI has wired the env (reference launch/controllers/collective.py),
# connect right here.
if int(_os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1 and (
        _os.environ.get("PADDLE_MASTER")):
    import jax as _jax
    if not _jax.distributed.is_initialized():  # raw-jax workers may have
        _jax.distributed.initialize(
            coordinator_address=_os.environ["PADDLE_MASTER"],
            num_processes=int(_os.environ["PADDLE_TRAINERS_NUM"]),
            process_id=int(_os.environ.get("PADDLE_TRAINER_ID", "0")))

from . import flags  # noqa: F401  (registers core flags first)
from .flags import set_flags, get_flags  # noqa: F401

from .core.dtype import (  # noqa: F401
    dtype, float16, bfloat16, float32, float64, int8, int16, int32, int64,
    uint8, bool_, complex64, complex128, get_default_dtype, set_default_dtype,
)
from .core.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .core.autograd import no_grad, enable_grad, grad, is_grad_enabled, set_grad_enabled  # noqa: F401

from .ops import *  # noqa: F401,F403
from .ops import __all__ as _ops_all
from .ops.random import seed, get_rng_state, set_rng_state  # noqa: F401

from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import io  # noqa: F401
from . import amp  # noqa: F401
from . import autograd  # noqa: F401
from . import metric  # noqa: F401
from . import device  # noqa: F401
from . import jit  # noqa: F401
from . import linalg  # noqa: F401
from . import distribution  # noqa: F401
from . import fft  # noqa: F401
from . import framework  # noqa: F401
from . import hapi  # noqa: F401
from . import profiler  # noqa: F401
from . import inference  # noqa: F401
from . import sparse  # noqa: F401
from . import geometric  # noqa: F401
from . import quantization  # noqa: F401
from . import audio  # noqa: F401
from . import incubate  # noqa: F401
from . import text  # noqa: F401
from . import utils  # noqa: F401
from . import static  # noqa: F401
from . import signal  # noqa: F401
from . import sysconfig  # noqa: F401
from . import onnx  # noqa: F401
from . import reader  # noqa: F401
from .hapi import Model, summary  # noqa: F401
from . import callbacks  # noqa: F401
from .framework.io import save, load  # noqa: F401
from .framework.param_attr import ParamAttr  # noqa: F401
from .device import set_device, get_device, is_compiled_with_cuda, is_compiled_with_tpu  # noqa: F401
from .metric import accuracy  # noqa: F401
from .framework.core import (  # noqa: F401
    finfo, iinfo, set_printoptions, CPUPlace, CUDAPlace, CUDAPinnedPlace,
    TPUPlace, XPUPlace, CustomPlace, in_dynamic_mode, in_dygraph_mode,
    enable_static, disable_static, create_parameter, LazyGuard,
    disable_signal_handler, is_complex, is_floating_point, is_integer,
    is_tensor, flops,
)

from .distributed.parallel import DataParallel  # noqa: F401

# dtype alias shadowing the builtin, as the reference does (paddle.bool)
globals()["bool"] = bool_


def batch(reader, batch_size, drop_last=False):
    """Legacy reader-decorator batching (reference: python/paddle/batch.py)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


def check_shape(shape, op_name="", expected_shape_type=(list, tuple),
                expected_element_type=(int,), expected_tensor_dtype=None):
    """Shape-argument validation (reference: base/data_feeder.py:212).
    Dygraph skips checks like the reference; static scripts get the type
    errors."""
    if in_dynamic_mode():
        return
    if not isinstance(shape, expected_shape_type):
        raise TypeError(f"The shape of '{op_name}' must be "
                        f"{expected_shape_type}, got {type(shape)}")
    for item in shape:
        if not isinstance(item, expected_element_type):
            raise TypeError(f"element of shape in '{op_name}' must be "
                            f"{expected_element_type}, got {type(item)}")


def get_cuda_rng_state():
    """Device RNG state (reference: paddle.get_cuda_rng_state; on TPU the
    accelerator RNG is the same counter-based generator)."""
    return get_rng_state()


def set_cuda_rng_state(state):
    return set_rng_state(state)


# remaining reference tensor methods living outside the op surface
# (tensor/__init__.py lists them in tensor_method_func too)
from .framework.core import (  # noqa: E402
    is_complex as _isc, is_floating_point as _isf, is_integer as _isi,
    create_parameter as _cp)
from .signal import stft as _stft, istft as _istft  # noqa: E402
from .ops.linalg import inverse as _inverse  # noqa: E402

for _name, _fn in [("is_complex", _isc), ("is_floating_point", _isf),
                   ("is_integer", _isi), ("create_parameter",
                                          staticmethod(_cp)),
                   ("stft", _stft), ("istft", _istft),
                   ("inverse", _inverse)]:
    if not hasattr(Tensor, _name):
        setattr(Tensor, _name, _fn)


def create_tensor(dtype, name=None, persistable=False):
    """reference tensor/creation.py create_tensor — an empty typed
    tensor slot."""
    import jax.numpy as _jnp
    from .core.dtype import convert_dtype
    t = Tensor(_jnp.zeros((), convert_dtype(dtype)), name=name)
    t.persistable = persistable
    return t


Tensor.create_tensor = staticmethod(create_tensor)

__version__ = "0.1.0"

__all__ = (
    ["Tensor", "Parameter", "to_tensor", "no_grad", "enable_grad", "grad",
     "seed", "save", "load", "set_default_dtype", "get_default_dtype",
     "set_flags", "get_flags", "set_device", "get_device", "ParamAttr",
     "Model", "summary", "accuracy",
     "finfo", "iinfo", "set_printoptions", "CPUPlace", "CUDAPlace",
     "CUDAPinnedPlace", "TPUPlace", "in_dynamic_mode", "in_dygraph_mode",
     "enable_static", "disable_static", "create_parameter", "LazyGuard",
     "disable_signal_handler", "is_complex", "is_floating_point",
     "is_integer", "is_tensor", "flops"]
    + list(_ops_all)
)
