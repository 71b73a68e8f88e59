"""paddle_tpu: a TPU-native deep learning framework with PaddlePaddle's user
surface (reference: tianyan01/Paddle at /root/reference), built on jax/XLA.

Dygraph Tensors are mutable handles over jax.Array with a tape-based autograd;
static graph / to_static is jax.jit capture; distributed training is
jax.sharding Meshes + XLA collectives instead of NCCL ProcessGroups.
"""
from __future__ import annotations

__version__ = "0.1.0"

from .framework.device import configure_compile_cache as _configure_cache
_configure_cache()

# core
from .framework import (  # noqa: F401
    CPUPlace, CUDAPinnedPlace, CUDAPlace, CustomPlace, Parameter, Place,
    TPUPlace, Tensor, XPUPlace, device_count, enable_grad, get_default_dtype,
    get_device, grad, is_compiled_with_cuda, is_compiled_with_tpu, no_grad,
    seed, set_default_dtype, set_device, set_grad_enabled, to_tensor,
)
from .framework.dtype import (  # noqa: F401
    bfloat16, bool_, complex64, complex128, float16, float32, float64, int8,
    int16, int32, int64, uint8,
)
from .framework import random as _framework_random  # noqa: F401
from .framework.random import get_rng_state, set_rng_state  # noqa: F401
from .framework.api_extras import (  # noqa: F401
    LazyGuard, check_shape, dtype, finfo, get_cuda_rng_state, iinfo,
    set_cuda_rng_state, set_printoptions,
)

# dtype aliases paddle exposes at top level
bool = bool_  # noqa: A001

# ops — install Tensor methods first, then re-export every op at top level
from . import ops  # noqa: E402
ops.install_tensor_methods()
from .ops import *  # noqa: F401,F403,E402
from .ops import rank, shape, is_floating_point, is_complex  # noqa: F401,E402

from . import amp  # noqa: F401,E402
from . import flags as _flags_mod  # noqa: E402
from .flags import get_flags, set_flags  # noqa: F401,E402

from . import nn  # noqa: F401,E402  (also installs paddle.ParamAttr)
from . import optimizer  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import regularizer  # noqa: F401,E402
from .regularizer import L1Decay, L2Decay  # noqa: F401,E402
from .nn.clip import (ClipGradByGlobalNorm, ClipGradByNorm,  # noqa: F401,E402
                      ClipGradByValue)
# paddle.nn re-exports the clip classes too
nn.ClipGradByGlobalNorm = ClipGradByGlobalNorm
nn.ClipGradByNorm = ClipGradByNorm
nn.ClipGradByValue = ClipGradByValue
nn.clip_grad_norm_ = __import__("paddle_tpu.nn.clip", fromlist=["x"]).clip_grad_norm_
nn.clip_grad_value_ = __import__("paddle_tpu.nn.clip", fromlist=["x"]).clip_grad_value_
nn.initializer.set_global_initializer  # noqa: B018

from . import jit  # noqa: F401,E402
from . import static  # noqa: F401,E402
from . import distributed  # noqa: F401,E402
from .distributed.parallel import DataParallel  # noqa: F401,E402
from . import parallel  # noqa: F401,E402
from . import metric  # noqa: F401,E402
from . import vision  # noqa: F401,E402
from . import audio  # noqa: F401,E402
from . import hapi  # noqa: F401,E402
from .hapi import Model  # noqa: F401,E402
from . import incubate  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import models  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import fft  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from . import onnx  # noqa: F401,E402
from . import hub  # noqa: F401,E402
from . import device  # noqa: F401,E402
from . import tensor  # noqa: F401,E402
from . import callbacks  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from . import version  # noqa: F401,E402
from . import reader  # noqa: F401,E402
from . import dataset  # noqa: F401,E402
from . import _C_ops  # noqa: F401,E402
from .batch import batch  # noqa: F401,E402
from . import distribution  # noqa: F401,E402
from . import sparse  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import signal  # noqa: F401,E402
from . import geometric  # noqa: F401,E402
from . import text  # noqa: F401,E402
from .framework import autograd as _autograd_mod  # noqa: E402
from . import autograd  # noqa: F401,E402

# disable_static/enable_static are paddle's dygraph/static switches; dygraph
# is the default and static graph is symbolic capture (framework/symbolic.py).
_static_mode = [False]


def enable_static():
    _static_mode[0] = True


def disable_static():
    _static_mode[0] = False


def in_dynamic_mode():
    return not _static_mode[0]


in_dygraph_mode = in_dynamic_mode


def is_grad_enabled():
    from .framework import autograd as _ag
    return _ag.tape_enabled()


def disable_signal_handler():
    pass


def save(obj, path, protocol=4, **configs):
    from .framework.io import save as _save
    return _save(obj, path, protocol=protocol, **configs)


def load(path, **configs):
    from .framework.io import load as _load
    return _load(path, **configs)


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.summary import summary as _summary
    return _summary(net, input_size, dtypes, input)


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.dynamic_flops import flops as _flops
    return _flops(net, input_size, custom_ops=custom_ops,
                  print_detail=print_detail)
