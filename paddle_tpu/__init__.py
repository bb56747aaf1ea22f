"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new implementation of the capability surface of the reference framework
(PaddlePaddle ~v2.5-dev, mounted at /root/reference), re-designed for TPU:
JAX/XLA is the kernel library and compiler, Pallas supplies the fused hot
kernels, pjit/shard_map over a `jax.sharding.Mesh` replaces the NCCL
ProcessGroup world, and whole-step XLA compilation replaces the reference's
per-op executor machinery.

Usage mirrors the reference's `import paddle`:

    import paddle_tpu as paddle
    paddle.set_device('tpu')
    x = paddle.randn([8, 128])
    y = paddle.matmul(x, x.T)
    y.sum().backward()
"""
from __future__ import annotations

from .version import full_version as __version__  # single version source

import os as _os

import jax as _jax

# f32 matmuls run at full float32 precision, matching the reference's cuBLAS
# default (TF32 disabled — `FLAGS_allow_tf32` analog). bf16 — the TPU perf
# path — is unaffected: the MXU consumes bf16 natively, and the Pallas
# kernels pin their own dot precision from the operand dtype
# (ops/pallas_ops._dot_precision). PADDLE_TPU_MATMUL_PRECISION overrides.
_jax.config.update("jax_default_matmul_precision",
                   _os.environ.get("PADDLE_TPU_MATMUL_PRECISION",
                                   "highest"))

# float64/int64 are first-class dtypes in the reference API; enable x64 so
# `paddle.float64` tensors keep their width (compute stays f32/bf16 unless
# the user explicitly asks for f64 — creation defaults are float32).
# PADDLE_TPU_X64=0 opts out: 64-bit dtypes silently narrow (JAX's native
# mode). The in-repo Pallas kernels run either way — their bodies carry
# typed 32-bit constants (ops/pallas_ops.py).
if _os.environ.get("PADDLE_TPU_X64", "1") != "0":
    _jax.config.update("jax_enable_x64", True)

# core types ------------------------------------------------------------------
from .core.tensor import Tensor, Parameter  # noqa: F401
from .core.dtype import (  # noqa: F401
    bool_ as bool,  # noqa: A001
    uint8, int8, int16, int32, int64, float16, bfloat16, float32, float64,
    complex64, complex128, set_default_dtype, get_default_dtype, DType,
)
from .core.place import (  # noqa: F401
    CPUPlace, TPUPlace, CUDAPlace, XPUPlace, Place, set_device, get_device,
    device_count, is_compiled_with_tpu, is_compiled_with_cuda,
    is_compiled_with_xpu, is_compiled_with_custom_device,
)
from .core.random import seed, get_rng_state, set_rng_state  # noqa: F401
from .core.flags import set_flags, get_flags  # noqa: F401
from .core.autograd import no_grad, enable_grad, set_grad_enabled, \
    is_grad_enabled, grad  # noqa: F401
from .core import autograd  # noqa: F401

# ops — flat namespace like `paddle.*` ---------------------------------------
from .ops import *  # noqa: F401,F403
from . import ops  # noqa: F401

# subsystems ------------------------------------------------------------------
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import static  # noqa: F401
from . import jit  # noqa: F401
from . import metric  # noqa: F401
from . import vision  # noqa: F401
from . import distributed  # noqa: F401
from . import incubate  # noqa: F401
from . import profiler  # noqa: F401
from . import distribution  # noqa: F401
from . import sparse  # noqa: F401
# `from .ops import *` already bound the ops.linalg submodule to the name
# `linalg`; import the namespace module explicitly so `paddle.linalg` is the
# full reference-parity namespace (importing the submodule rebinds the
# parent attribute).
import importlib as _importlib

linalg = _importlib.import_module(".linalg", __name__)
from . import fft  # noqa: F401,E402
from . import inference  # noqa: F401,E402
from . import serving  # noqa: F401,E402
from . import quantization  # noqa: F401,E402
from . import signal  # noqa: F401,E402
from .framework import save, load, in_dynamic_mode, enable_static, \
    disable_static  # noqa: F401
from . import framework  # noqa: F401
from . import device  # noqa: F401
from . import geometric  # noqa: F401
from . import text  # noqa: F401
from . import audio  # noqa: F401
from . import reader  # noqa: F401
from .reader import batch  # noqa: F401  (paddle.batch)
from . import regularizer  # noqa: F401
from . import sysconfig  # noqa: F401
from . import hub  # noqa: F401
from . import callbacks  # noqa: F401
from . import cost_model  # noqa: F401
from . import onnx  # noqa: F401
from . import version  # noqa: F401
from . import utils  # noqa: F401


def is_grad_enabled_():  # pragma: no cover - back-compat alias
    return is_grad_enabled()


# `paddle.disable_static()` is the default state; see static/ for the
# Program/Executor declarative mode.
