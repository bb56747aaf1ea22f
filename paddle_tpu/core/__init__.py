from . import dtype, place, random, flags, autograd, tensor  # noqa: F401
from .tensor import Tensor, Parameter  # noqa: F401
