from .dtype import (  # noqa: F401
    DType, convert_dtype, set_default_dtype, get_default_dtype,
    uint8, int8, int16, int32, int64, float16, bfloat16, float32, float64,
    complex64, complex128, bool_,
)
from .core import Tensor, Parameter, to_tensor, is_tensor, Place  # noqa: F401
from .autograd import (  # noqa: F401
    no_grad, enable_grad, set_grad_enabled, is_grad_enabled, grad,
)
from .random import seed, get_rng_key, default_generator  # noqa: F401
