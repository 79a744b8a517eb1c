"""Inference API. Reference analog: paddle/fluid/inference/ —
`AnalysisPredictor` (api/analysis_predictor.h:95), `AnalysisConfig`
(api/paddle_analysis_config.h), zero-copy input/output handles
(`Predictor.get_input_handle().copy_from_cpu(...)`).

TPU-first: the reference's IR-analysis/fusion pass pipeline and TensorRT
subgraph capture are XLA's job — the saved artifact is jax.export StableHLO
(produced by paddle_tpu.jit.save / static.save_inference_model), and the
predictor is a thin handle-based wrapper so reference deployment code ports
unchanged.

Scope: `Predictor` replays ONE exported program per `run()` — right for
stateless single-model inference (classification, embedding, scoring)
and for porting reference `paddle_infer` call sites. For **batched
autoregressive GENERATION under live traffic** use
`paddle_tpu.serving.LLMEngine` instead: it is the engine behind the
reference's serving deployments rebuilt for TPU — continuous
(iteration-level) batching over a paged KV cache, ONE compiled
decode-step executable for every tenant mix (zero retraces as requests
join/leave), bucketed prefill, preempt-resume, and streaming `on_token`
callbacks::

    from paddle_tpu.serving import LLMEngine
    engine = LLMEngine(model, max_batch_size=8, block_size=16)
    outs = engine.generate(prompt_id_lists, max_new_tokens=64)

A `PredictorPool` of per-request predictors (the reference's serving
pattern) freezes batch composition for a request's lifetime; `LLMEngine`
re-forms the batch at every token boundary — that is the difference
between one-user latency and millions-of-users throughput. See the
README "Serving" section; its speed is the `serve_124m_backlog` cell's
(`PERF.md`)."""
from __future__ import annotations

import numpy as np

from ..framework.core import Tensor

__all__ = ["Config", "Predictor", "create_predictor", "PrecisionType",
           "PlaceType", "get_version", "DataType", "PredictorPool",
           "get_num_bytes_of_data_type", "convert_to_mixed_precision",
           "get_trt_compile_version", "get_trt_runtime_version",
           "_get_phi_kernel_name"]


class PrecisionType:
    Float32 = 0
    Half = 1
    Bfloat16 = 2
    Int8 = 3


class PlaceType:
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM = 3


class Config:
    """Holds the model path + knobs. GPU/IR/TensorRT toggles are accepted for
    API parity; on TPU they map to XLA behaviors that are always on."""

    def __init__(self, prog_file=None, params_file=None):
        self._model_path = prog_file
        self._params_file = params_file
        self._ir_optim = True
        self._memory_optim = True
        self._precision = PrecisionType.Float32
        self._threads = 1
        self._place = PlaceType.TPU

    # --- model location
    def set_prog_file(self, path):
        self._model_path = path

    def prog_file(self):
        return self._model_path

    def set_params_file(self, path):
        self._params_file = path

    def set_model(self, prog_file, params_file=None):
        self._model_path = prog_file
        self._params_file = params_file

    # --- parity knobs
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._place = PlaceType.GPU  # honored as "accelerator": TPU here

    def disable_gpu(self):
        self._place = PlaceType.CPU

    def enable_memory_optim(self, x=True):
        self._memory_optim = x

    def switch_ir_optim(self, x=True):
        self._ir_optim = x

    def set_cpu_math_library_num_threads(self, n):
        self._threads = n

    def enable_tensorrt_engine(self, **kw):
        pass  # XLA owns fusion on TPU

    def enable_mkldnn(self):
        pass

    def switch_use_feed_fetch_ops(self, x):
        pass

    def switch_specify_input_names(self, x=True):
        pass

    def precision_mode(self):
        return self._precision


class _IOHandle:
    """Zero-copy-style tensor handle (reference: ZeroCopyTensor)."""

    def __init__(self, name):
        self.name = name
        self._array = None

    def reshape(self, shape):
        if self._array is None:
            self._array = np.zeros(shape, np.float32)
        else:
            self._array = np.resize(self._array, shape)

    def copy_from_cpu(self, arr):
        self._array = np.asarray(arr)

    def copy_to_cpu(self):
        return np.asarray(self._array)

    def shape(self):
        return list(self._array.shape) if self._array is not None else []

    def type(self):
        return str(self._array.dtype) if self._array is not None else None


class Predictor:
    def __init__(self, config):
        from ..jit.api import load as jload, TranslatedLayer
        self._config = config
        if config.prog_file() is None:
            raise ValueError("Config has no model path; call set_prog_file")
        art = jload(config.prog_file())
        if not isinstance(art, TranslatedLayer):
            raise ValueError(
                f"{config.prog_file()} is not a paddle_tpu.jit artifact")
        if not art.has_forward:
            raise ValueError(
                "artifact has no compiled forward; re-save with input_spec")
        self._layer = art
        n_in = max(1, self._infer_num_inputs(art))
        self._inputs = {f"x{i}": _IOHandle(f"x{i}") for i in range(n_in)}
        self._outputs = {}

    @staticmethod
    def _infer_num_inputs(art):
        n_state = len(art._param_values)
        try:
            # exported signature: (values list, key); count of avals minus
            # params/buffers minus the rng key
            total = len(art._exported.in_avals)
            return max(1, total - n_state - 1)
        except Exception:
            return 1

    def get_input_names(self):
        return list(self._inputs.keys())

    def get_input_handle(self, name):
        return self._inputs.setdefault(name, _IOHandle(name))

    def get_output_names(self):
        return list(self._outputs.keys())

    def get_output_handle(self, name):
        return self._outputs[name]

    def run(self, inputs=None):
        """Either pass arrays directly (returns list of np arrays) or use the
        handle API: copy_from_cpu -> run() -> copy_to_cpu."""
        if inputs is not None:
            args = [np.asarray(a) for a in inputs]
        else:
            args = [h.copy_to_cpu() for h in self._inputs.values()
                    if h._array is not None]
        out = self._layer(*args)
        outs = out if isinstance(out, (list, tuple)) else [out]
        arrays = [o.numpy() if isinstance(o, Tensor) else np.asarray(o)
                  for o in outs]
        self._outputs = {f"out{i}": _IOHandle(f"out{i}")
                         for i in range(len(arrays))}
        for h, a in zip(self._outputs.values(), arrays):
            h.copy_from_cpu(a)
        return arrays

    def clone(self):
        return Predictor(self._config)


def create_predictor(config):
    return Predictor(config)


def get_version():
    from .. import __version__
    return __version__


class DataType:
    """Reference paddle_infer.DataType enum."""
    FLOAT32 = 0
    INT64 = 1
    INT32 = 2
    UINT8 = 3
    INT8 = 4
    FLOAT16 = 5
    BFLOAT16 = 6


_DTYPE_BYTES = {DataType.FLOAT32: 4, DataType.INT64: 8, DataType.INT32: 4,
                DataType.UINT8: 1, DataType.INT8: 1, DataType.FLOAT16: 2,
                DataType.BFLOAT16: 2}


def get_num_bytes_of_data_type(dtype):
    """Reference inference API helper."""
    return _DTYPE_BYTES[dtype]


def get_trt_compile_version():
    """No TensorRT on TPU (XLA owns inference compilation)."""
    return (0, 0, 0)


def get_trt_runtime_version():
    return (0, 0, 0)


def _get_phi_kernel_name(op_name):
    """Fluid op name -> phi kernel name (reference pybind helper). The op
    registry here is already phi-style, so names pass through."""
    return op_name


def convert_to_mixed_precision(model_file, params_file, mixed_model_file,
                               mixed_params_file, mixed_precision=None,
                               backend=None, keep_io_types=True,
                               black_list=None, **kwargs):
    """Reference convert_to_mixed_precision: rewrite a saved model to
    mixed precision. TPU-native saved artifacts are StableHLO exports whose
    precision is chosen AT EXPORT (bf16 weights + jit) — re-export the
    layer with model.bfloat16() instead of rewriting the artifact."""
    raise NotImplementedError(
        "TPU inference artifacts fix precision at export: call "
        "model.bfloat16() before jit.save / save_inference_model instead "
        "of converting the saved file")


class PredictorPool:
    """Pool of Predictors sharing one config (reference
    paddle_infer.PredictorPool — serving worker pools). For generation
    workloads prefer `paddle_tpu.serving.LLMEngine`: one continuous
    batch instead of one frozen batch per pooled worker."""

    def __init__(self, config, size=1):
        self._predictors = [create_predictor(config)
                            for _ in range(max(int(size), 1))]

    def retrive(self, idx):
        return self._predictors[idx]

    retrieve = retrive          # reference spells it "retrive"

    def size(self):
        return len(self._predictors)
