"""Inference predictor.

Reference: ``paddle/fluid/inference/api/paddle_inference_api.h:81``
(Predictor), ``analysis_predictor.h:105`` (AnalysisPredictor: load program,
run IR pass pipeline, execute with zero-copy handles), Python surface
``paddle.inference.Config`` / ``create_predictor``.

TPU-native: the "analysis + executor" pipeline is XLA — a Predictor wraps
either a live Layer or a ``paddle_tpu.jit.save``d program prefix, compiles
the forward once with ``jax.jit`` over the parameter pytree, and serves
``run()`` as an executable-cache hit.  Zero-copy handles are jax device
arrays.
"""
from __future__ import annotations

import numpy as np


class Config:
    """Reference: paddle.inference.Config(prog_file, params_file)."""

    def __init__(self, model_path=None, params_path=None):
        self.model_path = model_path
        self.params_path = params_path
        if params_path is not None and params_path != model_path:
            # jit.save writes program + weights into one <prefix>.pdparams;
            # a separate params file would be silently ignored otherwise.
            raise NotImplementedError(
                "paddle_tpu saves program and weights in a single "
                f"'<prefix>.pdparams' file; pass that prefix as model_path "
                f"(got params_path={params_path!r})")
        self._device = None

    def enable_use_gpu(self, memory_pool_init_size_mb=100,
                       device_id=0, *a, **k):
        """Device binding (reference Config::EnableUseGpu).  Maps onto
        the accelerator jax exposes; device_id selects among local
        devices."""
        self._device = ("accel", int(device_id))

    def disable_gpu(self):
        self._device = ("cpu", 0)

    def set_cpu_math_library_num_threads(self, n):
        self._cpu_threads = int(n)

    # -- analysis/optimization toggles (analysis_predictor.h:105) ------
    # XLA always runs its own pass pipeline; these record the
    # reference's knobs and steer the pieces that exist here.

    def switch_ir_optim(self, flag=True):
        self._ir_optim = bool(flag)

    def enable_memory_optim(self, flag=True):
        """Reference memory-optim pass -> jax buffer donation on run()
        inputs (the analog: reuse input buffers for activations)."""
        self._memory_optim = bool(flag)

    def memory_optim_enabled(self):
        return getattr(self, "_memory_optim", False)

    def enable_mkldnn(self):
        pass  # x86-only backend knob; XLA:CPU handles vectorization

    def enable_tensorrt_engine(self, *a, **k):
        raise NotImplementedError(
            "TensorRT is a CUDA engine; the XLA pipeline is always on "
            "— precision is controlled via enable_low_precision()")

    def enable_low_precision(self, dtype="bfloat16"):
        """Serve in low precision (the EnableTensorRtEngine precision
        analog on TPU): weights+compute cast at load."""
        self._low_precision = str(dtype)

    def switch_use_feed_fetch_ops(self, flag=False):
        pass

    def switch_specify_input_names(self, flag=True):
        pass

    def set_optim_cache_dir(self, path):
        """Reference Config::SetOptimCacheDir — persists optimized
        programs.  TPU analog: the jax persistent compilation cache (the
        compiled XLA executable IS the optimized program).  Where
        ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside it
        stays there; the directory in use is what gets recorded."""
        from ..utils import enable_compile_cache

        self._optim_cache_dir = enable_compile_cache(cache_dir=path)

    def use_gpu(self):
        d = getattr(self, "_device", None)
        return bool(d) and d[0] == "accel"

    def gpu_device_id(self):
        d = getattr(self, "_device", None)
        return d[1] if d else 0

    def disable_glog_info(self):
        import logging

        logging.getLogger("jax").setLevel(logging.ERROR)
        self._glog_disabled = True

    def glog_info_disabled(self):
        return getattr(self, "_glog_disabled", False)

    def enable_profile(self):
        self._profile = True

    def pass_builder(self):
        """XLA owns the pass pipeline; expose a no-op recorder so tooling
        that deletes passes keeps working."""
        cfg = self

        class _PassBuilder:
            def all_passes(self):
                return []

            def delete_pass(self, name):
                cfg._deleted_passes = getattr(cfg, "_deleted_passes",
                                              set()) | {name}

        return _PassBuilder()

    def exp_disable_tensorrt_ops(self, ops):
        pass  # no TensorRT on TPU

    def set_model(self, model_path, params_path=None):
        self.model_path = model_path

    def model_dir(self):
        return self.model_path

    def summary(self):
        rows = [("model_path", str(self.model_path)),
                ("device", str(getattr(self, "_device", None))),
                ("ir_optim", str(getattr(self, "_ir_optim", True))),
                ("memory_optim",
                 str(getattr(self, "_memory_optim", False))),
                ("low_precision",
                 str(getattr(self, "_low_precision", None)))]
        w = max(len(k) for k, _ in rows) + 2
        return "\n".join(f"{k:<{w}}{v}" for k, v in rows)


class DataType:
    """Reference paddle_infer.DataType (paddle_inference_api.h)."""

    FLOAT32 = "float32"
    FLOAT16 = "float16"
    BFLOAT16 = "bfloat16"
    INT64 = "int64"
    INT32 = "int32"
    INT8 = "int8"
    UINT8 = "uint8"
    BOOL = "bool"


class PrecisionType:
    """Reference paddle_infer.PrecisionType."""

    Float32 = "float32"
    Half = "float16"
    Bfloat16 = "bfloat16"
    Int8 = "int8"


class PlaceType:
    """Reference paddle_infer.PlaceType."""

    CPU = "cpu"
    GPU = "gpu"
    XPU = "xpu"
    CUSTOM = "custom"
    UNK = "unk"


class Tensor:
    """Inference tensor handle (reference paddle_infer.Tensor /
    wrapper.py:45 tensor_copy_from_cpu): the zero-copy feed/fetch slot of
    the handle-based run workflow."""

    def __init__(self, name=""):
        self.name = name
        self._data = None

    def copy_from_cpu(self, data):
        import jax.numpy as jnp

        self._data = jnp.asarray(np.asarray(data))

    def share_external_data(self, data):
        """wrapper.py:59 — adopt the buffer without a copy (device arrays
        pass through)."""
        from ..core.tensor import Tensor as _T

        self._data = data._data if isinstance(data, _T) else data

    def copy_to_cpu(self):
        return np.asarray(self._data)

    def reshape(self, shape):
        if self._data is not None:
            self._data = self._data.reshape(tuple(shape))

    def shape(self):
        return list(self._data.shape) if self._data is not None else []

    def type(self):
        return str(self._data.dtype) if self._data is not None else None


class Predictor:
    """predictor = create_predictor(config)  # or Predictor(layer)
    out = predictor.run([np_array, ...])  -> [np_array, ...]

    Also serves the reference's handle workflow
    (paddle_inference_api.h:81):
        h = predictor.get_input_handle(name); h.copy_from_cpu(x)
        predictor.run()
        out = predictor.get_output_handle(
            predictor.get_output_names()[0]).copy_to_cpu()
    """

    def __init__(self, source, model_builder=None):
        from ..nn.layers import Layer

        if isinstance(source, Config):
            from .. import jit as pjit

            translated = pjit.load(source.model_path)
            if model_builder is not None:
                layer = model_builder()
                layer.set_state_dict(translated.state_dict())
                self.layer = layer
            elif translated.has_program():
                # Artifact-only inference: execute the saved program
                # directly — no python model code (reference
                # analysis_predictor.h:105 ability).
                self.layer = translated
            else:
                raise ValueError(
                    "this artifact carries no executable program (saved "
                    "without input_spec) — pass model_builder: a callable "
                    "returning the Layer to load the saved weights into")
        elif isinstance(source, Layer):
            self.layer = source
        else:
            raise TypeError(f"Predictor expects Config or Layer, got "
                            f"{type(source)}")
        self._config = source if isinstance(source, Config) else None
        self.layer.eval()
        self._jitted = None
        self._input_handles = {}
        self._output_handles = {}
        self._output_names = []

    def _build(self):
        import jax

        from ..jit.functional import functional_call, param_tree

        layer = self.layer
        self._params = param_tree(layer, trainable_only=False)
        cfg = self._config
        if cfg is not None and getattr(cfg, "_low_precision", None):
            import jax.numpy as jnp

            from ..core import dtype as _dt

            lp = _dt.convert_dtype(cfg._low_precision)
            self._params = {
                k: (v.astype(lp)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v)
                for k, v in self._params.items()}
        if cfg is not None and getattr(cfg, "_device", None):
            kind, idx = cfg._device
            devs = (jax.devices("cpu") if kind == "cpu"
                    else jax.devices())
            dev = devs[min(idx, len(devs) - 1)]
            self._params = jax.device_put(self._params, dev)

        def fwd(params, *inputs):
            return functional_call(layer, params, *inputs)

        if cfg is not None and cfg.memory_optim_enabled():
            # memory-optim pass analog: donate input buffers so XLA can
            # reuse them for activations (per-arity jit cache — donation
            # positions depend on how many inputs arrive).  Only buffers
            # the predictor itself created are donatable; caller-owned
            # arrays (handles, live Tensors) must survive run().
            cache = {}
            plain = jax.jit(fwd)

            def jitted(params, *ins, _donate=False):
                if not _donate:
                    return plain(params, *ins)
                fn = cache.get(len(ins))
                if fn is None:
                    fn = jax.jit(
                        fwd, donate_argnums=tuple(range(1, len(ins) + 1)))
                    cache[len(ins)] = fn
                return fn(params, *ins)

            self._jitted = jitted
            self._can_donate = True
        else:
            self._jitted = jax.jit(fwd)
            self._can_donate = False

    def get_input_names(self):
        import inspect

        sig = inspect.signature(self.layer.forward)
        return [p for p in sig.parameters if p != "self"]

    # -- handle workflow (reference get_input_handle / get_output_handle) --

    def get_input_handle(self, name):
        return self._input_handles.setdefault(name, Tensor(name))

    def get_output_names(self):
        if not self._output_names:
            # one generic slot per output; populated after the first run
            return ["output_0"]
        return list(self._output_names)

    def get_output_handle(self, name):
        return self._output_handles.setdefault(name, Tensor(name))

    def _run_handles(self):
        names = self.get_input_names()
        ins = []
        for n in names:
            h = self._input_handles.get(n)
            if h is None or h._data is None:
                raise ValueError(
                    f"input handle {n!r} not fed — call "
                    "get_input_handle(name).copy_from_cpu(data) first")
            ins.append(h._data)
        outs = self._execute(ins)
        self._output_names = [f"output_{i}" for i in range(len(outs))]
        for i, o in enumerate(outs):
            self.get_output_handle(self._output_names[i])._data = o
        return True

    def _execute(self, ins, donatable=False):
        if self._jitted is None:
            self._build()
        if donatable and self._can_donate:
            out = self._jitted(self._params, *ins, _donate=True)
        else:
            out = self._jitted(self._params, *ins)
        return list(out) if isinstance(out, (tuple, list)) else [out]

    def run(self, inputs=None):
        """List style: run([np, ...]) -> [np, ...].  Handle style (the
        reference's primary workflow): feed via get_input_handle, call
        run() with no args, fetch via get_output_handle."""
        import jax.numpy as jnp

        from ..core.tensor import Tensor as _T

        if inputs is None:
            return self._run_handles()
        # Donation is only safe for buffers created here from host data —
        # a live user Tensor must survive run().
        donatable = all(not isinstance(i, _T) and not hasattr(i, "devices")
                        for i in inputs)
        ins = [i._data if isinstance(i, _T) else jnp.asarray(i)
               for i in inputs]
        return [np.asarray(o)
                for o in self._execute(ins, donatable=donatable)]


def create_predictor(config, model_builder=None):
    return Predictor(config, model_builder=model_builder)


class PredictorPool:
    """Reference paddle_infer.PredictorPool(config, size): a pool of
    predictors sharing one loaded program (XLA executables are shared via
    the jit cache; parameters are shared by reference)."""

    def __init__(self, config, size=1, model_builder=None):
        self._predictors = [create_predictor(config, model_builder)
                            for _ in range(int(size))]

    def retrieve(self, idx):
        return self._predictors[idx]


class XpuConfig:
    """Signature-parity config for XPU device binding (no XPU backend in
    a TPU build; attributes are recorded)."""

    def __init__(self):
        self.device_id = 0
        self.l3_size = 0
        self.conv_autotune_level = 0


def get_version():
    from .. import __version__

    return f"paddle_tpu {__version__} (XLA inference)"


def get_num_bytes_of_data_type(dtype):
    import jax.numpy as jnp

    from ..core import dtype as _dt

    return jnp.dtype(_dt.convert_dtype(dtype)).itemsize


def get_trt_compile_version():
    return (0, 0, 0)  # no TensorRT in a TPU build


def get_trt_runtime_version():
    return (0, 0, 0)


def _get_phi_kernel_name(op_name):
    """Reference maps fluid op names to phi kernel names; the registry IS
    the kernel table here."""
    return op_name


def convert_to_mixed_precision(model_file, params_file=None,
                               mixed_model_file=None,
                               mixed_params_file=None,
                               mixed_precision="bfloat16", backend=None,
                               keep_io_types=True, black_list=None,
                               model_builder=None, **kwargs):
    """Reference wrapper.py:79 — rewrite a saved artifact with float
    weights cast to the mixed precision (fp16/bf16).

    The saved program (StableHLO export) bakes weights in as constants, so
    a program-carrying artifact needs ``model_builder`` (a callable
    returning the Layer) to re-lower at the new precision — the analog of
    the reference's program-proto rewrite pass.  Weights-only artifacts
    are cast in place."""
    import pickle

    import jax.numpy as jnp

    from ..core import dtype as _dt

    lp = _dt.convert_dtype(
        mixed_precision if isinstance(mixed_precision, str)
        else str(mixed_precision))
    black = set(black_list or [])
    with open(model_file + ".pdparams", "rb") as f:
        payload = pickle.load(f)
    state = {}
    for k, v in payload["state_dict"].items():
        arr = jnp.asarray(v)
        if k not in black and jnp.issubdtype(arr.dtype, jnp.floating):
            arr = arr.astype(lp)
        state[k] = np.asarray(arr)
    if "exported" in payload or "stablehlo" in payload:
        if model_builder is None:
            raise ValueError(
                "this artifact carries a lowered program whose weights "
                "are baked into the StableHLO — pass model_builder to "
                "re-lower it at the mixed precision")
        from .. import jit as pjit
        from ..core.tensor import Tensor as _T
        from ..jit import InputSpec
        from jax import export as _export

        layer = model_builder()
        layer.set_state_dict({k: _T(jnp.asarray(v))
                              for k, v in state.items()})
        exp = _export.deserialize(payload["exported"])
        specs = []
        for aval in exp.in_avals:
            dt = aval.dtype
            if not keep_io_types and jnp.issubdtype(dt, jnp.floating):
                dt = lp
            specs.append(InputSpec(shape=aval.shape, dtype=dt))
        pjit.save(layer, mixed_model_file, input_spec=specs)
        return mixed_model_file
    payload["state_dict"] = state
    with open(mixed_model_file + ".pdparams", "wb") as f:
        pickle.dump(payload, f)
    return mixed_model_file


from .paged import (  # noqa: F401,E402
    PagedKVCache, masked_multihead_attention, paged_decode_attention,
)
from .server import (  # noqa: F401,E402
    PagedExecutor, RequestHandle, RequestState, ServingEngine,
)
