"""Inference Config/Predictor surface (VERDICT r4 next #8).

Reference: ``paddle/fluid/inference/api/paddle_inference_api.h:81``
(Predictor + handle workflow), ``paddle_analysis_config.h`` (Config
knobs), ``python/paddle/inference/wrapper.py:79``
(convert_to_mixed_precision).
"""
from __future__ import annotations

import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import inference as infer


def _save_model(tmp_path, with_program=True):
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import InputSpec

    layer = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    prefix = str(tmp_path / "model")
    spec = [InputSpec(shape=(2, 8), dtype="float32")] if with_program \
        else None
    paddle.jit.save(layer, prefix, input_spec=spec)
    return layer, prefix


def test_predictor_handle_workflow(tmp_path):
    layer, prefix = _save_model(tmp_path)
    cfg = infer.Config(prefix)
    predictor = infer.create_predictor(cfg)
    names = predictor.get_input_names()
    x = np.random.RandomState(0).randn(2, 8).astype(np.float32)
    h = predictor.get_input_handle(names[0])
    h.copy_from_cpu(x)
    assert h.shape() == [2, 8]
    predictor.run()
    out_name = predictor.get_output_names()[0]
    got = predictor.get_output_handle(out_name).copy_to_cpu()
    want = np.asarray(layer(paddle.to_tensor(x)).numpy())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_config_knobs_drive_predictor(tmp_path):
    layer, prefix = _save_model(tmp_path)
    cfg = infer.Config(prefix)
    cfg.disable_gpu()
    cfg.enable_memory_optim()
    cfg.switch_ir_optim(True)
    assert cfg.memory_optim_enabled()
    assert not cfg.use_gpu()
    assert "model_path" in cfg.summary()
    predictor = infer.create_predictor(cfg)
    x = np.random.RandomState(0).randn(2, 8).astype(np.float32)
    out = predictor.run([x])[0]
    want = np.asarray(layer(paddle.to_tensor(x)).numpy())
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    # memory-optim path donates inputs; a second run must still work
    out2 = predictor.run([x.copy()])[0]
    np.testing.assert_allclose(out2, want, rtol=1e-4, atol=1e-5)


def test_config_low_precision(tmp_path):
    layer, prefix = _save_model(tmp_path, with_program=False)
    cfg = infer.Config(prefix)
    cfg.enable_low_precision("bfloat16")
    import paddle_tpu.nn as nn

    def builder():
        return nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))

    predictor = infer.Predictor(cfg, model_builder=builder)
    x = np.random.RandomState(0).randn(2, 8).astype(np.float32)
    out = predictor.run([x])[0]
    want = np.asarray(layer(paddle.to_tensor(x)).numpy())
    np.testing.assert_allclose(out.astype(np.float32), want,
                               rtol=0.05, atol=0.05)


def test_predictor_pool(tmp_path):
    _layer, prefix = _save_model(tmp_path)
    pool = infer.PredictorPool(infer.Config(prefix), 2)
    x = np.random.RandomState(0).randn(2, 8).astype(np.float32)
    a = pool.retrieve(0).run([x])[0]
    b = pool.retrieve(1).run([x])[0]
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_convert_to_mixed_precision_weights_only(tmp_path):
    import pickle

    _layer, prefix = _save_model(tmp_path, with_program=False)
    mixed = str(tmp_path / "mixed")
    infer.convert_to_mixed_precision(prefix, mixed_model_file=mixed,
                                     mixed_precision="bfloat16")
    with open(mixed + ".pdparams", "rb") as f:
        payload = pickle.load(f)
    for k, v in payload["state_dict"].items():
        assert str(v.dtype) == "bfloat16", (k, v.dtype)


def test_convert_program_to_mixed_precision_needs_builder(tmp_path):
    import paddle_tpu.nn as nn

    layer, prefix = _save_model(tmp_path, with_program=True)
    mixed = str(tmp_path / "mixed")
    with pytest.raises(ValueError, match="model_builder"):
        infer.convert_to_mixed_precision(prefix, mixed_model_file=mixed)

    def builder():
        return nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))

    infer.convert_to_mixed_precision(prefix, mixed_model_file=mixed,
                                     mixed_precision="bfloat16",
                                     model_builder=builder)
    predictor = infer.create_predictor(infer.Config(mixed))
    x = np.random.RandomState(0).randn(2, 8).astype(np.float32)
    out = predictor.run([x])[0]
    want = np.asarray(layer(paddle.to_tensor(x)).numpy())
    np.testing.assert_allclose(out.astype(np.float32), want,
                               rtol=0.05, atol=0.05)


def test_misc_inference_surface():
    assert infer.get_num_bytes_of_data_type("float32") == 4
    assert infer.get_num_bytes_of_data_type(infer.DataType.BFLOAT16) == 2
    assert infer.get_trt_compile_version() == (0, 0, 0)
    assert "paddle_tpu" in infer.get_version()
    t = infer.Tensor("x")
    t.copy_from_cpu(np.ones((2, 3), np.float32))
    t.reshape([3, 2])
    assert t.shape() == [3, 2] and t.type() == "float32"
    assert infer.PrecisionType.Bfloat16 == "bfloat16"
    assert infer.PlaceType.CPU == "cpu"
    infer.XpuConfig()


def test_optim_cache_dir(tmp_path):
    import jax

    prev = jax.config.jax_compilation_cache_dir
    try:
        cfg = infer.Config()
        cfg.set_optim_cache_dir(str(tmp_path / "cache"))
        assert jax.config.jax_compilation_cache_dir == \
            str(tmp_path / "cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
