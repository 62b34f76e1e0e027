"""Bring-up contracts (ISSUE 21): the compile cache can be placed from
outside and is never moved by building an engine; nothing hides the
device (the chip entry point fails without a chip); one process per
chip (importing the package starts no backend).
All CPU and cheap — the chip side is ``python chip_smoke.py``."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Snapshot/restore the process-global cache config a test moves."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    prev = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in prev.items():
        jax.config.update(n, v)


def test_cache_dir_from_env_is_left_alone(monkeypatch, tmp_path,
                                          cache_config):
    from paddle_tpu import utils

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert utils.jax_cache_dir() == "/placed/from/outside"
    # an explicit directory loses to the environment too, and nothing
    # calls jax.config.update("jax_compilation_cache_dir", ...)
    assert utils.enable_compile_cache(
        cache_dir=str(tmp_path)) == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.listdir(tmp_path)


def test_cache_dir_default_is_the_fixed_checkout_path(monkeypatch,
                                                      cache_config):
    from paddle_tpu import utils

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert utils.jax_cache_dir() == want
    assert utils.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the AOT executable store and autotune.json root inside it, not in
    # a home directory: they decide which kernel `auto` picks
    monkeypatch.delenv("PT_CACHE_DIR", raising=False)
    monkeypatch.delenv("PT_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("PT_AUTOTUNE_CACHE", raising=False)
    from paddle_tpu.core import aot
    from paddle_tpu.ops import autotune

    assert aot.compile_cache_dir() == os.path.join(
        want, "paddle_tpu", "compile")
    assert autotune.cache_path() == os.path.join(
        want, "paddle_tpu", "autotune.json")


def test_cache_enable_failure_is_reported(monkeypatch, tmp_path,
                                          cache_config):
    from paddle_tpu import utils

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    with pytest.raises(OSError):
        utils.enable_compile_cache(cache_dir=str(blocker / "cache"))


def test_building_compile_caches_does_not_move_the_global_cache(tmp_path):
    from paddle_tpu.core import aot

    before = jax.config.jax_compilation_cache_dir
    aot.CompileCache(str(tmp_path / "a"))
    aot.CompileCache(str(tmp_path / "b"))
    assert jax.config.jax_compilation_cache_dir == before


def test_registered_train_contract_does_not_pin_the_step():
    """The graph-contract registry outlives every step; it must not keep
    a dropped step's params/master/moments alive (on the chip that is
    10+ GB of HBM the next model cannot have)."""
    import gc
    import weakref

    import numpy as np

    import paddle_tpu.nn.functional as F
    from paddle_tpu import analysis, nn
    from paddle_tpu.models import CompiledTrainStep

    step = CompiledTrainStep(nn.Linear(4, 4), lr=1e-3, loss_fn=F.mse_loss)
    x = np.zeros((2, 4), np.float32)
    step.step(x, x)
    assert analysis.registered()["train.step"].example_args() is not None
    alive = weakref.ref(step.params["weight"])
    del step
    gc.collect()
    assert alive() is None
    assert analysis.registered()["train.step"].example_args() is None


def test_attention_kernel_runs_per_shard_under_a_mesh():
    """GSPMD cannot partition a Mosaic kernel (lowering one under a
    multi-device jit raises on real chips), so a sharded step runs the
    attention kernel per (batch, head) shard under shard_map.  Here:
    the real kernel in the Pallas interpreter on the virtual CPU mesh,
    forward and backward, against einsum attention."""
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops import nn_ops
    from paddle_tpu.ops.pallas_kernels.long_attention import long_attention

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    shard = (mesh, "dp", "mp")
    with nn_ops.kernel_mesh(*shard):
        assert nn_ops.current_kernel_mesh() == shard
    assert nn_ops.current_kernel_mesh() is None

    key = jax.random.PRNGKey(0)
    q, k, v = (jax.device_put(
        jax.random.normal(jax.random.fold_in(key, i), (2, 2, 128, 128),
                          jnp.float32) * 0.3,
        NamedSharding(mesh, P("dp", "mp"))) for i in range(3))

    def kernel(q, k, v):
        return nn_ops._per_shard(
            shard, lambda q, k, v: long_attention(
                q, k, v, None, 128, True, None), q, k, v)

    def ref(q, k, v):       # [B, H, S, D] -> einsum path's [B, S, H, D]
        out = nn_ops._sdpa_plain(*(jnp.swapaxes(t, 1, 2) for t in (q, k, v)),
                                 causal=True, impl="einsum")
        return jnp.swapaxes(out, 1, 2)

    def vg(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2)))

    with pltpu.force_tpu_interpret_mode(), jax.enable_x64(False):
        got, got_grads = vg(kernel)(q, k, v)
        want, want_grads = vg(ref)(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for a, b in zip(got_grads, want_grads):
        assert a.sharding.is_equivalent_to(q.sharding, a.ndim)
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_train_step_reads_kernel_axes_off_its_shardings():
    """The step tells the attention kernels which mesh axes split the
    batch and the heads from its own shardings, not from the names
    ``dp``/``mp``; a size-1 axis splits nothing."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.models import CompiledTrainStep

    def build(shape, rules):
        mesh = ProcessMesh(shape=shape, dim_names=["data", "tp", "x"])
        step = CompiledTrainStep(nn.Linear(4, 4), mesh=mesh,
                                 shard_rules=rules, dp_axis="data",
                                 loss_fn=F.mse_loss)
        return mesh.jax_mesh, step

    def column(name, shape):
        return (None, "tp") if len(shape) == 2 else (None,)

    mesh, step = build([2, 2, 1], column)
    assert step._kernel_shard == (mesh, "data", "tp")
    mesh, step = build([4, 1, 1], column)
    assert step._kernel_shard == (mesh, "data", None)
    with pytest.raises(NotImplementedError, match=r"\['tp', 'x'\]"):
        build([2, 2, 2], lambda name, shape:
              ("x", "tp") if len(shape) == 2 else (None,))


def test_sharded_attention_dropout_differs_across_shards():
    """short_attention hashes (seed, LOCAL program id): run per shard
    with one seed, every shard would drop the same elements.  The same
    rows in every (batch, head) make the masks comparable; v = I makes
    the output the dropped probability matrix, and the cotangent I
    makes dV its transpose, so the backward's mask shows too."""
    import importlib

    import numpy as np
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops import nn_ops

    sa = importlib.import_module(
        "paddle_tpu.ops.pallas_kernels.short_attention")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    B, H, n = 4, 2, 128             # two programs per shard
    eye = jnp.eye(n, dtype=jnp.float32)
    put = lambda x: jax.device_put(                     # noqa: E731
        jnp.broadcast_to(x, (B, H, n, n)),
        NamedSharding(mesh, P("dp", "mp")))
    q = put(jax.random.normal(jax.random.PRNGKey(0), (n, n),
                              jnp.float32) * 0.3)

    def run(v):
        return nn_ops._per_shard(
            (mesh, "dp", "mp"), lambda q, k, v, seed: sa.short_attention(
                q, k, v, seed, None, 0.3, False),
            q, q, v, seed=jnp.asarray(7, jnp.int32))

    with pltpu.force_tpu_interpret_mode(), jax.enable_x64(False):
        out = jax.jit(run)(put(eye))
        dv = jax.jit(jax.grad(lambda v: jnp.sum(run(v) * eye)))(put(eye))
    fwd = np.asarray(out).reshape(B * H, n, n) == 0
    bwd = np.swapaxes(np.asarray(dv).reshape(B * H, n, n), 1, 2) == 0
    assert abs(fwd.mean() - 0.3) < 0.02
    assert len({m.tobytes() for m in fwd}) == B * H
    assert (fwd == bwd).all()


def test_paged_decode_gate_is_what_the_chip_showed():
    from paddle_tpu.ops.pallas_kernels import paged_decode as pd

    # v5e, jax 0.9 (PERF.md "Bring-up on v5e"): page_size 8 compiles
    # and agrees for f32 AND bf16 pools, so the gate does not narrow
    # for bf16; int8 pools keep their 32-row gate
    assert pd.supported(128, 8, True) and pd.supported(128, 16, True)
    assert not pd.supported(128, 4, True)
    assert not pd.supported(64, 16, True)       # lanes
    assert not pd.supported(128, 16, False)     # off-TPU: dense path
    assert pd.supported_quant(128, 32, True)
    assert not pd.supported_quant(128, 16, True)


def test_walker_names_pallas_kernels_and_how_they_run():
    from paddle_tpu.analysis import walker
    from paddle_tpu.ops.pallas_kernels import paged_decode as pd

    kp = jnp.zeros((2, 4, 8, 128), jnp.float32)

    def fn(q):
        return pd.paged_decode(q, kp, kp, jnp.ones((1,), jnp.int32),
                               jnp.zeros((1, 2), jnp.int32))

    jaxpr = jax.make_jaxpr(fn)(jnp.zeros((1, 2, 128), jnp.float32))
    (kernel, interpreted), = walker.pallas_kernels(jaxpr)
    assert kernel.startswith("_kernel at ") and "paged_decode.py" in kernel
    assert interpreted          # off-TPU; compiled by Mosaic on the chip
    assert kernel in walker.name_inventory(jaxpr)


def _run(args, **env):
    return subprocess.run(
        [sys.executable] + args, cwd=REPO, capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_chip_smoke_without_a_chip_fails_and_names_the_platform():
    p = _run(["chip_smoke.py"])
    assert p.returncode not in (0, None)
    assert "platform is 'cpu'" in p.stderr
    assert p.stdout.strip() == ""           # no result line


def test_chip_smoke_last_line_is_the_verdict_and_nothing_else():
    # the smoke's checker reads the last stdout line and refuses any key
    # beyond these; the full report goes on the line before it
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    report = {"ok": True, "versions": {"jax": "0.9.0"}, "legs": {},
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1},
              "claim": None}
    line = json.loads(json.dumps(smoke.verdict(report)))
    assert line == {"ok": True, "device": {"platform": "tpu",
                                           "kind": "TPU v5 lite",
                                           "count": 1}}
    assert type(line["device"]["count"]) is int
    src = open(os.path.join(REPO, "chip_smoke.py")).read()
    assert (src.rindex("print(json.dumps(verdict(report))")
            > src.rindex("print(json.dumps(report)"))   # printed last


def test_importing_the_package_starts_no_backend():
    # a parent that has touched jax holds the chip; launchers rely on
    # the import alone being free
    p = _run(["-c", "import paddle_tpu, paddle_tpu.distributed.launch.main\n"
                    "from jax._src import xla_bridge\n"
                    "assert not xla_bridge._backends, xla_bridge._backends"])
    assert p.returncode == 0, p.stderr[-400:]
