"""Programs of the serving path compiled for the v5e at the benchmark's
size, here, with no chip attached: what the chip's compiler makes of them
costs no chip time to see.  Every test that describes the topology lives in
this one file (one process at a time may load the TPU's library), and the
description is made inside a fixture, never while a module is imported."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.inference.paged import _write_span

# mistral7b-serve's pools: 8 layers x 8 KV heads x 4,096 pages x 16 x 128
POOL = (8, 8, 4096, 16, 128)
POOL_SIZED_COPY = re.compile(
    r"= bf16\[8,8,4096,16,128\]\S* (copy|transpose)\(")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compile_writer(fn, one_chip, T, index):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds(POOL, jnp.bfloat16)
    kv = sds((POOL[0], POOL[1], T, POOL[4]), jnp.bfloat16)
    return jax.jit(fn, donate_argnums=(0, 1)).lower(
        pool, pool, kv, kv, *(sds(s, jnp.int32) for s in index)).compile()


@pytest.mark.parametrize("T", [256, 8])
def test_the_page_writer_moves_no_pool(one_chip, T):
    """serve.kv_write at a chunk of the cell: both pools aliased to the
    outputs, no temporary worth the name, and no copy of a pool's size."""
    exe = compile_writer(_write_span, one_chip, T,
                         [((T - 1) // 16 + 2,), ()])
    text, mem = exe.as_text(), exe.memory_analysis()
    assert "input_output_alias={ {0}: (0, {}, may-alias), " \
        "{1}: (1, {}, may-alias) }" in text[:text.index("\n")]
    assert mem.alias_size_in_bytes == 2 * 2 * 8 * 8 * 4096 * 16 * 128
    assert mem.temp_size_in_bytes < 64 << 20
    assert not POOL_SIZED_COPY.search(text)


def test_a_row_per_token_re_lays_the_pool(one_chip):
    """Why the writer moves whole pages: the same span as one scatter of
    token rows makes the compiler copy each pool into another layout and
    back (the control that shows the test above can see such a copy)."""
    def rows(kp, vp, k, v, pids, offs):
        return (kp.at[:, :, pids, offs].set(k),
                vp.at[:, :, pids, offs].set(v))

    exe = compile_writer(rows, one_chip, 256, [(256,), (256,)])
    assert exe.memory_analysis().temp_size_in_bytes > 1 << 30
    assert len(POOL_SIZED_COPY.findall(exe.as_text())) == 4
