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


# -- the hybrid executor's state path (granite4h-micro-serve) -------------------

# a run of 9 state-space layers at 64 slots: 1.2 GB of float32 state
STATE = (9, 64, 32, 128, 128)
STATE_SIZED_COPY = re.compile(
    r"= f32\[9,64,32,128,128\]\S* (copy|transpose)\(")


def test_the_state_kernel_updates_the_carried_pool_in_place(one_chip):
    """ops/pallas_kernels/ssm_decode.py at the cell's size, inside a scan
    over the run's layers with the pool as the carry: Mosaic takes the
    kernel, the pool is aliased to the output, and no copy of its size
    (nor any temporary worth the name) is made."""
    from paddle_tpu.ops.pallas_kernels import ssm_decode

    L, S, G, N, KP = STATE

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(pool, decay, xdt, B, C, live):
        def layer(carry, i):
            pool, acc = carry
            y, pool = ssm_decode._ssm_decode_call(pool, i, decay, xdt + acc,
                                                  B, C, live)
            return (pool, acc + y), None

        (pool, acc), _ = jax.lax.scan(
            layer, (pool, jnp.zeros((S, G, KP), jnp.float32)),
            jnp.arange(L, dtype=jnp.int32))
        return pool, acc

    rows = sds((S, G, KP), jnp.float32)
    exe = jax.jit(step, donate_argnums=0).lower(
        sds(STATE, jnp.float32), rows, rows, sds((S, N), jnp.float32),
        sds((S, N), jnp.float32), sds((S,), jnp.bool_)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert "tpu_custom_call" in text
    assert "input_output_alias={ {0}: (0, {}, may-alias) }" \
        in text[:text.index("\n")]
    assert mem.alias_size_in_bytes == 4 * 9 * 64 * 32 * 128 * 128
    assert mem.temp_size_in_bytes < 8 << 20
    assert not STATE_SIZED_COPY.search(text)


def test_the_state_writer_moves_no_pool(one_chip):
    """serve.state_write: one slot's rows of every layer of a run into the
    donated pools, nothing else of them moved."""
    from paddle_tpu.inference.state_cache import _write_slot

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ssm, conv = sds(STATE, jnp.float32), sds((9, 3, 64, 4352), jnp.bfloat16)
    exe = jax.jit(_write_slot, donate_argnums=(0, 1)).lower(
        (ssm,), (conv,), (sds((9, 32, 128, 128), jnp.float32),),
        (sds((9, 3, 4352), jnp.bfloat16),), sds((), jnp.int32)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * 9 * 64 * 32 * 128 * 128 \
        + 2 * 9 * 3 * 64 * 4352
    assert mem.temp_size_in_bytes < 8 << 20
    assert not STATE_SIZED_COPY.search(text)


def test_a_token_into_the_folded_kv_pool_moves_no_pool(one_chip):
    """The hybrid decode's page patch and window gather on the pool of 4
    attention layers x 4 folded KV heads x 8,192 pages x 16 x 128: the
    pool is aliased, and neither a layer (134 MB) nor the pool is copied
    — only each sequence's window is gathered."""
    from paddle_tpu.inference.server import hybrid_executor as hx

    shape = (4, 4, 8192, 16, 128)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(pool, pids, offs, x, q, lengths, tables):
        flat = hx._flat(pool)
        flat = hx._put_token(flat, shape, 2, pids, offs, x)
        o = hx._pool_attention(q, flat, flat, shape, 2, lengths, tables, 2)
        return flat.reshape(shape), o

    i32 = jnp.int32
    exe = jax.jit(step, donate_argnums=0).lower(
        sds(shape, jnp.bfloat16), sds((64,), i32), sds((64,), i32),
        sds((64, 4, 128), jnp.bfloat16), sds((64, 32, 64), jnp.bfloat16),
        sds((64,), i32), sds((64, 128), i32)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 4 * 4 * 8192 * 16 * 128
    # the gathered windows (134 MB each for keys and values) and no more
    assert mem.temp_size_in_bytes < 400 << 20
    assert not re.search(r"= bf16\[(4,)?4,8192,16,128\]\S* "
                         r"(copy|transpose|fusion)\(", text)
