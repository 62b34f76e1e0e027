"""Programs of the serving path compiled for the v5e at the benchmark's
size, here, with no chip attached: what the chip's compiler makes of them
costs no chip time to see.  Every test that describes the topology lives in
this one file (one process at a time may load the TPU's library), and the
description is made inside a fixture, never while a module is imported.

Pinned so far, each writer with the pools aliased from input to output and
no copy of a pool or of a layer of one: mistral7b-serve's page writer
(``serve.kv_write``), its whole decode step (``serve.decode``, the pools
carried through the layer scan into the Pallas kernel) and that kernel at a
window of 8,192 tokens; granite4h-micro's
whole decode step (``serve.hybrid_decode``), its state kernel inside a
scan, its state writer, and the page patch and the fused kernel's call on
its folded KV pool; and, reading the pools without writing them,
mistral7b-serve's prefill chunk (``serve.prefill_chunk``, its past gathered
by row inside the layer scan); and the grouped expert kernel of a chunk
(``ops/pallas_kernels/grouped_swiglu.py``) alone at both MoE cells' sizes
and inside ``serve.window_chunk`` / ``serve.mla_chunk``, where no expert is
sliced or copied out and no loop over experts is left.  Each pool test has
a control beside it or in it that shows the compiler's copies when the
program is written the other way, so a serving program can be checked for
pool copies before any chip time."""
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.inference.paged import (
    _flat, _put_token, _rows, _write_span,
)

# mistral7b-serve's pools: 8 layers x 8 KV heads x 4,096 pages x 16 x 128
POOL = (8, 8, 4096, 16, 128)
# a whole pool (1.07 GB) copied or re-laid: what a row-per-token scatter
# costs the page writer
POOL_SIZED_COPY = re.compile(
    r"= bf16\[8,8,4096,16,128\]\S* (copy|transpose)\(")
# a pool or ONE LAYER of it (134 MB) produced by a copy, a transpose or a
# fusion (a layer sliced out of the pool, re-laid, or stacked back): what a
# decode step made 8 + 2 times before the pools were a carry of its scan
POOL_OR_LAYER_MOVED = re.compile(
    r"= bf16\[(8,|1,)?8,4096,16,128\]\S* (copy|transpose|fusion)\(")


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


@pytest.fixture(scope="module")
def sds(one_chip):
    """A shape on the described chip (bf16 unless told)."""
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return sds


def compile_writer(fn, sds, T, index):
    pool = sds(POOL, jnp.bfloat16)
    kv = sds((POOL[0], POOL[1], T, POOL[4]), jnp.bfloat16)
    return jax.jit(fn, donate_argnums=(0, 1)).lower(
        pool, pool, kv, kv, *(sds(s, jnp.int32) for s in index)).compile()


@pytest.mark.parametrize("T", [256, 8])
def test_the_page_writer_moves_no_pool(sds, T):
    """serve.kv_write at a chunk of the cell: both pools aliased to the
    outputs, no temporary worth the name, and no copy of a pool's size."""
    exe = compile_writer(_write_span, sds, T,
                         [((T - 1) // 16 + 2,), ()])
    text, mem = exe.as_text(), exe.memory_analysis()
    assert "input_output_alias={ {0}: (0, {}, may-alias), " \
        "{1}: (1, {}, may-alias) }" in text[:text.index("\n")]
    assert mem.alias_size_in_bytes == 2 * 2 * 8 * 8 * 4096 * 16 * 128
    assert mem.temp_size_in_bytes < 64 << 20
    assert not POOL_SIZED_COPY.search(text)


def test_a_row_per_token_re_lays_the_pool(sds):
    """Why the writer moves whole pages: the same span as one scatter of
    token rows makes the compiler copy each pool into another layout and
    back (the control that shows the test above can see such a copy)."""
    def rows(kp, vp, k, v, pids, offs):
        return (kp.at[:, :, pids, offs].set(k),
                vp.at[:, :, pids, offs].set(v))

    exe = compile_writer(rows, sds, 256, [(256,), (256,)])
    assert exe.memory_analysis().temp_size_in_bytes > 1 << 30
    assert len(POOL_SIZED_COPY.findall(exe.as_text())) == 4


# -- the decode step (mistral7b-serve.closed32) ------------------------------


@pytest.fixture
def compiled_kernel(monkeypatch):
    """Steer the program as the chip would: the fused kernel, compiled
    (here the backend is the CPU, which takes the dense path and the
    interpreter)."""
    from paddle_tpu.ops.pallas_kernels import grouped_swiglu, paged_decode

    monkeypatch.setenv("PT_PAGED_IMPL", "pallas")
    monkeypatch.setattr(paged_decode, "_interpret", lambda: False)
    monkeypatch.setattr(grouped_swiglu, "_on_tpu", lambda: True)
    monkeypatch.setattr(grouped_swiglu, "_interpret", lambda: False)


def mistral_executor(sds):
    """mistral7b-serve's executor without its arrays (Mistral-7B widths, 8
    layers), and the shapes of its stacked layers and top weights."""
    from paddle_tpu.inference.server.executor import PagedExecutor

    ex = object.__new__(PagedExecutor)
    ex.config = types.SimpleNamespace(
        num_attention_heads=32, num_key_value_heads=8, head_dim=128,
        num_hidden_layers=POOL[0], rms_norm_eps=1e-5)
    ex.cache = types.SimpleNamespace(page_size=POOL[3],
                                     compute_dtype=jnp.bfloat16)
    ex._tied = False
    L, H, F, V = POOL[0], 4096, 14336, 32768
    layers = {"input_layernorm.weight": sds((L, H)),
              "post_attention_layernorm.weight": sds((L, H)),
              "self_attn.q_proj.weight": sds((L, H, H)),
              "self_attn.k_proj.weight": sds((L, H, 1024)),
              "self_attn.v_proj.weight": sds((L, H, 1024)),
              "self_attn.o_proj.weight": sds((L, H, H)),
              "mlp.gate_proj.weight": sds((L, H, F)),
              "mlp.up_proj.weight": sds((L, H, F)),
              "mlp.down_proj.weight": sds((L, F, H))}
    tops = {"embed": sds((V, H)), "norm_w": sds((H,)),
            "head_w": sds((H, V)), "cos": sds((V, 128), jnp.float32),
            "sin": sds((V, 128), jnp.float32)}
    return ex, layers, tops


@pytest.mark.parametrize("batch", [32, 17])
def test_the_decode_step_moves_no_pool(sds, compiled_kernel, batch):
    """serve.decode at the cell's size, a table of 128 pages a sequence:
    both pools aliased to the outputs, the kernel in the program, and
    neither a pool nor a layer of one copied, re-laid or stacked; the
    temporaries stay under one layer's 134 MB."""
    ex, layers, tops = mistral_executor(sds)
    i32 = jnp.int32
    exe = jax.jit(ex._decode_fwd, donate_argnums=(4, 5)).lower(
        layers, tops, sds((batch,), i32), sds((batch,), i32), sds(POOL),
        sds(POOL), sds((batch,), i32), sds((batch, 128), i32)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert re.search(r"input_output_alias=\{ \{1\}: \(\d+, \{\}, may-alias\), "
                     r"\{2\}: \(\d+, \{\}, may-alias\) \}",
                     text[:text.index("\n")])
    assert mem.alias_size_in_bytes == 2 * 2 * 8 * 8 * 4096 * 16 * 128
    assert mem.temp_size_in_bytes < 128 << 20
    # ONE kernel a layer: the scan's body holds a single custom call
    assert len(re.findall(r" custom-call\(.*custom_call_target=\"tpu_custom_call\"",
                          text)) == 1
    assert not POOL_OR_LAYER_MOVED.search(text)


def test_pools_scanned_layer_by_layer_are_moved(sds, compiled_kernel):
    """The control: the decode step's pool handling as it was before — the
    pools as scanned inputs, a row per token scattered into the layer's
    slice, the kernel on that slice, the slices stacked back as outputs.
    The compiler slices every layer out, re-lays it and copies it back."""
    from paddle_tpu.ops.pallas_kernels.paged_decode import paged_decode


    def step(q, k_pages, v_pages, kv, pids, offs, lengths, tables):
        def block(q, pools):
            kp, vp = pools
            kp = kp.at[:, pids, offs].set(kv)
            vp = vp.at[:, pids, offs].set(kv)
            return q + paged_decode(q, kp, vp, lengths, tables), (kp, vp)

        return jax.lax.scan(block, q, (k_pages, v_pages))

    i32 = jnp.int32
    exe = jax.jit(step, donate_argnums=(1, 2)).lower(
        sds((32, 32, 128)), sds(POOL), sds(POOL), sds((8, 32, 128)),
        sds((32,), i32), sds((32,), i32), sds((32,), i32),
        sds((32, 128), i32)).compile()
    assert exe.memory_analysis().temp_size_in_bytes > 128 << 20
    assert len(POOL_OR_LAYER_MOVED.findall(exe.as_text())) >= 4


def test_the_decode_kernel_compiles_at_a_window_of_8192_tokens(
        sds, compiled_kernel):
    """The kernel alone at 512 pages a sequence (a pool four times the
    cell's, 32 sequences): Mosaic takes it, the table of 32 x 512 page
    ids rides the scalar prefetch, and the VMEM scratch is what it is at
    the cell's 128 pages — two slots of one 256-key block of all 8 KV
    heads for K and for V, 4 x 512 KB.  (The whole-window form wanted
    4 MB of scratch and 8 MB of float32 copies a program and KV head
    there.)"""
    from paddle_tpu.ops.pallas_kernels import paged_decode

    i32 = jnp.int32

    def args(pages):
        pool = sds((8, 8, 32 * pages, 16, 128))
        return (sds((32, 8, 4, 128)), pool, pool, sds((32,), i32),
                sds((32, pages), i32), sds((), i32), sds((32,), i32),
                sds((32,), i32))

    def scratch(pages):
        text = str(paged_decode._call.trace(*args(pages), scale=0.088).jaxpr)
        return sorted(set(re.findall(r"Ref<vmem>\{(\w+\[[\d,]+\])\}", text)))

    assert scratch(512) == scratch(128) == ["bf16[2,8,256,128]"]
    exe = paged_decode._call.lower(*args(512), scale=0.088).compile()
    assert "tpu_custom_call" in exe.as_text()
    assert exe.memory_analysis().temp_size_in_bytes == 0


# -- the hybrid executor's state path (granite4h-micro-serve) -------------------

# a run of 9 state-space layers at 64 slots: 1.2 GB of float32 state
STATE = (9, 64, 32, 128, 128)
STATE_SIZED_COPY = re.compile(
    r"= f32\[9,64,32,128,128\]\S* (copy|transpose)\(")


def test_the_state_kernel_updates_the_carried_pool_in_place(sds):
    """ops/pallas_kernels/ssm_decode.py at the cell's size, inside a scan
    over the run's layers with the pool as the carry: Mosaic takes the
    kernel, the pool is aliased to the output, and no copy of its size
    (nor any temporary worth the name) is made."""
    from paddle_tpu.ops.pallas_kernels import ssm_decode

    L, S, G, N, KP = STATE

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


def test_the_state_writer_moves_no_pool(sds):
    """serve.state_write: one slot's rows of every layer of a run into the
    donated pools, nothing else of them moved."""
    from paddle_tpu.inference.state_cache import _write_slot

    ssm, conv = sds(STATE, jnp.float32), sds((9, 3, 64, 4352), jnp.bfloat16)
    exe = jax.jit(_write_slot, donate_argnums=(0, 1)).lower(
        (ssm,), (conv,), (sds((9, 32, 128, 128), jnp.float32),),
        (sds((9, 3, 4352), jnp.bfloat16),), sds((), jnp.int32)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * 9 * 64 * 32 * 128 * 128 \
        + 2 * 9 * 3 * 64 * 4352
    assert mem.temp_size_in_bytes < 8 << 20
    assert not STATE_SIZED_COPY.search(text)


def test_a_token_into_the_folded_kv_pool_moves_no_pool(sds, compiled_kernel):
    """The hybrid decode's page patch and attention on the pool of 4
    attention layers x 4 folded KV heads x 8,192 pages x 16 x 128: the
    pool is aliased and handed to the fused kernel where it lies — neither
    a layer (134 MB) nor the pool is copied, no sequence's window is
    gathered (``bf16[32768,16,128]``, 134 MB each for keys and values,
    before the kernel was on this path), and the temporaries are the
    widened queries and the output, a few MB."""
    from paddle_tpu.inference.server import hybrid_executor as hx

    assert (hx._flat, hx._put_token) == (_flat, _put_token)
    shape = (4, 4, 8192, 16, 128)

    def step(pool, pids, offs, x, q, lengths, tables):
        pool = _put_token(_flat(pool), shape, 2, pids, offs,
                          x).reshape(shape)
        return pool, hx._folded_attention(q, pool, pool, 2, lengths,
                                          tables, 2)

    i32 = jnp.int32
    exe = jax.jit(step, donate_argnums=0).lower(
        sds(shape, jnp.bfloat16), sds((64,), i32), sds((64,), i32),
        sds((64, 4, 128), jnp.bfloat16), sds((64, 32, 64), jnp.bfloat16),
        sds((64,), i32), sds((64, 128), i32)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 4 * 4 * 8192 * 16 * 128
    assert mem.temp_size_in_bytes < 4 << 20
    assert text.count("tpu_custom_call") == 1
    assert "bf16[32768,16,128]" not in text
    assert not re.search(r"= bf16\[(4,)?4,8192,16,128\]\S* "
                         r"(copy|transpose|fusion)\(", text)


def test_the_hybrid_decode_step_moves_no_pool(sds, compiled_kernel,
                                              monkeypatch):
    """serve.hybrid_decode at the cell's size (granite-4.0-h-micro whole:
    5 scanned runs of state-space layers, 4 inlined attention layers, 64
    slots, tables of 128 pages): the two KV pools and the ten state pools
    aliased to the outputs, 6.0 GB of them; the state kernel once a run
    and the fused paged-decode kernel once an attention layer; no KV pool
    or layer of one copied or re-laid, no sequence's window gathered; the
    temporaries 6 MB, far under one KV layer's 134 MB."""
    from paddle_tpu.inference.server.hybrid_executor import HybridExecutor
    from paddle_tpu.models import granite_hybrid as gh
    from paddle_tpu.ops.pallas_kernels import ssm_decode

    monkeypatch.setattr(ssm_decode, "_on_tpu", lambda: True)
    cfg = gh.GraniteHybridConfig(dtype="bfloat16")
    ex = object.__new__(HybridExecutor)
    ex.config, ex.kv_fold = cfg, 2
    ex.segments = [(kind, n) for run in (5, 9, 9, 9)
                   for kind, n in (("mamba", run), ("attention", 1))] \
        + [("mamba", 4)]
    assert sum(n for _, n in ex.segments) == cfg.num_hidden_layers
    ex.cache = types.SimpleNamespace(page_size=16, num_pages=8192)
    ex.state = types.SimpleNamespace(ssm_shape=ssm_decode.state_shape(
        cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state))
    h, i = cfg.hidden_size, cfg.shared_intermediate_size
    shared = {"input_layernorm.weight": (h,),
              "post_attention_layernorm.weight": (h,),
              "shared_mlp.input_linear.weight": (h, 2 * i),
              "shared_mlp.output_linear.weight": (i, h)}

    def layer(kind, lead):
        mixer = {f"{'mamba' if kind == 'mamba' else 'self_attn'}.{name}":
                 shape for name, (shape, _) in
                 gh._mixer_shapes(cfg, kind).items()}
        shapes = {**mixer, **shared}
        assert set(shapes) == set(gh.layer_param_names(kind))
        return {name: sds(lead + shape) for name, shape in shapes.items()}

    params = tuple(layer(kind, (n,) if kind == "mamba" else ())
                   for kind, n in ex.segments)
    tops = {"embed": sds((cfg.vocab_size, h)), "norm_w": sds((h,))}
    runs = [n for kind, n in ex.segments if kind == "mamba"]
    ssm = tuple(sds((n, 64) + ex.state.ssm_shape, jnp.float32) for n in runs)
    conv = tuple(sds((n, 3, 64, cfg.mamba_conv_dim)) for n in runs)
    pool, i32 = sds((4, 4, 8192, 16, 128)), jnp.int32
    exe = jax.jit(ex._decode_fwd, donate_argnums=(5, 6, 8, 9)).lower(
        params, tops, sds((64,), i32), sds((64,), i32),
        sds((64,), jnp.bool_), pool, pool, sds((64, 128), i32), ssm,
        conv).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    aliased = re.findall(r"\{(\d+)\}: \(\d+, \{\}, may-alias\)",
                         text[:text.index("\n")])
    assert aliased == [str(n) for n in range(1, 13)]
    assert mem.alias_size_in_bytes == 2 * 2 * 4 * 4 * 8192 * 16 * 128 \
        + 36 * 64 * (4 * 32 * 128 * 128 + 2 * 3 * cfg.mamba_conv_dim)
    assert mem.temp_size_in_bytes < 16 << 20
    assert text.count("tpu_custom_call") == 5 + 4
    assert "bf16[32768,16,128]" not in text
    assert not re.search(r"= bf16\[(4,)?4,8192,16,128\]\S* "
                         r"(copy|transpose|fusion)\(", text)


# -- the prefill chunk (mistral7b-serve.closed32) -----------------------------


def compile_chunk(sds, past_of, pages):
    """serve.prefill_chunk at the cell's size (a chunk of 256 tokens, a
    past of ``pages`` pages), its past read by ``past_of``."""
    from paddle_tpu.inference.server import executor

    ex, layers, tops = mistral_executor(sds)
    i32 = jnp.int32
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(executor, "_past_of", past_of)
        return jax.jit(ex._chunk_fwd).lower(
            layers, tops, sds((1, 256), i32), sds((), i32), sds(POOL),
            sds(POOL), sds((pages,), i32), sds((), i32)).compile()


@pytest.mark.parametrize("pages", [0, 16, 96])
def test_the_chunk_reads_its_past_and_moves_no_pool(sds, pages):
    """The chunk program gathers its past from the pools by row, layer by
    layer inside its scan: neither a pool nor a layer of one is copied,
    re-laid or produced by a fusion; what is gathered is the past's own
    pages (6.3 MB a layer at 96), and a first chunk gathers none.  The
    temporaries the compiler reports are 0.4 MB at the longest past (0 for
    the program that took its past dense): held under a quarter of a
    layer, which the control below passes four times over."""
    from paddle_tpu.inference.paged import _past_of

    exe = compile_chunk(sds, _past_of, pages)
    text, mem = exe.as_text(), exe.memory_analysis()
    assert not POOL_OR_LAYER_MOVED.search(text)
    assert mem.alias_size_in_bytes == 0
    assert mem.temp_size_in_bytes < 32 << 20
    assert re.findall(r"= bf16\[8,(\d+),16,128\]\S* gather\(", text) \
        == [str(pages)] * (2 if pages else 0)


def test_a_layer_sliced_out_for_the_chunk_is_copied(sds):
    """The control: the same program with the layer taken out of the pool
    first (``pool[layer][:, pids]``) copies that layer, 134 MB, for keys
    and for values in every iteration of the scan."""
    def sliced(pool, layer, pids, dtype):
        return pool[layer][:, pids].reshape(POOL[1], -1, POOL[4])

    exe = compile_chunk(sds, sliced, 96)
    assert len(POOL_OR_LAYER_MOVED.findall(exe.as_text())) >= 2
    assert exe.memory_analysis().temp_size_in_bytes > 128 << 20


# -- sarvam105b-serve: the latent pool ----------------------------------------

# 5 layers x one row a token x 4,096 pages x 128 tokens x 640 lanes: 3.36 GB
LATENT_POOL = (5, 1, 4096, 128, 640)
LATENT_POOL_OR_LAYER_MOVED = re.compile(
    r"= bf16\[(5,|1,)?(1,)?(4096|20480),128,640\]\S* (copy|transpose|fusion)\(")


def latent_executor(sds):
    """sarvam105b-serve's executor without its arrays (the published
    widths, one dense and four expert layers, 32 experts held, a quarter of
    the vocabulary), and the shapes of its parameters."""
    from paddle_tpu.inference.server.latent_executor import LatentExecutor
    from paddle_tpu.models import mla_moe as mm

    cfg = mm.MLAMoEConfig(num_hidden_layers=5, vocab_size=65536,
                          dtype="bfloat16")
    ex = object.__new__(LatentExecutor)
    ex.config, ex.held = cfg, tuple(range(32))
    ex.segments = [("mla_dense", 1), ("mla_moe", 4)]
    ex.n_expert_layers, ex.rank, ex.row_width = 4, 512, 640
    ex.cache = types.SimpleNamespace(page_size=LATENT_POOL[3],
                                     num_pages=LATENT_POOL[2])
    dense = {n: sds(s) for n, (s, _) in
             mm._layer_shapes(cfg, "mla_dense", 32).items()}
    run = {n: sds((4,) + tuple(s)) for n, (s, _) in
           mm._layer_shapes(cfg, "mla_moe", 32).items()}
    tops = {"embed": sds((65536, 4096)), "norm_w": sds((4096,)),
            "lm_head": sds((4096, 65536))}
    return ex, (dense, run), tops


@pytest.fixture
def compiled_latent_kernel(monkeypatch):
    """Steer the program as the chip would: the Pallas kernel, compiled
    (here the backend is the CPU, which takes the ``jax.numpy`` form)."""
    from paddle_tpu.ops.pallas_kernels import mla_decode

    monkeypatch.setattr(mla_decode, "_on_tpu", lambda: True)


def test_the_latent_decode_step_moves_no_pool(sds, compiled_latent_kernel):
    """serve.mla_decode at the cell's size (64 slots, 64 pages a
    sequence): the latent pool aliased to the output, the kernel in the
    program once for the dense layer and once in the scan, neither the
    pool nor a layer of it copied, re-laid or produced by a fusion, the
    temporaries under a quarter of one layer's 671 MB, and 12.43 GB of
    arguments: the weights and the pool, nothing twice."""
    ex, params, tops = latent_executor(sds)
    i32 = jnp.int32
    exe = jax.jit(ex._decode_fwd, donate_argnums=(5,)).lower(
        params, tops, sds((64,), i32), sds((64,), i32),
        sds((64,), jnp.bool_), sds(LATENT_POOL), sds((64, 64), i32)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert re.search(r"input_output_alias=\{ \{1\}: \(\d+, \{\}, may-alias\) \}",
                     text[:text.index("\n")])
    pool = 2 * 5 * 4096 * 128 * 640
    assert mem.alias_size_in_bytes == pool == 3_355_443_200
    assert mem.temp_size_in_bytes < 160 << 20
    assert 12.42e9 < mem.argument_size_in_bytes < 12.44e9
    assert text.count("tpu_custom_call") >= 2
    # the only programs that produce a pool are the token's two scatters
    # (the dense layer's and the scan's), each in place on its operand
    made = [line for line in text.splitlines()
            if LATENT_POOL_OR_LAYER_MOVED.search(line)]
    assert len(made) == 2
    assert all("/scatter\"" in line and '"aliasing_operands"' in line
               and " fusion(" in line for line in made)
    # the layer's experts are multiplied where they lie in the stacked run
    assert not re.search(r"= bf16\[(1,)?32,(4096,4096|2048,4096)\]\S* "
                         r"(copy|transpose)\(", text)


@pytest.mark.parametrize("pages", [0, 40])
def test_the_latent_chunk_reads_its_past_and_moves_no_pool(
        sds, compiled_kernel, pages):
    """serve.mla_chunk at a chunk of 1,024 tokens, first and last of a
    6,144-token prompt: the pool is read by page id inside the program
    and never copied; the routed experts are ONE grouped kernel in the
    scan's body, which reads each expert where it lies in the stacked
    run: neither the experts of a layer (1.6 GB a layer when the loop
    over experts took a scan's slice of them: PERF.md section 6, PR 32)
    nor one expert's matrices (once a 128-row block until PR 37) are
    sliced or copied out, and the only loops left are the scan over the
    expert layers and the attention's blocks of heads; the temporaries
    stay under 1 GB (a block of heads' scores, the sorted rows)."""
    ex, params, tops = latent_executor(sds)
    i32 = jnp.int32
    exe = jax.jit(ex._chunk_fwd).lower(
        params, tops, sds((1024,), i32), sds((), i32), sds(LATENT_POOL),
        sds((pages,), i32)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert not LATENT_POOL_OR_LAYER_MOVED.search(text)
    assert mem.alias_size_in_bytes == 0
    assert mem.temp_size_in_bytes < 1 << 30
    assert not re.search(r"= bf16\[(1,)?32,(4096,4096|2048,4096)\]\S* "
                         r"(copy|transpose|fusion|dynamic-slice)\(", text)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not re.search(r"bf16\[1,1,(4096|2048),4096\]", text)
    assert len(re.findall(r" while\(", text)) == 3
    assert len(re.findall(rf"= bf16\[{pages},128,640\]\S* gather\(",
                          text)) == (2 if pages else 0)


def test_the_latent_kernel_compiles_at_the_cells_size(sds):
    """The kernel alone: 64 sequences x 64 heads on a shared 640-lane row,
    pages of 128 fetched by the table from a 3.36 GB pool that stays in
    HBM (an argument, no temporary), walked in the blocks the pool's
    shape picks: 4 pages of 128, two slots of 640 KB in VMEM."""
    from paddle_tpu.ops.pallas_kernels import mla_decode

    i32 = jnp.int32
    args = (sds((64, 64, 640)), sds((5 * 4096, 128, 640)), sds((), i32),
            sds((64,), i32), sds((64, 64), i32))
    assert mla_decode.block_pages(128, 640, 2) == 4
    exe = mla_decode._mla_decode_call.lower(
        *args, pages=4096, rank=512).compile()
    mem = exe.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    assert mem.output_size_in_bytes == 64 * 64 * 512 * 2
    traced = jax.make_jaxpr(
        lambda *a: mla_decode._mla_decode_call(*a, pages=4096, rank=512))(
            *args).jaxpr.eqns[0].params["jaxpr"].jaxpr
    calls = [e for e in traced.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    rows = calls[0].params["grid_mapping"].scratch_avals[0]
    assert (rows.shape, rows.dtype) == ((2, 512, 640), jnp.bfloat16)


def test_the_latent_page_writer_moves_no_pool(sds):
    """serve.kv_write on the latent pool at the cell's chunk of 1,024
    rows: the pool aliased to the output, NO temporary, and the pool
    produced by one in-place scatter alone.  Indexed as K and V pools are
    (``pool.at[:, :, pids]``) the 640-lane row made the compiler split
    the whole pool by lanes into two copies: 2.0 GB of temporaries and
    10 ms a chunk on the chip (PERF.md section 6, PR 32)."""
    n = (1024 - 1) // LATENT_POOL[3] + 2
    exe = jax.jit(_write_span, donate_argnums=(0, 1)).lower(
        sds(LATENT_POOL), None, sds((5, 1, 1024, 640)), None,
        sds((n,), jnp.int32), sds((), jnp.int32)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert mem.alias_size_in_bytes == 3_355_443_200
    assert mem.temp_size_in_bytes < 16 << 20
    made = [line for line in text.splitlines() if re.search(
        r"= bf16\[(5,)?(1,)?(4096|20480),128,\d+\]\S* "
        r"(copy|transpose|fusion)\(", line)]
    assert len(made) == 1 and "/scatter" in made[0]


# -- trinity-mini-serve: a cache of two layer groups ---------------------------

# the full group: 1 layer x 4 KV heads x 64 x 112 pages x 128 x 128 (1.88 GB
# for K and V); the window group: 5 layers x 64 x 25 pages (2.10 GB)
FULL_POOL, WINDOW_POOL = (1, 4, 7168, 128, 128), (5, 4, 1600, 128, 128)
GROUP_POOL_MOVED = re.compile(
    r"= bf16\[(1,|5,)?(4,)?(7168|1600|28672|32000),128,128\]\S* "
    r"(copy|transpose|fusion)\(")
EXPERTS_MOVED = re.compile(
    r"= bf16\[(1,)?128,(2048,2048|1024,2048)\]\S* "
    r"(copy|transpose|dynamic-slice)\(")


def window_executor(sds):
    """trinity-mini-serve's executor without its arrays (the published
    widths, s s | s f s s, all 128 experts held, the whole vocabulary), and
    the shapes of its parameters."""
    from paddle_tpu.inference.server.window_executor import WindowExecutor
    from paddle_tpu.models import window_moe as wm

    cfg = wm.WindowMoEConfig(num_hidden_layers=6, dtype="bfloat16")
    kinds = cfg.layer_types
    assert kinds == (wm.SLIDING,) * 3 + (wm.FULL,) + (wm.SLIDING,) * 2
    ex = object.__new__(WindowExecutor)
    ex.config, ex.held = cfg, tuple(range(128))
    ex.slot_of = [(int(k == wm.SLIDING), kinds[:n].count(k))
                  for n, k in enumerate(kinds)]
    ex.cache = types.SimpleNamespace(page_size=128)
    params = tuple({name: sds(shape) for name, (shape, _) in
                    wm._layer_shapes(cfg, cfg.is_dense(n), 128).items()}
                   for n in range(6))
    tops = {"embed": sds((200192, 2048)), "norm_w": sds((2048,)),
            "lm_head": sds((2048, 200192))}
    return ex, params, tops


def test_the_window_decode_step_moves_no_pool(sds, compiled_kernel):
    """serve.window_decode at the cell's size (64 slots, 112 and 25 pages
    a sequence): the four pools of both layer groups aliased to the
    outputs, the one kernel in the program six times (once a layer, the
    windowed start an argument), no pool or layer of one copied, re-laid
    or produced by anything but the token's in-place scatters (K and V of
    six layers), the temporaries a few MB, and 12.59 GB of arguments: the
    weights and the pools, nothing twice; no expert leaf copied."""
    ex, params, tops = window_executor(sds)
    i32 = jnp.int32
    pools = [sds(FULL_POOL), sds(WINDOW_POOL)]
    exe = jax.jit(ex._decode_fwd, donate_argnums=(5, 6)).lower(
        params, tops, sds((64,), i32), sds((64,), i32),
        sds((64,), jnp.bool_), pools, pools,
        [sds((64, 112), i32), sds((64, 25), i32)], sds((64,), i32)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert re.search(r"input_output_alias=\{ " + ", ".join(
        rf"\{{{i}\}}: \(\d+, \{{\}}, may-alias\)" for i in (1, 2, 3, 4))
        + r" \}", text[:text.index("\n")])
    assert mem.alias_size_in_bytes == 3_976_200_192 == 2 * 4 * 128 * 128 \
        * 2 * (7168 + 5 * 1600)
    assert mem.temp_size_in_bytes < 64 << 20
    assert 12.58e9 < mem.argument_size_in_bytes < 12.60e9
    assert text.count("tpu_custom_call") == 6
    made = [line for line in text.splitlines()
            if GROUP_POOL_MOVED.search(line)]
    assert len(made) == 12
    assert all("/scatter\"" in line and " fusion(" in line for line in made)
    assert not EXPERTS_MOVED.search(text)


@pytest.mark.parametrize("pages", [0, 88])
def test_the_window_chunk_reads_its_past_and_moves_no_pool(
        sds, compiled_kernel, pages):
    """serve.window_chunk at a chunk of 1,024 tokens, first and last of a
    12,288-token prompt: both groups' pools are read by page id inside
    the program (the full layer's 88 pages, a sliding layer's 16) and
    never copied or written; each of the four expert layers is ONE
    grouped kernel that reads an expert where it lies in its leaf: no
    expert's matrix is sliced or copied out (``bf16[1,2048,2048]`` once a
    trip of a 128-trip loop until PR 37), and the only loops left are
    the attention's blocks of heads, one a layer; the temporaries stay
    under 512 MB (a block of heads' scores, the sorted rows)."""
    ex, params, tops = window_executor(sds)
    i32 = jnp.int32
    pools = [sds(FULL_POOL), sds(WINDOW_POOL)]
    exe = jax.jit(ex._chunk_fwd).lower(
        params, tops, sds((1024,), i32), sds((), i32), pools, pools,
        [sds((pages,), i32), sds((min(pages, 16),), i32)],
        sds((), i32)).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert not GROUP_POOL_MOVED.search(text)
    assert mem.alias_size_in_bytes == 0
    assert mem.temp_size_in_bytes < 512 << 20
    assert not EXPERTS_MOVED.search(text)
    # stock attention (PERF.md): the kernels are the experts'
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert not re.search(r"bf16\[1,(2048|1024),2048\]", text)
    assert len(re.findall(r" while\(", text)) == 6


@pytest.mark.parametrize("experts, H, F, run", [(128, 2048, 1024, 1),
                                                (32, 4096, 2048, 5)])
def test_the_grouped_expert_kernel_compiles_at_the_cells_size(
        sds, experts, H, F, run):
    """The kernel alone at 8,192 pairs in tiles of 128 rows: Trinity-Mini's
    128 experts in a leaf (a run of one), sarvam's 32 held in a stacked
    run of 5 layers.  Mosaic takes it inside the VMEM limit the call asks
    for (an expert's panels twice: 25 MB), the experts stay where they are
    (arguments, no temporary) and the output is the tiles' float32
    rows."""
    from paddle_tpu.ops.pallas_kernels import grouped_swiglu as gs

    i32 = jnp.int32
    tiles = (8192 + experts * (gs.ROW_TILE - 1)) // gs.ROW_TILE
    exe = gs._grouped_swiglu_call.lower(
        sds((tiles * gs.ROW_TILE, H)), sds((run, experts, H, 2 * F)),
        sds((run, experts, F, H)), sds((), i32), sds((tiles,), i32),
        sds((), i32)).compile()
    mem = exe.memory_analysis()
    assert "tpu_custom_call" in exe.as_text()
    assert mem.temp_size_in_bytes == 0
    assert mem.output_size_in_bytes == tiles * gs.ROW_TILE * H * 4
    assert 2 * 3 * H * gs.panel(H, F, 2) * 2 <= gs._PANEL_BYTES \
        < gs._VMEM_LIMIT


def test_the_window_page_writer_moves_no_pool(sds):
    """serve.kv_write on both groups' pools at the cell's chunk of 1,024
    tokens: ONE program, the four pools aliased to the outputs, no
    temporary worth the name and no pool re-laid."""
    n = (1024 - 1) // 128 + 2
    pools = [sds(FULL_POOL), sds(WINDOW_POOL)]
    span = [sds((1, 4, 1024, 128)), sds((5, 4, 1024, 128))]
    exe = jax.jit(_write_span, donate_argnums=(0, 1)).lower(
        pools, pools, span, span, [sds((n,), jnp.int32)] * 2,
        [sds((), jnp.int32)] * 2).compile()
    text, mem = exe.as_text(), exe.memory_analysis()
    assert mem.alias_size_in_bytes == 3_976_200_192
    assert mem.temp_size_in_bytes < 64 << 20
    assert not re.search(
        r"= bf16\[(1,|5,)4,(7168|1600),128,128\]\S* (copy|transpose)\(", text)
