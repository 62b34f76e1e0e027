"""Where the harness touches the program under test, for the
latent-attention + routed-experts configuration: as ``program_hybrid.py``
is for the hybrid one.  The one file of the benchmark that imports the
program's model: a program without it (a parent commit) fails at this
import, before any weight is made.

It builds the program's model from the configuration file's published
keys and its ``share`` (the router at its published width, the experts
this chip holds), hands it the harness's seeded weights
(``weights_mla_moe.py``) layer by layer through the public ``set_value``,
and reads back what the engine holds for a request in flight.
"""
import dataclasses

import numpy as np

from paddle_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM

from chipbench import weights_mla_moe

# harness leaf -> the program's parameter name inside a layer
_LAYER = {
    "ln1": "input_layernorm.weight", "ln2": "post_attention_layernorm.weight",
    "q": "self_attn.q_proj.weight", "q_norm": "self_attn.q_norm.weight",
    "kv_a": "self_attn.kv_a_proj_with_mqa.weight",
    "kv_norm": "self_attn.kv_a_layernorm.weight",
    "kv_b": "self_attn.kv_b_proj.weight", "o": "self_attn.o_proj.weight",
    "gate_up": "mlp.gate_up_proj.weight", "down": "mlp.down_proj.weight",
    "router": "mlp.gate.weight",
    "router_bias": "mlp.gate.e_score_correction_bias",
    "experts_gate_up": "mlp.experts.gate_up_proj",
    "experts_down": "mlp.experts.down_proj",
    "shared_gate_up": "mlp.shared_experts.gate_up_proj.weight",
    "shared_down": "mlp.shared_experts.down_proj.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "norm": "model.norm.weight",
        "head": "lm_head.weight"}


def build_model(cfg, dtype):
    """The program's model at the configuration's sizes, its parameters
    made in ``dtype`` at once and left at zero for ``load_weights``."""
    keys = {f.name for f in dataclasses.fields(MLAMoEConfig)}
    published = {k: v for k, v in cfg.items() if k in keys}
    if cfg["head_dim"] != cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"] \
            or cfg["q_head_dim"] != (cfg["qk_nope_head_dim"]
                                     + cfg["qk_rope_head_dim"]):
        raise SystemExit("models/mla_moe.py derives head_dim and "
                         "q_head_dim from the latent's and the heads' parts")
    share = cfg["share"]
    first, end = share["held_experts"]
    if end - first != cfg["num_experts"]:
        raise SystemExit("share.held_experts and num_experts disagree")
    published["num_experts"] = share["router_experts"]
    return MLAMoEForCausalLM(
        MLAMoEConfig(**published, dtype=np.dtype(dtype).name),
        held_experts=range(first, end), init_weights=False)


def load_weights(model, cfg, seed, dtype):
    """Every parameter of the model gets the harness's leaf of that name,
    one layer at a time; a parameter without a leaf, or a leaf without a
    parameter, is an error."""
    params = dict(model.named_parameters())
    todo = set(params)

    def put(name, value):
        if name not in todo:
            raise SystemExit(f"no parameter, or set twice: {name}")
        params[name].set_value(value)
        todo.discard(name)

    for leaf, value in weights_mla_moe.top(cfg, seed, dtype).items():
        put(_TOP[leaf], value)
    for n in range(cfg["num_hidden_layers"]):
        for leaf, value in weights_mla_moe.layer(cfg, seed, n,
                                                 dtype).items():
            put(f"model.layers.{n}.{_LAYER[leaf]}", value)
    if todo:
        raise SystemExit(f"parameters without weights: {sorted(todo)[:6]} ...")


def slot_rows(eng, rid, layers):
    """The latent rows the engine holds for request ``rid`` (in flight,
    prefilled) in each of ``layers``: float32 ``[len(layers), tokens,
    rank + rope]`` on the host, as the model's equations have them."""
    rows = eng.executor.slot_rows(eng.request(rid).sid)
    return np.asarray(rows[np.asarray(layers)], np.float32)


def expert_counter(eng):
    """The executor's running sums of its decode program's expert counter,
    as plain numbers."""
    ex = eng.executor
    return {"rows": int(ex.expert_rows.sum()), "steps": ex.expert_steps,
            "hit": ex.experts_hit, "max_over_mean": ex.expert_max_over_mean}
