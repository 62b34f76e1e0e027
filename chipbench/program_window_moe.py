"""Where the harness touches the program under test, for the window +
full attention configuration with routed experts: as ``program_mla_moe.py``
is for the latent one.  The one file of the benchmark that imports the
program's model: a program without it (a parent commit) fails at this
import, before any weight is made.

It builds the program's model from the configuration file's published
keys, hands it the harness's seeded weights (``weights_window_moe.py``)
layer by layer through the public ``set_value``, and reads back what the
engine holds for a request in flight and the executor's counters.
"""
import dataclasses

import numpy as np

from paddle_tpu.models.window_moe import (WindowMoEConfig,
                                          WindowMoEForCausalLM)

from chipbench import weights_window_moe

# harness leaf -> the program's parameter name inside a layer
_LAYER = {
    "ln1": "input_layernorm.weight", "ln2": "post_attention_layernorm.weight",
    "ln3": "pre_mlp_layernorm.weight", "ln4": "post_mlp_layernorm.weight",
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight", "gate": "self_attn.gate_proj.weight",
    "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
    "o": "self_attn.o_proj.weight",
    "gate_up": "mlp.gate_up_proj.weight", "down": "mlp.down_proj.weight",
    "router": "mlp.router.gate.weight", "router_bias": "mlp.expert_bias",
    "experts_gate_up": "mlp.experts.gate_up_proj",
    "experts_down": "mlp.experts.down_proj",
    "shared_gate_up": "mlp.shared_experts.gate_up_proj.weight",
    "shared_down": "mlp.shared_experts.down_proj.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "norm": "model.norm.weight",
        "head": "lm_head.weight"}


def build_model(cfg, dtype):
    """The program's model at the configuration's sizes, its parameters
    made in ``dtype`` at once and left at zero for ``load_weights``."""
    keys = {f.name for f in dataclasses.fields(WindowMoEConfig)}
    published = {k: v for k, v in cfg.items() if k in keys}
    return WindowMoEForCausalLM(
        WindowMoEConfig(**published, dtype=np.dtype(dtype).name),
        init_weights=False)


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

    for leaf, value in weights_window_moe.top(cfg, seed, dtype).items():
        put(_TOP[leaf], value)
    for n in range(cfg["num_hidden_layers"]):
        for leaf, value in weights_window_moe.layer(cfg, seed, n,
                                                    dtype).items():
            put(f"model.layers.{n}.{_LAYER[leaf]}", value)
    if todo:
        raise SystemExit(f"parameters without weights: {sorted(todo)[:6]} ...")


def slot_kv(eng, rid, layers):
    """What the engine holds for request ``rid`` (in flight, prefilled)
    in each of ``layers``: ``{layer: (the token its first row stands for,
    float32 [tokens held, 2, kv heads, D] on the host: keys, then
    values)}``."""
    sid = eng.request(rid).sid
    out = {}
    for n in layers:
        base, k, v = eng.executor.slot_kv(sid, n)
        out[n] = (base, np.stack([np.asarray(k, np.float32),
                                  np.asarray(v, np.float32)], axis=1))
    return out


def counters(eng):
    """The executor's running sums, as plain numbers: its decode
    program's expert counter and its cache's pages by layer group."""
    ex = eng.executor
    return {"experts": {"rows": int(ex.expert_rows.sum()),
                        "steps": ex.expert_steps, "hit": ex.experts_hit,
                        "max_over_mean": ex.expert_max_over_mean},
            "pages": {"used": list(ex.pages_used),
                      "samples": ex.page_samples,
                      "released": list(ex.pages_released),
                      "pool": [g.num_pages for g in ex.cache.groups]}}
