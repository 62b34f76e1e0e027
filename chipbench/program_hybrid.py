"""Where the harness touches the program under test, for the hybrid
(Mamba-2 + attention) configuration: as ``program.py`` is for the
Llama-shaped ones.

It builds the program's model from the configuration file's published
keys, hands it the harness's seeded weights (``weights_hybrid.py``) layer
by layer through the public ``set_value``, so that no second copy of the
model is ever held, and leaves the gate check to ``program.check_gates``.
A program without the model (a parent commit) fails at this import,
before any weight is made.
"""
import dataclasses

import numpy as np

from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                              GraniteHybridForCausalLM)

from chipbench import weights_hybrid

# harness leaf -> the program's parameter name inside a layer
_LAYER = {
    "ln1": "input_layernorm.weight", "ln2": "post_attention_layernorm.weight",
    "mlp_in": "shared_mlp.input_linear.weight",
    "mlp_out": "shared_mlp.output_linear.weight",
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
    "in_proj": "mamba.in_proj.weight", "conv_w": "mamba.conv1d.weight",
    "conv_b": "mamba.conv1d.bias", "dt_bias": "mamba.dt_bias",
    "A_log": "mamba.A_log", "D": "mamba.D", "mnorm": "mamba.norm.weight",
    "out_proj": "mamba.out_proj.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "norm": "model.norm.weight"}


def build_model(cfg, dtype):
    """The program's model at the configuration's sizes, its parameters
    made in ``dtype`` at once (a float32 copy of 3.2 B parameters would
    not fit beside anything) and left at zero for ``load_weights``."""
    keys = {f.name for f in dataclasses.fields(GraniteHybridConfig)}
    published = {k: v for k, v in cfg.items() if k in keys}
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise SystemExit("models/granite_hybrid.py derives head_dim from "
                         "hidden_size / num_attention_heads")
    if cfg.get("hidden_act", "silu") != "silu":
        raise SystemExit("models/granite_hybrid.py's MLP is SwiGLU")
    # no weight is drawn: load_weights sets every one
    return GraniteHybridForCausalLM(
        GraniteHybridConfig(**published, dtype=np.dtype(dtype).name),
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

    for leaf, value in weights_hybrid.top(cfg, seed, dtype).items():
        put(_TOP[leaf], value)
    for n in range(cfg["num_hidden_layers"]):
        for leaf, value in weights_hybrid.layer(cfg, seed, n, dtype).items():
            put(f"model.layers.{n}.{_LAYER[leaf]}", value)
    if todo:
        raise SystemExit(f"parameters without weights: {sorted(todo)[:6]} ...")


def slot_state(eng, rid):
    """The recurrent state the engine holds for request ``rid`` (in
    flight, prefilled): float32 ``[recurrent layers, heads, P, N]`` on the
    host, as the model's equations have it."""
    ssm, _ = eng.executor.slot_state(eng.request(rid).sid)
    return np.asarray(ssm, np.float32)
