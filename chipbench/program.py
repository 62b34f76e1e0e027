"""The one place where the harness touches the program under test.

It builds the program's model from a configuration file, hands it the
harness's seeded weights through the public ``set_value``, and checks
that no ``PT_*`` gate is set, so that the engine and the step run as a
user who sets nothing gets them.  The configuration's keys are the
published config.json's; what the program calls them is mapped here.
"""
import os

# harness leaf name -> the program's parameter name
_LAYER = {"ln1": "input_layernorm.weight", "q": "self_attn.q_proj.weight",
          "k": "self_attn.k_proj.weight", "v": "self_attn.v_proj.weight",
          "o": "self_attn.o_proj.weight",
          "ln2": "post_attention_layernorm.weight",
          "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
          "down": "mlp.down_proj.weight"}
_TOP = {"embed": "llama.embed_tokens.weight", "norm": "llama.norm.weight",
        "head": "lm_head.weight"}

GATES = ("PT_PREFIX_CACHE", "PT_SPEC_DECODE", "PT_ASYNC_EXEC", "PT_AOT",
         "PT_QUANT", "PT_WAL", "PT_SP_PREFILL", "PT_CLUSTER",
         "PT_PAGED_IMPL", "PT_OBS", "PT_CHAOS")


def program_name(leaf):
    if leaf in _TOP:
        return _TOP[leaf]
    _, n, part = leaf.split(".")
    return f"llama.layers.{n}.{_LAYER[part]}"


def check_gates():
    gated = sorted(k for k in os.environ if k in GATES)
    if gated:
        raise SystemExit(f"chipbench runs the program at its defaults; "
                         f"unset {gated}")


def check_config(cfg):
    """What the program's Llama-shaped model cannot express is refused,
    not ignored."""
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise SystemExit("models/llama.py derives head_dim from "
                         "hidden_size / num_attention_heads")
    if cfg.get("sliding_window") is not None:
        raise SystemExit("models/llama.py has no sliding-window attention")
    if cfg.get("hidden_act", "silu") != "silu":
        raise SystemExit("models/llama.py's MLP is SwiGLU")


def build_model(cfg, **extra):
    """The program's model at the configuration's sizes (its own random
    initial weights; :func:`load_weights` replaces them)."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    check_config(cfg)
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "max_position_embeddings",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings")
    return LlamaForCausalLM(LlamaConfig(**{k: cfg[k] for k in keys}, **extra))


def load_weights(model, weights):
    """Every parameter of the model gets the harness's leaf of that name;
    a parameter without one, or a leaf without a parameter, is an error."""
    params = dict(model.named_parameters())
    wanted = {program_name(leaf): leaf for leaf in weights}
    if set(wanted) != set(params):
        raise SystemExit(f"weights and parameters differ: "
                         f"{sorted(set(wanted) ^ set(params))[:6]} ...")
    for name, leaf in wanted.items():
        params[name].set_value(weights[leaf])
