"""Model zoo: flagship Llama family + training harness; vision models live
in paddle_tpu.vision.models, BERT in models/bert.py (as added); the Mamba-2 / attention hybrid in
models/granite_hybrid.py; latent attention + routed experts in models/mla_moe.py;
sliding-window + full attention with routed experts in models/window_moe.py; the
expert layer both import in models/moe.py."""
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, llama_shard_rules,
)
from .granite_hybrid import (  # noqa: F401
    GraniteHybridConfig, GraniteHybridForCausalLM,
)
from .mla_moe import MLAMoEConfig, MLAMoEForCausalLM  # noqa: F401
from .window_moe import (  # noqa: F401
    WindowMoEConfig, WindowMoEForCausalLM,
)
from .training import CompiledTrainStep  # noqa: F401
from .generation import LlamaDecoder  # noqa: F401
