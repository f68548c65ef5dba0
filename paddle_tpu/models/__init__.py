"""Model zoo (reference: the PaddleNLP/vision model families built on the
framework; in-tree analogs python/paddle/vision/models).

Flagship: Llama-2 decoder family (the BASELINE.md north-star workload),
built TPU-first — bf16 compute, flash-attention Pallas kernel, GSPMD
sharding plan over the hybrid mesh (dp/mp/pp/sep axes).
"""

from . import (cohere2_moe, deepseek_v32, dit, falcon_h1, gpt,  # noqa: F401
               llama, sarvam_mla, smallthinker, solar_open2)
from .deepseek_v32 import (DeepseekV32Config,  # noqa: F401
                           DeepseekV32ForCausalLM)
from .cohere2_moe import Cohere2MoeConfig, CohereMoeForCausalLM  # noqa: F401
from .falcon_h1 import FalconH1Config, FalconH1ForCausalLM  # noqa: F401
from .dit import DiT, DiTConfig, DiTTrainStep, GaussianDiffusion  # noqa: F401
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, llama_shard_plan,
)
from .gpt import GPTConfig, GPTForCausalLM  # noqa: F401
from .sarvam_mla import SarvamMlaConfig, SarvamMlaForCausalLM  # noqa: F401
from .smallthinker import (SmallThinkerConfig,  # noqa: F401
                           SmallThinkerForCausalLM)
from .solar_open2 import (SolarOpen2Config,  # noqa: F401
                          SolarOpen2ForCausalLM)
