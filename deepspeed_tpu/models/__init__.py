from .gpt2 import (GPT2, GPT2Config, PRESETS, GPT2_TINY, GPT2_125M,
                   GPT2_350M, GPT2_1_3B)
from .gpt2_moe import GPT2MoE, GPT2MoEConfig
from .gpt2_pipe import GPT2Pipe
from .llama import (Llama, LlamaConfig, LLAMA_PRESETS, LLAMA_TINY,
                    LLAMA2_7B, MISTRAL_7B)
from .mixtral import Mixtral, MixtralConfig, MIXTRAL_TINY, MIXTRAL_8X7B
from .olmoe import (OLMoE, OLMoEConfig, OLMOE_PRESETS, OLMOE_TINY,
                    OLMOE_1B_7B)
from .bloom import Bloom, BloomConfig, BLOOM_PRESETS
from .qwen import Qwen, QwenConfig, QWEN_PRESETS
from .phi import Phi, PhiConfig, PHI_PRESETS
from .phi4flash import (Phi4Flash, Phi4FlashConfig, PHI4FLASH_PRESETS,
                        PHI4FLASH_TINY, PHI4_MINI_FLASH)
from .olmo_hybrid import (OlmoHybrid, OlmoHybridConfig, OLMO_HYBRID_PRESETS,
                          OLMO_HYBRID_TINY, OLMO_HYBRID_7B)
from .deepseek_v32 import (DeepseekV32, DeepseekV32Config,
                           DEEPSEEK_V32_PRESETS, DEEPSEEK_V32_TINY,
                           DEEPSEEK_V32)
from .deepseek_v3 import (DeepseekV3, DeepseekV3Config, DEEPSEEK_V3_PRESETS,
                          DEEPSEEK_V3_TINY, KANANA_2_30B_A3B)
from .solar_open2 import (SolarOpen2, SolarOpen2Config, SOLAR_OPEN2_PRESETS,
                          SOLAR_OPEN2_TINY, SOLAR_OPEN2_250B)
from .falcon import Falcon, FalconConfig, FALCON_PRESETS
from .opt import OPT, OPTConfig, OPT_PRESETS
from .gptj import GPTJ, GPTJConfig, GPTJ_PRESETS
from .gpt_neo import GPTNeo, GPTNeoConfig, GPTNEO_PRESETS
from .gpt_neox import GPTNeoX, GPTNeoXConfig, GPTNEOX_PRESETS
from .internlm import InternLM, InternLMConfig, INTERNLM_PRESETS
from .diffusion import (UNet2D, UNet2DConfig, VAEDecoder,
                        VAEDecoderConfig, DSUNet, DSVAE)
