"""Model zoo: flagship architectures matching BASELINE.json configs."""

from .gpt import (GPTConfig, GPTModel, GPTForCausalLM, gpt3_1p3b, gpt_small,
                  gpt_tiny)
from .ernie import (ErnieConfig, ErnieModel, ErnieForSequenceClassification,
                    ernie3_base, ernie_tiny)

from .lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM, lfm2_moe_tiny
from .sdar import SdarMoeConfig, SdarMoeForCausalLM, sdar_moe_tiny
from .deepseek import (DeepseekV2Config, DeepseekV2ForCausalLM,
                       deepseek_v2_tiny)
from .falcon_h1 import FalconH1Config, FalconH1ForCausalLM, falcon_h1_tiny
from .nemotron_h import (NemotronHConfig, NemotronHForCausalLM,
                         nemotron_h_tiny)
from .exaone_moe import (ExaoneMoeConfig, ExaoneMoeForCausalLM,
                         exaone_moe_tiny)


__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM", "lfm2_moe_tiny",
           "SdarMoeConfig", "SdarMoeForCausalLM", "sdar_moe_tiny",
           "DeepseekV2Config", "DeepseekV2ForCausalLM", "deepseek_v2_tiny",
           "FalconH1Config", "FalconH1ForCausalLM", "falcon_h1_tiny",
           "NemotronHConfig", "NemotronHForCausalLM", "nemotron_h_tiny",
           "ExaoneMoeConfig", "ExaoneMoeForCausalLM", "exaone_moe_tiny",
           "GPTConfig", "GPTModel", "GPTForCausalLM", "gpt3_1p3b",
           "gpt_small", "gpt_tiny", "ErnieConfig", "ErnieModel",
           "ErnieForSequenceClassification", "ernie3_base", "ernie_tiny"]
