from .config import default_model_config, student_model_config
from .core import Model
from .lfm2 import LFM2, default_lfm2_config
from .deepseek_v3 import DeepseekV3, default_deepseek_v3_config
from .nemotron_h import NemotronH, default_nemotron_h_config
from .qwen3_next import Qwen3Next, default_qwen3_next_config
from .laguna import Laguna, default_laguna_config
from .phi4flash import Phi4Flash, default_phi4flash_config

# the token-sequence models ``LMLearner`` trains, by the ``model_type`` of their published configs
TOKEN_MODELS = {"lfm2_moe": (LFM2, default_lfm2_config), "nemotron_h": (NemotronH, default_nemotron_h_config),
                "deepseek_v3": (DeepseekV3, default_deepseek_v3_config),
                "qwen3_next": (Qwen3Next, default_qwen3_next_config), "laguna": (Laguna, default_laguna_config),
                "phi4flash": (Phi4Flash, default_phi4flash_config)}

__all__ = ["Model", "default_model_config", "student_model_config", "LFM2", "default_lfm2_config",
           "NemotronH", "default_nemotron_h_config", "DeepseekV3", "default_deepseek_v3_config",
           "Qwen3Next", "default_qwen3_next_config", "Laguna", "default_laguna_config", "Phi4Flash", "default_phi4flash_config",
           "TOKEN_MODELS"]
