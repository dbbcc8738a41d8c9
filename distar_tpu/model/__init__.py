from .config import default_model_config, student_model_config
from .core import Model
from .lfm2 import LFM2, default_lfm2_config

__all__ = ["Model", "default_model_config", "student_model_config", "LFM2", "default_lfm2_config"]
