from .config import Config, deep_merge_dicts, read_config, save_config
from .log import TextLogger, VariableRecord, AverageMeter, EMAMeter, build_logger

__all__ = [
    "Config",
    "deep_merge_dicts",
    "read_config",
    "save_config",
    "TextLogger",
    "VariableRecord",
    "AverageMeter",
    "EMAMeter",
    "build_logger",
]
