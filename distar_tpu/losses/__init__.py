from .distill_loss import DistillLossConfig, compute_distill_loss
from .lm_loss import compute_lm_loss
from .rl_loss import (
    HEADS,
    LOSS_TERMS,
    REWARD_FIELDS,
    ReinforcementLossConfig,
    compute_rl_loss,
)
from .sl_loss import SL_METRIC_KEYS, SupervisedLossConfig, compute_sl_loss

__all__ = [
    "DistillLossConfig",
    "compute_distill_loss",
    "compute_lm_loss",
    "HEADS",
    "LOSS_TERMS",
    "REWARD_FIELDS",
    "ReinforcementLossConfig",
    "compute_rl_loss",
    "SL_METRIC_KEYS",
    "SupervisedLossConfig",
    "compute_sl_loss",
]
