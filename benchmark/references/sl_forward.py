"""The SL step's loss vector without the step: the learner's own held-out
pass (``SLLearner.evaluate``: ``sl_forward`` and ``compute_sl_loss`` from a
cold hidden state, no gradient, no update) over the one batch."""
from typing import Dict


def first_step(learner, batch: Dict) -> Dict[str, float]:
    return learner.evaluate(iter([dict(batch)]), max_batches=1)
