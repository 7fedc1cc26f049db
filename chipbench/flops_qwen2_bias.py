"""Model operations a token of a training step for the configurations of
``reference/qwen2_bias.py``: three times ``flops.qwen2_forward_per_token``
(the q/k/v bias adds are elementwise and not counted)."""
from chipbench import flops


def per_token(spec: dict, seq: int) -> float:
    return 3.0 * flops.qwen2_forward_per_token(spec, seq)
