from ray_tpu.ops.attention import attention, mha_reference, flash_attention
from ray_tpu.ops.ring_attention import ring_attention

__all__ = ["attention", "mha_reference", "flash_attention",
           "ring_attention"]
