"""TPU-native LLM serving: continuous batching over jitted decode steps.

Analog of the reference's LLM layer (reference: python/ray/llm/ — the
`ray.serve.llm` / `ray.data.llm` entry points, which wrap vLLM engines);
here the engine itself is native jax: a static-shape paged KV pool,
bucketed prefill, one jitted decode per token across all live requests.
"""

from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.model import prefill

__all__ = ["LLMEngine", "prefill"]
