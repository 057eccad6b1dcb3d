"""Continuous-batching LLM inference engine for Serve replicas.

The two techniques that turn a batch-serving layer into an LLM-serving
layer, composed into one loop that runs inside a Serve replica:

- **iteration-level scheduling** (Orca, Yu et al. OSDI'22): admission,
  retirement and preemption decisions happen between every decode step
  — `scheduler.InferenceEngine`;
- **block-granular KV-cache management** (vLLM, Kwon et al. SOSP'23):
  fixed-size blocks in one preallocated buffer with per-sequence block
  tables — `kv_cache.KVCacheManager`.

Typical replica:

    from ray_tpu import serve
    from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                      TinyLM)

    @serve.deployment
    class LLM:
        def __init__(self):
            self.engine = InferenceEngine(TinyLM(), EngineConfig())
            self.engine.start()

        def generate(self, prompt, max_new_tokens=32):
            # Sync generator: streams over the handle
            # (`handle.options(stream=True)`) and the HTTP proxy's
            # chunked path.
            stream = self.engine.submit(prompt, max_new_tokens)
            for tok in stream:
                yield tok

        async def __call__(self, req):
            stream = self.engine.submit(req["prompt"],
                                        req.get("max_new_tokens"))
            return [tok async for tok in stream]

The engine drives a model through `prefill`, `prefill_paged` and
`decode_paged` (`model.py` states the protocol). Two keywords of
`decode_paged` are the scheduler's: `meanwhile`, which the model runs
between a step's dispatch and the wait for its ids (the step before's
tokens go to their streams there), and, for a model that has the
parameter, `ahead`, by which a full batch's next step is dispatched
before the last one's ids are read: it takes its tokens from those ids
on the device and the call returns its own step unread
(`DecodeStep.ids` waits when first looked at). A model without `ahead`
is never called with it. `InferenceEngine.stats()` counts the steps that
went out so (`decode_steps_ahead`, beside `paged_steps`) and the rows
whose end was found a step late (`decode_ends_found_late`).
"""

from ray_tpu.serve.engine.kv_cache import (CacheOverflowError,
                                           KVCacheManager)
from ray_tpu.serve.engine.gigachat_model import GigaChatEngineModel
from ray_tpu.serve.engine.hybrid_model import HybridEngineModel
from ray_tpu.serve.engine.keye_model import KeyeEngineModel
from ray_tpu.serve.engine.laguna_model import LagunaEngineModel
from ray_tpu.serve.engine.mimo_model import MimoEngineModel
from ray_tpu.serve.engine.minicpm_sala_model import MiniCPMSALAEngineModel
from ray_tpu.serve.engine.model import TinyLM, TransformerEngineModel
from ray_tpu.serve.engine.prefix_index import PrefixIndex
from ray_tpu.serve.engine.scheduler import (EngineConfig,
                                            EngineOverloadedError,
                                            EngineStoppedError,
                                            InferenceEngine, TokenStream)

__all__ = [
    "CacheOverflowError", "EngineConfig", "EngineOverloadedError",
    "EngineStoppedError", "GigaChatEngineModel", "HybridEngineModel",
    "InferenceEngine",
    "KVCacheManager", "KeyeEngineModel", "LagunaEngineModel", "MimoEngineModel",
    "MiniCPMSALAEngineModel",
    "PrefixIndex", "TinyLM", "TokenStream", "TransformerEngineModel",
]
