"""deepspeed_tpu.serving — the production serving engine.

Continuous in-flight batching over the FastGen-style ragged engine
(``inference/v2``): typed request lifecycle (QUEUED → PREFILL → DECODE →
DONE/EVICTED), token-budget admission with KV-pressure backpressure, LIFO
preemption-and-requeue on KV exhaustion, streaming per-token callbacks,
and the quantized paged-KV mode (``kv_cache_dtype: int8|fp8``).  See
docs/serving.md; ``perfbench/jobs/serve.py`` drives it with traffic.
"""

from .config import ServingConfig                          # noqa: F401
from .request import (IllegalTransition, Request,           # noqa: F401
                      RequestState)
from .scheduler import (AdmissionQueueFull,                 # noqa: F401
                        ServingScheduler, build_serving_engine)
