"""Continuous-batching serving engine (vLLM semantics, JAX backend).

``ServeEngine`` is the device-resident hot path; ``ReferenceServeEngine``
(``repro.engine.reference``) is the frozen pre-rewrite core kept as the
behavioural oracle the tests hold it to.
"""

from repro.engine.engine import (
    EngineAgent,
    EngineRequest,
    EngineStalledError,
    ServeEngine,
)
from repro.engine.reference import ReferenceServeEngine

__all__ = [
    "EngineAgent",
    "EngineRequest",
    "EngineStalledError",
    "ReferenceServeEngine",
    "ServeEngine",
]
