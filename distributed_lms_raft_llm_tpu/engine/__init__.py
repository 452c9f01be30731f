"""Inference runtime: sharded generation engine, sampling, batching, gate."""

from .batcher import PagedQueue  # noqa: F401
from .engine import EngineConfig, TutoringEngine  # noqa: F401
from .gate import GateConfig, RelevanceGate  # noqa: F401
from .paged import PagedEngine  # noqa: F401
from .sampling import SamplingParams  # noqa: F401
from .scoring import ScoringManager  # noqa: F401
