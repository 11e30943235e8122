"""repro.resilience — recovery layer: deterministic retry/backoff,
quorum degradation tiers, and keyed restore-stall draws shared by the
live `TransientTrainer` and both fleet engines (docs/resilience.md,
DESIGN.md §8)."""
from repro.resilience.policy import (DegradationPolicy, ResilienceConfig,
                                     RetryPolicy, stall_from_uniforms,
                                     stall_pool)
from repro.resilience.runtime import RetryExhausted, call_with_retries

__all__ = [
    "DegradationPolicy", "ResilienceConfig", "RetryPolicy",
    "RetryExhausted", "call_with_retries", "stall_from_uniforms",
    "stall_pool",
]
