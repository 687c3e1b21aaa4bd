"""AutoSAGE core: input-aware kernel scheduling (the paper's contribution).

Pipeline: features -> roofline estimate shortlist -> on-device micro-probe
on an induced subgraph -> guardrail (never regress, Prop. 1) -> persistent
cache with deterministic replay.
"""
from repro.core.features import (
    HardwareSpec,
    InputFeatures,
    ScheduleBucket,
    device_sig,
    features_from_neutral,
    waste_bin,
)
from repro.core.obs import (
    REGISTRY,
    MetricsRegistry,
    ScopedCounter,
    scorecard,
    span,
)
from repro.core.transfer import TransferPlan, best_plan, plan_transfer
from repro.core.scheduler import AutoSage, Decision, ProbeOutcome
from repro.core.faultinject import InjectedFault, fault_point
from repro.core.resilience import (
    CircuitBreaker,
    FaultPolicy,
    ProbeTimeout,
)
from repro.core.cache import (
    CacheKey,
    CacheLockTimeout,
    ScheduleCache,
    ReplayMiss,
    parse_key,
)
from repro.core.guardrail import apply_guardrail, GuardrailDecision
from repro.core.batch import BatchScheduler

__all__ = [
    "AutoSage",
    "BatchScheduler",
    "CacheKey",
    "CacheLockTimeout",
    "CircuitBreaker",
    "Decision",
    "FaultPolicy",
    "InjectedFault",
    "ProbeTimeout",
    "fault_point",
    "HardwareSpec",
    "InputFeatures",
    "MetricsRegistry",
    "ProbeOutcome",
    "REGISTRY",
    "ScheduleBucket",
    "ScheduleCache",
    "ScopedCounter",
    "ReplayMiss",
    "TransferPlan",
    "apply_guardrail",
    "GuardrailDecision",
    "best_plan",
    "device_sig",
    "features_from_neutral",
    "parse_key",
    "plan_transfer",
    "scorecard",
    "span",
    "waste_bin",
]
