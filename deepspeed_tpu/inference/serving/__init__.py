"""Continuous-batching serving: paged KV cache + per-step scheduler.

See ``docs/SERVING.md``. Layering:

- :mod:`.paging` — host-side page allocator (free list; page 0 reserved).
- :mod:`.buckets` — the shape-bucket helpers the serving engine and
  ``InferenceEngine`` share to bound compile counts.
- :mod:`.scheduler` — device-free admit/evict/preempt over decode slots.
- :mod:`.engine` — compiled prefill/decode/scatter programs (the executor).
- :mod:`.bench` — open-loop workload and TTFT/tokens-per-sec reports.
"""

from .buckets import bucket_for, default_buckets
from .engine import ServingConfig, ServingEngine
from .paging import (PageAllocator, PrefixIndex, RESERVED_PAGE, pages_for,
                     prefix_chain_hashes)
from .scheduler import (AdmissionVerdict, ContinuousBatchingScheduler,
                        Request, RequestState, SHED_POLICIES,
                        ServingFaultError)
from .speculate import (AdaptiveSpecK, DraftModelDrafter, NGramDrafter,
                        spec_k_ladder)
from .tenancy import (BROWNOUT_STAGES, BrownoutConfig, BrownoutController,
                      DEFAULT_TIER, StartTimeFairQueue, TIER_ORDER,
                      TenantConfig, TierConfig, TokenBucket, default_tiers,
                      resolve_tenants, resolve_tiers, sacrifice_key,
                      tier_rank)
from .bench import make_open_loop_workload, percentile, run_continuous

__all__ = [
    "PageAllocator", "PrefixIndex", "RESERVED_PAGE", "pages_for",
    "prefix_chain_hashes",
    "bucket_for", "default_buckets",
    "AdmissionVerdict", "ContinuousBatchingScheduler", "Request",
    "RequestState", "SHED_POLICIES", "ServingFaultError",
    "ServingConfig", "ServingEngine",
    "AdaptiveSpecK", "DraftModelDrafter", "NGramDrafter", "spec_k_ladder",
    "BROWNOUT_STAGES", "BrownoutConfig", "BrownoutController",
    "DEFAULT_TIER", "StartTimeFairQueue", "TIER_ORDER", "TenantConfig",
    "TierConfig", "TokenBucket", "default_tiers", "resolve_tenants",
    "resolve_tiers", "sacrifice_key", "tier_rank",
    "make_open_loop_workload", "percentile", "run_continuous",
]
