"""Serving: S-HPLB engine, shard_map attention islands, paged KV cache,
continuous batching, sampling."""
from repro.serving.engine import Engine, EngineConfig
from repro.serving.faults import (
    EpochSwapError,
    FaultError,
    FaultInjector,
    FaultPlan,
    FaultSpec,
    InjectedAllocError,
    IntegrityError,
    TransferError,
)
from repro.serving.kv_cache import BlockAllocator, PagedKVCache
from repro.serving.sampler import SamplingParams, sample
from repro.serving.snapshot import latest_snapshot, restore_serving, save_serving
from repro.serving.scheduler import (
    DEFAULT_CLASSES,
    ContinuousBatcher,
    PriorityClass,
    Request,
    SchedulerStats,
)
from repro.serving.sharded_attention import (
    flash_decode_attention,
    flash_decode_attention_paged,
    hplb_decode_attention_packed,
    hplb_prefill_attention,
)
