from .sharding import (Rules, DEFAULT_RULES, SEQ_PARALLEL_RULES, auto_rules,
                       logical_pspec, zero_pspec, tree_pspecs, tree_shardings,
                       bytes_per_device, pool_axes, pool_shard_count,
                       pooled_pspec)
from .async_trainer import AsyncTrainer, AsyncConfig
from .slot_serve import (SlotServer, SlotConfig, ServeResult, RetryPolicy,
                         OverloadPolicy, ServePreempted, SHED_POLICIES)
from .admission import (AdmissionPolicy, AdmissionTrace, draw_arrivals,
                        parse_admission)

__all__ = ["Rules", "DEFAULT_RULES", "SEQ_PARALLEL_RULES", "auto_rules", "logical_pspec", "zero_pspec",
           "tree_pspecs", "tree_shardings", "bytes_per_device",
           "pool_axes", "pool_shard_count", "pooled_pspec",
           "AsyncTrainer", "AsyncConfig",
           "SlotServer", "SlotConfig", "ServeResult", "RetryPolicy",
           "OverloadPolicy", "ServePreempted", "SHED_POLICIES",
           "AdmissionPolicy", "AdmissionTrace", "draw_arrivals",
           "parse_admission"]
