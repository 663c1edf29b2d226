"""The paper's contribution: the transient-aware distributed-training runtime.

Modules
-------
transient   lifetime distributions + server state (Fig 3, §II-B)
pricing     Table II price book, per-second billing
cluster     sparse mapping: slots / active set / shard ownership (§III-F)
elastic     membership as data: slot batches with row weights, adaptive
            LR, through the one train step and loop (C5/C6)
staleness   AsyncPSSimulator: exact async-PS semantics in JAX (C4)
checkpoint  master-less replicated checkpointing + fast-save (C2)
cost        analytic cost model + budget planner (C1, §III-C)
scheduler   heterogeneous shards, PS-capacity/collective map, offers,
            MC provisioning optimizer (C7/C8)
simulator   event-driven Monte-Carlo of full training runs (Tables I-V)
mc          batched (vectorized trial-axis) Monte-Carlo engine
policy      online transient-aware provisioning policies + trace-replay
            evaluator (static / greedy / lookahead-MC / oracle)
"""
from repro.core.cluster import SparseCluster, SlotState  # noqa: F401
from repro.core.checkpoint import CheckpointManager  # noqa: F401
from repro.core.elastic import (ElasticRuntime, RevocationEvent,  # noqa: F401
                                lr_scale, slot_batch, slot_counts)
from repro.core.staleness import AsyncPSSimulator, AsyncWorker  # noqa: F401
from repro.core.simulator import (ClusterSpec, WorkerSpec,  # noqa: F401
                                  simulate_many, simulate_run)
from repro.core.mc import MCBatch, simulate_batch  # noqa: F401
from repro.core.scheduler import (MCPlanEstimate,  # noqa: F401
                                  optimize_provisioning,
                                  sweep_configurations)
from repro.core.policy import (GreedyCheapest, LookaheadMC,  # noqa: F401
                               OraclePolicy, PolicyDecision, StaticPolicy,
                               evaluate_policy)
