"""Predictive Indexing core (Arulraj et al., "Predictive Indexing").

The paper's contribution as composable pieces:

* ``table`` / ``index`` / ``hybrid_scan`` -- the storage engine: paged
  MVCC column store, partially-built ad-hoc indexes (FULL/VBP/VAP
  population schemes) and the value-agnostic hybrid scan operator, run
  by ``engine`` as batches (a single statement is a batch of one).
* ``classifier`` / ``forecaster`` / ``knapsack`` / ``cost_model`` --
  the ML decision-logic components of Algorithm 1.
* ``monitor`` / ``executor`` -- workload monitoring and the query
  execution engine with optimizer-style access-path selection.
* ``tuner`` / ``baselines`` -- the predictive tuner plus the online /
  adaptive / self-managing / holistic baselines on the same substrate.
* ``build_service`` -- the async tuning pipeline: decide/apply split
  with interleavable build quanta drained between burst dispatches.
* ``layout`` -- the storage-layout tuner it cooperates with (Fig. 9).
"""
from repro.core.build_service import (BuildQuantum, BuildService, CyclePlan,
                                      apply_quantum)
from repro.core.cost_model import IndexDescriptor
from repro.core.engine import ScanEngine
from repro.core.executor import Database, ExecStats, Query
from repro.core.hybrid_scan import (BatchScanResult, HybridPrefixResult,
                                    batched_full_table_scan,
                                    batched_hybrid_index_prefix,
                                    batched_hybrid_scan,
                                    batched_pure_index_scan)
from repro.core.index import (AdHocIndex, ShardedIndex, ShardedVbpState,
                              VbpState, advance_build_shard, build_full,
                              build_pages_vap, make_index,
                              make_sharded_index, make_sharded_vbp,
                              make_vbp, prefix_is_round_robin,
                              shard_full_pages, shard_remaining_pages,
                              sharded_build_pages_vap)
from repro.core.planner import (BuiltIndex, QueryPlanner, ScanPlan,
                                scan_cost)
from repro.core.replica import (ReplicaSet, ReplicaSetTuner,
                                cluster_assignments)
from repro.core.table import (ShardedTable, Table, load_table, make_table,
                              shard_table, unshard_table)
from repro.core.tuner import PredictiveTuner, TunerConfig, make_dl_tuner

__all__ = [
    "AdHocIndex", "BatchScanResult", "BuildQuantum", "BuildService",
    "BuiltIndex", "CyclePlan", "Database", "ExecStats", "apply_quantum",
    "HybridPrefixResult", "IndexDescriptor", "PredictiveTuner", "Query",
    "QueryPlanner", "ReplicaSet", "ReplicaSetTuner", "ScanEngine", "ScanPlan",
    "cluster_assignments",
    "ShardedIndex", "ShardedTable", "ShardedVbpState", "Table", "TunerConfig",
    "VbpState", "batched_full_table_scan", "batched_hybrid_index_prefix",
    "batched_hybrid_scan", "batched_pure_index_scan", "build_full",
    "build_pages_vap", "load_table",
    "make_dl_tuner", "make_index", "make_sharded_index", "make_sharded_vbp",
    "make_table", "make_vbp", "scan_cost", "shard_table",
    "sharded_build_pages_vap", "unshard_table",
]
