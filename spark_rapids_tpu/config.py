"""Typed configuration system for the TPU accelerator.

TPU-native analog of the reference's ``RapidsConf`` builder DSL
(reference: sql-plugin RapidsConf.scala:235 ``conf(key)``, ~60 ``spark.rapids.*`` keys,
doc generation at RapidsConf.scala:641).

Every tunable in the framework is declared here with a type, default, and doc string.
``TpuConf`` is an immutable snapshot of key->value overrides layered over the defaults;
``generate_docs()`` emits the markdown configuration reference (analog of docs/configs.md).

Per-rule enable keys (``spark.rapids.tpu.sql.expression.<Name>`` etc.) are derived
dynamically by the rule registry (see plan/overrides.py), mirroring
GpuOverrides.scala:126 ``ReplacementRule.confKey``.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional

_PREFIX = "spark.rapids.tpu"


@dataclass(frozen=True)
class ConfEntry:
    """One declared configuration key (analog of ConfEntry, RapidsConf.scala)."""

    key: str
    conf_type: type
    default: Any
    doc: str
    internal: bool = False
    checker: Optional[Callable[[Any], Optional[str]]] = None

    def convert(self, raw: Any) -> Any:
        if raw is None:
            return None
        if self.conf_type is bool:
            if isinstance(raw, bool):
                return raw
            return str(raw).strip().lower() in ("true", "1", "yes", "on")
        if self.conf_type is int:
            return int(str(raw), 0) if isinstance(raw, str) else int(raw)
        if self.conf_type is float:
            return float(raw)
        return str(raw)


_REGISTRY: Dict[str, ConfEntry] = {}
_REG_LOCK = threading.Lock()


def _conf(key: str, conf_type: type, default: Any, doc: str,
          internal: bool = False,
          checker: Optional[Callable[[Any], Optional[str]]] = None) -> ConfEntry:
    full = key if key.startswith(_PREFIX) else f"{_PREFIX}.{key}"
    entry = ConfEntry(full, conf_type, default, doc, internal, checker)
    with _REG_LOCK:
        if full in _REGISTRY:
            raise ValueError(f"duplicate conf key {full}")
        _REGISTRY[full] = entry
    return entry


def _positive(name: str) -> Callable[[Any], Optional[str]]:
    def check(v: Any) -> Optional[str]:
        return None if v > 0 else f"{name} must be > 0, got {v}"
    return check


def _fraction(name: str) -> Callable[[Any], Optional[str]]:
    def check(v: Any) -> Optional[str]:
        return None if 0.0 < v <= 1.0 else f"{name} must be in (0, 1], got {v}"
    return check


def _non_negative(name: str) -> Callable[[Any], Optional[str]]:
    def check(v: Any) -> Optional[str]:
        return None if v >= 0 else f"{name} must be >= 0, got {v}"
    return check


# --------------------------------------------------------------------------------------
# General / plan-rewrite keys (analog of spark.rapids.sql.* in RapidsConf.scala)
# --------------------------------------------------------------------------------------
SQL_ENABLED = _conf(
    "sql.enabled", bool, True,
    "Enable (true) or disable (false) TPU acceleration of Spark SQL plans. When disabled "
    "every operator runs on the CPU engine (analog of spark.rapids.sql.enabled).")

EXPLAIN = _conf(
    "sql.explain", str, "NONE",
    "Explain why parts of a query were or were not placed on the TPU. Values: NONE, "
    "NOT_ON_TPU (print only fallback reasons), ALL (analog of spark.rapids.sql.explain).")

INCOMPATIBLE_OPS = _conf(
    "sql.incompatibleOps.enabled", bool, False,
    "Enable operators that produce results slightly different from Spark's CPU semantics "
    "(e.g. float-sum ordering). Analog of spark.rapids.sql.incompatibleOps.enabled.")

HAS_NANS = _conf(
    "sql.hasNans", bool, True,
    "Assume floating point columns may contain NaN; some ops (joins/aggregates on float "
    "keys) fall back when true. Analog of spark.rapids.sql.hasNans.")

ENABLE_FLOAT_AGG = _conf(
    "sql.variableFloatAgg.enabled", bool, False,
    "Allow float/double aggregations whose result can vary with evaluation order "
    "(parallel reductions). Analog of spark.rapids.sql.variableFloatAgg.enabled.")

CACHED_SCAN_ENABLED = _conf(
    "sql.cachedScan.enabled", bool, True,
    "Scan df.cache()/persist() data on the TPU. Cached batches live in the tiered "
    "spillable store (device->host->disk); disabling this serves them to the CPU engine "
    "instead. Analog of the reference accelerating Spark-cached data (HostColumnarToGpu).")

SCAN_CACHE_ENABLED = _conf(
    "sql.scanCache.enabled", bool, True,
    "Keep device copies of scanned in-memory tables across actions, so repeated queries "
    "over the same DataFrame skip the host-to-device upload (device-tier analog of the "
    "RapidsBufferCatalog's cached batches).")

SCAN_CACHE_BYTES = _conf(
    "sql.scanCache.maxBytes", int, 0,
    "Upper bound on device bytes held by the scan cache; least-recently-used tables are "
    "evicted past it. 0 means derive from the device: half of "
    "memory.outOfCore.headroomFraction of the device budget (memory.tpu.poolSizeBytes, or "
    "allocFraction of the detected HBM) that the device store does not hold.")

ENABLE_CAST_FLOAT_TO_STRING = _conf(
    "sql.castFloatToString.enabled", bool, False,
    "Cast float/double to string on the TPU; formatting may differ from Java in corner "
    "cases. Analog of spark.rapids.sql.castFloatToString.enabled.")

TEST_CONF = _conf(
    "sql.test.enabled", bool, False,
    "Test-mode: assert every supported operator actually ran on the TPU "
    "(analog of spark.rapids.sql.test.enabled).", internal=True)

MAX_READER_BATCH_SIZE_ROWS = _conf(
    "sql.reader.batchSizeRows", int, 2147483647,
    "Soft cap on rows per batch produced by scans "
    "(analog of spark.rapids.sql.reader.batchSizeRows).", checker=_positive("batchSizeRows"))

MAX_READER_BATCH_SIZE_BYTES = _conf(
    "sql.reader.batchSizeBytes", int, 2147483647,
    "Soft cap on bytes per batch produced by scans "
    "(analog of spark.rapids.sql.reader.batchSizeBytes).", checker=_positive("batchSizeBytes"))

TPU_BATCH_SIZE_BYTES = _conf(
    "sql.batchSizeBytes", int, 1 << 31,
    "Target size for coalesced batches flowing between TPU operators (analog of "
    "spark.rapids.sql.batchSizeBytes; default 2 GiB).", checker=_positive("batchSizeBytes"))

STRING_MAX_BYTES = _conf(
    "sql.string.maxBytes", int, 256,
    "Fixed per-row byte width of device string columns. Device strings are stored as a "
    "[rows, maxBytes] uint8 matrix plus a length vector (TPU-friendly layout); rows longer "
    "than this fall back to CPU.", checker=_positive("string.maxBytes"))

ADAPTIVE_ENABLED = _conf(
    "sql.adaptive.enabled", bool, False,
    "Adaptive query execution: run shuffle map stages first, then re-plan with "
    "the observed statistics — coalesce small reduce partitions into "
    "CustomShuffleReader groups and switch shuffled hash joins to broadcast "
    "when the built side turned out small (spark.sql.adaptive.enabled role).")

ADAPTIVE_ADVISORY_PARTITION_BYTES = _conf(
    "sql.adaptive.advisoryPartitionSizeInBytes", int, 64 * 1024 * 1024,
    "Target post-shuffle partition size for AQE coalescing "
    "(spark.sql.adaptive.advisoryPartitionSizeInBytes role).",
    checker=_positive("advisoryPartitionSizeInBytes"))

ADAPTIVE_SKEW_SPLIT_ENABLED = _conf(
    "sql.adaptive.skewSplit.enabled", bool, True,
    "Skew-split readers under AQE (spark.sql.adaptive.skewJoin.enabled role): "
    "a reduce partition observed larger than skewedPartitionFactor x the "
    "median splits into map-id slices (PartialReducerPartitionSpec "
    "semantics); the consuming shuffled hash join reads the other side's "
    "whole partition once per slice, so the union of the per-slice joins is "
    "the unsplit join bit-identically up to row order. Hash aggregates over "
    "a skewed exchange re-partition by group key instead "
    "(split-then-reaggregate via the out-of-core grace machinery).")

ADAPTIVE_SKEW_FACTOR = _conf(
    "sql.adaptive.skewedPartitionFactor", float, 5.0,
    "A reduce partition is skewed when its observed size exceeds this factor "
    "times the median partition size of its shuffle "
    "(spark.sql.adaptive.skewJoin.skewedPartitionFactor role).",
    checker=_positive("skewedPartitionFactor"))

ADAPTIVE_SKEW_THRESHOLD_BYTES = _conf(
    "sql.adaptive.skewedPartitionThreshold.bytes", int, 64 * 1024 * 1024,
    "Minimum observed partition size for skew handling to engage — partitions "
    "under this are never split however lopsided the shuffle "
    "(spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes role).",
    checker=_positive("skewedPartitionThreshold.bytes"))

ADAPTIVE_REFUSION_ENABLED = _conf(
    "sql.adaptive.refusion.enabled", bool, True,
    "Re-run the whole-stage fusion pass over the AQE-rewritten plan so "
    "fusible chains the rewrite created (e.g. the CoalesceBatches inserted "
    "above a coalesced shuffle reader, under a not-yet-fused device op) "
    "compile as one program. Programs keep the normal expression-signature "
    "cache keys, so identical rewritten chains share compilations and "
    "distinct ones never collide.")

ADAPTIVE_COST_MODEL_ENABLED = _conf(
    "sql.adaptive.costModel.enabled", bool, False,
    "Cost-based CPU-vs-TPU placement (generalizing the static float-agg "
    "fallback): at plan time, operators whose estimated input is under "
    "costModel.minDeviceRows stay on the CPU engine — device dispatch and "
    "compile overhead dominates tiny inputs; under AQE, shuffled hash joins "
    "whose OBSERVED inputs are under the threshold are re-placed on the CPU "
    "engine even when the estimates said otherwise.")

ADAPTIVE_COST_MODEL_MIN_DEVICE_ROWS = _conf(
    "sql.adaptive.costModel.minDeviceRows", int, 4096,
    "Row-count threshold for the adaptive cost model: operators with fewer "
    "(estimated or observed) input rows than this run on the CPU engine when "
    "sql.adaptive.costModel.enabled is on.",
    checker=_positive("costModel.minDeviceRows"))

BROADCAST_JOIN_THRESHOLD = _conf(
    "sql.broadcastJoinThreshold.bytes", int, 10 * 1024 * 1024,
    "Maximum estimated build-side size for a join to use the broadcast hash "
    "join strategy (the spark.sql.autoBroadcastJoinThreshold role). Sides with "
    "unknown size never broadcast.")

REPLACE_SORT_MERGE_JOIN = _conf(
    "sql.replaceSortMergeJoin.enabled", bool, True,
    "Replace CPU sort-merge joins with TPU shuffled-hash joins, dropping the sorts "
    "(analog of spark.rapids.sql.replaceSortMergeJoin.enabled).")

UDF_COMPILER_ENABLED = _conf(
    "sql.udfCompiler.enabled", bool, False,
    "Compile Python row UDFs into columnar expression trees so they ride the normal "
    "acceleration path (analog of spark.rapids.sql.udfCompiler.enabled).")

MESH_ENABLED = _conf(
    "sql.mesh.enabled", bool, False,
    "Distributed SPMD execution over a jax.sharding.Mesh: device subtrees run "
    "sharded across the mesh data axis with exchanges as ICI collectives "
    "(all_to_all repartition, all-gather broadcast/merge) — the role the "
    "reference fills with one-task-per-GPU executors plus the UCX accelerated "
    "shuffle (RapidsShuffleInternalManager). With sql.adaptive.enabled, mesh "
    "shuffled joins switch to broadcast at runtime when a build side "
    "materializes under broadcastJoinThreshold (observed size, not an "
    "estimate — every mesh exchange counts before it compiles, so there is "
    "no host-side re-planning pass to run). Mesh aggregations always pick "
    "their merge strategy from actual partial-group counts "
    "(sql.mesh.aggRepartitionThreshold), adaptive flag or not.")

MESH_NUM_DEVICES = _conf(
    "sql.mesh.numDevices", int, 0,
    "Devices in the execution mesh; 0 uses every visible device.")

MESH_SCAN_ASSIGNMENT = _conf(
    "sql.mesh.scan.shardAssignment", str, "rowgroup",
    "How mesh file scans split work across shards: 'rowgroup' balances "
    "statistics-clipped parquet ROW GROUPS over shards AT PLAN TIME (exact "
    "footer row counts, greedy LPT — one huge file still spreads over the "
    "mesh) and each shard's read uploads straight onto its owning device "
    "through the chunked transfer pipeline; 'file' keeps the execute-time "
    "whole-file assignment (formats without row-group metadata always use "
    "it).",
    checker=lambda v: (None if v in ("rowgroup", "file")
                       else f"sql.mesh.scan.shardAssignment must be "
                            f"rowgroup | file, got {v!r}"))

MESH_REQUIRE_ICI = _conf(
    "sql.mesh.requireIci", bool, True,
    "Clip the collective-exchange mesh to ONE ICI domain (the largest "
    "single-slice, single-process device group): in-mesh all_to_all / "
    "all-gather exchanges then never ride DCN, whose loss/latency profile "
    "belongs to the fault-tolerant TCP shuffle stack (shuffle/tcp.py + "
    "retry/checksum layers) instead. Disable only to let XLA schedule "
    "collectives across slices itself.")

EXCHANGE_KEEP_ENCODINGS = _conf(
    "sql.exchange.keepEncodings", bool, True,
    "Shuffle exchanges carry dictionary-encoded columns as int32 INDICES "
    "plus the shared dictionary through the partition/repack kernels "
    "instead of materializing decoded values first — shuffled bytes shrink "
    "by the same ratio the encoded scan bought, and encoded-domain "
    "operators keep working downstream of an exchange (the dictionary "
    "token survives).")

PARQUET_DEVICE_DICT = _conf(
    "io.parquet.deviceDictDecode.enabled", bool, True,
    "TPU parquet scans keep fixed-width columns dictionary-encoded through "
    "the read and decode them ON DEVICE with a gather (narrow indices + the "
    "small dictionary cross the host link instead of the decoded column — "
    "the GpuParquetScan.scala:576 device-decode role for the dictionary "
    "encoding). Strings stay host-decoded.")

PARQUET_DEVICE_RLE = _conf(
    "io.parquet.deviceRleExpand.enabled", bool, True,
    "TPU parquet scans keep RLE-dominant dictionary-encoded column chunks "
    "as (run-ends, run-values) pairs across the host link and expand them "
    "in HBM in one cached program (a scatter of the run ends, a cumsum, a "
    "gather) — often hundreds of bytes on the wire for millions of rows. "
    "Chunks whose index stream is mostly "
    "bit-packed ship as dictionary indices instead; requires "
    "deviceDictDecode.")

ENCODED_DOMAIN = _conf(
    "sql.encodedDomain.enabled", bool, True,
    "Run filters, group-by keys and equi-join keys directly on dictionary "
    "INDICES when a column's encoded form survived upload "
    "(DeviceColumn.encoding): predicates evaluate over the k dictionary "
    "values and gather per row, grouping hashes narrow int32 keys instead "
    "of wide string byte-matrices, and joins match on remapped indices — "
    "key values materialize only for the surviving groups (late "
    "materialization).")

FUSION_ENABLED = _conf(
    "sql.fusion.enabled", bool, True,
    "Whole-stage fusion: collapse maximal chains of fusable device execs "
    "(Project / Filter / Expand / CoalesceBatches, plus the partial-"
    "aggregate fold) between pipeline breakers into one FusedStageExec "
    "whose whole chain traces into a SINGLE jitted XLA program — a filter "
    "becomes a mask threaded through the downstream expressions and no "
    "intermediate batch materializes in HBM between the fused operators "
    "(the WholeStageCodegenExec role; Flare's whole-pipeline compilation "
    "argument). Breakers (exchange, sort, join, limit, union, cache and "
    "mesh boundaries) end a stage; fused stages render with a '*(id)' "
    "prefix in the plan tree.")

FUSION_MAX_OPS = _conf(
    "sql.fusion.maxOps", int, 16,
    "Upper bound on operators collapsed into one fused stage; chains "
    "longer than this split so a pathological plan cannot trace one "
    "enormous XLA program (the spark.sql.codegen.maxFields spirit).",
    checker=_positive("fusion.maxOps"))

SCAN_PREFETCH_BATCHES = _conf(
    "io.scan.prefetchBatches", int, 2,
    "Device parquet scans decode and upload this many chunks ahead of the "
    "consumer on a producer thread, overlapping host decode with the "
    "asynchronous host->device transfer and device compute (the "
    "bufferTime/gpuDecodeTime overlap in GpuParquetScan). 0 reads "
    "serially.")

SHUFFLE_KERNEL_MODE = _conf(
    "shuffle.kernel.mode", str, "auto",
    "Map-side partition reorder strategy: 'auto' uses the fused Pallas "
    "kernel (one streaming HBM pass: MXU one-hot spread into quota-padded "
    "partition pieces; rate not measured on the current machine) on real "
    "TPU backends and the sort path elsewhere; 'interpret' "
    "forces the kernel in Pallas interpreter mode (tests); 'off' always "
    "uses the sort path. Overflowing quotas or non-packable batches fall "
    "back to the sort path automatically.",
    checker=lambda v: (None if v in ("auto", "interpret", "off")
                       else f"shuffle.kernel.mode must be auto | interpret"
                            f" | off, got {v!r}"))

SHUFFLE_DMA_CONSOLIDATE = _conf(
    "shuffle.kernel.dmaConsolidate.enabled", bool, False,
    "Consolidate the partition kernel's quota-padded pieces with ONE "
    "pipelined-DMA compaction program (per-partition semaphores, n copies "
    "in flight, barrier-free unpack) instead of per-partition gather "
    "programs. TPU backends only; elsewhere the gather path runs. Off by "
    "default: it pays a 128-lane pad pass, measured ahead only on wide "
    "schemas (see docs/perf-notes.md round 5).")

SHUFFLE_FETCH_TIMEOUT = _conf(
    "shuffle.fetch.timeoutSeconds", int, 300,
    "How long a reduce-side reader waits for remote shuffle blocks before "
    "raising ShuffleFetchFailedError (the stage-retry signal). Cold cluster "
    "executors pay first-compile latency on the serving path, so this "
    "defaults well above the transfer time itself.")

CLUSTER_EXECUTORS = _conf(
    "sql.cluster.numExecutors", int, 0,
    "Multi-executor query execution: plans split into shuffle stages at "
    "exchange boundaries and tasks run across this many executors, each with "
    "its own shuffle environment (tiered stores + catalogs + transport "
    "server). Exchanges write through the caching shuffle writer and reducers "
    "fetch local blocks from their catalog and remote blocks via the "
    "transport client — the load-bearing RapidsShuffleInternalManager path "
    "(RapidsShuffleInternalManager.scala:194, RapidsCachingReader.scala). "
    "0 disables (single-process engine). Mutually exclusive with mesh "
    "execution.")

CLUSTER_PROCESS_EXECUTORS = _conf(
    "sql.cluster.processExecutors", bool, False,
    "Run each cluster executor as its own OS process (daemon spawned per "
    "executor, tasks dispatched over a control socket, shuffle data over the "
    "TCP transport) instead of in-process executors — the cross-host "
    "topology. Requires the TCP shuffle transport; a registry directory is "
    "created automatically when not configured.")

CLUSTER_TASK_SLOTS = _conf(
    "sql.cluster.taskSlots", int, 4,
    "Concurrent tasks per cluster executor: a stage fans one task per "
    "partition and each executor runs up to this many at once, so stage "
    "wall-clock scales with partitions rather than executors (the "
    "executor-cores role in Spark's task model). Device admission within "
    "each executor is still gated by the concurrentTpuTasks semaphore "
    "(GpuSemaphore.scala:74).", checker=_positive("cluster.taskSlots"))

MESH_AGG_REPARTITION_THRESHOLD = _conf(
    "sql.mesh.aggRepartitionThreshold", int, 8192,
    "Distributed aggregations whose total partial-group count exceeds this "
    "switch from all-gather-and-merge-everywhere to a hash repartition of the "
    "partial buffers by key (each shard merges only its own key range) — the "
    "partial/final split over a hash exchange the reference uses for "
    "arbitrary-cardinality group-bys (aggregate.scala:227 + "
    "GpuHashPartitioning). Small groupings keep the all-gather merge: one "
    "collective, no repartition program.")

# --------------------------------------------------------------------------------------
# Transfer pipeline (host link overlap; the HostToGpuCoalesceIterator pinned-
# memory async-H2D role, engineered per Theseus: the link, device compute and
# host decode must run concurrently, with BOUNDED in-flight buffers)
# --------------------------------------------------------------------------------------
TRANSFER_CHUNK_ROWS = _conf(
    "transfer.chunkRows", int, 1 << 20,
    "Host->device uploads larger than this many rows split into row chunks "
    "so chunk N+1 stages on host while chunk N's asynchronous device_put is "
    "in flight, then reassemble on device (one concat program per schema/"
    "capacity). 0 uploads every table in a single shot.",
    checker=_non_negative("transfer.chunkRows"))

TRANSFER_MAX_INFLIGHT = _conf(
    "transfer.maxInflight", int, 2,
    "Bound on in-flight transfers: at most this many upload chunks (and, "
    "with streaming collect, per-batch downloads) may be outstanding before "
    "the pipeline blocks on the oldest — bounded buffering instead of an "
    "unbounded queue so HBM and host staging memory cannot be overrun.",
    checker=_positive("transfer.maxInflight"))

TRANSFER_PIPELINE_ENABLED = _conf(
    "transfer.pipeline.enabled", bool, True,
    "Planner-inserted bounded-async dispatch between scan and compute "
    "stages: a PipelinedExec wrapper keeps up to transfer.pipeline.depth "
    "batches in flight on a producer thread instead of the strict "
    "pull-per-batch lockstep, sharing the consumer task's device-admission "
    "semaphore hold for backpressure. Skipped on single-core hosts (the "
    "producer thread would only contend with the consumer).")

TRANSFER_PIPELINE_DEPTH = _conf(
    "transfer.pipeline.depth", int, 2,
    "How many batches a PipelinedExec stage boundary keeps in flight "
    "between the producing scan and the consuming compute stage.",
    checker=_positive("transfer.pipeline.depth"))

TRANSFER_STREAMING_COLLECT = _conf(
    "transfer.streamingCollect.enabled", bool, True,
    "collect() enqueues each result batch's device->host download as soon "
    "as its program is dispatched (copy_to_host_async) instead of syncing "
    "then downloading the full result at the end, so D2H overlaps the "
    "remaining compute; at most transfer.maxInflight downloads are "
    "outstanding. Batch order, error propagation and per-operator metrics "
    "are preserved.")

# --------------------------------------------------------------------------------------
# Memory / scheduling (analog of spark.rapids.memory.*)
# --------------------------------------------------------------------------------------
CONCURRENT_TPU_TASKS = _conf(
    "sql.concurrentTpuTasks", int, 2,
    "Number of tasks that may hold the TPU concurrently; the device-admission semaphore "
    "blocks the rest (analog of spark.rapids.sql.concurrentGpuTasks).",
    checker=_positive("concurrentTpuTasks"))

DEVICE_POOL_FRACTION = _conf(
    "memory.tpu.allocFraction", float, 0.9,
    "Fraction of available HBM the buffer arena may occupy "
    "(analog of spark.rapids.memory.gpu.allocFraction).", checker=_fraction("allocFraction"))

DEVICE_POOL_BYTES = _conf(
    "memory.tpu.poolSizeBytes", int, 0,
    "Explicit HBM arena size in bytes; 0 means derive from allocFraction and the "
    "detected device memory.")

HOST_SPILL_STORAGE_SIZE = _conf(
    "memory.host.spillStorageSize", int, 1 << 30,
    "Bytes of host memory used to hold batches spilled from HBM "
    "(analog of spark.rapids.memory.host.spillStorageSize).",
    checker=_positive("spillStorageSize"))

OOC_ENABLED = _conf(
    "memory.outOfCore.enabled", bool, True,
    "Out-of-core execution for hash aggregate, shuffled/broadcast hash join "
    "and sort: when an operator's working set will not fit the device "
    "budget (planner footprint estimate up front, or runtime pressure "
    "reactively), the input is hash/range-partitioned by key into "
    "spillable partitions across the device->host->disk tiers and the "
    "operator recurses per partition — grace-style degradation instead of "
    "an HBM allocation failure (the RapidsBufferCatalog spill design). "
    "With ample budget the single-pass hot path runs unchanged.")

OOC_HEADROOM = _conf(
    "memory.outOfCore.headroomFraction", float, 0.8,
    "Fraction of the free device budget an operator's estimated working "
    "set may occupy before the out-of-core path engages; the rest is "
    "headroom for the operator's own intermediates (sort passes, join "
    "output) and concurrent queries.", checker=_fraction("headroomFraction"))

OOC_FANOUT = _conf(
    "memory.outOfCore.fanout", int, 8,
    "Grace partitions created per recursion level when runtime pressure "
    "triggers partitioning without a plan-time footprint estimate; each "
    "level re-partitions with a depth-salted hash, so colliding key groups "
    "separate on the next level.", checker=_positive("outOfCore.fanout"))

OOC_MAX_PARTITIONS = _conf(
    "memory.outOfCore.maxPartitions", int, 256,
    "Upper bound on grace partitions one operator creates per recursion "
    "level (clamps the planner's footprint-derived choice).",
    checker=_positive("outOfCore.maxPartitions"))

OOC_MAX_DEPTH = _conf(
    "memory.outOfCore.maxRecursionDepth", int, 4,
    "Bound on grace recursion depth. A partition that still exceeds the "
    "budget at the deepest level (e.g. one giant key group, which no hash "
    "can split) runs single-pass there — completion is preferred over "
    "enforcing the budget exactly.",
    checker=_positive("outOfCore.maxRecursionDepth"))

OOC_FORCE_PARTITIONS = _conf(
    "memory.outOfCore.forcePartitions", int, 0,
    "Force every out-of-core-capable operator to grace-partition its input "
    "into this many partitions regardless of budget (0 disables). "
    "Deterministic degradation-path testing knob — the partitioned plan "
    "runs even when everything would fit.",
    checker=_non_negative("outOfCore.forcePartitions"))

MEMORY_FAULTS_PLAN = _conf(
    "memory.faults.plan", str, "",
    "Deterministic HBM-pressure fault-injection plan (empty = no faults), "
    "mirroring shuffle.faults.plan. Semicolon-separated specs, e.g. "
    "'alloc_fail:op=agg,after=1;budget_clamp:fraction=0.25'. Kinds: "
    "alloc_fail (the Nth working-set admission check of a matching "
    "operator fails, forcing the reactive out-of-core path), budget_clamp "
    "(the effective device budget shrinks to fraction of its real value; "
    "sustained — count defaults to 0 = every read). "
    "Keys: op (agg | join | sort | *), after, count (0 = every event), "
    "fraction. Honored by memory/faults.py probes in the grace layer.")

MEMORY_FAULTS_SEED = _conf(
    "memory.faults.seed", int, 0,
    "Identity of the memory fault schedule: the schedule itself is fully "
    "deterministic from the plan text, and (plan, seed) keys one "
    "process-wide event-counter instance — a new seed starts a fresh "
    "chaos run, the same pair replays the same one.")

# --------------------------------------------------------------------------------------
# Shuffle (analog of spark.rapids.shuffle.*)
# --------------------------------------------------------------------------------------
SHUFFLE_TRANSPORT_CLASS = _conf(
    "shuffle.transport.class", str,
    "spark_rapids_tpu.shuffle.inprocess.InProcessTransport",
    "Fully qualified class of the shuffle transport used for peer-to-peer fetches "
    "(analog of spark.rapids.shuffle.transport.class selecting the UCX transport). "
    "InProcessTransport serves executors within one process; cross-host DCN transports "
    "implement the same traits. Mesh-local exchanges bypass this entirely via the ICI "
    "all_to_all path (shuffle/ici.py).")

SHUFFLE_TCP_PORT = _conf(
    "shuffle.tcp.listenPort", int, 0,
    "Listen port of the TCP shuffle transport's management/data socket "
    "(UCX.scala:113 startManagementPort analog); 0 picks an ephemeral port, "
    "published through the registry directory.")

SHUFFLE_TCP_REGISTRY = _conf(
    "shuffle.tcp.registryDir", str, "",
    "Directory where TCP-transport executors publish their host:port for peer "
    "discovery (the management-handshake rendezvous; shared storage or the "
    "control plane's executor registry on a real cluster).")

SHUFFLE_TCP_WORKER_THREADS = _conf(
    "shuffle.tcp.workerThreads", int, 2,
    "Request-handler worker threads per TCP transport (the server "
    "copy-executor pool). The shuffle data plane needs few; the serving "
    "wire protocol (serving/server.py) raises this so bounded-poll "
    "serve.next handlers from many clients do not head-of-line-block each "
    "other.", checker=_positive("shuffle.tcp.workerThreads"))

SHUFFLE_MAX_INFLIGHT_BYTES = _conf(
    "shuffle.maxReceiveInflightBytes", int, 1 << 30,
    "Per-client cap on bytes of shuffle data in flight "
    "(analog of spark.rapids.shuffle.ucx.maxReceiveInflightBytes).")

SHUFFLE_BOUNCE_BUFFER_SIZE = _conf(
    "shuffle.bounceBuffers.size", int, 4 << 20,
    "Size of each bounce buffer used to stage shuffle sends/receives.")

SHUFFLE_BOUNCE_BUFFER_COUNT = _conf(
    "shuffle.bounceBuffers.count", int, 32,
    "Number of bounce buffers per direction.")

SHUFFLE_COMPRESSION_CODEC = _conf(
    "shuffle.compression.codec", str, "none",
    "Codec for shuffle batches: none, copy (memcpy pseudo-codec for testing), "
    "lz4 (always available; the fast default for network-bound shuffles), "
    "zlib, zstd (needs the zstandard package) — analog of "
    "spark.rapids.shuffle.compression.codec. A peer that lacks the "
    "requested codec negotiates the transfer down to copy (TableMeta.codec "
    "carries the codec actually applied).")

SHUFFLE_ZLIB_LEVEL = _conf(
    "shuffle.compression.zlib.level", int, 1,
    "zlib compression level (0-9) for shuffle batches when "
    "shuffle.compression.codec=zlib; 1 favors speed, 9 ratio.",
    checker=lambda v: (None if 0 <= v <= 9
                       else f"zlib.level must be in [0, 9], got {v}"))


SHUFFLE_MAX_RETRIES = _conf(
    "shuffle.maxRetries", int, 3,
    "How many times a transient shuffle failure is retried before it becomes "
    "fatal, at every level of the stack: TCP connect attempts, metadata/"
    "transfer RPCs, per-block transfers (including checksum mismatches), and "
    "reduce-side per-peer re-fetches (which reconnect after a peer loss). "
    "0 disables retries — the first failure surfaces immediately as "
    "ShuffleFetchFailedError (the lineage-recompute signal).",
    checker=_non_negative("maxRetries"))

SHUFFLE_RETRY_BACKOFF_MS = _conf(
    "shuffle.retryBackoffMs", int, 50,
    "Base delay between shuffle retries. Attempt i sleeps roughly "
    "base * 2^i with deterministic jitter (seeded by the retry key), so "
    "retries from many reducers hitting one recovering peer spread out "
    "instead of stampeding.", checker=_positive("retryBackoffMs"))

SHUFFLE_CONNECT_TIMEOUT = _conf(
    "shuffle.connectTimeout", float, 30.0,
    "Seconds a single TCP shuffle connect attempt (registry resolution + "
    "socket establishment) may take before it counts as a transient failure "
    "and enters the retry/backoff schedule.",
    checker=_positive("connectTimeout"))

SHUFFLE_CHECKSUM_ENABLED = _conf(
    "shuffle.checksum.enabled", bool, True,
    "Verify a crc32 over every fetched shuffle buffer (computed by the "
    "server over the on-wire bytes, carried in TransferResponse/TableMeta). "
    "A mismatch marks the transfer as a retryable corruption instead of "
    "silently producing wrong rows; disabling skips client-side "
    "verification only.")

SHUFFLE_RECOMPUTE_MAX_STAGE_ATTEMPTS = _conf(
    "shuffle.recompute.maxStageAttempts", int, 2,
    "How many lineage-scoped recompute rounds one stage may run after its "
    "reduce side exhausts per-peer fetch retries (ShuffleFetchFailedError). "
    "Each round re-executes ONLY the lost map tasks on surviving executors "
    "and replaces their blocks exactly-once; past the budget the error "
    "re-surfaces and the serving failover path (replica re-run) owns "
    "recovery. 0 disables recompute — every fetch failure escalates "
    "directly, the pre-lineage behavior.",
    checker=_non_negative("maxStageAttempts"))

SHUFFLE_FAULTS_PLAN = _conf(
    "shuffle.faults.plan", str, "",
    "Deterministic fault-injection plan for chaos testing the shuffle stack "
    "(empty = no faults). Semicolon-separated specs, e.g. "
    "'drop_conn:peer=exec-1,after=3;corrupt_frame:after=1,count=2'. Kinds: "
    "drop_conn, corrupt_frame, delay_frame, dup_frame, fail_request. Only "
    "honored by the FaultInjectingTransport (shuffle/faults.py).")

SHUFFLE_FAULTS_SEED = _conf(
    "shuffle.faults.seed", int, 0,
    "Seed for the fault-injection plan's random choices (which byte a "
    "corrupt_frame flips, backoff jitter inside the harness) — the same "
    "seed replays the exact same chaos schedule.")

SHUFFLE_FAULTS_TRANSPORT = _conf(
    "shuffle.faults.transport.class", str,
    "spark_rapids_tpu.shuffle.inprocess.InProcessTransport",
    "Transport the FaultInjectingTransport wraps (in-process fabric or the "
    "TCP transport); all traffic flows through the wrapped transport with "
    "faults injected at the connection layer.")

# --------------------------------------------------------------------------------------
# I/O formats (analog of spark.rapids.sql.format.*)
# --------------------------------------------------------------------------------------
PARQUET_ENABLED = _conf(
    "sql.format.parquet.enabled", bool, True,
    "Enable TPU parquet scan/write as a whole.")
PARQUET_READ_ENABLED = _conf(
    "sql.format.parquet.read.enabled", bool, True, "Enable TPU parquet scans.")
PARQUET_WRITE_ENABLED = _conf(
    "sql.format.parquet.write.enabled", bool, True, "Enable TPU parquet writes.")
ORC_ENABLED = _conf(
    "sql.format.orc.enabled", bool, True, "Enable TPU ORC scan/write as a whole.")
ORC_READ_ENABLED = _conf(
    "sql.format.orc.read.enabled", bool, True, "Enable TPU ORC scans.")
ORC_WRITE_ENABLED = _conf(
    "sql.format.orc.write.enabled", bool, True, "Enable TPU ORC writes.")
CSV_ENABLED = _conf(
    "sql.format.csv.enabled", bool, True, "Enable TPU CSV scanning as a whole.")
CSV_READ_ENABLED = _conf(
    "sql.format.csv.read.enabled", bool, True, "Enable TPU CSV scans.")

# --------------------------------------------------------------------------------------
# Serving (concurrent query scheduler + cross-query program cache)
# --------------------------------------------------------------------------------------
SERVING_MAX_CONCURRENT = _conf(
    "serving.maxConcurrentQueries", int, 4,
    "How many submitted queries the session scheduler runs concurrently "
    "(the shared worker-pool size). Queries past the bound wait in their "
    "tenant's FIFO queue under fair-share admission; device admission "
    "within a running query is still gated by sql.concurrentTpuTasks.",
    checker=_positive("serving.maxConcurrentQueries"))

SERVING_TENANT_WEIGHTS = _conf(
    "serving.tenantWeights", str, "",
    "Per-tenant fair-share weights as 'tenant:weight,...' (e.g. "
    "'etl:3,adhoc:1'). Admission picks the queued tenant with the lowest "
    "served/weight deficit (FIFO within a tenant); unlisted tenants weigh "
    "1. The same weights drive the device-admission semaphore so a heavy "
    "tenant cannot starve the rest at either layer.")

SERVING_SHAPE_BUCKETS = _conf(
    "serving.shapeBuckets", bool, True,
    "Bucket row counts to powers of two in cross-query program-cache keys "
    "(the tpu-lint R001 discipline): row-count drift between batches of "
    "the same plan reuses one compiled program instead of recompiling per "
    "exact shape. Disabling keys programs on exact capacities — only for "
    "debugging recompile behavior.")

SERVING_QUERY_TIMEOUT = _conf(
    "serving.queryTimeoutSeconds", float, 0.0,
    "Default per-query deadline for submitted queries, enforced "
    "cooperatively at exec boundaries and in the pipeline producer; a "
    "query past its deadline fails with QueryTimeoutError and releases "
    "its device-semaphore hold and catalog buffers. 0 disables; "
    "session.submit(timeout=...) overrides per query.",
    checker=_non_negative("serving.queryTimeoutSeconds"))

SERVING_CACHE_DIR = _conf(
    "serving.cache.dir", str, "",
    "Directory of the serving program-cache's on-disk plan-key index "
    "(plus the jax persistent compilation cache it rides on): a restarted "
    "server warms compiled programs from disk instead of re-tracing them "
    "cold. Empty uses the process compilation-cache directory configured "
    "at startup (device.py); 'off' disables the index.")

SERVING_CACHE_MAX_PROGRAMS = _conf(
    "serving.cache.maxPrograms", int, 4096,
    "Upper bound on compiled programs the in-memory cross-query cache "
    "retains; least-recently-used programs are dropped past it (their "
    "on-disk compilation-cache entries survive, so a re-miss recompiles "
    "warm).", checker=_positive("serving.cache.maxPrograms"))

# --------------------------------------------------------------------------------------
# Serving: network wire protocol, footprint admission, preemption
# --------------------------------------------------------------------------------------
SERVING_NET_PORT = _conf(
    "serving.net.listenPort", int, 0,
    "Listen port of the query service's wire transport (Arrow IPC over the "
    "TCP shuffle framing); 0 picks an ephemeral port, printed by the server "
    "process at startup.")

SERVING_NET_TRANSPORT = _conf(
    "serving.net.transportClass", str,
    "spark_rapids_tpu.shuffle.tcp.TcpTransport",
    "Transport class the query service speaks over — the PR 2 "
    "framing/checksum/retry stack, NOT new plumbing. Any ShuffleTransport "
    "implementation works; tests swap in the in-process fabric.")

SERVING_NET_FAULTS_PLAN = _conf(
    "serving.net.faults.plan", str, "",
    "Deterministic wire-chaos plan for the query service (empty = none): "
    "the shuffle FaultPlan grammar (drop_conn / corrupt_frame / "
    "delay_frame / dup_frame / fail_request) injected by wrapping the "
    "serving transport in the FaultInjectingTransport — corrupted result "
    "frames must surface as retryable checksum failures, dropped "
    "connections as failed handles with a batches-delivered count.")

SERVING_NET_FAULTS_SEED = _conf(
    "serving.net.faults.seed", int, 0,
    "Seed for the serving wire-chaos plan's random choices; a fixed seed "
    "replays the same schedule (mirrors shuffle.faults.seed).")

SERVING_NET_POLL_MS = _conf(
    "serving.net.nextPollMs", int, 20,
    "How long a serve.next handler waits (bounded — the R010 discipline) "
    "for the query's next streamed batch before answering WAIT and "
    "releasing its transport worker thread; the client re-polls "
    "immediately, so this bounds handler occupancy, not stream latency.",
    checker=_positive("serving.net.nextPollMs"))

SERVING_NET_STREAM_DEPTH = _conf(
    "serving.net.streamQueueDepth", int, 4,
    "Bound on result batches buffered server-side per streaming query "
    "between the scheduler worker (producer) and the wire layer "
    "(consumer); a full queue backpressures the producer at its next "
    "batch boundary — bounded buffering, never an unbounded queue.",
    checker=_positive("serving.net.streamQueueDepth"))

SERVING_NET_MAX_STREAM_ROWS = _conf(
    "serving.net.maxStreamBatchRows", int, 1 << 20,
    "Result batches larger than this many rows are sliced into multiple "
    "wire frames before streaming, bounding per-frame memory on both ends "
    "(slices concatenate client-side to the bit-identical table). "
    "0 streams every exec batch whole.",
    checker=_non_negative("serving.net.maxStreamBatchRows"))

SERVING_NET_RPC_TIMEOUT = _conf(
    "serving.net.rpcTimeoutSeconds", float, 60.0,
    "Client-side bound on any single wire RPC (submit / next / fetch / "
    "cancel) and on each posted batch receive; an expired wait surfaces "
    "as a failed handle with its batches-delivered count, never a hang.",
    checker=_positive("serving.net.rpcTimeoutSeconds"))

SERVING_ADMIT_FOOTPRINT = _conf(
    "serving.admission.byFootprint.enabled", bool, True,
    "Admit RUNNING queries against the device budget using the plan's "
    "working_set_estimate (the PR 11 footprint contract) instead of a "
    "bare query count: a query whose estimate does not fit the free "
    "budget waits (cancellable, visible in "
    "serving.admission_rejections_footprint) until running queries "
    "release their share. A query larger than the whole budget is "
    "admitted under a grace hint, charged the out-of-core HEADROOM "
    "share of the budget — the grace/spill layer completes it within "
    "that share, and the remaining fraction stays free so interactive "
    "queries still reach the device semaphore (where preemption can "
    "see them) alongside a whale.")

SERVING_PREEMPT_ENABLED = _conf(
    "serving.preemption.enabled", bool, False,
    "Batch-granularity preemption of RUNNING queries: when another "
    "tenant's query has starved on device admission past "
    "preemption.starvationMs, a preemptible running query yields its "
    "device-semaphore permit at its next exec-boundary checkpoint "
    "(check_cancelled sites), optionally parks spillable device state "
    "down the grace/spill tiers, and re-acquires under fair share — so a "
    "whale cannot starve interactive tenants between its batches.")

SERVING_PREEMPT_STARVATION_MS = _conf(
    "serving.preemption.starvationMs", int, 50,
    "How long another tenant's head-of-line device-admission waiter must "
    "have been blocked before a running preemptible query yields at its "
    "next batch boundary.",
    checker=_positive("serving.preemption.starvationMs"))

SERVING_PREEMPT_PARK = _conf(
    "serving.preemption.parkSpillable", bool, True,
    "On yield, shed the device store down to the out-of-core headroom "
    "watermark (memory.outOfCore.headroomFraction) — coldest-first, so "
    "the overage parked down the host/disk tiers is in practice the "
    "yielding whale's grace partitions, and the admitted tenant gets "
    "immediate HBM headroom; parked state re-admits on next access. "
    "Disabling leaves parking to the store's reactive pressure path.")

# --------------------------------------------------------------------------------------
# Serving: replica health, failover, routing (the fleet-resilience layer)
# --------------------------------------------------------------------------------------

SERVING_NET_REGISTRY = _conf(
    "serving.net.registryDir", str, "",
    "Registry directory for serving-replica discovery (the shuffle "
    "registry-dir rendezvous applied to the query service): each replica "
    "publishes <dir>/<executor_id> containing host:port and refreshes the "
    "file's mtime as a liveness heartbeat; clients scan the directory to "
    "discover replicas, skipping (and garbage-collecting) entries whose "
    "heartbeat is older than serving.health.livenessWindowSeconds. Empty "
    "disables discovery — clients then need explicit addresses.")

SERVING_HEALTH_HEARTBEAT = _conf(
    "serving.health.heartbeatSeconds", float, 1.0,
    "How often a serving replica refreshes its registry-file mtime (the "
    "liveness heartbeat). A SIGKILL'd replica stops heartbeating, so its "
    "entry ages out of the liveness window and clients stop routing to "
    "it even though the process never removed its file.",
    checker=_positive("serving.health.heartbeatSeconds"))

SERVING_HEALTH_LIVENESS_WINDOW = _conf(
    "serving.health.livenessWindowSeconds", float, 5.0,
    "Registry entries whose heartbeat mtime is older than this are "
    "considered dead: discovery scans skip them and remove the stale "
    "file (a crashed replica cannot retract its own entry). Keep this "
    "a few multiples of serving.health.heartbeatSeconds so a slow "
    "heartbeat is not mistaken for a death.",
    checker=_positive("serving.health.livenessWindowSeconds"))

SERVING_HEALTH_PROBE_INTERVAL = _conf(
    "serving.health.probeIntervalSeconds", float, 2.0,
    "How often the client re-probes each replica's serve.health RPC "
    "(liveness + the serve.stats snapshot load-aware routing scores). "
    "Probes run on the routing path when the last snapshot is older "
    "than this; 0 probes before every routing decision (tests).",
    checker=_non_negative("serving.health.probeIntervalSeconds"))

SERVING_HEALTH_PROBE_TIMEOUT = _conf(
    "serving.health.probeTimeoutSeconds", float, 5.0,
    "Bound on one serve.health probe RPC — probes must fail fast so a "
    "hung replica costs the router one bounded wait, not the full "
    "serving.net.rpcTimeoutSeconds.",
    checker=_positive("serving.health.probeTimeoutSeconds"))

SERVING_FAILOVER_ENABLED = _conf(
    "serving.failover.enabled", bool, True,
    "Resubmit a mid-stream query to a healthy replica when its replica "
    "dies (connection lost / RPC timeout / exhausted frame retries), "
    "resuming the result stream from the last delivered batch sequence "
    "number: the new replica re-runs the query and skips already-"
    "delivered frames (dedup by seq — exactly-once delivery to the "
    "caller). Only queries marked idempotent fail over (the default for "
    "pure SELECTs); non-idempotent queries surface WireQueryError with "
    "batches_delivered as before.")

SERVING_FAILOVER_MAX_ATTEMPTS = _conf(
    "serving.failover.maxAttempts", int, 3,
    "How many times one query may fail over to another replica before "
    "the client gives up and surfaces the failure.",
    checker=_positive("serving.failover.maxAttempts"))

SERVING_BREAKER_THRESHOLD = _conf(
    "serving.failover.breakerFailureThreshold", int, 3,
    "Consecutive probe/submit/stream failures against one replica that "
    "flip its client-side circuit breaker OPEN. An OPEN replica receives "
    "ZERO submissions; only health probes (on the exponential-backoff "
    "schedule) go there, and one probe success closes the breaker.",
    checker=_positive("serving.failover.breakerFailureThreshold"))

SERVING_BREAKER_BACKOFF_MS = _conf(
    "serving.failover.breakerBackoffMs", int, 200,
    "Base backoff between an OPEN breaker's health probes; successive "
    "failed probes back off exponentially with deterministic jitter "
    "(the shuffle/retry.py schedule, seeded by serving.net.faults.seed).",
    checker=_positive("serving.failover.breakerBackoffMs"))

SERVING_ROUTING_POLICY = _conf(
    "serving.routing.policy", str, "loadaware",
    "How the client picks a replica for a new submission: 'loadaware' "
    "scores each healthy replica's latest serve.health snapshot (free "
    "device budget after footprint charges, queue depth + running "
    "count, p99 wall over the stats window) and routes to the best — "
    "the whale lands on the replica with free budget; 'roundrobin' is "
    "the PR 12 rotation. Replicas behind an OPEN breaker or DRAINING "
    "are excluded under either policy.",
    checker=lambda v: (None if v in ("loadaware", "roundrobin") else
                       f"serving.routing.policy must be 'loadaware' or "
                       f"'roundrobin', got {v!r}"))

# --------------------------------------------------------------------------------------
# Serving: elastic fleet (supervisor + autoscaler) and overload shedding
# --------------------------------------------------------------------------------------

SERVING_FLEET_MIN_REPLICAS = _conf(
    "serving.fleet.minReplicas", int, 1,
    "Lower bound on supervised replica slots: the autoscaler never "
    "scales the fleet below this many (DEGRADED crash-looping slots "
    "still count toward the bound — the controller cannot drain its "
    "way to an empty fleet).",
    checker=_positive("serving.fleet.minReplicas"))

SERVING_FLEET_MAX_REPLICAS = _conf(
    "serving.fleet.maxReplicas", int, 4,
    "Upper bound on supervised replica slots: scale-up stops here no "
    "matter the pressure — past it the front door sheds "
    "(serving.maxQueuedPerTenant / OverloadedError) instead of growing.",
    checker=_positive("serving.fleet.maxReplicas"))

SERVING_FLEET_SUPERVISE_INTERVAL = _conf(
    "serving.fleet.superviseIntervalSeconds", float, 0.2,
    "Supervisor sweep period: each tick polls every slot's process for "
    "exit, checks registry heartbeats against the liveness window, and "
    "restarts due slots on the deterministic backoff schedule.",
    checker=_positive("serving.fleet.superviseIntervalSeconds"))

SERVING_FLEET_RESTART_BACKOFF_MS = _conf(
    "serving.fleet.restartBackoffMs", int, 200,
    "Base delay before restarting a dead replica slot; successive "
    "deaths of the same slot back off exponentially with deterministic "
    "jitter (the shuffle/retry.py schedule, keyed by slot index), and "
    "the attempt counter resets after "
    "serving.fleet.stableUptimeSeconds of healthy uptime.",
    checker=_positive("serving.fleet.restartBackoffMs"))

SERVING_FLEET_STABLE_UPTIME = _conf(
    "serving.fleet.stableUptimeSeconds", float, 30.0,
    "A replica that stays up this long is considered stable: its slot's "
    "restart-backoff attempt counter resets, so the next (unrelated) "
    "death restarts fast instead of inheriting an old slow schedule.",
    checker=_positive("serving.fleet.stableUptimeSeconds"))

SERVING_FLEET_CRASH_LOOP_THRESHOLD = _conf(
    "serving.fleet.crashLoopThreshold", int, 3,
    "Crash-loop breaker: this many deaths of one slot within "
    "serving.fleet.crashLoopWindowSeconds stops the restart storm — the "
    "slot is marked DEGRADED (no further restarts, surfaced in fleet "
    "stats and excluded from the autoscaler's healthy count) instead of "
    "burning CPU forever. reset_slot() re-arms it after the operator "
    "fixes the cause.",
    checker=_positive("serving.fleet.crashLoopThreshold"))

SERVING_FLEET_CRASH_LOOP_WINDOW = _conf(
    "serving.fleet.crashLoopWindowSeconds", float, 10.0,
    "Sliding window the crash-loop breaker counts slot deaths over: "
    "deaths older than this no longer count toward the threshold.",
    checker=_positive("serving.fleet.crashLoopWindowSeconds"))

SERVING_FLEET_CONTROL_INTERVAL = _conf(
    "serving.fleet.controlIntervalSeconds", float, 1.0,
    "Autoscaler control-loop period: each tick aggregates serve.health "
    "snapshots across the fleet and makes one scaling decision "
    "(watermarks + hysteresis + cooldowns).",
    checker=_positive("serving.fleet.controlIntervalSeconds"))

SERVING_FLEET_SCALE_UP_WATERMARK = _conf(
    "serving.fleet.scaleUpWatermark", float, 0.8,
    "High watermark on the fleet pressure signal (max of normalized "
    "admission queue depth and device-budget fraction across healthy "
    "replicas): pressure at or above this for "
    "serving.fleet.scaleUpStableTicks consecutive ticks requests one "
    "more replica (bounded by maxReplicas and the up-cooldown).",
    checker=_fraction("serving.fleet.scaleUpWatermark"))

SERVING_FLEET_SCALE_DOWN_WATERMARK = _conf(
    "serving.fleet.scaleDownWatermark", float, 0.25,
    "Low watermark on the fleet pressure signal: pressure at or below "
    "this for serving.fleet.scaleDownStableTicks consecutive ticks "
    "retires one replica through the graceful-drain path (bounded by "
    "minReplicas and the down-cooldown). Keep it well under the high "
    "watermark — the dead band between them is the hysteresis that "
    "stops flapping.",
    checker=_fraction("serving.fleet.scaleDownWatermark"))

SERVING_FLEET_SCALE_UP_STABLE_TICKS = _conf(
    "serving.fleet.scaleUpStableTicks", int, 2,
    "Consecutive control ticks the pressure must hold at/above the high "
    "watermark before a scale-up fires (a one-tick spike is noise, not "
    "a trend).", checker=_positive("serving.fleet.scaleUpStableTicks"))

SERVING_FLEET_SCALE_DOWN_STABLE_TICKS = _conf(
    "serving.fleet.scaleDownStableTicks", int, 5,
    "Consecutive control ticks the pressure must hold at/below the low "
    "watermark before a scale-down fires — longer than the up "
    "requirement on purpose: growing late queues work, shrinking early "
    "sheds it.", checker=_positive("serving.fleet.scaleDownStableTicks"))

SERVING_FLEET_SCALE_UP_COOLDOWN = _conf(
    "serving.fleet.scaleUpCooldownSeconds", float, 5.0,
    "Minimum wall time between two scale-ups: a freshly started replica "
    "needs time to register and absorb load before the controller may "
    "conclude the fleet is still too small.",
    checker=_non_negative("serving.fleet.scaleUpCooldownSeconds"))

SERVING_FLEET_SCALE_DOWN_COOLDOWN = _conf(
    "serving.fleet.scaleDownCooldownSeconds", float, 30.0,
    "Minimum wall time between two scale-downs, and after any scale-up "
    "before the first scale-down — the asymmetry (longer than the up "
    "cooldown) biases the fleet toward capacity under oscillating load.",
    checker=_non_negative("serving.fleet.scaleDownCooldownSeconds"))

SERVING_FLEET_P99_OBJECTIVE = _conf(
    "serving.fleet.p99ObjectiveSeconds", float, 0.0,
    "Latency objective the autoscaler folds into fleet pressure: a "
    "replica's rolling-window p99 query wall divided by this objective "
    "becomes a pressure component alongside footprint and queue depth, "
    "so a fleet that is slow (not just full) still scales up. 0 "
    "disables the latency component.",
    checker=_non_negative("serving.fleet.p99ObjectiveSeconds"))

SERVING_MAX_QUEUED_PER_TENANT = _conf(
    "serving.maxQueuedPerTenant", int, 256,
    "Bound on one tenant's scheduler queue depth: a submission past it "
    "is shed at the front door with a structured RETRYABLE "
    "OverloadedError carrying a retry-after hint (counted in "
    "serving.sheds) instead of queueing without limit — one flooding "
    "tenant cannot OOM the scheduler. 0 disables the bound.",
    checker=_non_negative("serving.maxQueuedPerTenant"))

SERVING_QUOTA_MAX_PER_CLIENT = _conf(
    "serving.quota.maxConcurrentPerClient", int, 0,
    "Per-client concurrent-query quota at the serving wire: a client "
    "(wire peer) with this many open queries on a replica gets further "
    "submits rejected with a structured RETRYABLE QuotaExceededError "
    "(counted in serving.quota_rejections). 0 disables the quota.",
    checker=_non_negative("serving.quota.maxConcurrentPerClient"))

SERVING_OVERLOAD_RETRY_AFTER = _conf(
    "serving.overload.retryAfterSeconds", float, 0.25,
    "Base retry-after hint shipped inside OverloadedError / "
    "QuotaExceededError rejections; the server scales it with how far "
    "past the bound the tenant's queue is, and the client honors the "
    "hint (floored by its deterministic backoff schedule) before "
    "retrying.", checker=_positive("serving.overload.retryAfterSeconds"))

SERVING_OVERLOAD_CLIENT_RETRIES = _conf(
    "serving.overload.clientRetries", int, 2,
    "How many full rotation passes the client retries a submission that "
    "EVERY replica shed (each pass sleeps the max of the replicas' "
    "retry-after hints and the deterministic backoff for that attempt) "
    "before surfacing the OverloadedError to the caller.",
    checker=_non_negative("serving.overload.clientRetries"))

# --------------------------------------------------------------------------------------
# Observability (SQLMetrics / NVTX analog)
# --------------------------------------------------------------------------------------
METRICS_ENABLED = _conf(
    "metrics.enabled", bool, True,
    "Collect per-operator metrics (rows, batches, op time) — analog of SQLMetrics.")

TRACE_ENABLED = _conf(
    "trace.enabled", bool, False,
    "Structured query tracing (utils/tracing.py): record per-operator "
    "execute() spans (rows/batches/bytes, wall + self time, keyed by plan "
    "node id), transfer chunk upload / async download spans, shuffle "
    "fetch/retry events, grace partition/spill events, and serving "
    "lifecycle/admission/preemption/wire spans into a bounded ring "
    "buffer, and emit a named jax.profiler range PER OPERATOR (analog of "
    "the NVTX ranges). Feeds EXPLAIN ANALYZE (tree_string(analyze=True) "
    "/ QueryHandle.explain_analyze()) and the Chrome/Perfetto trace "
    "export. Off: every hook reduces to one boolean read.")

TRACE_EXPORT_PATH = _conf(
    "trace.export.path", str, "",
    "When set (and trace.enabled), each action writes its span window as "
    "Chrome trace-event JSON to this path on completion — loadable in "
    "ui.perfetto.dev / chrome://tracing to inspect overlapped pipelines "
    "(chunked upload vs compute, streaming D2H). The file is rewritten "
    "per action (last-action semantics, like session.last_metrics); use "
    "QueryHandle.export_trace(path) for one specific query's spans.")

TRACE_BUFFER_SPANS = _conf(
    "trace.maxBufferedSpans", int, 262144,
    "Capacity of the tracing ring buffer: a long-running traced server "
    "overwrites its oldest spans past this bound instead of growing "
    "without limit. Exports and EXPLAIN ANALYZE see at most this many "
    "trailing spans. The default holds a whole 48 s traced window of "
    "TPC-H SF1 queries over parquet files (over a thousand spans a "
    "query, most of them one a page decompressed).",
    checker=_positive("trace.maxBufferedSpans"))

SERVING_STATS_WINDOW = _conf(
    "serving.stats.windowSeconds", float, 300.0,
    "Rolling window of the serve.stats time-series (serving/stats.py): "
    "per-replica gauge samples (device budget in use, admission queue "
    "depth, running/queued per tenant) and query wall times older than "
    "this are dropped; p50/p99 query wall is computed over the window. "
    "The feed load-aware replica routing consumes (ROADMAP item 4).",
    checker=_positive("serving.stats.windowSeconds"))

SERVING_STATS_SAMPLE_INTERVAL = _conf(
    "serving.stats.sampleIntervalSeconds", float, 1.0,
    "Period of the scheduler's background gauge-sampler tick: before it, "
    "gauges were sampled only at terminal queries and stats requests, so "
    "an idle or wedged replica reported a stale time-series exactly when "
    "the autoscaler most needed truth. The daemon tick keeps the series "
    "fresh and snapshot() stamps its age (age_s) so consumers can treat "
    "a stalled sampler as unhealthy. 0 disables the tick (tests).",
    checker=_non_negative("serving.stats.sampleIntervalSeconds"))

SERVING_STATS_STALE_AFTER = _conf(
    "serving.stats.staleAfterSeconds", float, 10.0,
    "Snapshot age (serve_stats age_s — seconds since the last sampler "
    "tick) past which the autoscaler treats a replica's stats as stale: "
    "a stale replica is excluded from the pressure signal AND from the "
    "healthy count, so a wedged replica flat-lining its gauges cannot "
    "read as idle and trigger a scale-down.",
    checker=_positive("serving.stats.staleAfterSeconds"))


class TpuConf:
    """Immutable snapshot of configuration overrides (analog of RapidsConf)."""

    def __init__(self, overrides: Optional[Dict[str, Any]] = None):
        self._values: Dict[str, Any] = {}
        if overrides:
            for key, raw in overrides.items():
                entry = _REGISTRY.get(key)
                if entry is None:
                    # Unknown keys under our prefix are kept for dynamic per-rule
                    # enable keys; anything else is ignored like Spark does.
                    self._values[key] = raw
                    continue
                val = entry.convert(raw)
                if entry.checker is not None:
                    err = entry.checker(val)
                    if err:
                        raise ValueError(f"{key}: {err}")
                self._values[key] = val

    def get(self, entry: ConfEntry) -> Any:
        if entry.key in self._values:
            return self._values[entry.key]
        return entry.default

    def get_raw(self, key: str, default: Any = None) -> Any:
        return self._values.get(key, default)

    def is_rule_enabled(self, conf_key: str, default: bool = True) -> bool:
        raw = self._values.get(conf_key)
        if raw is None:
            return default
        return str(raw).strip().lower() in ("true", "1", "yes", "on")

    def with_overrides(self, extra: Dict[str, Any]) -> "TpuConf":
        merged = dict(self._values)
        merged.update(extra)
        return TpuConf(merged)

    # Convenience properties for hot keys -------------------------------------------------
    @property
    def sql_enabled(self) -> bool: return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str: return str(self.get(EXPLAIN)).upper()

    @property
    def batch_size_bytes(self) -> int: return self.get(TPU_BATCH_SIZE_BYTES)

    @property
    def string_max_bytes(self) -> int: return self.get(STRING_MAX_BYTES)

    @property
    def is_test_enabled(self) -> bool: return self.get(TEST_CONF)

    @property
    def concurrent_tpu_tasks(self) -> int: return self.get(CONCURRENT_TPU_TASKS)

    @property
    def shuffle_transport_class(self) -> str: return self.get(SHUFFLE_TRANSPORT_CLASS)

    @property
    def shuffle_tcp_port(self) -> int: return self.get(SHUFFLE_TCP_PORT)

    @property
    def shuffle_tcp_registry(self) -> str: return self.get(SHUFFLE_TCP_REGISTRY)

    @property
    def shuffle_max_inflight_bytes(self) -> int:
        return self.get(SHUFFLE_MAX_INFLIGHT_BYTES)

    @property
    def shuffle_bounce_buffer_size(self) -> int:
        return self.get(SHUFFLE_BOUNCE_BUFFER_SIZE)

    @property
    def shuffle_bounce_buffer_count(self) -> int:
        return self.get(SHUFFLE_BOUNCE_BUFFER_COUNT)

    @property
    def shuffle_codec(self) -> str: return self.get(SHUFFLE_COMPRESSION_CODEC)

    @property
    def shuffle_max_retries(self) -> int: return self.get(SHUFFLE_MAX_RETRIES)

    @property
    def shuffle_retry_backoff_ms(self) -> int:
        return self.get(SHUFFLE_RETRY_BACKOFF_MS)

    @property
    def shuffle_connect_timeout(self) -> float:
        return self.get(SHUFFLE_CONNECT_TIMEOUT)

    @property
    def shuffle_checksum_enabled(self) -> bool:
        return self.get(SHUFFLE_CHECKSUM_ENABLED)

    @property
    def shuffle_recompute_max_stage_attempts(self) -> int:
        return self.get(SHUFFLE_RECOMPUTE_MAX_STAGE_ATTEMPTS)

    @property
    def shuffle_faults_plan(self) -> str: return self.get(SHUFFLE_FAULTS_PLAN)

    @property
    def shuffle_faults_seed(self) -> int: return self.get(SHUFFLE_FAULTS_SEED)

    @property
    def shuffle_faults_transport_class(self) -> str:
        return self.get(SHUFFLE_FAULTS_TRANSPORT)


def all_entries() -> List[ConfEntry]:
    return sorted(_REGISTRY.values(), key=lambda e: e.key)


def generate_docs(include_internal: bool = False) -> str:
    """Emit the markdown configuration reference (analog of RapidsConf.help(),
    RapidsConf.scala:641 -> docs/configs.md)."""
    lines = [
        "# TPU Accelerator Configuration",
        "",
        "All configs are set like ordinary Spark confs. Generated by "
        "`python -m spark_rapids_tpu.config`.",
        "",
        "| Name | Description | Default |",
        "|---|---|---|",
    ]
    for entry in all_entries():
        if entry.internal and not include_internal:
            continue
        lines.append(f"| {entry.key} | {entry.doc} | {entry.default} |")
    lines.append("")
    return "\n".join(lines)


def from_environ() -> TpuConf:
    """Build a TpuConf from SPARK_RAPIDS_TPU_* environment variables (key dots -> _)."""
    overrides: Dict[str, Any] = {}
    for env_key, val in os.environ.items():
        if env_key.startswith("SPARK_RAPIDS_TPU_"):
            key = _PREFIX + "." + env_key[len("SPARK_RAPIDS_TPU_"):].lower().replace("_", ".")
            overrides[key] = val
    return TpuConf(overrides)


if __name__ == "__main__":
    import sys
    out = sys.argv[1] if len(sys.argv) > 1 else None
    text = generate_docs()
    if out:
        with open(out, "w") as f:
            f.write(text)
    else:
        print(text)
