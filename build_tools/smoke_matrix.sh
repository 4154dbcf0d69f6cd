#!/usr/bin/env bash
# Smoke cells of the build_tools matrix: the standalone end-to-end
# gates that run NEXT TO the unit-tier device-count cells (mesh_4.sh /
# mesh_8.sh / wheel_ci.sh). Each asserts a PR's acceptance criterion in
# a fresh process on the CPU mesh:
#
#   compile_cache_smoke.py  — two fresh processes, one cache dir: the
#                             second cold wall <= 0.5x of the first
#                             (persistent compile cache PR).
#   serving_smoke.py        — 1k mixed-shape requests from 8 threads:
#                             >= 5x throughput over per-request
#                             batch_predict, 0 post-warmup compiles, 0
#                             dropped futures, p99 bounded, bitwise
#                             parity with batch_predict (serving PR).
#   compaction_smoke.py     — skewed 480-task grid: compacted warm wall
#                             >= 1.3x over single-slice lockstep, >=60%
#                             of lanes retired in slice 0, cv_results_
#                             parity <= 1e-5, 0 compiles after warmup
#                             (convergence-compacted scheduler PR).
#   sparse_fit_smoke.py     — ~1%-density hashed-text OvR grid: packed
#                             warm wall >= 2x over the densified path,
#                             shared device bytes >= 5x smaller,
#                             converged coefficient / cv-score parity
#                             <= 1e-5, 0 compiles after warmup
#                             (sparse-native fit data plane PR).
#   asha_smoke.py           — 480-task quality-skewed grid: adaptive
#                             (ASHA) warm wall >= 3x over exhaustive
#                             compacted execution, SAME best candidate,
#                             survivor-score parity <= 1e-5, coherent
#                             rung/convergence retirement split, 0
#                             compiles after warmup (adaptive-search PR).
#   streaming_smoke.py      — out-of-core data plane: disk-backed
#                             dataset >= 4x an enforced host budget fit
#                             STREAMED with warmed peak-RSS delta under
#                             budget, streamed-vs-resident cv_results_
#                             parity <= 1e-5 (aligned SGD), the
#                             double-buffered feed hiding >= 50% of
#                             measured read+H2D time vs the serial
#                             feed, streamed batch_predict
#                             byte-identical to the blocked resident
#                             path with bounded RSS, 0 post-warmup
#                             compiles (streaming data plane PR).
#   fault_smoke.py          — fault-injection matrix: transient faults
#                             on rounds retried to a bitwise-identical
#                             cv_results_; NaN lane quarantined to
#                             error_score with FitFailedWarning; SIGKILL
#                             mid-search resumed from the durable
#                             checkpoint (>=50% of journaled tasks
#                             reused, <=1e-5 vs uninterrupted); lane
#                             guard adds <=2% warm wall and 0 compiles
#                             (fault-tolerance PR).
#   kernels_smoke.py        — kernel push: the packed CV grid's
#                             kernel_mode attribution, chunked-gram
#                             parity, int8/bf16 registration parity
#                             inside the documented bound with smaller
#                             staged params, 0 post-warmup compiles
#                             across all three serve_dtype variants
#                             (quantized serving PR).
#   elastic_smoke.py        — elastic execution: a specific mesh
#                             participant preempted at round 2 of a
#                             checkpointed search -> mesh shrinks once,
#                             >=50% of tasks salvaged (journal-backed),
#                             re-grows at a round boundary, cv_results_
#                             parity 0.0 vs un-preempted; 1-of-3
#                             serving replicas killed under threaded
#                             load -> 0 failed requests, dead replica
#                             drained+respawned warm (0 compiles),
#                             respawned replica serves, p99 bounded
#                             (elastic mesh + replica fleet PR).
#   procfleet_smoke.py      — process fault domains: a 3-replica
#                             ProcessReplicaSet (replicas = supervised
#                             OS child processes behind unix-socket
#                             front doors, shared disk AOT tier) under
#                             6x40 threaded load with replica 1's
#                             PROCESS SIGKILLed at request 60 ->
#                             240/240 served, exactly 1 supervised
#                             respawn, respawned process serves with 0
#                             post-warmup compiles, p99 reported; plus
#                             a 2-process gloo elastic leg: mid-search
#                             participant death -> epoch agreement
#                             (KV-store prefix/roster), mesh shrinks
#                             to the survivor, search resumes with
#                             bitwise cv parity and >=50% of tasks
#                             salvaged instead of failing loud
#                             (process-fault-domain PR).
#   gbdt_smoke.py           — native histogram GBDT: batched
#                             candidate x fold grid >= 2x warm wall
#                             over sequential per-task fits, adaptive
#                             race same-best with rung kills, sklearn
#                             HistGradientBoosting accuracy parity
#                             <= 0.02, per-task score parity vs the
#                             sequential leg, kernel_mode stamped,
#                             0 post-warmup compiles (GBDT fan-out PR).
#   multitenant_smoke.py    — multi-tenant banked serving: >=1000
#                             same-family tenants stacked into one
#                             parameter bank on the 8-vdev CPU mesh,
#                             mixed-tenant threaded load >= 5x the
#                             per-model-dispatch aggregate throughput,
#                             paced equal-QPS p99 within 2x of
#                             single-model serving, per-tenant outputs
#                             byte-identical to unbanked dispatch, 0
#                             post-warmup compiles; 2-replica banked
#                             ReplicaSet leg with a mid-load re-bank
#                             rollover (0 failed requests) and an
#                             unload leg (bank compaction releases
#                             device bytes) (multi-tenant banking PR).
#   obs_smoke.py            — telemetry plane: tracing-off overhead
#                             bound <= 1% and tracing-on <= 5% warm
#                             wall on the compacted ASHA grid,
#                             Perfetto-loadable trace with >= 1 span
#                             per round + rung/retire events,
#                             Prometheus exposition parses with
#                             per-replica / per-name@version serving
#                             labels (telemetry-plane PR).
#   obs_fleet_smoke.py      — fleet-wide observability: 3-process
#                             ProcessReplicaSet under threaded load
#                             with replica 1's process SIGKILLed
#                             mid-load -> pre-kill /metrics scrape
#                             covers all three replicas' harvested
#                             counters (stale gauges 0), 0 failed
#                             requests, exactly 1 respawn, HARVESTED
#                             compiles_after_warmup 0 fleet-wide,
#                             parsed incident file embedding the dead
#                             worker's standing flight-recorder
#                             snapshot, stitched Perfetto trace with
#                             >= 3 pid tracks + cross-process
#                             route->flush flow links, telemetry
#                             harvest overhead <= 5% vs
#                             SKDIST_OBS_HARVEST=0 (distributed
#                             observability PR).
#   wirespeed_smoke.py      — wire-speed transport: shm data plane's
#                             supervisor-measured per-request transport
#                             overhead >= 5x lower than the pickle
#                             baseline (SKDIST_SHM=0) on identical
#                             8 MiB threaded load, 3-replica fleet p99
#                             <= 2x single-replica p99 at the same
#                             offered load, mid-load autotune ladder
#                             swap with 0 failed requests and 0
#                             HARVESTED post-warmup compiles
#                             (prewarm-before-swap), /dev/shm segment
#                             census conserved across replica SIGKILL
#                             + respawn and zero after close
#                             (wire-speed transport PR).
#   catalog_smoke.py        — tenant-lifecycle plane: a 10k-tenant
#                             catalog published to a durable
#                             CatalogStore (torn-manifest debris
#                             skipped), cold-loaded onto a banked
#                             engine in ONE bulk placement
#                             (bank generations built counter-asserted
#                             ≪ tenants), mid-traffic streamed
#                             warm-refit cohort refresh + rollout with
#                             0 failed requests, gate-rejected refresh
#                             never reaches serving, 0 post-warmup
#                             compiles, 3-replica bank-SHARDED
#                             rollout_many (each replica holds a
#                             strict catalog subset, every tenant
#                             servable) with shard failover restage
#                             (living-catalog PR).
#   streamed_asha_smoke.py  — terabyte-scale adaptive search: a
#                             streamed ASHA race over a disk-backed
#                             ChunkedDataset >= 4x an enforced
#                             peak-RSS budget on a 2D (task x data)
#                             mesh, rungs at block-pass boundaries,
#                             >= 2x warm wall vs the exhaustive
#                             streamed search with the SAME best
#                             candidate, survivor parity <= 1e-5,
#                             passes/bytes-saved accounting > 0,
#                             0 post-warmup compiles, and a mid-rung
#                             elastic shrink that RESUMES the race
#                             (same kill record and winner) on the
#                             halved mesh (streamed-ASHA PR).
#   streamed_gbdt_smoke.py  — out-of-core boosting: streamed
#                             DistHistGradientBoosting* fit over a
#                             disk-backed ChunkedDataset >= 4x an
#                             enforced peak-RSS budget on a 2D mesh;
#                             raw features streamed exactly twice
#                             (sketch + bin), every boosting round
#                             reads the uint8 binned block cache
#                             (byte accounting exact, cache HIT on
#                             fit 2+), streamed-vs-resident holdout
#                             accuracy <= 0.02, 0 post-warmup
#                             compiles, and a streamed ASHA race
#                             over boosting carries with the SAME
#                             best candidate as exhaustive
#                             (streamed-GBDT PR).
set -euo pipefail
cd "$(dirname "$0")/.."
python build_tools/serving_smoke.py
python build_tools/compile_cache_smoke.py
python build_tools/compaction_smoke.py
python build_tools/sparse_fit_smoke.py
python build_tools/asha_smoke.py
python build_tools/fault_smoke.py
python build_tools/streaming_smoke.py
python build_tools/elastic_smoke.py
python build_tools/procfleet_smoke.py
python build_tools/kernels_smoke.py
python build_tools/gbdt_smoke.py
python build_tools/obs_smoke.py
python build_tools/obs_fleet_smoke.py
python build_tools/multitenant_smoke.py
python build_tools/wirespeed_smoke.py
python build_tools/catalog_smoke.py
python build_tools/streamed_asha_smoke.py
python build_tools/streamed_gbdt_smoke.py
