"""Layered configuration: defaults < TOML file < env < flags.

Reference: server/config.go — one Config struct populated from a TOML
file, PILOSA_* environment variables, and cobra flags, in that
precedence order; ``featurebase generate-config`` prints the default
file (cmd generate-config).  Env prefix here: ``PILOSA_TPU_``;
nested TOML tables flatten with ``_`` (``[auth] secret`` ->
``PILOSA_TPU_AUTH_SECRET``).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, fields

try:
    import tomllib  # Python >= 3.11
except ModuleNotFoundError:  # 3.10: only needed when a file is given
    tomllib = None


@dataclass
class Config:
    data_dir: str = ""
    bind: str = "127.0.0.1"
    port: int = 10101
    grpc_port: int = 20101
    cluster_name: str = "cluster0"
    replicas: int = 1
    auth_secret: str = ""
    auth_policy: str = ""
    # queries slower than this (seconds) go to the long-query log;
    # 0 disables (server.go:201 OptServerLongQueryTime)
    long_query_time: float = 0.0
    # serving path (executor/serving.py): concurrent queries coalesce
    # into one device dispatch per admission window, and repeated
    # reads serve from a write-version-guarded result cache.
    # Env-overridable like every knob (PILOSA_TPU_SERVING_BATCHING=0,
    # PILOSA_TPU_SERVING_CACHE_MB=0, ...).
    serving_batching: bool = True
    serving_batch_window_ms: float = 1.0
    serving_batch_max: int = 32
    serving_cache_mb: int = 64
    # ragged paged dispatch + QoS admission (executor/ragged.py,
    # executor/sched.py): ragged fuses a whole mixed batch — different
    # indexes and shard subsets — into ONE page-table device program;
    # admission classes keep point reads ahead of heavy analytics
    # (heavy-slots bounds concurrent heavy queries, queue-max bounds
    # the wait queue, overflow sheds typed 503 + Retry-After).
    # tenant-weights ("analytics:4,adhoc:1") weight the per-tenant
    # fair queue; default-deadline-ms applies to requests that carried
    # no X-Pilosa-Deadline-Ms of their own (0 = none).
    serving_ragged: bool = True
    serving_admission: bool = True
    serving_heavy_slots: int = 2
    serving_queue_max: int = 128
    serving_tenant_weights: str = ""
    serving_default_deadline_ms: float = 0.0
    # incremental stack maintenance (executor/stacked.py delta
    # patching + models/fragment.py delta log): patch device-resident
    # stacks on write instead of rebuilding them.  delta-log-max
    # bounds the per-fragment mutation log (older snapshots fall back
    # to slice rebuilds); patch-max-frac is the dirty fraction past
    # which one dense rebuild upload beats scattering runs.
    stack_patch: bool = True
    stack_delta_log_max: int = 256
    stack_patch_max_frac: float = 0.5
    # container-adaptive device format (memory/encode.py): per page
    # block pick dense / packed-array / run encoding.  sparse-format
    # false = all-dense (the A/B arm, env twin
    # PILOSA_TPU_SPARSE_FORMAT); sparse-dense-frac is the entry
    # threshold — a sparse candidate must be <= this fraction of the
    # dense page's bytes to leave the dense format.
    stack_sparse_format: bool = True
    stack_sparse_dense_frac: float = 0.5
    # HBM residency manager (pilosa_tpu/memory): one process-wide
    # device-byte budget shared by the tile-stack/jit/result caches.
    # budget-bytes 0 = auto (device memory_stats minus headroom-frac;
    # a CPU backend without stats gets 8 GiB, a TPU without them is
    # an error).  paged turns stack cache entries into fixed
    # page-bytes device pages (sub-stack eviction + patching);
    # prefetch warms predicted pages from the flight recorder off
    # the hot path; oom-retry / host-fallback are
    # the RESOURCE_EXHAUSTED backstop rungs.
    memory_budget_bytes: int = 0
    memory_headroom_frac: float = 0.1
    memory_page_bytes: int = 4 << 20
    memory_paged: bool = True
    memory_prefetch: bool = True
    memory_prefetch_interval_s: float = 0.5
    memory_oom_retry: bool = True
    memory_host_fallback: bool = True
    # streaming write plane (ingest/stream.py): concurrent mutations
    # coalesce per (field, shard) into one bulk apply + ONE durable
    # WAL-synced storage write per admission window; a submit acks
    # only after the window landed.  queue / tenant-queue bound the
    # admission backlog (shed = typed 503 + Retry-After); sync=false
    # turns off the per-window durability barrier (ack = applied).
    ingest_stream: bool = True
    ingest_window_ms: float = 2.0
    ingest_max_batch: int = 4096
    ingest_queue: int = 8192
    ingest_tenant_queue: int = 4096
    ingest_sync: bool = True
    # failure-tolerance plane (obs/faults.py + cluster hedging):
    # fault-spec arms named fault points at startup
    # ("point[@match][,times=N][,delay=MS];..." — obs/faults.py);
    # hedge-ms < 0 disables hedged replica reads, 0 auto-derives the
    # hedge delay from flight-recorder p99 records, > 0 fixes it;
    # deadline-s is the default end-to-end cluster query deadline
    # (0 = none; every RPC attempt/hedge/retry budgets from it).
    fault_spec: str = ""
    cluster_hedge_ms: float = 0.0
    cluster_deadline_s: float = 0.0
    # mesh-sharded serving (memory/placement.py): mesh-devices > 1
    # splits the paged working set over the first N local devices —
    # every (index, shard) gets a sticky owner balanced by live
    # per-device ledger bytes, and the fused ragged program runs as
    # ONE shard_map with in-program psum/scatter combines.  0/1 = off
    # (the exact single-device behavior).  The env twin
    # PILOSA_TPU_MESH_DEVICES outranks the config (bench A/B lever).
    # placement-pin force-places shards ("idx/3=1,idx/*=0"; env twin
    # PILOSA_TPU_PLACEMENT_PIN) — pins override the balancer.
    cluster_mesh_devices: int = 0
    cluster_placement_pin: str = ""
    # online resharding (cluster/rebalance.py): chase-lag is the
    # delta-span backlog under which DELTA-CHASE hands off to the
    # FENCE (smaller = shorter write-blocked window, more chase
    # rounds); max-rounds bounds chase/copy retry loops;
    # fence-timeout-s bounds the drain + blocked-writer wait.
    cluster_rebalance_chase_lag: int = 8
    cluster_rebalance_max_rounds: int = 12
    cluster_rebalance_fence_timeout_s: float = 10.0
    # query flight recorder (obs/flight.py): always-on per-query ring
    # of phase-attributed records feeding /debug/queries and
    # /debug/trace.  recorder=false disables record keeping (the
    # tracing-overhead A/B switch; also PILOSA_TPU_FLIGHT=0);
    # ring bounds how many records are kept.
    flight_recorder: bool = True
    flight_ring: int = 512
    # roofline attribution (obs/roofline.py): join bytes-touched with
    # device execute time per op family into achieved-GB/s and
    # fraction-of-peak gauges.  peak-gbps 0 = measure a STREAM-style
    # probe at startup (PILOSA_TPU_PEAK_GBPS also overrides);
    # attribution=false drops the per-dispatch note entirely (the
    # overhead-smoke A/B switch, also PILOSA_TPU_ROOFLINE=0).
    roofline_attribution: bool = True
    roofline_peak_gbps: float = 0.0
    # statistics catalog (obs/stats.py + storage/stats_store.py):
    # persisted flight/roofline telemetry driving the engine's cost
    # decisions (cost gates, admission classing, cache eviction,
    # hedge derivation) plus the per-fingerprint regression sentinel.
    # enabled=false (or PILOSA_TPU_STATS=0 — the bench A/B lever)
    # reverts every consumer to its static heuristic, bit-exact.
    # The runtime plane samples FLIGHT RECORDS: disabling the flight
    # recorder ([flight] recorder=false) stops profile/sentinel/
    # hedge accumulation (the ingest-fed data plane keeps working).
    # persist=false keeps the catalog memory-only; snapshot-interval-s
    # is the tmp+rename snapshot cadence; heavy-cost-ms is the
    # measured-cost admission threshold; regression-ratio /
    # regression-min-samples arm the sentinel.
    stats_enabled: bool = True
    stats_persist: bool = True
    stats_snapshot_interval_s: float = 60.0
    stats_heavy_cost_ms: float = 5.0
    stats_regression_ratio: float = 3.0
    stats_regression_min_samples: int = 6
    # SQL serving plane (sql/costplan.py + sql/engine.py): pushdown
    # routes SELECT plan operators through the fused serving plane
    # (batcher, ragged dispatch, QoS admission, result cache) with
    # the catalog-fed cost-based planner; false — or the
    # PILOSA_TPU_SQL_PUSHDOWN=0 env kill-switch, the bench A/B
    # lever — reverts SQL to the solo host path, bit-exact.
    sql_pushdown: bool = True
    # incident forensics plane (obs/incidents.py + obs/watchdog.py +
    # obs/profiler.py continuous ring + obs/logger.py log ring):
    # anomaly triggers (SLO burn over slo-burn-threshold, the perf
    # sentinel, watchdog stalls, OOM-ladder trips, batch-leader
    # exceptions, ingest crashes) each capture ONE rate-limited
    # (min-interval-s), size-bounded (max-bundle-bytes) black-box
    # bundle persisted tmp+fsync+rename under dir (default
    # <data-dir>/incidents; empty + no data dir = memory-only ring).
    # enabled=false — or PILOSA_TPU_INCIDENTS=0 — kills the plane.
    # profile* drive the always-on continuous profiler whose window
    # ring rides in every bundle; log-ring sizes the log tail.
    incidents_enabled: bool = True
    incidents_dir: str = ""
    incidents_min_interval_s: float = 60.0
    incidents_max_bundles: int = 32
    incidents_max_bundle_bytes: int = 1 << 20
    incidents_slo_burn_threshold: float = 8.0
    incidents_profile: bool = True
    incidents_profile_hz: float = 7.0
    incidents_profile_window_s: float = 10.0
    incidents_profile_windows: int = 6
    incidents_log_ring: int = 512
    # stall watchdogs (obs/watchdog.py): progress-stamped deadlines
    # on the serving batch leader, ingest window drain, rebalance
    # controller, maintenance ticker, and heartbeat loops.  A loop
    # armed past deadline-s fires pilosa_watchdog_stalls_total{loop}
    # + a watchdog-stall incident naming the stuck phase; interval-s
    # paces the monitor.  enabled=false (or PILOSA_TPU_WATCHDOG=0)
    # disarms detection; the stamps themselves stay (~sub-us).
    watchdog_enabled: bool = True
    watchdog_interval_s: float = 1.0
    watchdog_deadline_s: float = 10.0
    # SLO burn-rate plane (obs/slo.py): latency-ms + latency-objective
    # define the latency SLO ("latency-objective of queries answer
    # under latency-ms"); availability-objective bounds the typed-
    # error fraction (503 sheds, 504 deadlines, partial results).
    # windows is the multi-window burn-rate set ("5m,1h,6h" or bare
    # seconds), evaluated at /debug/slo and exported as
    # pilosa_slo_burn_rate{slo,window}.
    slo_latency_ms: float = 250.0
    slo_latency_objective: float = 0.99
    slo_availability_objective: float = 0.999
    slo_windows: str = "5m,1h,6h"
    # temporal analytics ([timeq], models/timeq.py): write-finest
    # lands TIME writes in standard + the finest quantum unit only
    # (coarse views compact on the rollup tick instead of fanning out
    # per write); rollup arms the HTTP ticker's quantum-rollup sweep;
    # qcover plans multi-view range covers as per-view fused leaves
    # (one restack per cover shift instead of a whole-cover rebuild;
    # env twin PILOSA_TPU_QCOVER is the bench A/B lever).
    timeq_write_finest: bool = False
    timeq_rollup: bool = False
    timeq_qcover: bool = True
    # standing queries ([standing], executor/standing.py): registered
    # Count/TopN/GroupBy/SQL results are delta-maintained on write —
    # the serving ResultCache entry is ADVANCED by maintenance
    # instead of swept.  PILOSA_TPU_STANDING=0 is the kill-switch /
    # bench A/B lever and outranks a default-True config; max bounds
    # live registrations (register past it -> typed error).
    standing_enabled: bool = True
    standing_max: int = 256
    # continuous correctness auditing ([audit], obs/audit.py): the
    # shadow-execution sampler + ticker scrubbers.  PILOSA_TPU_AUDIT=0
    # is the runtime kill-switch and outranks a default-True config;
    # sample-rate is the per-served-read sampling fraction,
    # route-rates overrides it per serve route
    # ("cached=0.05,fused=0.01"), queue-max/concurrency bound the
    # shadow worker, scrub-*-n budget each ticker scrubber, and
    # quarantine caps the mismatch evidence ring.
    audit_enabled: bool = True
    audit_sample_rate: float = 0.01
    audit_route_rates: str = ""
    audit_queue_max: int = 64
    audit_concurrency: int = 1
    audit_scrub_cache_n: int = 4
    audit_scrub_standing_n: int = 2
    audit_scrub_replica_n: int = 2
    audit_quarantine: int = 32

    # -- disaggregated DAX tier ([dax] + [blob], dax/settings.py) --
    # blob names the tier kill-switch (PILOSA_TPU_DAX_BLOB=0 outranks
    # it); backend/root pick the blob store; worker-budget-bytes
    # bounds each stateless worker's resident set (0 = unbounded);
    # the scale-* thresholds drive the autoscaler's reconcile loop.
    blob_backend: str = ""
    blob_root: str = ""
    dax_blob: bool = True
    dax_lazy_hydrate: bool = True
    dax_worker_budget_bytes: int = 0
    dax_prefetch: int = 2
    dax_scale_out_burn: float = 2.0
    dax_scale_in_burn: float = 0.5
    dax_pressure_high: float = 0.9
    dax_min_workers: int = 1
    dax_max_workers: int = 8
    dax_standby: int = 1
    dax_reconcile_interval_s: float = 5.0
    dax_cooldown_s: float = 30.0
    dax_chase_lag: int = 8
    dax_chase_rounds: int = 12

    def apply_stack_settings(self):
        """Push the [stacked] knobs into the runtime modules (the env
        flag for the A/B toggle, module globals for the numeric
        bounds — both read dynamically by the hot paths)."""
        os.environ["PILOSA_TPU_STACK_PATCH"] = \
            "1" if self.stack_patch else "0"
        os.environ["PILOSA_TPU_SPARSE_FORMAT"] = \
            "1" if self.stack_sparse_format else "0"
        from pilosa_tpu.executor import stacked
        from pilosa_tpu.memory import encode
        from pilosa_tpu.models import fragment
        fragment.DELTA_LOG_MAX = int(self.stack_delta_log_max)
        stacked._PATCH_MAX_FRAC = float(self.stack_patch_max_frac)
        encode.configure(dense_frac=self.stack_sparse_dense_frac)

    def apply_flight_settings(self):
        """Configure the process-global flight recorder ([flight])."""
        from pilosa_tpu.obs import flight
        flight.recorder.configure(enabled=self.flight_recorder,
                                  keep=self.flight_ring)

    def apply_fault_settings(self):
        """Arm config-specified fault points and publish the cluster
        hedge/deadline knobs (read dynamically per fan-out by
        cluster/coordinator.py, so a reconfigure applies live).
        Test-armed faults (faults.inject) are never touched."""
        from pilosa_tpu.obs import faults
        # config.load already folds PILOSA_TPU_FAULT_SPEC into
        # fault_spec: drop the import-time env arming before re-arming
        # as config, or every env rule's budget doubles.  A Config
        # carrying NO spec of its own (directly constructed, not
        # load()-built) must leave the operator's env arming alone —
        # clearing it here would silently disarm the chaos drill
        if self.fault_spec:
            faults.clear(source="env")
        faults.configure(self.fault_spec)
        # publish the knobs only when this Config actually carries a
        # non-default value (config.load folds the env var in, so a
        # loaded Config always does) — a directly-built default
        # Config must not clobber an operator-set env override
        for env, val, default in (
                ("PILOSA_TPU_CLUSTER_HEDGE_MS",
                 self.cluster_hedge_ms, 0.0),
                ("PILOSA_TPU_CLUSTER_DEADLINE_S",
                 self.cluster_deadline_s, 0.0),
                ("PILOSA_TPU_REBALANCE_CHASE_LAG",
                 self.cluster_rebalance_chase_lag, 8),
                ("PILOSA_TPU_REBALANCE_MAX_ROUNDS",
                 self.cluster_rebalance_max_rounds, 12),
                ("PILOSA_TPU_REBALANCE_FENCE_TIMEOUT_S",
                 self.cluster_rebalance_fence_timeout_s, 10.0)):
            if val != default or env not in os.environ:
                os.environ[env] = str(val)

    def apply_roofline_settings(self):
        """Configure roofline attribution ([roofline]) and kick the
        peak-bandwidth probe on a background thread (startup must not
        block ~50 ms on a STREAM probe).  A default-True config must
        not override an operator's PILOSA_TPU_ROOFLINE env
        kill-switch — leave the module resolving from env in that
        case (same contract as the hedge/deadline knobs in
        apply_fault_settings)."""
        from pilosa_tpu.obs import roofline
        enabled = self.roofline_attribution
        if enabled and "PILOSA_TPU_ROOFLINE" in os.environ:
            enabled = None  # env kill-switch stays in charge
        roofline.configure(enabled=enabled,
                           peak_gbps=self.roofline_peak_gbps or None)
        if roofline.enabled():
            roofline.ensure_peak(block=False)

    def apply_stats_settings(self, data_dir: str | None = None):
        """Configure the process statistics catalog ([stats]).  An
        operator's PILOSA_TPU_STATS env kill-switch outranks a
        default-True config (same contract as apply_roofline_settings);
        persistence lands under ``data_dir`` (the holder's path) when
        one exists — memory-only otherwise."""
        from pilosa_tpu.obs import stats
        enabled = self.stats_enabled
        if enabled and "PILOSA_TPU_STATS" in os.environ:
            enabled = None  # env kill-switch stays in charge
        base = data_dir if data_dir is not None else (self.data_dir
                                                      or None)
        path = (os.path.join(base, "stats.jsonl")
                if (self.stats_persist and base) else None)
        stats.configure(
            enabled=enabled, path=path,
            heavy_cost_ms=self.stats_heavy_cost_ms,
            regression_ratio=self.stats_regression_ratio,
            regression_min_samples=self.stats_regression_min_samples,
            snapshot_interval_s=self.stats_snapshot_interval_s)

    def apply_sql_settings(self):
        """Configure the SQL serving plane ([sql]).  The default-True
        config leaves the PILOSA_TPU_SQL_PUSHDOWN env kill-switch in
        charge (it is the bench A/B lever and may flip at runtime);
        an explicit pushdown=false pins the host path."""
        from pilosa_tpu.sql import costplan
        costplan.configure(
            enabled=None if self.sql_pushdown else False)

    def apply_watchdog_settings(self):
        """Configure the stall-watchdog monitor ([watchdog]).  The
        PILOSA_TPU_WATCHDOG env kill-switch outranks a default-True
        config (same contract as apply_roofline_settings)."""
        from pilosa_tpu.obs import watchdog
        enabled = self.watchdog_enabled
        if enabled and "PILOSA_TPU_WATCHDOG" in os.environ:
            enabled = None  # env kill-switch stays in charge
        watchdog.configure(enabled=enabled,
                           interval_s=self.watchdog_interval_s,
                           deadline_s=self.watchdog_deadline_s)

    def apply_incident_settings(self, data_dir: str | None = None):
        """Configure the incident forensics plane ([incidents]):
        bundle manager (persistence under ``data_dir``/incidents when
        one exists — memory-only otherwise), the continuous profiler,
        and the log-ring size.  The PILOSA_TPU_INCIDENTS env
        kill-switch outranks a default-True config."""
        from pilosa_tpu.obs import incidents, logger, profiler
        enabled = self.incidents_enabled
        if enabled and "PILOSA_TPU_INCIDENTS" in os.environ:
            enabled = None  # env kill-switch stays in charge
        base = data_dir if data_dir is not None else (self.data_dir
                                                     or None)
        dir = self.incidents_dir or (
            os.path.join(base, "incidents") if base else None)
        snap = {f.name: getattr(self, f.name)
                for f in fields(Config)
                if "secret" not in f.name}  # bundles must not leak auth
        # dir=None leaves the manager's current dir alone (a
        # path-less embedded server must not detach a data-dir'd
        # sibling's persistence — same contract as stats paths)
        incidents.configure(
            enabled=enabled, dir=dir,
            min_interval_s=self.incidents_min_interval_s,
            max_bundles=self.incidents_max_bundles,
            max_bundle_bytes=self.incidents_max_bundle_bytes,
            slo_burn_threshold=self.incidents_slo_burn_threshold,
            config_snapshot=snap)
        on = (incidents.enabled() if enabled is None
              else bool(enabled)) and self.incidents_profile
        profiler.configure_continuous(
            enabled=on, hz=self.incidents_profile_hz,
            window_s=self.incidents_profile_window_s,
            keep=self.incidents_profile_windows)
        logger.ring.configure(int(self.incidents_log_ring))

    def apply_slo_settings(self):
        """Build the process SLO tracker from the [slo] knobs."""
        from pilosa_tpu.obs import slo
        slo.configure(
            latency_ms=self.slo_latency_ms,
            latency_objective=self.slo_latency_objective,
            availability_objective=self.slo_availability_objective,
            windows=self.slo_windows)

    def apply_timeq_settings(self):
        """Push the [timeq] knobs into models/timeq.py.  Env twins
        (PILOSA_TPU_TIMEQ_WRITE_FINEST / PILOSA_TPU_TIMEQ_ROLLUP /
        PILOSA_TPU_QCOVER) are read dynamically by the module and
        outrank these values (bench A/B levers)."""
        from pilosa_tpu.models import timeq
        qc = self.timeq_qcover
        if qc and "PILOSA_TPU_QCOVER" in os.environ:
            qc = None  # env kill-switch stays in charge
        timeq.configure(write_finest=self.timeq_write_finest,
                        rollup=self.timeq_rollup, qcover=qc)

    def apply_standing_settings(self):
        """Configure the standing-query registry ([standing]).  The
        PILOSA_TPU_STANDING env kill-switch outranks a default-True
        config (same contract as apply_roofline_settings)."""
        from pilosa_tpu.executor import standing
        enabled = self.standing_enabled
        if enabled and "PILOSA_TPU_STANDING" in os.environ:
            enabled = None  # env kill-switch stays in charge
        standing.configure(enabled=enabled,
                           max_registrations=self.standing_max)

    def apply_audit_settings(self):
        """Configure the correctness-auditing plane ([audit]).  The
        PILOSA_TPU_AUDIT env kill-switch outranks a default-True
        config (same contract as apply_standing_settings)."""
        from pilosa_tpu.obs import audit
        enabled = self.audit_enabled
        if enabled and "PILOSA_TPU_AUDIT" in os.environ:
            enabled = None  # env kill-switch stays in charge
        audit.configure(
            enabled=enabled,
            sample_rate=self.audit_sample_rate,
            route_rates=self.audit_route_rates,
            queue_max=self.audit_queue_max,
            concurrency=self.audit_concurrency,
            scrub_cache_n=self.audit_scrub_cache_n,
            scrub_standing_n=self.audit_scrub_standing_n,
            scrub_replica_n=self.audit_scrub_replica_n,
            quarantine=self.audit_quarantine)

    def apply_dax_settings(self):
        """Push the [dax]/[blob] stanzas into dax/settings.py.  The
        PILOSA_TPU_DAX_BLOB env kill-switch outranks a default-True
        config (same contract as apply_standing_settings); the other
        knobs' env twins are re-read dynamically by the accessors."""
        from pilosa_tpu.dax import settings as dax_settings
        blob = self.dax_blob
        if blob and "PILOSA_TPU_DAX_BLOB" in os.environ:
            blob = None  # env kill-switch stays in charge
        dax_settings.configure(
            blob=blob,
            backend=self.blob_backend,
            root=self.blob_root,
            lazy_hydrate=self.dax_lazy_hydrate,
            worker_budget_bytes=self.dax_worker_budget_bytes,
            prefetch=self.dax_prefetch,
            scale_out_burn=self.dax_scale_out_burn,
            scale_in_burn=self.dax_scale_in_burn,
            pressure_high=self.dax_pressure_high,
            min_workers=self.dax_min_workers,
            max_workers=self.dax_max_workers,
            standby=self.dax_standby,
            reconcile_interval_s=self.dax_reconcile_interval_s,
            cooldown_s=self.dax_cooldown_s,
            chase_lag=self.dax_chase_lag,
            chase_rounds=self.dax_chase_rounds)

    def apply_placement_settings(self):
        """Push the [cluster] serving-mesh knobs into the placement
        module (memory/placement.py).  Env twins
        (PILOSA_TPU_MESH_DEVICES / PILOSA_TPU_PLACEMENT_PIN) are read
        dynamically by the module and outrank these values; configure
        bumps the placement epoch only when something changed."""
        from pilosa_tpu.memory import placement
        placement.configure(
            mesh_devices=self.cluster_mesh_devices,
            pin=self.cluster_placement_pin)

    def apply_memory_settings(self):
        """Push the [memory] knobs into the process residency manager
        (pilosa_tpu/memory: budget ledger, paged stacks, OOM
        backstop)."""
        from pilosa_tpu import memory
        memory.configure(budget_bytes=self.memory_budget_bytes,
                         headroom_frac=self.memory_headroom_frac,
                         page_bytes=self.memory_page_bytes,
                         paged=self.memory_paged,
                         oom_retry=self.memory_oom_retry,
                         host_fallback=self.memory_host_fallback)


# TOML key (possibly [table] key) -> Config attribute
_TOML_KEYS = {
    "data-dir": "data_dir",
    "bind": "bind",
    "port": "port",
    "grpc-port": "grpc_port",
    "cluster.name": "cluster_name",
    "cluster.replicas": "replicas",
    "auth.secret": "auth_secret",
    "auth.policy": "auth_policy",
    "long-query-time": "long_query_time",
    "serving.batching": "serving_batching",
    "serving.batch-window-ms": "serving_batch_window_ms",
    "serving.batch-max": "serving_batch_max",
    "serving.cache-mb": "serving_cache_mb",
    "serving.ragged": "serving_ragged",
    "serving.admission": "serving_admission",
    "serving.heavy-slots": "serving_heavy_slots",
    "serving.queue-max": "serving_queue_max",
    "serving.tenant-weights": "serving_tenant_weights",
    "serving.default-deadline-ms": "serving_default_deadline_ms",
    "stacked.patch": "stack_patch",
    "stacked.delta-log-max": "stack_delta_log_max",
    "stacked.patch-max-frac": "stack_patch_max_frac",
    "stacked.sparse-format": "stack_sparse_format",
    "stacked.sparse-dense-frac": "stack_sparse_dense_frac",
    "flight.recorder": "flight_recorder",
    "flight.ring": "flight_ring",
    "roofline.attribution": "roofline_attribution",
    "roofline.peak-gbps": "roofline_peak_gbps",
    "stats.enabled": "stats_enabled",
    "stats.persist": "stats_persist",
    "stats.snapshot-interval-s": "stats_snapshot_interval_s",
    "stats.heavy-cost-ms": "stats_heavy_cost_ms",
    "stats.regression-ratio": "stats_regression_ratio",
    "stats.regression-min-samples": "stats_regression_min_samples",
    "sql.pushdown": "sql_pushdown",
    "incidents.enabled": "incidents_enabled",
    "incidents.dir": "incidents_dir",
    "incidents.min-interval-s": "incidents_min_interval_s",
    "incidents.max-bundles": "incidents_max_bundles",
    "incidents.max-bundle-bytes": "incidents_max_bundle_bytes",
    "incidents.slo-burn-threshold": "incidents_slo_burn_threshold",
    "incidents.profile": "incidents_profile",
    "incidents.profile-hz": "incidents_profile_hz",
    "incidents.profile-window-s": "incidents_profile_window_s",
    "incidents.profile-windows": "incidents_profile_windows",
    "incidents.log-ring": "incidents_log_ring",
    "watchdog.enabled": "watchdog_enabled",
    "watchdog.interval-s": "watchdog_interval_s",
    "watchdog.deadline-s": "watchdog_deadline_s",
    "slo.latency-ms": "slo_latency_ms",
    "slo.latency-objective": "slo_latency_objective",
    "slo.availability-objective": "slo_availability_objective",
    "slo.windows": "slo_windows",
    "ingest.stream": "ingest_stream",
    "ingest.window-ms": "ingest_window_ms",
    "ingest.max-batch": "ingest_max_batch",
    "ingest.queue": "ingest_queue",
    "ingest.tenant-queue": "ingest_tenant_queue",
    "ingest.sync": "ingest_sync",
    "faults.spec": "fault_spec",
    "cluster.mesh-devices": "cluster_mesh_devices",
    "cluster.placement-pin": "cluster_placement_pin",
    "cluster.hedge-ms": "cluster_hedge_ms",
    "cluster.deadline-s": "cluster_deadline_s",
    "cluster.rebalance-chase-lag": "cluster_rebalance_chase_lag",
    "cluster.rebalance-max-rounds": "cluster_rebalance_max_rounds",
    "cluster.rebalance-fence-timeout-s":
        "cluster_rebalance_fence_timeout_s",
    "memory.budget-bytes": "memory_budget_bytes",
    "memory.headroom-frac": "memory_headroom_frac",
    "memory.page-bytes": "memory_page_bytes",
    "memory.paged": "memory_paged",
    "memory.prefetch": "memory_prefetch",
    "memory.prefetch-interval-s": "memory_prefetch_interval_s",
    "memory.oom-retry": "memory_oom_retry",
    "memory.host-fallback": "memory_host_fallback",
    "timeq.write-finest": "timeq_write_finest",
    "timeq.rollup": "timeq_rollup",
    "timeq.qcover": "timeq_qcover",
    "standing.enabled": "standing_enabled",
    "standing.max": "standing_max",
    "audit.enabled": "audit_enabled",
    "audit.sample-rate": "audit_sample_rate",
    "audit.route-rates": "audit_route_rates",
    "audit.queue-max": "audit_queue_max",
    "audit.concurrency": "audit_concurrency",
    "audit.scrub-cache-n": "audit_scrub_cache_n",
    "audit.scrub-standing-n": "audit_scrub_standing_n",
    "audit.scrub-replica-n": "audit_scrub_replica_n",
    "audit.quarantine": "audit_quarantine",
    "blob.backend": "blob_backend",
    "blob.root": "blob_root",
    "dax.blob": "dax_blob",
    "dax.lazy-hydrate": "dax_lazy_hydrate",
    "dax.worker-budget-bytes": "dax_worker_budget_bytes",
    "dax.prefetch": "dax_prefetch",
    "dax.scale-out-burn": "dax_scale_out_burn",
    "dax.scale-in-burn": "dax_scale_in_burn",
    "dax.pressure-high": "dax_pressure_high",
    "dax.min-workers": "dax_min_workers",
    "dax.max-workers": "dax_max_workers",
    "dax.standby": "dax_standby",
    "dax.reconcile-interval-s": "dax_reconcile_interval_s",
    "dax.cooldown-s": "dax_cooldown_s",
    "dax.chase-lag": "dax_chase_lag",
    "dax.chase-rounds": "dax_chase_rounds",
}

ENV_PREFIX = "PILOSA_TPU_"


def _parse_toml_minimal(text: str) -> dict:
    """Fallback TOML subset parser for Python 3.10 (no stdlib
    tomllib): ``[table]`` headers and scalar ``key = value`` pairs
    (quoted strings, booleans, ints, floats) — exactly the shape of
    this server's config files.  Anything fancier raises."""
    doc: dict = {}
    table = doc
    for ln, raw in enumerate(text.splitlines(), 1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if line.startswith("[") and line.endswith("]"):
            table = doc
            for part in line[1:-1].strip().split("."):
                table = table.setdefault(part.strip(), {})
            continue
        if "=" not in line:
            raise ValueError(f"config line {ln}: not key = value")
        key, _, val = line.partition("=")
        key, val = key.strip(), val.strip()
        if not val or val.startswith("#"):
            raise ValueError(f"config line {ln}: missing value")
        if val[:1] in "\"'":
            # quoted string: close at the MATCHING quote so '#' (and
            # anything else) inside the value survives; a trailing
            # comment after the close quote is dropped
            end = val.find(val[0], 1)
            if end < 0:
                raise ValueError(f"config line {ln}: unclosed string")
            table[key] = val[1:end]
            continue
        val = val.split("#", 1)[0].strip()
        if val in ("true", "false"):
            table[key] = val == "true"
        else:
            try:
                table[key] = int(val)
            except ValueError:
                table[key] = float(val)  # raises on junk — good
    return doc


def _flatten(doc: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in doc.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, key + "."))
        else:
            out[key] = v
    return out


def load(path: str | None = None, env: dict | None = None,
         overrides: dict | None = None) -> Config:
    """Build a Config with flag > env > file > default precedence
    (server/config.go's viper layering)."""
    cfg = Config()
    names = {f.name for f in fields(Config)}
    if path:
        if tomllib is not None:
            with open(path, "rb") as f:
                doc = tomllib.load(f)
        else:
            with open(path, "r", encoding="utf-8") as f:
                doc = _parse_toml_minimal(f.read())
        flat = _flatten(doc)
        for tk, attr in _TOML_KEYS.items():
            if tk in flat:
                setattr(cfg, attr, _coerce(cfg, attr, flat[tk]))
    env = os.environ if env is None else env
    for attr in names:
        ev = env.get(ENV_PREFIX + attr.upper())
        if ev is not None:
            setattr(cfg, attr, _coerce(cfg, attr, ev))
    for k, v in (overrides or {}).items():
        if v is not None and k in names:
            setattr(cfg, k, _coerce(cfg, k, v))
    return cfg


def _coerce(cfg: Config, attr: str, value):
    cur = getattr(cfg, attr)
    if isinstance(cur, bool):
        return str(value).lower() in ("1", "true", "yes", "on")
    if isinstance(cur, int):
        return int(value)
    if isinstance(cur, float):
        return float(value)
    return str(value)
