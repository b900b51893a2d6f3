"""Unified runtime configuration — successor of the upstream flag/config
tree (``H2O.OptArgs`` launcher args + system properties) [UNVERIFIED
upstream paths, SURVEY.md §5.6].

One place defines every environment knob, its default, and its doc; every
subsystem reads through :func:`get` so ``python -c "import h2o3_tpu.config as
c; print(c.describe())"`` is the single source of truth for operators.

Knobs (env var → meaning):
- ``H2O3_TPU_NATIVE``        "0" disables the C++ scoring runtime (native.py)
- ``H2O3_TPU_HIST``          "matmul" forces the XLA matmul histogram over Pallas
- ``H2O3_TPU_HIST_SUBTRACT`` "0" disables sibling-subtraction in the fused
                             tree builder (direct per-node histograms)
- ``H2O3_TPU_STREAM_BYTES``  CSV size threshold that flips parse to streaming
- ``H2O3_TPU_PORT``          default REST port
- ``H2O3_TPU_ALLOWED_HOSTS`` extra Hosts allowed for state-changing REST
                             requests ('*' disables the CSRF guard)
- ``H2O3_TPU_LOG_LEVEL``     default log level for init()
"""

from __future__ import annotations

import os

_KNOBS: dict[str, tuple[str, str]] = {
    # name -> (default, doc)
    "H2O3_TPU_NATIVE": ("1", "C++ scoring runtime on (1) / off (0)"),
    "H2O3_TPU_NATIVE_PARSE": (
        "1", "native chunked CSV parser fast path on (1) / off (0); files "
             "outside the strict dialect always fall back to pandas"),
    "H2O3_TPU_HIST": (
        "", "histogram impl override: '' auto (scatter on CPU, Pallas on "
            "TPU), 'matmul' forces the plain-XLA MXU path, 'scatter' forces "
            "the scatter-add path (TPU-side debug A/B — all three local "
            "impls are reachable on any backend)"),
    "H2O3_TPU_HIST_SUBTRACT": (
        "1", "fused tree builder: build lighter child's histogram, derive "
        "sibling by parent subtraction (0 = direct per-node histograms)"),
    "H2O3_TPU_PALLAS_TILES": (
        "", "Pallas histogram kernel tile sizes as 'ROW,COL,NODE' "
            "(e.g. '512,8,64' — the built-in defaults). Tiles are a static "
            "compile key: every setting gets its own executable, so the "
            "tile sweep (tools/bench_kernel_sweep.py) varies them via the "
            "environment with no monkeypatching. "
            "'auto' = the tile AUTOTUNER: a first-build micro-sweep over a "
            "small tile grid, cached per (shape-bucket, mesh) beside the "
            "compile cache (config.compile_cache_dir) — same-bucket "
            "rebuilds (and later processes) perform zero new sweeps "
            "(pallas_tile_sweeps_total); explicit values bypass the sweep "
            "unchanged. '' = built-in defaults"),
    "H2O3_TPU_SPLIT_SHARD": (
        "1", "column-sharded split pipeline on meshes with >1 device: the "
             "histogram reduction ends in a reduce-scatter over column "
             "blocks (each device keeps only its C/P columns), the split "
             "scan runs on the local block, and a tiny all-gather of "
             "per-block winners merges bit-exactly against jnp.argmax's "
             "lowest-index tie-breaking. 0 = replicated histogram + "
             "replicated split scan (the pre-sharding path)"),
    "H2O3_TPU_GLM_FUSE": (
        "auto", "whole-program GLM IRLS (the PR-1 tree pattern ported to "
                "hex.glm): the IRLS loop runs as a compiled lax.while_loop "
                "executing up to K iterations per host dispatch, the Gram "
                "pass ends in a psum_scatter of contiguous G row blocks over "
                "the rows mesh axis (gathered once for the solve), and the "
                "Cholesky-with-jitter / ADMM solve moves on-device "
                "(float32); the host float64 lstsq lane remains as the "
                "singular-tail fallback. 'auto' = on with K=8; an integer "
                "N>=1 forces chunk size N; '0' restores the per-iteration "
                "host-solve path bit-for-bit. With export_checkpoints_dir "
                "set the chunk is clamped to 1 so PR-2 irls_state snapshots "
                "land at every iteration boundary (multinomial included — "
                "its cycling IRLS now fuses as a lax.scan over classes "
                "inside one while_loop, and ordinal fits run one on-device "
                "BFGS program; ISSUE 15). compute_p_values rides the fused "
                "lane too (ISSUE 16): the covariance comes from the final "
                "device Gram at the converged beta, so p-values no longer "
                "force the per-iteration host trajectory. Fallback matrix "
                "(docs/MIGRATION.md): L_BFGS and out-of-core streamed fits "
                "stay on their existing paths (glm_fuse_fallbacks_total "
                "tallies)"),
    "H2O3_TPU_MUNGE_FUSE": (
        "1", "compiled sharded data-munging plane (frame/munge.py + "
             "frame/lazy.py): group-by aggregation runs as ONE mesh-sharded "
             "segment-reduce program per .agg() call (all value columns "
             "stacked; sum lanes through the ops/collectives.py psum wrapper "
             "so the quant lane and 2-D rows×cols hierarchy apply, min/max "
             "through the exact pmax lane), merge expands (li, ri) ON DEVICE "
             "instead of host np.repeat — single-key inner joins on >1-device "
             "meshes additionally take the radix-partition all_to_all "
             "exchange lane — sort compiles key prep + lexsort into one "
             "cached program, and elementwise/ifelse chains build lazy "
             "expression graphs (frame/lazy.py LazyExprVec) that fuse into "
             "ONE jitted dispatch at first touch (munge_dispatches_total "
             "proves the reduction; streamed block materialization through "
             "the ChunkStore window when one is configured — the PR-11 "
             "residency fix). Ineligible shapes (string ops, STR/TIME keys, "
             "pivot, rank_within_group_by, host aggs like median/mode) stay "
             "eager and tally munge_fuse_fallbacks_total{reason}; see the "
             "docs/MIGRATION.md fallback matrix. '0' restores every eager "
             "seed path bit-for-bit"),
    "H2O3_TPU_DL_EPOCH_CHUNK": (
        "auto", "DeepLearning epoch fusion: fold this many epochs into ONE "
                "compiled program per dispatch with donated (params, "
                "opt_state) buffers; the shuffle permutations are "
                "precomputed host-side and the dropout RNG threads through "
                "the carry, so epoch trajectories are bit-identical to the "
                "per-epoch path. 'auto' = 8; '1' = one dispatch per epoch "
                "(the pre-fusion cadence). Clamped to 1 when "
                "export_checkpoints_dir, early stopping (stopping_rounds>0) "
                "or fault injection is active so per-epoch snapshots/stops "
                "keep their positions"),
    "H2O3_TPU_DL_GRAD_SHARD": (
        "auto", "DeepLearning minibatch gradient reduction sharded over the "
                "mesh: each device grads its local batch rows, the flat "
                "gradient is psum_scatter'd (1/P per device), the optimizer "
                "updates only its parameter shard and the updated params "
                "all_gather for the next step (ZeRO-style; replaces the "
                "replicated allreduce+update). 'auto' = on for >1-device "
                "meshes when eligible (elementwise optimizer state, "
                "mini_batch_size divisible by the shard count; dropout "
                "composes since ISSUE 15 — each device folds its shard "
                "index into the dropout key); '0' = always replicated "
                "(today's full-batch masks); '1' = on when eligible; "
                "'ctl' = the replicated PARITY CONTROL drawing the sharded "
                "lane's exact per-chunk dropout masks (the A/B lane). "
                "Ineligible configs use the replicated reduce and tally "
                "dl_shard_fallbacks_total"),
    "H2O3_TPU_COLLECTIVE_QUANT": (
        "auto", "block-quantized collective lane (ops/collectives.py, "
                "EQuARX-style) for the hot reduces — the tree histogram "
                "hist_reduce, the GLM Gram gram_reduce, the DL gradient "
                "dl_grad_reduce: each device's contribution crosses the "
                "wire as an int8 payload + one f32 power-of-two scale per "
                "block (all_to_all + dequantize-sum), ~4x fewer reduce "
                "bytes; gain/solve-critical side payloads (b/deviance "
                "psums, node totals, winner gathers, solve/param gathers) "
                "stay exact f32, and the Gram/gradient reduces add a "
                "residual-correction pass (~14 effective mantissa bits). "
                "'auto' = on only when the mesh spans >1 process (the "
                "ICI+DCN regime); '1' forces it anywhere (the A/B + parity "
                "lane); '0' restores the stock f32 collectives bit-for-bit"),
    "H2O3_TPU_COLLECTIVE_QUANT_BLOCK": (
        "256", "elements per quantization block (one f32 scale each) in the "
               "quantized collective lane; smaller blocks = tighter scales "
               "= more accuracy and more scale overhead"),
    "H2O3_TPU_COLLECTIVE_HIER": (
        "auto", "two-stage hierarchical reduction placement for the "
                "collective lane (arXiv:2110.10548): reduce exactly within "
                "each contiguous inner sub-axis group first (the cheap ICI "
                "level), then move only the — quantized, under "
                "COLLECTIVE_QUANT — chunk payloads across groups (the "
                "expensive DCN hop). 'auto' = group by each process's "
                "devices when the mesh spans >1 process; an integer forces "
                "that inner-group size (the A/B/test lane on the CPU "
                "proxy); '0' = single-stage"),
    "H2O3_TPU_FRAME_COMPRESS": (
        "1", "compressed device residency for the out-of-core data plane "
             "(frame/chunkstore.py): tree features live on device as the "
             "uint8 bin codes the histogram kernels consume (4x capacity "
             "vs f32, zero accuracy cost), categoricals as their narrow "
             "int8/int16 codes, and f32 columns materialize only at "
             "dispatch boundaries — streaming builds release the f32 "
             "device copies of binned feature columns to the host tier "
             "and rebuild them lazily on next touch. '0' disables the "
             "whole plane (no spill, no streaming, no release) and "
             "restores the fully-resident behavior bit-for-bit, even "
             "when H2O3_TPU_HBM_WINDOW_BYTES is set"),
    "H2O3_TPU_HBM_WINDOW_BYTES": (
        "0", "device-memory budget for one training pipeline's frame "
             "residency (the out-of-core streaming window): a frame whose "
             "per-row lanes exceed it trains as a block-accumulate outer "
             "loop — row-block chunks stream host->device through an LRU "
             "window of this many bytes (double-buffered prefetch, "
             "H2O3_TPU_PREFETCH_DEPTH) while evicted chunks park as host "
             "arrays, so GBM histograms / GLM IRLS Grams / DL epochs run "
             "at rows >> HBM through a fixed device footprint. Frames "
             "that fit take the resident path unchanged (bit-parity by "
             "construction). '0' (default) = unbounded, everything "
             "resident (today's behavior)"),
    "H2O3_TPU_PREFETCH_DEPTH": (
        "1", "how many row-block chunks ahead the out-of-core streaming "
             "loop issues host->device transfers (frame/chunkstore.py): "
             "1 = double buffering (block k+1 uploads while block k "
             "computes — jax device_put is async), higher values deepen "
             "the pipeline at the cost of a proportionally larger share "
             "of the HBM window; 0 = synchronous fetches (the A/B "
             "control for frame_prefetch_overlap_seconds)"),
    "H2O3_TPU_STREAM_BYTES": (str(256 * 1024 * 1024),
                              "CSV bytes above which parse streams in chunks"),
    "H2O3_TPU_INGEST_SHARDS": (
        "0", "coordinator-free sharded CSV ingest (frame/parse.py "
             "parse_sharded): how many byte ranges ONE process splits the "
             "source into and parses independently (each range located by "
             "a streaming newline scan and tokenized by the native "
             "byte-range parser) before concatenating — the single-process "
             "test/A-B lane of the per-host sharded parse, pinned "
             "byte-equal to the plain parse. 0 = one range per process "
             "(multi-process clouds still parse per-rank ranges)"),
    "H2O3_TPU_PORT": ("54321", "default REST port"),
    "H2O3_TPU_AUTH_TOKEN": (
        "", "opt-in REST auth token ('' = open, upstream default); when set "
            "every route requires Bearer/Basic auth (hash_login analog)"),
    "H2O3_TPU_ALLOWED_HOSTS": (
        "", "extra Host header names accepted for state-changing REST "
        "requests (comma list; '*' disables the CSRF/rebinding guard)"),
    "H2O3_TPU_LOG_LEVEL": ("INFO", "default log level"),
    "H2O3_TPU_TREE_GOSS": (
        "", "gradient-based one-side sampling for tree builds (arXiv:"
            "1706.08359, ISSUE 16): 'a,b' keeps the top-a fraction of rows "
            "by |gradient| plus a uniformly-sampled b fraction of the rest, "
            "with the sampled rows' stat lanes amplified by (1-a)/b so "
            "split gains stay unbiased — each tree then streams ~(a+b) of "
            "the rows' stats through the unchanged fused level programs. "
            "Composes with sample_rate (GOSS applies after the bootstrap "
            "mask), the streamed out-of-core lane (per-block threshold) "
            "and the 2-D mesh row axis (global sort). '' = off "
            "(bit-for-bit today's path); tree_rows_sampled_total counts "
            "rows kept"),
    "H2O3_TPU_TREE_EFB": (
        "0", "exclusive feature bundling (arXiv:1706.08359, ISSUE 16): a "
             "host-side greedy pass at BinSpec build time packs columns "
             "that are almost-everywhere at their dominant bin code "
             "(sparse/one-hot suites) into shared u8 bundle columns, "
             "shrinking the histogram C dimension before the kernel grid "
             "sees it; the device histogram is expanded back to real "
             "columns right after accumulation so split records, varimp, "
             "MOJO and scoring are unchanged (bundling requires ZERO "
             "conflicts, so expanded histograms — and split decisions — "
             "are bit-equal). Dense-histogram lane only (the fused Pallas "
             "split path and streamed blocks skip bundling); "
             "tree_cols_bundled_total counts columns eliminated. "
             "0 = off (today's path bit-for-bit)"),
    "H2O3_TPU_HIST_I16": (
        "0", "int16 histogram accumulation lanes (arXiv:1806.11248, ISSUE "
             "16): per-(node,stat) rescaled gradient/hessian codes "
             "accumulate through the scatter/matmul histogram impls in a "
             "+-32767 integer budget and rescale back after the reduce — "
             "exact on in-range integer stats (weights/counts), ~15-bit "
             "mantissa otherwise. An overflow latch recomputes the full "
             "f32 histogram on-device when any cell would exceed the "
             "budget (tree_hist_i16_overflows_total tallies). Applies to "
             "the non-Pallas local impls; 0 = off (f32 accumulation, "
             "today's path bit-for-bit)"),
    "H2O3_TPU_TREE_U8CACHE": (
        "1", "u8-code-native frames (ISSUE 16): bin_frame memoizes the "
             "binned u8 code matrix on the frame keyed by the BinSpec "
             "fingerprint, so repeated builds over one frame (AutoML, "
             "grids, CV, checkpoint restarts) re-read the cached codes "
             "instead of re-binning every f32 column per build — "
             "tree_hist_hbm_bytes_total{path=rebin} accounts the traffic "
             "actually spent binning and stays flat on cache hits. 0 = "
             "re-bin every call (today's path; a hit returns the identical "
             "buffer, so this knob is bit-for-bit by construction)"),
    "H2O3_TPU_FUSED_MAX_DEPTH": (
        "20", "deepest tree the whole-tree fused program is built for; "
              "beyond it the per-level dispatch loop takes over"),
    "H2O3_TPU_WHOLE_TREE": (
        "1", "device-resident whole-tree build: the level loop runs INSIDE "
             "the compiled program (unrolled growth levels + a lax.while_loop "
             "over the node_cap-saturated levels with an on-device early-exit "
             "predicate), one dispatch per tree/chunk on every backend. "
             "0 = host-driven per-level dispatch loop (debug escape hatch)"),
    "H2O3_TPU_SHAPE_BUCKETS": (
        "1", "shape-bucketed padding: round rows (above 64k, ~12.5% geometric "
             "ladder), feature columns (multiple of 8) and histogram bins "
             "(power of two) up to a small ladder so AutoML/grid builds of "
             "near-identical shapes reuse one compiled program instead of "
             "recompiling per shape. Padding is masked out and proven inert "
             "(bucketed builds score identically); 0 = exact shapes"),
    "H2O3_TPU_NPS_DIR": (
        "", "NodePersistentStorage root (saved Flow notebooks; '' = "
        "~/.h2o3tpu/nps)"),
    "H2O3_TPU_HEARTBEAT_TIMEOUT": (
        "100", "multi-host dead-member detection bound, seconds "
        "(jax coordination-service heartbeat timeout)"),
    "H2O3_TPU_MESH_ROWS": (
        "", "2-D rows×cols pod mesh (parallel/mesh.py): how many ROWS-axis "
            "groups the device mesh factors into. Frame rows still shard "
            "over EVERY device (cols-major, so shard i sits on device i "
            "exactly like the 1-D mesh); histogram/Gram/gradient reduces "
            "run stage-1 EXACT over the rows axis (the contiguous-device / "
            "ICI level) and the collective lane proper over cols, and the "
            "split phase's column blocks shard over cols only — row "
            "sharding and the PR-5/PR-6 column blocks compose instead of "
            "sharing one axis, and the PR-9 quantized lane compresses "
            "exactly the cross-group stage. ''/'0'/'1' = the legacy 1-D "
            "rows mesh (bit-for-bit today's programs); 'auto' = rows = "
            "each process's local device count on multi-process clouds "
            "(rows=ICI, cols=DCN) and 1-D otherwise; an integer forces "
            "that rows size (the CPU-proxy A/B lane — '2' makes the "
            "8-device proxy a 2x4 pod stand-in). Non-dividing values fall "
            "back to 1-D with a warning"),
    "H2O3_TPU_COORDINATOR": (
        "", "jax.distributed coordinator address host:port for env-driven "
            "pod bootstrap (cluster/multihost.py): when set, launch.py and "
            "bootstrap_from_env() initialize the coordination service "
            "before any backend touch — the k8s StatefulSet points every "
            "pod at the rank-0 pod's headless-service DNS name. '' = "
            "single-host (no distributed init)"),
    "H2O3_TPU_NUM_PROCESSES": (
        "0", "process count of the env-driven pod bootstrap (must equal "
             "the StatefulSet replica count); 0 = unset"),
    "H2O3_TPU_PROCESS_ID": (
        "", "this process's rank in the env-driven pod bootstrap; '' = "
            "derive from the trailing ordinal of H2O3_TPU_POD_NAME / "
            "POD_NAME / HOSTNAME (the k8s StatefulSet convention "
            "pod-name-N), the launcher arg, or fail loudly"),
    "H2O3_TPU_POD_EXIT_DEGRADED": (
        "0", "pod-restart recovery loop (cluster/multihost.py): on a "
             "MULTI-PROCESS cloud whose degraded latch persists past this "
             "many seconds, the process EXITS (code 23) instead of holding "
             "a survivor island — the JAX runtime cannot re-initialize "
             "in-process, so on k8s the restartPolicy brings every rank "
             "back, the cloud re-forms, and the PR-10 supervisor resumes "
             "from the interval snapshot (recovery_seconds lands in the "
             "flight recorder + metrics). '0' = never exit (the in-process "
             "survivor island keeps serving — single-host default and the "
             "two-process test fixture's mode)"),
    "H2O3_TPU_PERSIST_RETRIES": (
        "4", "transient persist IO failures are retried this many times "
             "before surfacing (deterministic errors — bad path, collision, "
             "corrupt file — always fail fast, preserving spmd lockstep)"),
    "H2O3_TPU_PERSIST_BACKOFF": (
        "0.2", "base persist retry backoff, seconds: delay = base * 2^attempt "
               "plus up to +50% DETERMINISTIC jitter (keyed on op+attempt, "
               "identical on every rank and every run)"),
    "H2O3_TPU_METRICS": (
        "1", "observability layer on (1) / off (0): the /3/Metrics registry, "
             "span tracing and timing histograms (utils/metrics.py). Read "
             "ONCE at import — hot paths must not re-read the environment. "
             "The tree-build counters behind BUILD_STATS keep counting "
             "either way (test/bench contract, not optional telemetry)"),
    "H2O3_TPU_FAULTS": (
        "", "fault-injection spec for the chaos suite (utils/faults.py): "
            "';'-separated entries — 'site=N' fails the first N IO calls at "
            "the site, 'site@K' aborts training at iteration K, 'death:site' "
            "raises a synthetic coordination-service death error, "
            "'die:site' raises one at a COLLECTIVE BOUNDARY site (the "
            "worker-death-mid-collective stand-in the supervised-recovery "
            "drills use), 'blackout:SECS' fails EVERY persist IO for a "
            "wall-clock window of SECS from arming (storage-outage "
            "stand-in), 'stall:site:SECS' sleeps once at the site "
            "(wedged-collective stand-in), 'slow:site:SECS' sleeps at EVERY "
            "call to the site (slow-handler injection), 'oom:site' raises "
            "one synthetic XlaRuntimeError RESOURCE_EXHAUSTED at the "
            "dispatch site (the OOM-degrade drill), 'hang:site:SECS' "
            "sleeps once INSIDE the dispatch at the site (wedged-dispatch "
            "stand-in the hang watchdog trips on). '' = off"),
    "H2O3_TPU_RECOVERY": (
        "auto", "supervised auto-recovery (cluster/recovery.py): on a cloud "
                "failure — degraded latch, watchdog trip, coordination-"
                "service death signature, stale generation — supervised "
                "jobs with export_checkpoints_dir re-form the cloud "
                "(degraded -> recovering -> healthy, cloud_generation "
                "ticks) and resume from their latest interval snapshot "
                "with no operator in the path. 'auto'/'1' = on; '0' = off "
                "(restores the pure fail-stop contract: failures surface, "
                "the degraded latch stays one-way until clear_degraded)"),
    "H2O3_TPU_RECOVERY_MAX_RESTARTS": (
        "3", "supervised-recovery restart budget per job: after this many "
             "reform+resume attempts the failure surfaces "
             "(RecoveryExhausted) with the latest snapshot path in the "
             "message"),
    "H2O3_TPU_RECOVERY_BACKOFF": (
        "0.5", "supervised-recovery base backoff, seconds: delay = "
               "base * 2^attempt (capped at 30 s) plus up to +50% "
               "DETERMINISTIC jitter (keyed on job+attempt, identical "
               "run-to-run)"),
    "H2O3_TPU_RECOVERY_RESET_SECS": (
        "300", "supervised-recovery healthy window, seconds: a job that "
               "runs this long since its last relaunch without a cloud "
               "failure gets its restart budget back (attempt counter "
               "resets to 0) — a days-long job that restarted twice early "
               "on no longer dies on its 3rd unrelated transient. 0 = "
               "never reset (the lifetime budget of PR 10)"),
    "H2O3_TPU_FORMATION_MANIFEST": (
        "", "formation manifest path (cluster/multihost.py): every "
            "formation() writes the agreed member set + mesh shape here "
            "(atomic publish), and a RESTARTED rank compares the recorded "
            "process count against its env — a changed "
            "H2O3_TPU_NUM_PROCESSES is logged as an ELASTIC TRANSITION "
            "(scale-down after preemption / scale-up after autoscale) and "
            "the rank bootstraps into the NEW shape instead of "
            "crash-looping against the old barrier count; a rank whose "
            "ordinal fell off the shrunk formation exits cleanly (retired) "
            "instead of raising. '' = <tmpdir>/h2o3tpu_formation_<uid>."
            "json; '0' disables the manifest"),
    "H2O3_TPU_AUTOML_STEP_RETRIES": (
        "2", "AutoML poison-step guard: a plan step whose build has already "
             "crashed this many recorded attempts (the step manifest "
             "tracks per-step attempt counts across auto-resumes) is "
             "SKIPPED with a Log.warn instead of killing every resume at "
             "the same place forever. 0 = unlimited attempts (the "
             "pre-guard behavior)"),
    "H2O3_TPU_MAX_INFLIGHT": (
        "64", "REST admission gate: max concurrently executing mutating "
              "(POST/DELETE) requests; excess requests are shed with "
              "429 + Retry-After instead of piling up threads. 0 = unbounded"),
    "H2O3_TPU_MAX_QUEUED_JOBS": (
        "32", "REST admission gate: max live (pending+running) REST-created "
              "jobs; job-creating requests beyond it are shed with "
              "503 + Retry-After. 0 = unbounded"),
    "H2O3_TPU_OVERLOAD": (
        "1", "overload-survival plane (utils/overload.py): memory-aware "
             "admission with per-job HBM reservations "
             "(hbm_reserved_bytes{job}) and streamed-lane auto-routing, "
             "RESOURCE_EXHAUSTED catch-and-degrade (one supervised retry "
             "in streamed/halved-window mode, oom_degrades_total), the "
             "dispatch hang watchdog (dispatch_hangs_total), and computed "
             "Retry-After on shed responses. '0' disables the whole plane "
             "and pins pre-overload behavior bit-for-bit (static-window "
             "routing only, no reservations, no OOM retry, no watchdog, "
             "historical Retry-After constants)"),
    "H2O3_TPU_ADMIT_MIN_HEADROOM_BYTES": (
        "0", "REST admission memory gate: mutating requests are shed with "
             "503 + computed Retry-After (reason 'memory') while measured "
             "devmem.headroom() is below this many bytes — the cheap "
             "whole-server pressure valve in front of the per-job "
             "footprint check. 0 = off; backends without memory_stats "
             "(the CPU proxy) are never gated"),
    "H2O3_TPU_ADMIT_HEADROOM_FRAC": (
        "0.7", "share of measured device headroom the admission preflight "
               "treats as usable by job data (the rest stays free for "
               "compiled programs and temporaries — the capacity-model "
               "USABLE_FRACTION). Footprints are admitted resident against "
               "frac*headroom net of live reservations; larger jobs "
               "auto-route to the streamed lane; jobs that fit nowhere "
               "shed 503"),
    "H2O3_TPU_HANG_FACTOR": (
        "8", "dispatch hang watchdog trip multiplier: a dispatch open "
             "longer than FACTOR x its site's rolling mean completed "
             "duration (and past H2O3_TPU_HANG_MIN_SECS) is declared "
             "wedged — dispatch_hangs_total ticks, an incident bundle "
             "freezes the ring, the degraded latch trips and supervised "
             "jobs resume from their latest snapshot"),
    "H2O3_TPU_HANG_MIN_SECS": (
        "120", "dispatch hang watchdog floor, seconds: no dispatch is "
               "declared wedged before this age regardless of baseline — "
               "sites with fewer than 3 completed dispatches use ONLY the "
               "floor, so a legitimately long first compile never "
               "false-trips"),
    "H2O3_TPU_HANG_POLL_SECS": (
        "2", "dispatch hang watchdog poll cadence, seconds (background "
             "daemon installed by start_server/launch)"),
    "H2O3_TPU_REQUEST_READ_TIMEOUT": (
        "60", "REST per-connection socket read deadline, seconds — a client "
              "that stops sending mid-request cannot pin a handler thread "
              "forever. 0 = no deadline"),
    "H2O3_TPU_HANDLER_DEADLINE_SECS": (
        "300", "deadline for REST handlers that wait synchronously on a job "
               "(SplitFrame/CreateFrame/Interaction): past it the route "
               "returns 504 with the job key and the job keeps running "
               "(poll /3/Jobs). 0 = unbounded"),
    "H2O3_TPU_JOB_DEADLINE_SECS": (
        "0", "default deadline applied to every REST-created job, seconds; "
             "enforced between iterations via the soft-deadline plumbing "
             "(iterative builders truncate GRACEFULLY, keeping the partial "
             "model) and surfaced as 'deadline' on /3/Jobs. 0 = none"),
    "H2O3_TPU_SPMD_WATCHDOG_SECS": (
        "0", "collective watchdog: a replicated command still running after "
             "this many seconds is presumed wedged mid-collective and trips "
             "the fail-stop degraded latch (coordinator-side only — rank "
             "clocks diverge, so followers never arm it). 0 = disabled "
             "(the default: only an operator who knows the workload's "
             "longest legitimate command should set a budget)"),
    "H2O3_TPU_DRAIN_TIMEOUT_SECS": (
        "30", "graceful-drain bound for H2OServer.stop(drain=True) / "
              "POST /3/Shutdown?drain=true: how long to wait for running "
              "jobs to truncate and flush checkpoints before the listener "
              "closes anyway"),
    "H2O3_TPU_SCORE_BATCH_WINDOW_MS": (
        "2", "scoring tier micro-batch window: concurrent "
             "/3/Predictions/rows requests for one model coalesce for up to "
             "this many ms (or until H2O3_TPU_SCORE_BATCH_MAX rows) and "
             "dispatch as ONE device call. 0 = per-request dispatch (the "
             "unbatched control lane of the load-test A/B)"),
    "H2O3_TPU_SCORE_BATCH_MAX": (
        "4096", "scoring tier: max rows per batched dispatch — a full batch "
                "dispatches immediately without waiting out the window"),
    "H2O3_TPU_SCORE_DEADLINE_MS": (
        "2000", "per-request deadline on /3/Predictions/rows: a request "
                "that cannot be scored within this budget is shed with 504 "
                "+ Retry-After instead of queueing unboundedly (a late "
                "scoring answer is worthless). 0 = no deadline"),
    "H2O3_TPU_SCORE_QUEUE_MAX": (
        "32768", "scoring tier admission bound: max rows waiting in the "
                 "coalescing queue; arrivals beyond it are shed with 429 + "
                 "Retry-After. 0 = unbounded"),
    "H2O3_TPU_SERVE_REGISTRY": (
        "auto", "fleet serving registry (serving/registry.py): scoring "
                "replicas resolve /3/Predictions/rows model keys through a "
                "generation-tagged model registry fed by a watch-and-load "
                "loop over shared storage, so AutoML winners roll out with "
                "no operator action. 'auto' = on when "
                "H2O3_TPU_SERVE_WATCH_DIR is set; '1' = registry resolution "
                "on even without a watch dir (models enter via /3/Recover-"
                "style explicit loads); '0' = off — restores the PR-7 "
                "manual-load behavior bit-for-bit (models only via "
                "/99/Models.bin + DKV)"),
    "H2O3_TPU_SERVE_WATCH_DIR": (
        "", "shared model store the serving registry watches: every "
            "serialize_model file in this directory (the same files "
            "save_model / AutoML export_checkpoints_dir write) is loaded "
            "and kept current by mtime/size etag polling — a changed file "
            "swaps in as a NEW generation of its model key; in-flight "
            "batches finish on the old generation. '' = no watching "
            "(registry still serves explicitly loaded models under "
            "SERVE_REGISTRY=1)"),
    "H2O3_TPU_SERVE_POLL_SECS": (
        "5", "serving-registry watch poll period, seconds: an exported "
             "model is picked up within one poll (the rollout latency "
             "floor). Polling is one directory scan + per-file stat etag "
             "probes (persist.probe) — no bytes are read unless an etag "
             "changed"),
    "H2O3_TPU_SERVE_HBM_BYTES": (
        "0", "device-memory budget for resident scorer model payloads "
             "(serving/residency.py): the stacked forests / coefficient / "
             "MLP-parameter device arguments of compiled scorer lanes live "
             "in an LRU bounded by this many bytes — past it, "
             "least-recently-scored models demote to their host-RAM "
             "mirrors (page-in re-uploads on next score, "
             "serving_page_in_seconds) so one replica serves far more "
             "models than fit in HBM. The budget floor is one model: the "
             "model currently dispatching is never evicted. '0' (default) "
             "= unbounded, every scored model stays device-resident "
             "(the pre-fleet behavior)"),
    "H2O3_TPU_SCORE_IDLE_SECS": (
        "30", "scoring-tier idle reaping: a per-model batcher whose "
              "dispatcher thread saw no work for this many seconds retires "
              "the thread, drops the batcher from the per-model cache and "
              "demotes the model's scorer device arguments to host RAM — "
              "an idle model costs neither a parked thread nor HBM. The "
              "next request rebuilds the batcher and pages the scorer "
              "back in"),
    "H2O3_TPU_SERVE_WARM_MODELS": (
        "0", "serving-registry warm boot (serving/registry.py): at replica "
             "boot the watcher's FIRST poll pre-loads the newest N model "
             "files from the watch dir, pages their payloads into device "
             "residency and precompiles each model's smallest scoring "
             "shape bucket — a fresh HPA replica serves its first request "
             "at speed instead of paying model load + page-in + compile on "
             "the request path. 0 = no warm-up (load on first pickup, "
             "compile on first request — the pre-warm behavior)"),
    "H2O3_TPU_SERVE_BAD_GEN_ERRORS": (
        "3", "serving-registry rollout breaker: this many consecutive "
             "scoring failures on a freshly rolled-out model generation "
             "trip a rollback — the registry re-serves the previous "
             "generation and quarantines the bad file's etag (it will not "
             "be reloaded until the file changes). A successful score "
             "resets the count. 0 = never roll back"),
    "H2O3_TPU_FLIGHTREC_SIZE": (
        "4096", "incident flight recorder ring capacity, events "
                "(utils/flightrec.py): the always-on bounded ring of "
                "structured dispatch/collective/residency/cluster events "
                "every process keeps — O(µs) lock-free append, read once "
                "at import like H2O3_TPU_METRICS (the append is the hot "
                "path). Served over GET /3/FlightRecorder and frozen into "
                "incident bundles. '0' disables the ring (incident "
                "bundles still capture metrics/devmem/logs)"),
    "H2O3_TPU_DEVMEM_POLL_SECS": (
        "5", "device-memory ledger poll period, seconds "
             "(utils/devmem.py): how often device.memory_stats() is "
             "actually read — the ONE reader behind the "
             "device_hbm_bytes{device,kind} gauges, the computed "
             "hbm_owned_bytes{owner=unattributed} series, the "
             "hbm_headroom_bytes gauge and /3/Cloud's per-node memory "
             "fields. Dispatch boundaries and the background poller both "
             "refresh through this rate limit, so a hot loop never "
             "reads stats more than once per period"),
    "H2O3_TPU_INCIDENT_DIR": (
        "", "directory incident bundles are written to "
            "(utils/flightrec.py: ring dump + metrics snapshot + devmem "
            "attribution + log tail, atomic through persist — any persist "
            "scheme works, s3://... included). '' = "
            "<system tmp>/h2o3_incidents"),
    "H2O3_TPU_PREDICTIONS_RETAIN": (
        "64", "bounded retention of GENERATED /3/Predictions result frames: "
              "the newest N generated prediction frames stay in the DKV, "
              "older ones are removed (replicated delete) — serving load no "
              "longer grows the DKV without bound. Frames named explicitly "
              "via predictions_frame are never auto-evicted. 0 = keep all "
              "(the pre-retention behavior)"),
}


def compile_cache_dir() -> str:
    """THE persistent compile-cache directory, and the home of everything
    kept beside the executables (the Pallas tile store): wherever
    ``JAX_COMPILATION_CACHE_DIR`` places it — jax reads that variable
    itself, so the program then sets no directory in code — else
    ``<checkout>/.jax_cache``. The path is part of the cache key: it must
    not move between processes."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def get(name: str) -> str:
    default, _ = _KNOBS[name]
    return os.environ.get(name, default)


def get_int(name: str) -> int:
    return int(get(name))


def get_float(name: str) -> float:
    return float(get(name))


def get_bool(name: str) -> bool:
    return get(name) not in ("0", "false", "False", "")


def describe() -> str:
    lines = ["h2o3_tpu runtime configuration:"]
    for name, (default, doc) in _KNOBS.items():
        cur = os.environ.get(name)
        mark = f"{cur!r} (env)" if cur is not None else f"{default!r} (default)"
        lines.append(f"  {name:24s} = {mark:24s} — {doc}")
    return "\n".join(lines)
