"""Command-line interface.

Single entry point replacing the reference's two binaries
(``/root/reference/src/bin/producer.rs``, ``bin/worker.rs``): there is no
broker to stand between a producer and workers, so one ``run`` command reads
Parquet, executes the pipeline (host oracle or compiled TPU path), and writes
the kept/excluded Parquet pair.  ``validate-config`` is the reference worker's
``--validate-config`` fast path (bin/worker.rs:29-51).

Argument names mirror the reference's clap definitions
(``config/producer.rs:7-47``, ``config/worker.rs:8-39``) minus the AMQP knobs,
which have no equivalent here.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from . import __version__
from .config.pipeline import load_pipeline_config
from .errors import PeerFailure, PipelineError
from .utils.logging_setup import init_logging
from .utils.metrics import (
    METRICS,
    build_run_report,
    format_funnel_summary,
    funnel_report,
    funnel_snapshot,
    metrics_snapshot,
    setup_prometheus_metrics,
    write_run_report,
)
from .resilience.watchdog import WATCHDOG
from .utils.events import EVENTS, flight_record
from .utils.profiler import PROFILER
from .utils.slo import SLO, parse_slo_arg
from .utils.telemetry import TELEMETRY, format_latency_summary
from .utils.trace import TRACER, device_profile

__all__ = ["main", "build_parser"]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="textblast",
        description="TPU-native text-dataset cleaning pipeline",
    )
    p.add_argument("--version", action="version", version=f"%(prog)s {__version__}")
    sub = p.add_subparsers(dest="command", required=True)

    run = sub.add_parser("run", help="Process a Parquet shard through the pipeline")
    run.add_argument("-i", "--input-file", required=True,
                     help="Path to the input Parquet file")
    run.add_argument("--text-column", default="text",
                     help="Text column name in the Parquet file")
    run.add_argument("--id-column", default="id",
                     help="ID column name in the Parquet file")
    run.add_argument("-c", "--pipeline-config",
                     default="configs/pipeline_config.yaml",
                     help="Path to the pipeline configuration YAML file")
    run.add_argument("-o", "--output-file", default="output_processed.parquet",
                     help="Path to the output Parquet file")
    run.add_argument("-e", "--excluded-file", default="excluded.parquet",
                     help="Path to the excluded output Parquet file")
    run.add_argument("--errors-file", default=None,
                     help="Opt-in dead-letter Parquet file: every Error "
                          "outcome and every unreadable/quarantined row "
                          "lands here with step/reason/worker columns.  "
                          "Default: no file (the reference's behavior — "
                          "errored rows appear in neither output)")
    run.add_argument("--backend", choices=("host", "tpu", "cpu"), default="tpu",
                     help="Execution backend: compiled pipeline on the TPU "
                          "(tpu — refuses to start when JAX's default "
                          "platform is not a TPU, unless JAX_PLATFORMS=cpu "
                          "asks for the CPU), the same compiled pipeline "
                          "pinned to the local CPU backend (cpu), or the "
                          "host oracle (host)")
    run.add_argument("--batch-size", type=int, default=1024,
                     help="Parquet read batch size")
    run.add_argument("--buckets", default=None,
                     help="Comma-separated codepoint length buckets for the device "
                          "path (e.g. 512,2048,8192).  Smaller sets compile faster; "
                          "docs past the largest bucket take the bit-exact host "
                          "fallback.  Default: the built-in long-doc set.")
    run.add_argument("--device-batch", type=int, default=None,
                     help="Documents per device batch (tpu backend), over "
                          "all chips of a data mesh; by default each chip "
                          "gets the rows one chip holds alone")
    run.add_argument("--auto-geometry", action="store_true",
                     help="Calibrate device geometry from the data: sample "
                          "document lengths from the head of the stream, "
                          "choose bucket boundaries minimizing padded-"
                          "codepoint waste, and give each bucket a work-"
                          "equalized batch size (B ∝ lane_budget / bucket).  "
                          "Off by default (the built-in geometry is used); "
                          "mutually exclusive with --buckets and "
                          "--device-batch.  Checkpointed runs record the "
                          "calibrated geometry and resume with it")
    run.add_argument("--pipeline-depth", type=int, default=None,
                     help="Device batches kept in flight by the overlapped "
                          "host pipeline (default: the config's "
                          "overlap.pipeline_depth, 2).  Higher values hide "
                          "more host time behind device compute at the cost "
                          "of one packed batch of host memory each")
    run.add_argument("--speculate-depth", type=int, default=None,
                     help="Multi-host only: next-phase rounds launched at "
                          "each phase barrier before the tail verdicts "
                          "resolve (default: the window depth).  The gang "
                          "min-negotiates the value, so 0 on any host "
                          "restores the classic three-post barrier for "
                          "everyone — same as TEXTBLAST_SPECULATE=off.  "
                          "Outputs are byte-identical at any depth")
    run.add_argument("--stage-deadline-s", type=float, default=None,
                     metavar="S",
                     help="Arm the stall watchdog: deadline-bound every "
                          "host-side stage (device fetch, pack wait, "
                          "write-behind queue, reader prefetch) at S "
                          "seconds.  A stalled stage raises a typed "
                          "StallError and escalates through the ordinary "
                          "retry -> split -> host ladder (lockstep runs "
                          "convert it to a joint fault verdict), so hangs "
                          "degrade instead of wedging a rank.  0 (the "
                          "default) disarms the watchdog entirely; "
                          "scheduling-only — outputs are byte-identical "
                          "with any value.  TEXTBLAST_STAGE_DEADLINE_S "
                          "sets the same knob from the environment")
    run.add_argument("--no-overlap", action="store_true",
                     help="Disable the overlapped host pipeline (reader "
                          "thread, pack pool, in-flight window, writer "
                          "thread) and run the serial path.  Outputs are "
                          "byte-identical either way; this is the "
                          "escape hatch and A/B baseline")
    run.add_argument("--warmup", choices=("auto", "on", "off"), default="auto",
                     help="Pre-compile every (bucket, phase) device program "
                          "before the stream starts, consulting the "
                          "serialized AOT executable cache first (a warm "
                          "start loads finished executables in well under a "
                          "second instead of re-compiling for 15-29 s).  "
                          "'auto' warms on accelerator backends and stays "
                          "lazy on CPU; TEXTBLAST_WARMUP overrides the "
                          "default, TEXTBLAST_NO_COMPILE_CACHE=1 disables "
                          "the executable cache itself")
    run.add_argument("--metrics-port", type=int, default=None,
                     help="Port for the Prometheus metrics HTTP endpoint "
                          "(with --coordinator the port is offset by "
                          "--process-id so co-located processes don't "
                          "collide on the bind)")
    run.add_argument("--trace", default=None, metavar="OUT.JSON",
                     help="Record a Chrome trace-event JSON of the run "
                          "(per-batch spans for every pipeline stage across "
                          "the overlap threads, per-round spans on the "
                          "multihost path, instant events for resilience "
                          "transitions).  Load it at https://ui.perfetto.dev "
                          "or chrome://tracing.  Near-zero cost when off; "
                          "with --coordinator, process i>0 writes "
                          "OUT.JSON.host<i>.  The same spans land in the "
                          "--trace-device profile whether or not this is "
                          "given")
    run.add_argument("--trace-device", default=None, metavar="LOGDIR",
                     help="Capture a jax.profiler trace into LOGDIR "
                          "(TensorBoard/Perfetto-loadable): the XLA device "
                          "operations and the program's own per-batch, "
                          "per-group and per-chunk spans (named tb.<stage>, "
                          "on each emitting thread's line) in one .xplane.pb "
                          "on one clock, so every idle stretch of the device "
                          "lines up with the stage the host was in.  "
                          "Independent of --trace")
    run.add_argument("--events-file", default=None, metavar="OUT.JSONL",
                     help="Write the structured operational event journal: "
                          "every retry/breaker/ladder transition, negotiated "
                          "verdict, peer failure, reformation, membership "
                          "change, watchdog stall, speculation void, "
                          "checkpoint commit, and warmup outcome as one "
                          "schema-validated JSONL record, sequence-numbered "
                          "and stamped on the aligned trace clock so "
                          "multi-host journals interleave.  Near-zero cost "
                          "when off; with --coordinator, process i>0 writes "
                          "OUT.JSONL.host<i>.  TEXTBLAST_EVENTS sets the "
                          "same path from the environment")
    run.add_argument("--slo", action="append", default=None,
                     metavar="KEY=TARGET",
                     help="Declare a service-level objective (repeatable): "
                          "availability=0.999, p99_latency_s=0.25 (needs "
                          "--doc-sample-rate), throughput_floor=500.  The "
                          "engine evaluates multi-window burn rates against "
                          "the error budget, publishes slo_* gauges on "
                          "/metrics and /slo, fires edge-triggered "
                          "slo_alert journal events, and lands an `slo` "
                          "section in the run report.  Overrides the "
                          "config's `slo:` block per key; TEXTBLAST_SLO "
                          "takes comma-separated pairs from the "
                          "environment.  Arms the event journal (ring "
                          "buffer only unless --events-file is also given)")
    run.add_argument("--run-report", default=None, metavar="REPORT.JSON",
                     help="Write a machine-readable end-of-run report "
                          "(stage breakdown, occupancy, resilience "
                          "counters, per-filter drop funnel, wall time, "
                          "config provenance).  With --coordinator, pass it "
                          "on every process; process 0 writes one merged "
                          "report with per-host snapshots and summed "
                          "totals")
    run.add_argument("--doc-sample-rate", type=int, default=0, metavar="N",
                     help="Sample 1-in-N documents for per-document "
                          "tail-latency lineage: sampled docs are stamped "
                          "at every stage seam and feed the "
                          "doc_latency_* HDR histograms (p50/p95/p99 in "
                          "the run report, /metrics, and the end-of-run "
                          "summary) plus the live rollup windows on "
                          "/telemetry.  Deterministic on the doc id, so "
                          "multi-host runs sample the same documents on "
                          "every host.  0 = off (zero hot-path cost)")
    run.add_argument("--profile", action="store_true",
                     help="Device-time attribution: capture the XLA cost "
                          "model (flops/bytes per compiled program, AOT "
                          "cache hits included), per-(bucket, phase) "
                          "device-time histograms with roofline "
                          "utilization gauges, a top-K slowest-dispatch "
                          "table, and — on the multihost path — the "
                          "lockstep stall decomposition.  Lands in the "
                          "run report's device_profile section, /metrics, "
                          "and --trace span args.  Off by default (the "
                          "hot path then pays a single attribute check)")
    run.add_argument("--quiet", action="store_true", help="Suppress progress output")
    run.add_argument("--checkpoint-dir", default=None,
                     help="Enable chunk-level checkpointing in this directory; "
                          "an interrupted run resumes from the last committed "
                          "chunk (the reference cannot do this)")
    run.add_argument("--checkpoint-every", type=int, default=8192,
                     help="Documents per checkpointed chunk")
    run.add_argument("--coordinator", default=None,
                     help="host:port of process 0 — enables the multi-host "
                          "SPMD path: every process runs this same command "
                          "with its own --process-id, reads its row stripe, "
                          "and process 0 merges the per-host output shards "
                          "(the AMQP-address analogue, utils/common.rs:15)")
    run.add_argument("--num-processes", type=int, default=1,
                     help="Total participating processes (with --coordinator)")
    run.add_argument("--process-id", type=int, default=0,
                     help="This process's rank (with --coordinator)")
    run.add_argument("--force", action="store_true",
                     help="With --coordinator: remove stale *.shard* "
                          "leftovers from a previous crashed run instead of "
                          "failing fast when they would be silently ignored "
                          "by the final merge")
    run.add_argument("--exchange-deadline-s", type=float, default=None,
                     help="With --coordinator: budget for each lockstep "
                          "exchange; on expiry the run fails fast with a "
                          "typed PeerFailure naming the rank(s) that never "
                          "posted (default 300)")
    run.add_argument("--lease-ttl-s", type=float, default=None,
                     help="With --coordinator: liveness-lease TTL, renewed "
                          "at TTL/3; a rank whose lease is older is "
                          "classified dead (default 10)")
    run.add_argument("--elastic", action="store_true",
                     help="With --coordinator: elastic gang membership — "
                          "ranks coordinate through shared-filesystem "
                          "leases and per-stripe checkpoint cursors "
                          "instead of lockstep collectives; survivors "
                          "adopt a dead rank's stripe, a relaunched "
                          "rank rejoins in place replaying no completed "
                          "work, and a new rank (--process-id >= "
                          "--num-processes) joins live via an admission "
                          "request")
    run.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                     help="With --elastic: the lowest live home rank "
                          "spawns joiner ranks while the stripe cursors "
                          "show sustained backlog, up to MAX total "
                          "workers; joiners drain (fence-and-leave) at "
                          "idle")
    run.add_argument("--exchange-transport", default="auto",
                     choices=("auto", "kv", "file"),
                     help="With --coordinator: carrier for the lockstep "
                          "exchanges — kv = the XLA/coordination-service "
                          "funnel (fastest, dies with its peers), file = "
                          "shared-filesystem slots riding the membership "
                          "leases (required for --survive-peer-loss); "
                          "auto picks file iff --survive-peer-loss")
    run.add_argument("--survive-peer-loss", action="store_true",
                     help="With --coordinator: gang reformation — on a "
                          "peer death the survivors fence the dead rank's "
                          "incarnation, re-elect the member set, adopt "
                          "its stripe, and finish the run with outputs "
                          "byte-identical to a fault-free run (file "
                          "exchange transport only)")

    val = sub.add_parser("validate-config",
                         help="Validate a pipeline configuration and exit")
    val.add_argument("-c", "--pipeline-config",
                     default="configs/pipeline_config.yaml")
    return p


def _cmd_validate(args: argparse.Namespace) -> int:
    # bin/worker.rs:29-51: load+validate, exit 0/1.
    try:
        config = load_pipeline_config(args.pipeline_config)
    except PipelineError as e:
        print(f"Configuration is invalid: {e}", file=sys.stderr)
        return 1
    print(
        f"Configuration at '{args.pipeline_config}' is valid "
        f"({len(config.pipeline)} steps: "
        + ", ".join(s.type for s in config.pipeline)
        + ")"
    )
    return 0


def _cmd_run(args: argparse.Namespace) -> int:
    init_logging("textblast")
    metrics_port = args.metrics_port
    if metrics_port is not None and args.coordinator:
        # Co-located processes (multi-process CPU, one host) would collide
        # on the bind; rank-offset ports keep every /metrics reachable.
        metrics_port += args.process_id
    setup_prometheus_metrics(metrics_port)

    if args.backend == "cpu":
        # Compiled pipeline pinned to the in-process CPU backend.
        import jax

        jax.config.update("jax_platforms", "cpu")
        # Packed-int64 sort2 path (~4.4x on XLA:CPU; pallas_sort.sort2).
        jax.config.update("jax_enable_x64", True)
        args.backend = "tpu"
    elif args.backend == "tpu" and not args.coordinator:
        # A gang checks once it has formed (parallel.multihost.run_multihost):
        # jax.distributed must initialize before anything touches a backend.
        from .ops.device import tpu_refusal

        refusal = tpu_refusal()
        if refusal:
            print(refusal, file=sys.stderr)
            return 1

    if args.backend == "tpu":
        # Large traced pipelines: persist compiled programs so re-runs and
        # checkpoint resumes skip the compile entirely.
        from .utils.compile_cache import enable_compilation_cache

        enable_compilation_cache()

    try:
        config = load_pipeline_config(args.pipeline_config)
    except PipelineError as e:
        print(f"Failed to load pipeline config: {e}", file=sys.stderr)
        return 1

    if args.no_overlap:
        config.overlap.enabled = False
    if args.pipeline_depth is not None:
        if args.pipeline_depth < 1:
            print(f"Invalid --pipeline-depth value: {args.pipeline_depth}",
                  file=sys.stderr)
            return 1
        config.overlap.pipeline_depth = args.pipeline_depth
    if args.speculate_depth is not None:
        if args.speculate_depth < 0:
            print(f"Invalid --speculate-depth value: {args.speculate_depth}",
                  file=sys.stderr)
            return 1
        config.overlap.speculate_depth = args.speculate_depth
    if args.stage_deadline_s is not None:
        if args.stage_deadline_s < 0:
            print(f"Invalid --stage-deadline-s value: {args.stage_deadline_s}",
                  file=sys.stderr)
            return 1
        config.resilience.stage_deadline_s = args.stage_deadline_s
    elif os.environ.get("TEXTBLAST_STAGE_DEADLINE_S", "").strip():
        try:
            env_deadline = float(os.environ["TEXTBLAST_STAGE_DEADLINE_S"])
        except ValueError:
            print("Invalid TEXTBLAST_STAGE_DEADLINE_S value: "
                  f"{os.environ['TEXTBLAST_STAGE_DEADLINE_S']!r}",
                  file=sys.stderr)
            return 1
        if env_deadline < 0:
            print(f"Invalid TEXTBLAST_STAGE_DEADLINE_S value: {env_deadline}",
                  file=sys.stderr)
            return 1
        config.resilience.stage_deadline_s = env_deadline

    buckets = None
    if args.buckets:
        try:
            buckets = tuple(sorted(int(x) for x in args.buckets.split(",") if x.strip()))
        except ValueError:
            buckets = ()
        if not buckets or any(b < 64 for b in buckets):
            print(f"Invalid --buckets value: {args.buckets!r}", file=sys.stderr)
            return 1

    if args.auto_geometry and (buckets or args.device_batch):
        print("--auto-geometry chooses buckets and batch sizes itself; "
              "it cannot be combined with --buckets or --device-batch",
              file=sys.stderr)
        return 1
    if args.auto_geometry and args.backend == "host":
        print("--auto-geometry tunes the device geometry; it has no effect "
              "on --backend host", file=sys.stderr)
        return 1

    if args.trace:
        trace_path = args.trace
        if args.coordinator and args.process_id:
            trace_path = f"{args.trace}.host{args.process_id}"
        TRACER.configure(
            trace_path,
            process_name=f"textblast-host{args.process_id}"
            if args.coordinator else "textblast",
            pid=args.process_id,
        )

    if args.doc_sample_rate < 0:
        print(f"Invalid --doc-sample-rate value: {args.doc_sample_rate}",
              file=sys.stderr)
        return 1
    if args.doc_sample_rate > 0:
        TELEMETRY.configure(args.doc_sample_rate)
    if args.profile:
        PROFILER.configure()
    WATCHDOG.configure(config.resilience.stage_deadline_s)

    # SLO objectives: config block first, --slo overrides per key, the env
    # fallback only when no flag was passed (mirrors TEXTBLAST_STAGE_DEADLINE_S).
    slo_pairs = list(args.slo or [])
    if not slo_pairs and os.environ.get("TEXTBLAST_SLO", "").strip():
        slo_pairs = [
            s for s in os.environ["TEXTBLAST_SLO"].split(",") if s.strip()
        ]
    slo_objectives = dict(config.slo.objectives)
    for raw in slo_pairs:
        try:
            key, target = parse_slo_arg(raw)
        except ValueError as e:
            print(f"Invalid --slo value: {e}", file=sys.stderr)
            return 1
        slo_objectives[key] = target

    events_path = args.events_file or (
        os.environ.get("TEXTBLAST_EVENTS", "").strip() or None
    )
    if events_path or slo_objectives:
        # Objectives without a journal path still arm the ring buffer:
        # slo_alert events must land somewhere the flight recorder can see.
        journal_path = events_path
        if journal_path and args.coordinator and args.process_id:
            journal_path = f"{events_path}.host{args.process_id}"
        EVENTS.configure(journal_path, rank=args.process_id)
    if slo_objectives:
        SLO.configure(
            slo_objectives,
            fast_window_s=config.slo.fast_window_s,
            slow_window_s=config.slo.slow_window_s,
            burn_threshold=config.slo.burn_threshold,
            tick_s=config.slo.tick_s,
        )
    provenance = {
        "entry": "textblast run",
        "version": __version__,
        "pipeline_config": args.pipeline_config,
        "steps": [s.type for s in config.pipeline],
        "input_file": args.input_file,
        "backend": args.backend,
        "buckets": list(buckets) if buckets else None,
        "device_batch": args.device_batch,
        "auto_geometry": bool(args.auto_geometry),
        "overlap_enabled": bool(config.overlap.enabled),
        "pipeline_depth": int(config.overlap.pipeline_depth),
        "speculate_depth": (
            None if config.overlap.speculate_depth is None
            else int(config.overlap.speculate_depth)
        ),
        "num_processes": args.num_processes,
        "doc_sample_rate": int(args.doc_sample_rate),
        "profile": bool(args.profile),
        "stage_deadline_s": float(config.resilience.stage_deadline_s),
        "events_file": args.events_file,
        "slo": dict(sorted(slo_objectives.items())) or None,
    }
    report_baseline = metrics_snapshot() if args.run_report else None
    funnel_before = funnel_snapshot()
    if EVENTS.enabled:
        # After the baseline snapshot, so the report's events section
        # charges run_start to this run rather than to history.
        EVENTS.emit(
            "run_start",
            input=args.input_file,
            backend=args.backend,
            num_processes=args.num_processes,
        )

    start = time.perf_counter()
    fallbacks_before = METRICS.get("worker_host_fallback_total")

    if args.coordinator and args.checkpoint_dir:
        print("--coordinator and --checkpoint-dir are mutually exclusive "
              "(multihost runs restart per shard; use smaller input stripes "
              "for resumability)", file=sys.stderr)
        return 1
    if args.coordinator and args.backend == "host":
        print("--coordinator requires the compiled pipeline "
              "(--backend tpu or cpu, not host)", file=sys.stderr)
        return 1
    if not args.coordinator and (
        args.elastic
        or args.survive_peer_loss
        or args.exchange_transport != "auto"
        or args.exchange_deadline_s is not None
        or args.lease_ttl_s is not None
    ):
        print("--elastic / --survive-peer-loss / --exchange-transport / "
              "--exchange-deadline-s / --lease-ttl-s shape the "
              "multi-host membership layer and require --coordinator",
              file=sys.stderr)
        return 1
    if args.elastic and args.auto_geometry:
        print("--elastic is incompatible with --auto-geometry (geometry "
              "negotiation is a full-gang collective with no lockstep "
              "exchange to ride; --run-report IS supported — the merging "
              "rank folds per-rank report shards)",
              file=sys.stderr)
        return 1
    if args.autoscale and not args.elastic:
        print("--autoscale requires --elastic (the supervisor spawns and "
              "drains joiner ranks through the elastic membership "
              "protocol)", file=sys.stderr)
        return 1
    if args.elastic and (
        args.survive_peer_loss or args.exchange_transport == "file"
    ):
        print("--elastic is incompatible with --survive-peer-loss and "
              "--exchange-transport file (elastic membership has no "
              "lockstep exchanges for the transport to carry)",
              file=sys.stderr)
        return 1
    if args.survive_peer_loss and args.exchange_transport == "kv":
        print("--survive-peer-loss requires the file-lease exchange "
              "transport (the kv transport rides the jax coordination "
              "service, which force-terminates survivors ~90-100s after a "
              "peer death); pass --exchange-transport file or auto",
              file=sys.stderr)
        return 1
    for name, val in (("--exchange-deadline-s", args.exchange_deadline_s),
                      ("--lease-ttl-s", args.lease_ttl_s)):
        if val is not None and val <= 0:
            print(f"{name} must be positive, got {val}", file=sys.stderr)
            return 1
    if args.coordinator:
        # Parse-time sanity for the deadline/TTL pair (effective values,
        # library defaults filled in): a deadline at or under the TTL
        # turns every slow lease renewal into a diagnosed "death".
        from .resilience.membership import (
            DEFAULT_EXCHANGE_DEADLINE_S,
            DEFAULT_LEASE_TTL_S,
        )

        eff_deadline = (args.exchange_deadline_s
                        if args.exchange_deadline_s is not None
                        else DEFAULT_EXCHANGE_DEADLINE_S)
        eff_ttl = (args.lease_ttl_s if args.lease_ttl_s is not None
                   else DEFAULT_LEASE_TTL_S)
        if eff_deadline <= eff_ttl:
            print(f"--exchange-deadline-s ({eff_deadline:g}s) must exceed "
                  f"--lease-ttl-s ({eff_ttl:g}s): with the exchange "
                  "deadline at or under the lease TTL, every slow lease "
                  "renewal is misclassified as a peer death",
                  file=sys.stderr)
            return 1
    # --warmup on/off overrides the backend-default policy everywhere; the
    # env form reaches paths that build their pipeline deep inside the
    # multi-host negotiation layers (ops.pipeline.should_warmup reads it).
    warmup_opt = {"auto": None, "on": True, "off": False}[args.warmup]
    if args.profile and warmup_opt is None:
        # The cost model is captured at warmup compile/AOT-load time; the
        # CPU default (lazy first-dispatch compiles) would leave it empty.
        # An explicit --warmup off still wins: timing-only profile.
        warmup_opt = True
    if warmup_opt is not None:
        os.environ["TEXTBLAST_WARMUP"] = "1" if warmup_opt else "0"

    # Entered manually (not a with-block) so the existing dispatch block
    # keeps its indentation; TRACER.close() must run on every path so a
    # failed run still leaves a loadable (truncation-tolerant) trace.
    profile_ctx = device_profile(args.trace_device)
    profile_ctx.__enter__()
    try:
        if args.coordinator:
            from .parallel.multihost import run_multihost

            mh_kwargs = {}
            if buckets:
                mh_kwargs["buckets"] = buckets
            if args.device_batch:
                mh_kwargs["device_batch"] = args.device_batch
            if args.auto_geometry:
                mh_kwargs["auto_geometry"] = True
            if args.exchange_deadline_s is not None:
                mh_kwargs["exchange_deadline_s"] = args.exchange_deadline_s
            if args.lease_ttl_s is not None:
                mh_kwargs["lease_ttl_s"] = args.lease_ttl_s
            if args.elastic:
                mh_kwargs["elastic"] = True
            if args.autoscale:
                mh_kwargs["autoscale"] = args.autoscale
            if args.exchange_transport != "auto":
                mh_kwargs["exchange_transport"] = args.exchange_transport
            if args.survive_peer_loss:
                mh_kwargs["survive_peer_loss"] = True
            result = run_multihost(
                config,
                args.input_file,
                args.output_file,
                args.excluded_file,
                coordinator=args.coordinator,
                num_processes=args.num_processes,
                process_id=args.process_id,
                text_column=args.text_column,
                id_column=args.id_column,
                read_batch_size=args.batch_size,
                errors_file=args.errors_file,
                force=args.force,
                run_report=args.run_report,
                provenance=provenance,
                **mh_kwargs,
            )
        elif args.checkpoint_dir:
            from .checkpoint import run_checkpointed
            from .parallel.runner import _Progress

            progress = _Progress(enabled=not args.quiet)
            result = run_checkpointed(
                config=config,
                input_file=args.input_file,
                output_file=args.output_file,
                excluded_file=args.excluded_file,
                ckpt_dir=args.checkpoint_dir,
                chunk_size=args.checkpoint_every,
                text_column=args.text_column,
                id_column=args.id_column,
                backend=args.backend,
                read_batch_size=args.batch_size,
                device_batch=args.device_batch,
                buckets=buckets,
                auto_geometry=args.auto_geometry,
                progress=progress.update,
                errors_file=args.errors_file,
                warmup=warmup_opt,
            )
            progress.finish()
        else:
            from .parallel.runner import run_pipeline

            result = run_pipeline(
                config=config,
                input_file=args.input_file,
                output_file=args.output_file,
                excluded_file=args.excluded_file,
                text_column=args.text_column,
                id_column=args.id_column,
                backend=args.backend,
                read_batch_size=args.batch_size,
                device_batch=args.device_batch,
                buckets=buckets,
                auto_geometry=args.auto_geometry,
                quiet=args.quiet,
                errors_file=args.errors_file,
                warmup=warmup_opt,
            )
        if EVENTS.enabled:
            EVENTS.emit("run_end", exit_code=0)
    except PeerFailure as e:
        # A dead gang member: run_multihost already abandoned the
        # distributed client, but the coordination service's C++ error
        # poller races normal interpreter teardown and may SIGABRT us
        # mid-exit.  Flush the diagnosis and hard-exit deterministically —
        # there is no graceful path out of a broken gang.
        print(f"Pipeline run failed: {e}", file=sys.stderr, flush=True)
        if EVENTS.enabled:
            # Journal the diagnosis and leave a flight-recorder dump beside
            # the output before the hard exit — the dump is the post-mortem
            # when the gang dies faster than any scrape.
            EVENTS.emit(
                "fatal",
                reason="peer_failure",
                missing_ranks=list(e.missing_ranks),
                dead_ranks=list(e.dead_ranks),
                seq=e.seq,
            )
            EVENTS.emit("run_end", exit_code=1)
            flight_record(
                args.output_file,
                rank=args.process_id,
                reason="peer_failure",
                exc=e,
            )
        if args.run_report:
            # Post-mortems of unreformable gangs shouldn't be blind: commit
            # a partial, schema-tagged report naming the failed exchange
            # before the hard exit.  Best-effort — the abort path must
            # never mask the diagnosis above.
            try:
                report = build_run_report(
                    baseline=report_baseline,
                    wall_time_s=time.perf_counter() - start,
                    counts={},
                    provenance=provenance,
                )
                report["aborted"] = True
                report["peer_failure"] = {
                    "message": str(e),
                    "missing_ranks": list(e.missing_ranks),
                    "dead_ranks": list(e.dead_ranks),
                    "seq": e.seq,
                    "epoch": e.epoch,
                }
                write_run_report(args.run_report, report)
            except Exception:
                pass
        profile_ctx.__exit__(None, None, None)
        TRACER.close()  # flushes the trace spill to disk
        SLO.close()
        EVENTS.close()  # flushes the journal spill; os._exit skips finally
        sys.stdout.flush()
        os._exit(1)
    except PipelineError as e:
        print(f"Pipeline run failed: {e}", file=sys.stderr)
        if EVENTS.enabled:
            EVENTS.emit("fatal", reason="pipeline_error", error=str(e))
            EVENTS.emit("run_end", exit_code=1)
            flight_record(
                args.output_file,
                rank=args.process_id,
                reason="pipeline_error",
                exc=e,
            )
        return 1
    except BaseException as e:
        # Anything else escaping here (KeyboardInterrupt, MemoryError, a
        # plain bug) unwinds the interpreter: leave the flight-recorder
        # dump behind first, then let it propagate.
        if EVENTS.enabled:
            EVENTS.emit("fatal", reason=type(e).__name__)
            flight_record(
                args.output_file,
                rank=args.process_id,
                reason="unhandled",
                exc=e,
            )
        raise
    finally:
        profile_ctx.__exit__(None, None, None)
        TRACER.close()
        TELEMETRY.close()  # stops the rollup ticker; HDR state stays in METRICS
        PROFILER.close()  # stops recording; captured state stays for the report
        SLO.close()  # final evaluation tick, then disarm
        EVENTS.close()  # flushes the journal spill; counters stay in METRICS

    elapsed = time.perf_counter() - start
    total = result.received
    rate = total / elapsed if elapsed > 0 else 0.0
    # Final summary (bin/producer.rs:169-181).
    print(
        f"Processed {total} documents in {elapsed:.2f}s ({rate:.1f} docs/sec): "
        f"{result.success} kept -> {args.output_file}, "
        f"{result.filtered} excluded -> {args.excluded_file}, "
        f"{result.errors} errored "
        + (
            f"-> {args.errors_file}."
            if args.errors_file
            else "(in neither file)."
        )
    )
    deadlettered = int(METRICS.get("deadletter_rows_total"))
    if args.errors_file and deadlettered:
        print(
            f"Dead-letter rows: {deadlettered} -> {args.errors_file} "
            "(errored + unreadable)."
        )
    neg_retries = int(METRICS.get("resilience_negotiated_retries_total"))
    neg_degraded = int(
        METRICS.get("resilience_negotiated_degraded_rounds_total")
    )
    if neg_retries or neg_degraded:
        # The negotiated counters move identically on every host (the
        # verdicts are allgathered), so each process reports the same global
        # story.  Printed even under --quiet: a degraded round is an
        # operational signal, not progress chatter.
        print(
            f"Negotiated resilience: {neg_retries} jointly retried rounds, "
            f"{neg_degraded} rounds degraded to the host oracle.",
            file=sys.stderr,
        )
    reformations = int(METRICS.get("multihost_gang_reformations_total"))
    if reformations:
        # A reformed gang finished the run without the member(s) it started
        # with — operationally loud even though the outputs are intact.
        print(
            f"Gang reformation: survived {reformations} peer-loss "
            f"event(s); "
            f"{int(METRICS.get('multihost_fenced_ranks_total'))} rank "
            "incarnation(s) fenced, "
            f"{int(METRICS.get('multihost_adopted_stripes_total'))} "
            "stripe(s) adopted; final membership epoch "
            f"{int(METRICS.get('multihost_membership_epoch'))}.",
            file=sys.stderr,
        )
    evictions = int(METRICS.get("multihost_evictions_total"))
    rejoins = int(METRICS.get("multihost_rejoins_total"))
    adopted = int(METRICS.get("multihost_adopted_stripes_total"))
    joins = int(METRICS.get("multihost_rank_joins_total"))
    if (evictions or rejoins or adopted or joins) and not reformations:
        # Membership churn is an operational signal like a degraded round:
        # the run completed, but not with the gang it started with.
        print(
            f"Elastic membership: {evictions} eviction(s), {rejoins} "
            f"rejoin(s), {joins} join(s), {adopted} stripe(s) adopted; "
            f"final epoch "
            f"{int(METRICS.get('multihost_membership_epoch'))}.",
            file=sys.stderr,
        )
    tripped = int(METRICS.get("resilience_breaker_trips_total"))
    if tripped:
        print(
            "Warning: device circuit breaker tripped — the run degraded to "
            "the host backend after repeated device failures "
            f"(retries={int(METRICS.get('resilience_retries_total'))}, "
            f"host-rung docs="
            f"{int(METRICS.get('resilience_ladder_host_total'))}).",
            file=sys.stderr,
        )
    fallbacks = int(
        METRICS.get("worker_host_fallback_total") - fallbacks_before
    )
    if fallbacks:
        # Outlier documents (over-length / table overflow) re-ran the host
        # oracle — bit-exact outcomes, but worth surfacing: a high rate means
        # the device path is not carrying the load it appears to.
        print(
            f"Host-fallback documents: {fallbacks} "
            f"({fallbacks / max(total, 1):.1%} of stream)."
        )
    if result.read_errors:
        print(f"Warning: {result.read_errors} rows could not be read.",
              file=sys.stderr)
    if not args.quiet:
        from .utils.metrics import (
            STAGE_COUNTERS,
            format_occupancy_summary,
            format_stage_summary,
        )

        if any(METRICS.get(name) > 0 for name in STAGE_COUNTERS):
            print(format_stage_summary(), file=sys.stderr)
        if METRICS.get("occupancy_device_batches_total") > 0:
            print(format_occupancy_summary(), file=sys.stderr)
        if funnel_report(funnel_before)["dropped_total"] > 0:
            print(
                format_funnel_summary(
                    funnel_before, order=[s.type for s in config.pipeline]
                ),
                file=sys.stderr,
            )
        if args.doc_sample_rate > 0:
            print(format_latency_summary(report_baseline), file=sys.stderr)
        if args.profile:
            fp = PROFILER.cost_fingerprint()
            top = PROFILER.top_dispatches()
            line = f"Device profile: cost fingerprint {str(fp)[:12]}"
            if top:
                worst = top[0]
                line += (
                    f"; slowest dispatch {worst['seconds'] * 1e3:.1f} ms "
                    f"(bucket {worst['bucket']}, phase {worst['phase']})"
                )
            print(line, file=sys.stderr)
        if args.trace:
            print(f"Trace written -> {args.trace} "
                  "(load at https://ui.perfetto.dev)", file=sys.stderr)
        if args.events_file:
            emitted = int(METRICS.get("events_emitted_total"))
            dropped = int(METRICS.get("events_dropped_total"))
            line = f"Event journal -> {args.events_file} ({emitted} events"
            if dropped:
                line += f", {dropped} dropped"
            print(line + ")", file=sys.stderr)
        if slo_objectives:
            alerts = int(METRICS.get("slo_alerts_total"))
            worst = min(
                (
                    METRICS.get(f"slo_budget_remaining_{k}")
                    for k in slo_objectives
                ),
                default=1.0,
            )
            print(
                f"SLO: {len(slo_objectives)} objective(s), {alerts} "
                f"alert(s), {worst * 100.0:.1f}% of the tightest error "
                "budget left.",
                file=sys.stderr,
            )

    if args.run_report and not args.coordinator:
        # Coordinator runs write the merged report from run_multihost
        # (process 0, after the snapshot allgather) instead.
        report = build_run_report(
            baseline=report_baseline,
            wall_time_s=elapsed,
            counts={
                "received": result.received,
                "success": result.success,
                "filtered": result.filtered,
                "errors": result.errors,
                "read_errors": result.read_errors,
            },
            provenance=provenance,
        )
        write_run_report(args.run_report, report)
        if not args.quiet:
            print(f"Run report -> {args.run_report}", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.command == "validate-config":
        return _cmd_validate(args)
    return _cmd_run(args)


if __name__ == "__main__":
    sys.exit(main())
