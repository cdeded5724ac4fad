"""Unified CLI — replaces the reference's nine overlapping entry scripts
(resnet_single.py, resnet_cifar_train.py, resnet_cifar_main.py,
resnet_imagenet_train.py, the eval sidecars and predict tools — SURVEY.md §1
L4) with one command:

    python -m tpu_resnet train --preset cifar10 train.train_dir=/tmp/run
    python -m tpu_resnet eval  --preset cifar10 train.train_dir=/tmp/run
    python -m tpu_resnet info  --preset imagenet
"""

from __future__ import annotations

import argparse
import json
import logging
import sys


def _setup_logging():
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s: %(message)s",
        datefmt="%H:%M:%S",
        stream=sys.stderr,
    )


def main(argv=None):
    _setup_logging()
    raw = sys.argv[1:] if argv is None else list(argv)
    if raw[:1] == ["check"]:
        # Delegated wholesale: the analysis CLI owns its flag surface
        # (argparse.REMAINDER can't forward leading --flags), and this
        # path must not import jax until it decides to.
        from tpu_resnet.analysis.cli import main as check_main
        return check_main(raw[1:])
    if raw[:1] == ["trace-export"]:
        # Same delegation: stdlib-only timeline export (obs/trace.py) —
        # never imports jax, works on a machine with no backend.
        from tpu_resnet.obs.trace import main as trace_main
        return trace_main(raw[1:])
    if raw[:1] == ["scenario"]:
        # Same delegation: the chaos-scenario conductor is jax-free by
        # contract — its CHILDREN are the processes that touch jax.
        from tpu_resnet.scenario.cli import main as scenario_main
        return scenario_main(raw[1:])
    parser = argparse.ArgumentParser(prog="tpu_resnet")
    sub = parser.add_subparsers(dest="command", required=True)
    for name, help_text in [
        ("train", "run the training loop"),
        ("train_and_eval", "train with an in-process eval sidecar"),
        ("eval", "continuous checkpoint-polling evaluation (or --once)"),
        ("info", "print resolved config, param count and per-step FLOPs"),
        ("export", "freeze a checkpoint into a serialized inference artifact"),
        ("predict", "run a frozen artifact over the eval split"),
        ("serve", "online inference: dynamic-batching HTTP predict server "
                  "with checkpoint hot-reload (docs/SERVING.md)"),
        ("route", "serving-fleet front router: spread /predict over N "
                  "serve replicas with health-probed failover, SLO-aware "
                  "load shedding and rolling drains (docs/SERVING.md)"),
        ("fleetmon", "fleet telemetry aggregator: discover every "
                     "serve/route/train endpoint in a dir, scrape all "
                     "/metrics on an interval into an on-disk "
                     "timeseries, merge per-replica latency histograms "
                     "into true fleet p50/p95/p99, page on SLO "
                     "error-budget burn (docs/OBSERVABILITY.md)"),
        ("autopilot", "traffic-driven autoscaling control plane: scrape "
                      "the router + fleetmon signals, run the "
                      "deterministic target-replica policy (hysteresis "
                      "bands, cooldowns, min/max), spawn replicas via "
                      "supervise/discovery gated by colocation "
                      "admission, drain via the router's rolling "
                      "contract (docs/AUTOPILOT.md)"),
        ("inspect", "list arrays in a checkpoint (tf_saver equivalent)"),
        ("plot", "render precision/loss/throughput curves from metrics.jsonl"),
        ("trace-export", "merge a run's spans/metrics/eval/serve events "
                         "into one Chrome-trace JSON (open in "
                         "ui.perfetto.dev; docs/OBSERVABILITY.md)"),
        ("fetch", "download + verify + extract a dataset (cifar10/cifar100)"),
        ("doctor", "environment triage: backend probe, CPU mesh smoke, "
                   "native plane, dataset layout, run telemetry"),
        ("check", "static analysis: JAX/TPU AST lints + config-matrix "
                  "abstract verifier (docs/CHECKS.md)"),
    ]:
        p = sub.add_parser(name, help=help_text)
        if name not in ("fetch", "doctor", "check",
                        "trace-export"):  # no run config
            p.add_argument("--preset", default="")
            p.add_argument("--config", default="")
            p.add_argument("overrides", nargs="*")
        if name == "eval":
            p.add_argument("--once", action="store_true",
                           help="evaluate latest checkpoint once and exit")
        if name == "route":
            p.add_argument("--drain", default="",
                           help="rolling operations: ask a RUNNING "
                                "router to drain replica NAME (exclude "
                                "from rotation, wait out in-flight, "
                                "SIGTERM per the drain contract) and "
                                "exit — instead of starting a router")
            p.add_argument("--router-url", default="",
                           help="with --drain: the running router's "
                                "base url (default: discovered from "
                                "route.json in route.discover_dir)")
            p.add_argument("--watch-discovery", action="store_true",
                           help="merit-gated dynamic membership: a "
                                "replica whose discovery record appears "
                                "after boot enters rotation only after "
                                "its first successful health probe "
                                "(shorthand for "
                                "route.watch_discovery=true; the "
                                "autopilot's spawn path relies on it)")
        if name == "info":
            p.add_argument("--layers", action="store_true",
                           help="per-parameter table (tfprof-style dump)")
        if name == "export":
            p.add_argument("--out", required=True,
                           help="output directory for the frozen artifact")
            p.add_argument("--step", type=int, default=None)
            p.add_argument("--batch-size", type=int, default=0,
                           help="0 = dynamic batch dimension")
        if name == "predict":
            p.add_argument("--export-dir", required=True)
            p.add_argument("--out", default="/tmp/tpu_resnet_predict")
            p.add_argument("--num-examples", type=int, default=256)
            p.add_argument("--label-file", default="",
                           help="imagenet idx→name map file")
        if name == "inspect":
            p.add_argument("--dir", required=True, help="train/ckpt dir")
            p.add_argument("--step", type=int, default=None)
            p.add_argument("--peek", default=None,
                           help="print stats+head of one array by path")
        if name == "plot":
            p.add_argument("--dir", required=True, help="train dir")
            p.add_argument("--out", default=None, help="output PNG path")
            p.add_argument("--csv", default=None,
                           help="also export merged series as CSV")
        if name == "fetch":
            p.add_argument("dataset",
                           choices=["cifar10", "cifar100", "imagenet"])
            p.add_argument("--out", required=True, help="dataset directory")
            p.add_argument("--keep-archive", action="store_true")
        if name == "doctor":
            p.add_argument("--list-probes", action="store_true",
                           help="enumerate every scenario-backed drill "
                                "(scenarios/*.json) and every legacy "
                                "bespoke probe, then exit")
            p.add_argument("--check", action="store_true",
                           help="also run the static-analysis suite "
                                "(lints + config-matrix verifier)")
            p.add_argument("--dataset", default="",
                           help="with --data-dir: layout to validate")
            p.add_argument("--data-dir", default="")
            p.add_argument("--train-dir", default="",
                           help="running run's dir: check its telemetry "
                                "server answers /metrics + /healthz")
            p.add_argument("--probe-timeout", type=int, default=60)
            p.add_argument("--mesh-devices", type=int, default=8)
            p.add_argument("--fault-drill", action="store_true",
                           help="run a live SIGTERM+resume drill against "
                                "a temp train_dir (~30s tiny CPU run): "
                                "preemption exit code, final checkpoint, "
                                "exact-step resume")
            p.add_argument("--serve-probe", action="store_true",
                           help="live predict-server smoke (~60s tiny CPU "
                                "run): train a small model, serve it on "
                                "an ephemeral port, fire requests, check "
                                "/healthz readiness and the SIGTERM "
                                "drain exit-code contract")
            p.add_argument("--coldstart-probe", action="store_true",
                           help="cold-vs-warm serve restart drill "
                                "(~3min scrubbed CPU): train a small "
                                "ResNet, serve it cold, SIGTERM, "
                                "restart warm on the same train_dir — "
                                "zero XLA compiles on the warm pass "
                                "(all bucket programs are persistent-"
                                "cache hits), time-to-ready >= 3x "
                                "faster, perfwatch ingests both points")
            p.add_argument("--fleet-probe", action="store_true",
                           help="serving-fleet resilience drill (~2min "
                                "scrubbed CPU): 2 serve replicas + the "
                                "front router on ephemeral ports, "
                                "SIGKILL one replica mid-traffic -> "
                                "zero failed requests, circuit opens "
                                "within a probe interval, hot-reload on "
                                "the survivor, rolling admin drain, "
                                "exit-code contract, trace-export "
                                "router+replica lanes")
            p.add_argument("--data-bench", action="store_true",
                           help="~20s synthetic-JPEG decode throughput "
                                "probe: images/sec at 1 vs N decode "
                                "processes + implied max steps/sec — "
                                "tells host-bound from chip-bound "
                                "without a full bench run")
            p.add_argument("--trace-probe", action="store_true",
                           help="live observability drill (~60s tiny CPU "
                                "run): scrape the live model FLOP/s gauge + "
                                "train_step_ms histogram mid-run, then "
                                "trace-export and schema-check the "
                                "merged Chrome trace")
            p.add_argument("--perfwatch", action="store_true",
                           help="perf-regression verdict over the "
                                "archived BENCH_*.json trajectory "
                                "(tools/perfwatch.py)")
            p.add_argument("--sweep-probe", action="store_true",
                           help="~30s scrubbed-CPU drill of the per-knob "
                                "sweep harness: 2-point sweep end-to-end "
                                "— child deadlines honored, complete "
                                "RESULT_JSON trajectory, perfwatch "
                                "ingestion")
            p.add_argument("--mem-probe", action="store_true",
                           help="memory-observability drill (~60s tiny "
                                "CPU runs): live hbm gauge scrape + "
                                "memory.json ledger matching flops.json "
                                "keys, then a fault-injected "
                                "RESOURCE_EXHAUSTED that must leave a "
                                "schema-valid oom_report.json")
            p.add_argument("--partition-probe", action="store_true",
                           help="ZeRO-1 partitioner drill (~90s tiny CPU "
                                "runs on an 8-device fakepod): zero1 "
                                "optimizer-slot ledger bytes < 0.3x the "
                                "replicated twin's, SIGTERM + exact-step "
                                "resume under zero1, perfwatch peak-HBM "
                                "ingestion")
            p.add_argument("--fleetmon-probe", action="store_true",
                           help="fleet-observability drill (~2min "
                                "scrubbed CPU): 2 replicas + router + "
                                "fleetmon, one replica fault-slowed -> "
                                "zero failed requests, traced requests "
                                "attribute the tail to the slow "
                                "replica's inference segment, fleet-"
                                "merged p99 > healthy replica's own "
                                "p99, burn-rate alert span fires, "
                                "perfwatch ingests fleet latency")
            p.add_argument("--autoscale-probe", action="store_true",
                           help="autoscaling drill (~3min scrubbed "
                                "CPU): 1 replica + watch-discovery "
                                "router + fleetmon + autopilot; a "
                                "traffic burst overruns the replica -> "
                                "autopilot spawns a second via "
                                "supervise/discovery, admitted on "
                                "merit within the advertised scale-up "
                                "latency; calm traffic -> drains back "
                                "to min and leases the freed capacity "
                                "to a colocated trainer; perfwatch "
                                "gates the scale-up-latency / SLO-"
                                "violation / utilization series")
            p.add_argument("--reshape-drill", action="store_true",
                           help="elastic-capacity drill (~2min tiny CPU "
                                "runs): mesh8 train preempted by an "
                                "injected SIGTERM, resumed on a 4-device "
                                "child as zero1 — loss stream equal to "
                                "an uninterrupted mesh8 reference within "
                                "1e-6 at every logged step, "
                                "topology_change span recorded, "
                                "perfwatch ingests pre/post steps/s")
    args = parser.parse_args(argv)

    if args.command == "fetch":
        from tpu_resnet.tools.datasets import fetch
        fetch(args.dataset, args.out, keep_archive=args.keep_archive)
        return 0

    if args.command == "doctor":
        if args.list_probes:
            # The scenario catalog owns the probe inventory — the same
            # listing `tpu_resnet scenario list` prints.
            from tpu_resnet.scenario.cli import main as scenario_main
            return scenario_main(["list", "--paths"])
        from tpu_resnet.tools.doctor import run_doctor
        if args.dataset and not args.data_dir:
            parser.error("doctor --dataset requires --data-dir")
        summary = run_doctor(dataset=args.dataset, data_dir=args.data_dir,
                             train_dir=args.train_dir,
                             probe_timeout=args.probe_timeout,
                             mesh_devices=args.mesh_devices,
                             fault_drill=args.fault_drill,
                             data_bench=args.data_bench,
                             check=args.check,
                             serve_probe=args.serve_probe,
                             coldstart_probe=args.coldstart_probe,
                             fleet_probe=args.fleet_probe,
                             fleetmon_probe=args.fleetmon_probe,
                             trace_probe=args.trace_probe,
                             perfwatch=args.perfwatch,
                             sweep_probe=args.sweep_probe,
                             mem_probe=args.mem_probe,
                             partition_probe=args.partition_probe,
                             reshape_drill=args.reshape_drill,
                             autoscale_probe=args.autoscale_probe)
        return 0 if summary["ok"] else 1

    from tpu_resnet.config import load_config
    cfg = load_config(args.preset, args.config, args.overrides)

    if args.command in ("train", "train_and_eval", "eval", "serve",
                        "export", "predict", "info"):
        # The commands that compile (the router, fleetmon and autopilot
        # stay jax-free).
        from tpu_resnet.hostenv import enable_compile_cache
        enable_compile_cache()

    if args.command == "train":
        from tpu_resnet import parallel
        from tpu_resnet.resilience import Preempted
        from tpu_resnet.train import train
        parallel.initialize()
        try:
            train(cfg)
        except Preempted as e:
            # Distinct exit code: a supervisor (tools/supervise.py, or any
            # restart policy) resumes on this code instead of backing off
            # as for a crash. The final checkpoint is already on disk.
            logging.getLogger("tpu_resnet").warning(
                "%s — exiting %d", e, cfg.resilience.preempt_exit_code)
            return cfg.resilience.preempt_exit_code
        return 0

    if args.command == "train_and_eval":
        from tpu_resnet import parallel
        from tpu_resnet.evaluation import train_and_eval
        from tpu_resnet.resilience import Preempted
        parallel.initialize()
        try:
            train_and_eval(cfg)
        except Preempted as e:
            logging.getLogger("tpu_resnet").warning(
                "%s — exiting %d", e, cfg.resilience.preempt_exit_code)
            return cfg.resilience.preempt_exit_code
        return 0

    if args.command == "eval":
        from tpu_resnet import parallel
        from tpu_resnet.evaluation import evaluate
        parallel.initialize()
        if args.once:
            cfg.train.eval_once = True
        precision = evaluate(cfg)
        if args.once and precision is None:
            # No checkpoint, or none that restored: `--once` that
            # evaluated nothing is a failure, not a quiet success.
            logging.getLogger("tpu_resnet").error(
                "eval --once evaluated nothing in %s", cfg.train.train_dir)
            return 1
        return 0

    if args.command == "info":
        from tpu_resnet.tools.analysis import print_model_info
        print_model_info(cfg, layers=args.layers)
        return 0

    if args.command == "export":
        from tpu_resnet.export import export_from_checkpoint
        out = export_from_checkpoint(cfg, args.out, step=args.step,
                                     batch_size=args.batch_size)
        print(f"exported inference artifact to {out}")
        return 0

    if args.command == "predict":
        from tpu_resnet.tools.predict import predict_from_export
        predict_from_export(cfg, args.export_dir, args.out,
                            num_examples=args.num_examples,
                            label_file=args.label_file)
        return 0

    if args.command == "serve":
        from tpu_resnet import parallel
        from tpu_resnet.serve import serve as serve_fn
        parallel.initialize()
        return serve_fn(cfg)

    if args.command == "route":
        # The router is pure host code — it must come up (and stay up)
        # on a machine whose accelerator stack is the thing that is
        # broken, so no parallel.initialize() here.
        from tpu_resnet.serve.router import (read_route_port,
                                             request_drain, route)
        if args.drain:
            url = args.router_url
            if not url:
                port = read_route_port(cfg.route.discover_dir
                                       or cfg.train.train_dir)
                if port is None:
                    parser.error("route --drain: no route.json found; "
                                 "pass --router-url or "
                                 "route.discover_dir=<dir>")
                url = f"http://127.0.0.1:{port}"
            result = request_drain(url, args.drain)
            print(json.dumps(result))
            return 0 if result.get("ok") else 1
        if args.watch_discovery:
            cfg.route.watch_discovery = True
        return route(cfg)

    if args.command == "fleetmon":
        # Control-plane sensor, same host-isolation contract as the
        # router: stdlib-only scraping, no parallel.initialize() — it
        # must keep reporting while the data plane is on fire.
        from tpu_resnet.obs.fleet import fleetmon
        return fleetmon(cfg)

    if args.command == "autopilot":
        # The autoscaling control plane shares the host-isolation
        # contract: it must keep steering the fleet while the
        # accelerator stack is the thing that is melting, so no
        # parallel.initialize() — only its CHILD serve processes may
        # touch jax.
        from tpu_resnet.autopilot.cli import autopilot as autopilot_fn
        return autopilot_fn(cfg)

    if args.command == "inspect":
        from tpu_resnet.tools.inspect_ckpt import main as inspect_main
        inspect_main(args.dir, step=args.step, peek=args.peek)
        return 0

    if args.command == "plot":
        from tpu_resnet.tools.plot_metrics import plot
        out = plot(args.dir, out=args.out, csv_out=args.csv)
        print(f"wrote {out}")
        return 0

    parser.error(f"unknown command {args.command}")


if __name__ == "__main__":
    sys.exit(main())
