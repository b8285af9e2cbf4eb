"""Single CLI entry: ``python -m keystone_tpu <AppName> [app args...]``.

Reference: bin/run-pipeline.sh selects the pipeline class by fully
qualified name as argv[1]; here short app names map to the app modules'
``main``.
"""

from __future__ import annotations

import sys

APPS = {
    "MnistRandomFFT": "keystone_tpu.pipelines.images.mnist_random_fft",
    "RandomPatchCifar": "keystone_tpu.pipelines.images.random_patch_cifar",
    "ImageNetSiftLcsFV": "keystone_tpu.pipelines.images.imagenet_sift_lcs_fv",
    "VOCSIFTFisher": "keystone_tpu.pipelines.images.voc_sift_fisher",
    "TimitPipeline": "keystone_tpu.pipelines.speech.timit",
    "NewsgroupsPipeline": "keystone_tpu.pipelines.text.newsgroups",
    "AmazonReviewsPipeline": "keystone_tpu.pipelines.text.amazon_reviews",
    "StupidBackoffPipeline": "keystone_tpu.pipelines.nlp.stupid_backoff_pipeline",
}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--admin-port" in argv:
        # observability plane: /metrics (Prometheus), /varz, /healthz,
        # /tracez on a background thread, span tracing enabled so
        # executor/serving spans land in /tracez. Peeled before app
        # dispatch so EVERY app is scrapeable.
        i = argv.index("--admin-port")
        try:
            port = int(argv[i + 1])
        except (IndexError, ValueError):
            print("--admin-port requires an integer port (0 = ephemeral)")
            return 2
        del argv[i : i + 2]
        from keystone_tpu.observability import (
            enable_tracing,
            start_admin_server,
        )

        enable_tracing()
        server = start_admin_server(port=port)
        print(f"admin endpoint: {server.url()} "
              "(/metrics /varz /healthz /tracez /profilez)", flush=True)
    if "--otlp-endpoint" in argv:
        # OTLP/HTTP span export: every finished span batches to a
        # collector's /v1/traces on a background thread (stdlib urllib,
        # nothing to install). Peeled before app dispatch like
        # --admin-port; implies tracing on.
        i = argv.index("--otlp-endpoint")
        try:
            endpoint = argv[i + 1]
            if endpoint.startswith("-"):
                raise ValueError(endpoint)
        except (IndexError, ValueError):
            print("--otlp-endpoint requires a collector URL "
                  "(e.g. http://127.0.0.1:4318)")
            return 2
        del argv[i : i + 2]

        def peel_value(flag, default):
            if flag not in argv:
                return default
            j = argv.index(flag)
            try:
                value = argv[j + 1]
                if value.startswith("-"):
                    raise ValueError(value)
            except (IndexError, ValueError):
                raise SystemExit(f"{flag} requires a value") from None
            del argv[j : j + 2]
            return value

        import os
        import socket

        # resource identity: which SERVICE (router vs gateway vs app)
        # and which REPLICA this process is — what lets an external
        # collector lay the fleet's halves of one trace out as the
        # same topology the router's stitched /debugz shows. The app
        # name is a sensible service default; cross-host fleets pass
        # --otlp-replica the advertised host:port.
        default_service = (
            f"keystone-{argv[0].removeprefix('serve-')}"
            if argv and not argv[0].startswith("-")
            else "keystone-tpu"
        )
        service = peel_value("--otlp-service", default_service)
        replica = peel_value(
            "--otlp-replica", f"{socket.gethostname()}:{os.getpid()}"
        )
        from keystone_tpu.observability import (
            OtlpSpanExporter,
            enable_tracing,
        )

        enable_tracing()
        exporter = OtlpSpanExporter(
            endpoint,
            service_name=service,
            resource_attrs={"replica": replica},
        )
        exporter.install()
        print(
            f"otlp export: {exporter.endpoint} "
            f"(service.name={service} replica={replica})",
            flush=True,
        )
    gateway_port = None
    if "--gateway-port" in argv:
        # request plane: admission control + replica lanes + live
        # engine swap in front of a compiled pipeline, HTTP /predict
        # frontend (keystone_tpu/gateway/). Peeled here so
        # `python -m keystone_tpu --gateway-port N` alone stands up the
        # serve-gateway demo (serving/demo_model.py); with an explicit
        # serve-gateway app the port just rides along.
        i = argv.index("--gateway-port")
        try:
            gateway_port = int(argv[i + 1])
        except (IndexError, ValueError):
            print("--gateway-port requires an integer port (0 = ephemeral)")
            return 2
        del argv[i : i + 2]
        if not argv or argv[0].startswith("-"):
            # no app named: everything left is serve-gateway options
            argv = ["serve-gateway"] + argv
        if argv[0] != "serve-gateway":
            print("--gateway-port only applies to the serve-gateway app")
            return 2
    if "--debug-optimizer" in argv:
        # Per-rule optimizer trace: node-count deltas at INFO, full DOT
        # graphs after each effective rule at DEBUG (reference logs DOT on
        # every rule application, RuleExecutor.scala:44-50).
        argv.remove("--debug-optimizer")
        import logging

        logging.basicConfig()
        for mod in ("keystone_tpu.workflow.rules",
                    "keystone_tpu.workflow.auto_cache",
                    "keystone_tpu.workflow.node_optimization"):
            logging.getLogger(mod).setLevel(logging.DEBUG)
    if not argv or argv[0] in ("-h", "--help"):
        print(
            "usage: python -m keystone_tpu [--debug-optimizer] "
            "[--admin-port N] [--gateway-port N] [--otlp-endpoint URL] "
            "<AppName> [app args...]"
        )
        print("apps:")
        for name in sorted(APPS):
            print(f"  {name}")
        print("  serve-gateway  (HTTP request plane over the demo "
              "model; keystone_tpu/gateway/. --shard-model serves "
              "the model mesh-sharded over the local devices — "
              "keystone_tpu/serving/sharding.py)")
        print("  serve-router  (fleet tier: cross-host router over N "
              "serve-gateway replicas — replica registry with "
              "--replica URLs + POST /registerz self-registration, "
              "background health probes with half-open recovery, "
              "least-loaded routing with retry-on-another-replica, "
              "federated /metrics + /slz over the replicas' scraped "
              "le buckets, /fleetz roster; keystone_tpu/fleet/)")
        print("  serve-loadgen  (trace-driven open-loop load generator "
              "+ chaos harness against a live gateway; replays "
              "--request-log recordings or synthesizes Poisson/heavy-"
              "tail arrivals, arms fault points mid-run via /chaosz, "
              "and exits nonzero unless the serving invariants held; "
              "keystone_tpu/loadgen/)")
        print("  serve-autoscale  (autonomous fleet elasticity: an "
              "in-process fleet router + a supervisor spawning "
              "serve-gateway replicas as subprocesses + an SLO-driven "
              "control loop — scrapes the router's federated /metrics "
              "+ /slz, scales out when queue_wait-dominated latency "
              "burns the SLO, replaces kill -9'd replicas, and "
              "drain-retires idle ones; every decision is a JSON "
              "event, keystone_autoscale_* series, and a trace span; "
              "keystone_tpu/autoscale/)")
        print("  serve-capacity-plan  (replay a recorded --request-log "
              "peak x1..xN against 1..K supervised replicas, fit the "
              "replicas-vs-offered-load curve, and write the JSON "
              "plan artifact serve-autoscale --plan loads — the "
              "policy thresholds are measured, not guessed; "
              "keystone_tpu/autoscale/planner.py)")
        print("  serve-lifecycle  (operator controls for a gateway's "
              "online model lifecycle — status/tick/rollback against "
              "a serve-gateway --refit frontend's /lifecyclez: "
              "streaming refit from POST /feedback, shadow-mirrored "
              "candidates, deterministic canary fractions, atomic "
              "promote with auto-rollback; keystone_tpu/lifecycle/)")
        print("  serve-aot-build  (pre-populate the AOT serialized-"
              "executable store: compile every bucket once and "
              "serialize the executables so a brand-new host's "
              "serve-gateway goes from exec() to serving with zero "
              "XLA compiles; keystone_tpu/serving/aot.py)")
        print("  keystone-lint  (AST contract analyzer over this "
              "repo's own source: lock discipline, blocking-under-"
              "lock, strippable asserts, absent-not-zero metrics, "
              "hot-path host syncs, fault-point catalog drift; "
              "nonzero exit on unbaselined findings — the CI gate; "
              "keystone_tpu/analysis/)")
        print("options:")
        print("  --gateway-port N shorthand for `serve-gateway "
              "--gateway-port N`: admission-")
        print("                   controlled HTTP inference frontend "
              "(POST /predict, GET /readyz,")
        print("                   POST /swap) with N replica lanes and "
              "live re-bucketing. Lanes")
        print("                   run as staged pipelines — host-prep/"
              "upload/compute of")
        print("                   consecutive windows overlap "
              "(--pipeline-depth 0 reverts to")
        print("                   serial dispatch). N=0 picks an "
              "ephemeral port.")
        print("  --admin-port N   serve metrics on http://127.0.0.1:N —"
              " /metrics (Prometheus")
        print("                   text exposition of every live engine's"
              " compile/dispatch/latency")
        print("                   counters), /varz (JSON + build info),"
              " /healthz, /tracez (recent")
        print("                   spans; add ?format=chrome for a"
              " Perfetto/chrome://tracing trace),")
        print("                   /slz (SLO burn rates), /debugz (flight"
              " recorder), /profilez")
        print("                   (on-demand jax.profiler capture of"
              " ?seconds=N of live traffic).")
        print("                   N=0 picks an ephemeral port. Off by"
              " default — zero overhead when")
        print("                   absent.")
        print("  --otlp-endpoint URL  export spans to an OTLP/HTTP"
              " collector (POST")
        print("                   URL/v1/traces, background batching,"
              " stdlib-only). Implies")
        print("                   tracing on. Off by default."
              " --otlp-service NAME and")
        print("                   --otlp-replica HOST:PORT stamp the"
              " service.name/replica")
        print("                   resource attrs (defaults: the app"
              " name, hostname:pid) so an")
        print("                   external collector sees the fleet's"
              " stitched topology.")
        return 0 if argv else 2
    app = argv[0]
    if app == "serve-gateway":
        from keystone_tpu.gateway.http import main as serve_gateway_main

        rest = argv[1:]
        if gateway_port is not None:
            rest = ["--gateway-port", str(gateway_port)] + rest
        return serve_gateway_main(rest)
    if app == "serve-router":
        from keystone_tpu.fleet.router import main as serve_router_main

        return serve_router_main(argv[1:])
    if app == "serve-loadgen":
        from keystone_tpu.loadgen.cli import main as serve_loadgen_main

        return serve_loadgen_main(argv[1:])
    if app == "serve-autoscale":
        from keystone_tpu.autoscale.cli import main as serve_autoscale_main

        return serve_autoscale_main(argv[1:])
    if app == "serve-capacity-plan":
        from keystone_tpu.autoscale.planner import main as capacity_plan_main

        return capacity_plan_main(argv[1:])
    if app == "serve-lifecycle":
        # stdlib-only HTTP client: no jax import for operator controls
        from keystone_tpu.lifecycle.cli import main as lifecycle_main

        return lifecycle_main(argv[1:])
    if app == "serve-aot-build":
        from keystone_tpu.serving.aot import build_main

        return build_main(argv[1:])
    if app == "keystone-lint":
        # stdlib-only path by design: the linter must run in hooks and
        # CI without paying the jax import (analysis/ never imports it)
        from keystone_tpu.analysis.cli import main as lint_main

        return lint_main(argv[1:])
    if app not in APPS:
        print(f"unknown app {app!r}; run with --help for the list")
        return 2
    import importlib

    # join the multi-host runtime when the environment says this is one
    # process of several; a single host starts nothing and touches no
    # network (see parallel/runtime.py)
    from keystone_tpu.parallel.runtime import initialize

    initialize()
    module = importlib.import_module(APPS[app])
    return module.main(argv[1:])


if __name__ == "__main__":
    raise SystemExit(main())
