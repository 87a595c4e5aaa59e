"""`cyclonus-tpu serve`: the long-running verdict service
(cyclonus_tpu/serve; docs/DESIGN.md "Verdict service").

Boot a cluster (policies from YAML plus a synthesized or synthetic pod
set), then answer a JSON-lines stream of Batch envelopes on stdin —
Deltas apply incrementally to the live device-resident encoding,
Queries answer from it — one reply object per line, until EOF.  With
--metrics-port, /state and /query make the engine curl-able alongside
/metrics."""

from __future__ import annotations

import sys


def setup_serve(sub) -> None:
    cmd = sub.add_parser(
        "serve",
        help="run the persistent verdict service: stream deltas/queries "
        "over stdin/stdout (worker wire Batch envelopes), with "
        "incremental encode of the live engine",
    )
    cmd.add_argument(
        "--policies",
        default="",
        metavar="PATH",
        help="YAML file/dir of NetworkPolicies for the initial state "
        "(default: start with no policies)",
    )
    cmd.add_argument(
        "--anps",
        default="",
        metavar="PATH",
        help="YAML file/dir of AdminNetworkPolicy / "
        "BaselineAdminNetworkPolicy objects layered over --policies "
        "(docs/DESIGN.md \"Precedence tiers\")",
    )
    cmd.add_argument(
        "--synthesize-pods",
        action="store_true",
        help="synthesize an initial pod set exercising every policy-"
        "referenced shape (analysis.synthesize_cluster) instead of "
        "starting pod-less",
    )
    cmd.add_argument(
        "--synthetic-pods",
        type=int,
        default=0,
        metavar="N",
        help="start with N synthetic pods across --synthetic-namespaces "
        "namespaces (seeded; for benchmarks and smoke tests)",
    )
    cmd.add_argument(
        "--synthetic-namespaces",
        type=int,
        default=4,
        metavar="M",
        help="namespace count for --synthetic-pods (default 4)",
    )
    cmd.add_argument(
        "--seed", type=int, default=7, help="synthetic-cluster seed"
    )
    cmd.add_argument(
        "--no-simplify",
        action="store_true",
        help="compile policies without matcher simplification",
    )
    cmd.add_argument(
        "--class-compress",
        default="",
        choices=["", "auto", "1", "0"],
        help="override CYCLONUS_CLASS_COMPRESS for the serving engine",
    )
    cmd.add_argument(
        "--metrics-port",
        type=int,
        default=None,
        metavar="PORT",
        help="serve /metrics plus the serve-specific /state and /query "
        "on 127.0.0.1:PORT (0 = ephemeral; bound port printed)",
    )
    cmd.add_argument(
        "--max-lines",
        type=int,
        default=None,
        metavar="N",
        help="exit after N input lines (smoke tests)",
    )
    cmd.add_argument(
        "--no-prewarm",
        action="store_true",
        help="skip the startup prewarm (compile the query-path bucket "
        "set lazily on first use instead; /readyz reports ready "
        "immediately).  CYCLONUS_SERVE_PREWARM=0 is the env twin.",
    )
    cmd.set_defaults(func=run_serve)


def run_serve(args) -> int:
    from ..kube.yaml_io import load_policies_from_path
    from ..serve import VerdictService, run_stdio
    from ..serve.service import register_http
    from ..synthetic import synthetic_cluster
    from ..telemetry.server import MetricsPortBusy, start_metrics_server

    policies = (
        load_policies_from_path(args.policies) if args.policies else []
    )
    tiers = None
    if args.anps:
        from ..tiers.model import load_tier_set_from_path

        tiers = load_tier_set_from_path(args.anps) or None
    pods, namespaces = [], {}
    if args.synthetic_pods:
        pods, namespaces = synthetic_cluster(
            args.synthetic_pods, args.synthetic_namespaces, args.seed
        )
    elif args.synthesize_pods and policies:
        from ..analysis import synthesize_cluster
        from ..matcher.builder import build_network_policies

        compiled = build_network_policies(not args.no_simplify, policies)
        pods, namespaces = synthesize_cluster(compiled)
    for p in policies:
        namespaces.setdefault(p.effective_namespace(), {})
    from ..utils import envflags

    prewarm_on = (
        not args.no_prewarm and envflags.get_bool("CYCLONUS_SERVE_PREWARM")
    )
    service = VerdictService(
        pods,
        namespaces,
        policies,
        simplify=not args.no_simplify,
        class_compress=args.class_compress or None,
        tiers=tiers,
        defer_ready=prewarm_on,
    )
    if args.metrics_port is not None:
        try:
            srv = start_metrics_server(args.metrics_port)
        except MetricsPortBusy as e:
            raise SystemExit(f"error: {e}")
        register_http(service)
        # readiness rides /readyz from here on: while prewarm below is
        # still compiling, a router probing /readyz sees 503 warming
        # (and /query answers degraded from the scalar oracle);
        # /healthz stays pure liveness
        from ..telemetry.server import register_readiness

        register_readiness(service.readiness)
        print(
            f"serve: metrics on {srv.url}/metrics, state on "
            f"{srv.url}/state, queries on {srv.url}/query, readiness "
            f"on {srv.url}/readyz, slo on {srv.url}/slo, audit on "
            f"{srv.url}/audit (port {srv.port})",
            file=sys.stderr,
        )
    if prewarm_on:
        pw = service.prewarm()
        aot = pw.get("aot_cache") or {}
        print(
            f"serve: prewarmed {pw['programs']} pair buckets in "
            f"{pw['seconds']}s (aot adopted={aot.get('adopted')} "
            f"compiles={aot.get('compiles')})",
            file=sys.stderr,
        )
        if pw.get("error"):
            # a replica whose query programs cannot initialise, compile
            # or run is dead, not degraded: answering from the scalar
            # oracle is a stated behaviour WHILE warming, never a way
            # to look healthy without the device
            print(f"serve: prewarm failed: {pw['error']}", file=sys.stderr)
            return 1
    st = service.state()
    tier_note = ""
    if st["tiers"]["active"]:
        tier_note = (
            f", {st['tiers']['anp_count']} ANPs"
            f"{' + BANP' if st['tiers']['banp'] else ''}"
        )
    audit_note = ""
    if service.audit is not None:
        audit_note = (
            f", audit armed (rate {service.audit.rate:g}, "
            f"seed {service.audit.seed})"
        )
    from ..engine import device_identity

    dev = device_identity()
    print(
        f"serve: engine ready on {dev['platform']} "
        f"({dev['kind']} x{dev['count']}) — {st['pods']} pods, "
        f"{st['policies']} policies{tier_note} (epoch {st['epoch']})"
        f"{audit_note}; reading batches from stdin",
        file=sys.stderr,
    )
    run_stdio(service, sys.stdin, sys.stdout, max_lines=args.max_lines)
    return 0
