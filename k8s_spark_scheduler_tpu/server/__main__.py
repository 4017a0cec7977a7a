"""CLI entry point (reference main.go + cmd/root.go + cmd/server.go).

    python -m k8s_spark_scheduler_tpu.server [--port P] [--config FILE]
    python -m k8s_spark_scheduler_tpu.server --version
    python -m k8s_spark_scheduler_tpu.server --webhook-only [--port P]

``--config`` takes a JSON file in the reference's install.yml shape
(config/config.go keys).  ``--webhook-only`` serves just the CRD
conversion webhook, mirroring the standalone
spark-scheduler-conversion-webhook module.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import logging
import signal
import sys

from .. import __version__
from ..config import Install
from ..kube.apiserver import APIServer
from ..utils.compilecache import configure_compile_cache
from .http import ExtenderHTTPServer
from .wiring import init_server_with_clients


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="tpu-gang-scheduler")
    parser.add_argument("--version", action="store_true", help="print version and exit")
    parser.add_argument("--port", type=int, default=8080)
    parser.add_argument("--host", type=str, default="", help="bind address (default: all interfaces)")
    parser.add_argument("--config", type=str, default=None, help="install config JSON file")
    parser.add_argument(
        "--webhook-only",
        action="store_true",
        help="serve only the CRD conversion webhook (standalone module)",
    )
    # backend selection (reference cmd/clients.go:37-44: kubeconfig path
    # or in-cluster config; default here is the embedded store for
    # single-process runs and demos)
    parser.add_argument(
        "--kubeconfig",
        type=str,
        default=None,
        help="connect to the cluster in this kubeconfig (real-cluster mode)",
    )
    parser.add_argument(
        "--kube-context",
        type=str,
        default=None,
        help="kubeconfig context override",
    )
    parser.add_argument(
        "--in-cluster",
        action="store_true",
        help="use the pod service account to reach the API server",
    )
    # HTTPS serving: required for the CRD conversion webhook on a real
    # cluster (the apiserver only dials webhooks over TLS) and supported
    # by kube-scheduler's extender tlsConfig
    parser.add_argument("--tls-cert", type=str, default=None, help="PEM server certificate")
    parser.add_argument("--tls-key", type=str, default=None, help="PEM server private key")
    args = parser.parse_args(argv)
    if bool(args.tls_cert) != bool(args.tls_key):
        print("--tls-cert and --tls-key must be given together", file=sys.stderr)
        return 2

    if args.version:
        print(__version__)
        return 0

    class _JsonFormatter(logging.Formatter):
        def format(self, record):
            return json.dumps(
                {
                    "time": self.formatTime(record),
                    "level": record.levelname,
                    "logger": record.name,
                    "message": record.getMessage(),
                }
            )

    handler = logging.StreamHandler()
    handler.setFormatter(_JsonFormatter())
    logging.basicConfig(level=logging.INFO, handlers=[handler])
    # stacktrace-on-signal, as the reference registers in main.go:24-27
    faulthandler.register(signal.SIGUSR1, all_threads=True)

    # Python-level handlers (run in the main thread no matter which
    # thread receives the signal) so SIGTERM reliably takes the
    # graceful-stop path; a SECOND signal restores the default
    # disposition and re-raises, so a wedged shutdown can still be
    # terminated without SIGKILL
    import os
    import threading

    stop_event = threading.Event()

    def _on_signal(signum, frame):
        if stop_event.is_set():
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)
            return
        stop_event.set()

    signal.signal(signal.SIGINT, _on_signal)
    signal.signal(signal.SIGTERM, _on_signal)

    if args.webhook_only:
        http = ExtenderHTTPServer(
            None,
            port=args.port,
            webhook_only=True,
            host=args.host,
            tls_cert_file=args.tls_cert,
            tls_key_file=args.tls_key,
        )
        http.start()
        scheme = "https" if http.tls else "http"
        print(f"conversion webhook serving on :{http.port} ({scheme})", flush=True)
        stop_event.wait()
        http.stop()
        return 0

    install = Install()
    if args.config:
        with open(args.config) as f:
            raw = f.read()
        if args.config.endswith((".yml", ".yaml")):
            # the reference's install.yml shape (config/config.go);
            # pyyaml ships as the optional [yaml] extra
            try:
                import yaml
            except ImportError:
                print(
                    "YAML configs need pyyaml (pip install 'tpu-gang-scheduler[yaml]') "
                    "or use a JSON config",
                    file=sys.stderr,
                )
                return 2
            install = Install.from_dict(yaml.safe_load(raw) or {})
        else:
            install = Install.from_dict(json.loads(raw))

    if args.in_cluster or args.kubeconfig:
        # install.qps/burst are applied by the wiring's shared write-back
        # token bucket (clients.go:53-54 analog); the REST client's own
        # bucket stays off so the limit isn't double-counted
        from ..kube.restbackend import RestAPIServer
        from ..kube.restclient import in_cluster_config, load_kubeconfig

        if args.in_cluster:
            cluster = in_cluster_config()
        else:
            cluster = load_kubeconfig(args.kubeconfig, args.kube_context)
        api = RestAPIServer(cluster)
        backend_desc = f"kubernetes {cluster.host}"
    else:
        api = APIServer()
        backend_desc = "embedded"
    cache_dir = configure_compile_cache()
    scheduler = init_server_with_clients(api, install)
    http = ExtenderHTTPServer(
        scheduler,
        port=args.port,
        host=args.host,
        tls_cert_file=args.tls_cert,
        tls_key_file=args.tls_key,
    )
    http.start()
    print(
        f"extender serving on :{http.port} "
        f"(binpack={install.binpack_algo}, backend={backend_desc}, "
        f"tls={'on' if http.tls else 'off'}, compile-cache={cache_dir})",
        flush=True,
    )
    rc = 0
    try:
        # a policy whose kernels this platform cannot compile must not
        # linger unready behind a log line: the process fails
        while not scheduler.warmup_complete() and not stop_event.wait(0.2):
            if scheduler.warmup_error is not None:
                print(
                    f"solver warmup failed: {scheduler.warmup_error!r}",
                    file=sys.stderr,
                    flush=True,
                )
                rc = 1
                break
        if rc == 0:
            stop_event.wait()
    finally:
        http.stop()
        scheduler.stop()
        if hasattr(api, "close"):
            api.close()
    return rc


if __name__ == "__main__":
    sys.exit(main())
