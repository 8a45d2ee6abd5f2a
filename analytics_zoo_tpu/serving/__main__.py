"""``python -m analytics_zoo_tpu.serving config.yaml`` — the
``cluster-serving-start`` entry point (ref: scripts/cluster-serving/
cluster-serving-start reading config.yaml): parse the config, load the
model artifact it names, start the serving loop, block until SIGINT.

``--embedded-broker`` runs the bundled RESP broker in-process (local/
single-box deployments); without it the config's redis host:port must
already be running.

Engine modes come from the config's ``params`` block (see
ServingConfig): ``engine_paged`` / ``engine_chunked`` /
``engine_speculation_k`` compose freely on a draft-loaded model —
paged blocks, budgeted prefill chunks, and draft-verify decoding are
one scheduler, not three exclusive engines (docs/serving_memory.md
'Composed modes').
"""

import argparse
import signal
import sys
import threading


def main(argv=None, block=True):
    ap = argparse.ArgumentParser(
        prog="python -m analytics_zoo_tpu.serving",
        description="Start a Cluster Serving job from a config.yaml")
    ap.add_argument("config", help="path to config.yaml")
    ap.add_argument("--embedded-broker", action="store_true",
                    help="run the bundled RESP broker in-process")
    ap.add_argument("--http-port", type=int, default=None,
                    help="also start the HTTP frontend (ref: "
                         "FrontEndApp) on this port (0 = an ephemeral "
                         "port, printed in the banner)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="on shutdown, dump the telemetry event ring "
                         "as Chrome trace-event JSON to PATH (load at "
                         "https://ui.perfetto.dev); the same data is "
                         "live at GET /trace while serving")
    args = ap.parse_args(argv)

    from analytics_zoo_tpu.common.compile_cache import enable_compile_cache
    from analytics_zoo_tpu.serving import ClusterServing

    enable_compile_cache()      # before the model load's first compile

    # default signal behavior DURING assembly (a hung model load or
    # broker connect must stay killable with Ctrl-C/SIGTERM); graceful
    # handlers go in after start() but BEFORE the banner, so a
    # supervisor signalling the instant it sees the banner still gets a
    # clean shutdown rather than the SIGTERM default
    serving = ClusterServing.from_config(
        args.config, embedded_broker=args.embedded_broker).start()
    frontend = None
    if args.http_port is not None:
        from analytics_zoo_tpu.serving import HttpFrontend

        try:
            frontend = HttpFrontend(
                redis_host=serving.config.redis_host,
                redis_port=serving.port, http_port=args.http_port,
                serving=serving).start()
        except BaseException:
            # a bind failure (port in use) must not abandon the already-
            # started serving loop / broker / decode pool
            serving.stop()
            raise
    stop = threading.Event()
    banner = (f"serving up on {serving.config.redis_host}:"
              f"{serving.port}"
              + (f", http on :{frontend.port}" if frontend else "")
              + " (Ctrl-C to stop)")

    def shutdown():
        if frontend is not None:
            frontend.stop()
        serving.stop()
        if args.trace:
            serving.telemetry.dump_trace(args.trace)
            print(f"trace written to {args.trace}", flush=True)

    if not block:       # tests drive the assembled stack directly
        return serving, frontend, shutdown
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    print(banner, flush=True)
    stop.wait()
    shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
