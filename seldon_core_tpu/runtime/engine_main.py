"""Engine process entrypoint — the reference's engine pod boot re-designed.

Config resolution order mirrors ``EnginePredictor.init()`` (engine
EnginePredictor.java:56-150):

  1. ``ENGINE_PREDICTOR``          base64(JSON PredictorSpec)
  2. ``ENGINE_SELDON_DEPLOYMENT``  base64(JSON SeldonDeployment) [+ name]
  3. ``./deploymentdef.json``      file fallback
  4. default SIMPLE_MODEL stub graph (the reference's in-engine test stub)

Ports: ``ENGINE_SERVER_PORT`` (default 8000) REST,
``ENGINE_SERVER_GRPC_PORT`` (default 5001) gRPC — the ports the reference
operator wires into every engine container
(cluster-manager SeldonDeploymentOperatorImpl.java:98-144).

Serving-mesh extensions:

* ``ENGINE_GRAPH_NODE`` / ``--node NAME`` — serve ONE node of the loaded
  deployment's graph as a standalone engine (graph/sharding.py
  node_subspec): the pod-per-node topology; the root engine dispatches
  to it over ``POST /predict``.
* ``ENGINE_UDS_PATH`` / ``--uds-path`` — additionally bind the zero-copy
  length-prefixed relay lane on a unix socket (runtime/udsrelay.py) for
  a co-located gateway.  ``SELDON_TPU_UDS=0`` skips the bind.
* ``ENGINE_HTTP_UDS_PATH`` / ``--http-uds-path`` — additionally serve
  the FULL HTTP route table on a unix socket (httpfast.py fast lane) so
  a co-located root engine can dial this node engine with a ``unix:``
  binding (runtime/client.py UnixConnector).  Distinct from the framed
  relay above: this one speaks HTTP.

    python -m seldon_core_tpu.runtime.engine_main [--file deployment.json]
"""

from __future__ import annotations

import argparse
import asyncio
import base64
import json
import logging
import os
import sys
import time
from typing import Optional

from seldon_core_tpu.graph.defaulting import default_and_validate
from seldon_core_tpu.graph.spec import (
    PredictorSpec,
    SeldonDeploymentSpec,
)

__all__ = ["load_deployment_from_env", "main"]

DEFAULT_GRAPH = {
    "spec": {
        "name": "default",
        "predictors": [
            {
                "name": "default",
                "graph": {
                    "name": "simple-model",
                    "implementation": "SIMPLE_MODEL",
                    "type": "MODEL",
                },
            }
        ],
    }
}


def load_deployment_from_env(
    file_path: Optional[str] = None,
) -> SeldonDeploymentSpec:
    raw = os.environ.get("ENGINE_PREDICTOR")
    if raw:
        predictor = json.loads(base64.b64decode(raw))
        spec = SeldonDeploymentSpec(
            name=os.environ.get("SELDON_DEPLOYMENT_ID", "engine"),
            predictors=[PredictorSpec.from_json_dict(predictor)],
        )
        return default_and_validate(spec)
    raw = os.environ.get("ENGINE_SELDON_DEPLOYMENT")
    if raw:
        spec = SeldonDeploymentSpec.from_json(base64.b64decode(raw))
        return default_and_validate(spec)
    path = file_path or "./deploymentdef.json"
    if os.path.exists(path):
        with open(path) as f:
            return default_and_validate(SeldonDeploymentSpec.from_json(f.read()))
    return default_and_validate(SeldonDeploymentSpec.from_json_dict(DEFAULT_GRAPH))


async def serve(deployment: SeldonDeploymentSpec, predictor_name=None,
                host="0.0.0.0", rest_port=None, grpc_port=None,
                uds_path=None, http_uds_path=None, gen_role=None,
                decode_peers=None, relay_tcp_port=None) -> None:
    from seldon_core_tpu.runtime.engine import EngineService
    from seldon_core_tpu.runtime.grpc_server import make_engine_grpc_server
    from seldon_core_tpu.runtime.rest import make_engine_app, serve_app
    from seldon_core_tpu.utils.genperf import BOOT

    rest_port = rest_port or int(os.environ.get("ENGINE_SERVER_PORT", "8000"))
    grpc_port = grpc_port or int(os.environ.get("ENGINE_SERVER_GRPC_PORT", "5001"))
    # batching knobs, part of the engine env contract the operator renders
    # (the reference's engine JVM opts role, SeldonDeploymentOperatorImpl)
    engine = EngineService(
        deployment,
        predictor_name,
        max_batch=int(os.environ.get("ENGINE_MAX_BATCH", "1024")),
        max_wait_ms=float(os.environ.get("ENGINE_BATCH_WAIT_MS", "2.0")),
        pipeline_depth=int(os.environ.get("ENGINE_PIPELINE_DEPTH", "8")),
        # large models (100M+-param generators) compile for minutes on a
        # cold cache; the per-dispatch 504 budget must cover that first
        # trace when prewarm is skipped
        dispatch_timeout_s=float(
            os.environ.get("ENGINE_DISPATCH_TIMEOUT_S", "30")
        ),
        # disaggregated serving mesh (runtime/servingmesh.py): this
        # replica's generation role and, for prefill replicas, the
        # decode peers it streams finished KV blocks to
        gen_role=gen_role,
        decode_peers=decode_peers,
    )
    # boot-time shape compilation: ENGINE_PREWARM_WIDTHS="784,16" compiles
    # every batch bucket of those feature widths before the server binds,
    # so live traffic never waits on an XLA compile (engine.prewarm)
    prewarm_raw = os.environ.get("ENGINE_PREWARM_WIDTHS", "")
    if prewarm_raw.strip():
        widths = [int(w) for w in prewarm_raw.split(",") if w.strip()]
        t0 = time.monotonic()
        n = engine.prewarm(widths)
        BOOT.span("prewarm", None, t0, time.monotonic(), engine._boot_owner)
        print(
            f"prewarmed {n} batch shapes for widths {widths} "
            f"in {time.monotonic() - t0:.1f}s",
            flush=True,
        )
    t_listen = time.monotonic()
    # data plane, fastest eligible lane first:
    #   native (C++ HTTP termination + batching, runtime/nativeplane.py)
    #   fast   (asyncio.Protocol, runtime/httpfast.py)
    #   aiohttp (full framework app, runtime/rest.py)
    # ENGINE_HTTP_IMPL picks explicitly.  Under the default the lane is
    # chosen from the GRAPH by a stated rule (native_ineligible_reason):
    # an ineligible graph — a generator, whose streaming and GenLane
    # scheduler live on the fast lane; a stateful or tag-emitting graph —
    # is announced on the fast lane; on an eligible graph a plane that
    # fails to build, load or bind is FATAL, never a quiet lane change
    http_impl = os.environ.get("ENGINE_HTTP_IMPL", "native").strip().lower()
    if http_impl not in ("native", "fast", "aiohttp"):
        # never boot with NO data plane: unknown names get the most
        # compatible lane plus a loud line in the pod log
        print(f"unknown ENGINE_HTTP_IMPL={http_impl!r}; serving aiohttp",
              flush=True)
        http_impl = "aiohttp"
    # gRPC lane selection: native (C++ HTTP/2 in the same plane), fast
    # (runtime/grpcfast.py asyncio lane), aio (stock grpc.aio server).
    # Default rides the native plane when the HTTP lane does.
    grpc_impl = os.environ.get("ENGINE_GRPC_IMPL", "").strip().lower()
    if grpc_impl not in ("", "native", "fast", "aio"):
        print(f"unknown ENGINE_GRPC_IMPL={grpc_impl!r}; serving fast lane",
              flush=True)
        grpc_impl = "fast"
    native_plane = None
    fast_server = None
    runner = None
    if http_impl == "native":
        from seldon_core_tpu.runtime.nativeplane import (
            native_ineligible_reason,
            serve_native,
        )

        reason = native_ineligible_reason(engine)
        if reason is not None:
            print(f"http lane: fast — {reason}", flush=True)
            http_impl = "fast"
        else:
            # the C++ listener binds a single address; 0.0.0.0 maps to ANY
            native_plane = await serve_native(
                engine, host if host != "0.0.0.0" else "", rest_port,
                grpc_port=(grpc_port if grpc_impl in ("", "native")
                           else None),
            )
    if http_impl == "fast":
        from seldon_core_tpu.runtime.httpfast import serve_fast

        fast_server = await serve_fast(engine, host, rest_port)
    elif http_impl == "aiohttp":
        runner = await serve_app(make_engine_app(engine), host, rest_port)
    if grpc_impl in ("", "native"):
        # the native gRPC lane exists only inside the native plane
        grpc_impl = "native" if native_plane is not None else "fast"
    if grpc_impl == "native":
        async def grpc_stop():
            pass  # stopped with the shared native plane below
    elif grpc_impl == "fast":
        from seldon_core_tpu.runtime.grpcfast import serve_grpc_fast

        grpc_server = await serve_grpc_fast(engine, host, grpc_port)
        grpc_stop = grpc_server.stop
    else:
        grpc_server = make_engine_grpc_server(engine, host, grpc_port)
        await grpc_server.start()

        async def grpc_stop():
            await grpc_server.stop(grace=5.0)
    # zero-copy relay lane for a co-located gateway (runtime/udsrelay.py);
    # rides ALONGSIDE the TCP lanes — /stats scrape + SSE stay on TCP
    uds_server = None
    uds_path = uds_path or os.environ.get("ENGINE_UDS_PATH", "").strip()
    if uds_path and os.environ.get("SELDON_TPU_UDS", "1") != "0":
        from seldon_core_tpu.runtime.udsrelay import serve_uds

        uds_server = await serve_uds(engine, uds_path)
    # the framed relay on a TCP port: the cross-host lane decode
    # replicas receive KV-block handoffs on (runtime/kvstream.py)
    relay_tcp_server = None
    relay_tcp_port = relay_tcp_port if relay_tcp_port is not None else int(
        os.environ.get("ENGINE_RELAY_TCP_PORT", "0") or 0)
    if relay_tcp_port:
        from seldon_core_tpu.runtime.udsrelay import serve_relay_tcp

        relay_tcp_server = await serve_relay_tcp(
            engine, host if host != "0.0.0.0" else "0.0.0.0",
            relay_tcp_port,
        )
    # HTTP face on a unix socket: the node-mesh lane a sharded root's
    # `unix:` binding dials (runtime/client.py).  Bound regardless of the
    # main HTTP lane's impl — the native plane can't listen on a UDS
    http_uds_server = None
    http_uds_path = http_uds_path or \
        os.environ.get("ENGINE_HTTP_UDS_PATH", "").strip()
    if http_uds_path and os.environ.get("SELDON_TPU_UDS", "1") != "0":
        from seldon_core_tpu.runtime.httpfast import FastHttpServer

        http_uds_server = FastHttpServer(engine)
        await http_uds_server.start_uds(http_uds_path)
    kernels = sorted({
        k for u in (engine.compiled.units.values()
                    if engine.compiled is not None else ())
        for k in u.kernels
    })
    print(
        f"engine up: predictor={engine.predictor.name} mode={engine.mode} "
        f"rest=:{rest_port} grpc=:{grpc_port} "
        # the lanes and kernels actually serving — chip_smoke.py and
        # bench/ read these instead of inferring the path
        f"http={http_impl} grpc-lane={grpc_impl} "
        f"kernels={','.join(kernels) or 'none'}"
        + (f" uds={uds_path}" if uds_server is not None else "")
        + (f" http-uds={http_uds_path}"
           if http_uds_server is not None else "")
        + (f" relay-tcp=:{relay_tcp_server.port}"
           if relay_tcp_server is not None else "")
        + (f" gen-role={engine.gen_role}"
           if engine.gen_role != "unified" else ""),
        flush=True,
    )
    BOOT.span("listen", None, t_listen, time.monotonic(), engine._boot_owner)

    # engine liveness lease: when a shared gateway state file and an
    # advertise URL are configured, heartbeat this replica's row (with
    # its boot_id epoch) so gateway balancers learn about a dead or
    # restarted engine within one lease TTL instead of waiting out
    # 3 failed scrapes (gateway/balancer.py ReplicaSet.apply_leases)
    lease_store = None
    advertise_url = os.environ.get("ENGINE_ADVERTISE_URL", "").strip()
    state_path = os.environ.get("GATEWAY_STATE_PATH", "").strip()
    heartbeat_task = None
    if advertise_url and state_path:
        from seldon_core_tpu.gateway.federation import lease_ttl_s
        from seldon_core_tpu.gateway.state import SqliteDeploymentStore

        lease_store = SqliteDeploymentStore(state_path)
        lease_ttl = lease_ttl_s()

        async def _heartbeat_loop():
            while True:
                try:
                    lease_store.heartbeat_engine(
                        advertise_url, engine.boot_id, lease_ttl)
                except Exception as e:  # noqa: BLE001 — a wedged store
                    # must not kill the engine; the lease just lapses
                    print(f"engine lease heartbeat failed: {e}", flush=True)
                await asyncio.sleep(max(lease_ttl / 3.0, 0.05))

        heartbeat_task = asyncio.get_running_loop().create_task(
            _heartbeat_loop())
        print(f"engine lease: heartbeating {advertise_url} "
              f"(ttl {lease_ttl:.1f}s) into {state_path}", flush=True)

    # graceful shutdown: SIGTERM/SIGINT flips readiness and drains before
    # exit — the reference's Tomcat drain (App.java:85-95, 20 s) + pre-stop
    # pause contract, built into the process itself
    import signal

    stop = asyncio.Event()
    hurry = asyncio.Event()  # second signal: skip the drain
    loop = asyncio.get_running_loop()

    def _on_signal():
        if stop.is_set():
            hurry.set()
        else:
            stop.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, _on_signal)
        except (NotImplementedError, RuntimeError):
            pass  # platforms without signal support: external kill only
    await stop.wait()
    drain_s = float(os.environ.get("ENGINE_SHUTDOWN_DRAIN_S", "20"))
    print(
        f"engine draining: up to {drain_s:.0f}s (readiness now 503; "
        f"signal again to skip)",
        flush=True,
    )
    engine.pause()  # /ready -> 503; the LB stops routing here
    if lease_store is not None:
        # deregister FIRST: balancers mark this replica dead (lease row
        # gone while it previously had one) before the drain even starts,
        # so no new work is routed at a draining engine
        if heartbeat_task is not None:
            heartbeat_task.cancel()
        try:
            lease_store.drop_engine(advertise_url)
        except Exception:  # noqa: BLE001 — best effort on the way out
            pass
    # poll-drain: exit the moment the last inflight request/sequence
    # finishes instead of always sleeping out the full window (a 20 s
    # fixed sleep was the old behavior — rolling restarts paid it even
    # on an idle engine)
    deadline = loop.time() + drain_s
    while loop.time() < deadline and not hurry.is_set():
        if engine.drained():
            print("engine drained early "
                  f"({drain_s - (deadline - loop.time()):.1f}s)", flush=True)
            break
        try:
            await asyncio.wait_for(
                hurry.wait(), min(0.1, max(deadline - loop.time(), 0.01)))
        except asyncio.TimeoutError:
            pass
    if hurry.is_set():
        print("drain skipped by second signal", flush=True)
    await grpc_stop()
    if runner is not None:
        await runner.cleanup()
    if fast_server is not None:
        await fast_server.stop()
    if uds_server is not None:
        await uds_server.stop()
    if relay_tcp_server is not None:
        await relay_tcp_server.stop()
    if http_uds_server is not None:
        await http_uds_server.stop()
    if native_plane is not None:
        await native_plane.stop()
    print("engine stopped", flush=True)


def _serves_in_process(deployment: SeldonDeploymentSpec,
                       predictor_name: Optional[str]) -> bool:
    """Whether any node of the graph runs in this process (a built-in or an
    in-process unit): such an engine initialises the device runtime; one
    whose every node is a remote binding never touches it."""
    predictor = deployment.predictor(predictor_name)
    bound = predictor.component_map()
    return any(
        bound.get(node.name) is None
        or bound[node.name].runtime not in ("rest", "grpc")
        for node in predictor.graph.walk())


def _boot_log_to_stdout() -> None:
    """The scheduler's boot lines (``loaded N of the record's programs``,
    ``boot timeline: {...}``) belong in a pod's log; nothing else
    configures logging in this process, which drops INFO."""
    log = logging.getLogger("seldon_core_tpu.runtime.genserver")
    if log.level == logging.NOTSET and not log.handlers:
        handler = logging.StreamHandler(sys.stdout)
        handler.setFormatter(logging.Formatter("%(message)s"))
        log.addHandler(handler)
        log.setLevel(logging.INFO)


def main(argv=None) -> None:
    # the boot timeline (utils/genperf.py BOOT; GET /stats ``boot``) begins
    # here: what lies before this stamp is the interpreter and this
    # module's own imports
    t_main = time.monotonic()
    parser = argparse.ArgumentParser(description="seldon_core_tpu engine")
    parser.add_argument("--file", default=None, help="deployment JSON path")
    parser.add_argument("--predictor", default=None)
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--rest-port", type=int, default=None)
    parser.add_argument("--grpc-port", type=int, default=None)
    parser.add_argument(
        "--node", default=None,
        help="serve ONE graph node of the deployment as a standalone "
             "node engine (graph sharding; env ENGINE_GRAPH_NODE)",
    )
    parser.add_argument(
        "--uds-path", default=None,
        help="also bind the zero-copy UDS relay lane on this socket path "
             "(env ENGINE_UDS_PATH)",
    )
    parser.add_argument(
        "--http-uds-path", default=None,
        help="also serve the HTTP route table on this unix socket — the "
             "node-mesh lane a sharded root's unix: binding dials "
             "(env ENGINE_HTTP_UDS_PATH)",
    )
    parser.add_argument(
        "--gen-role", default=None,
        choices=["unified", "prefill", "decode"],
        help="generation role in a disaggregated serving mesh (env "
             "ENGINE_GEN_ROLE; SELDON_TPU_DISAGG=0 forces unified)",
    )
    parser.add_argument(
        "--decode-peers", default=None,
        help="comma-separated relay specs (uds:/path or tcp:host:port) "
             "of decode replicas a prefill replica hands KV blocks to "
             "(env ENGINE_DECODE_PEERS)",
    )
    parser.add_argument(
        "--relay-tcp-port", type=int, default=None,
        help="also bind the framed relay lane on this TCP port — the "
             "cross-host KV-handoff receiver (env ENGINE_RELAY_TCP_PORT)",
    )
    args = parser.parse_args(argv)
    # everything serve() will import, here and under one name: JAX, the
    # engine and its lanes
    import jax

    import seldon_core_tpu.runtime.engine  # noqa: F401
    import seldon_core_tpu.runtime.grpc_server  # noqa: F401
    import seldon_core_tpu.runtime.rest  # noqa: F401
    from seldon_core_tpu.runtime.compilecache import (
        compile_cache_dir,
        enable_compile_cache,
    )
    from seldon_core_tpu.utils.genperf import BOOT

    _boot_log_to_stdout()
    if enable_compile_cache():
        print(f"compile cache: {compile_cache_dir()}", flush=True)
    t_deployment = time.monotonic()
    BOOT.span("process", None, BOOT.process_start, t_main)
    BOOT.span("imports", None, t_main, t_deployment)
    deployment = load_deployment_from_env(args.file)
    node = args.node or os.environ.get("ENGINE_GRAPH_NODE", "").strip()
    if node:
        # pod-per-node topology: this process serves ONE leaf of the graph
        # (the operator ships the FULL deployment to every shard; the node
        # name selects the slice — graph/sharding.py)
        from seldon_core_tpu.graph.sharding import node_subspec

        deployment = default_and_validate(
            node_subspec(deployment, node, args.predictor)
        )
    t_backend = time.monotonic()
    BOOT.span("deployment", None, t_deployment, t_backend)
    if _serves_in_process(deployment, args.predictor):
        # the device runtime comes up on its own, under its own name,
        # where the first unit's first array would have brought it up
        jax.devices()
        BOOT.span("backend", None, t_backend, time.monotonic(),
                  platform=jax.default_backend())
    decode_peers = None
    if args.decode_peers is not None:
        from seldon_core_tpu.runtime.servingmesh import parse_decode_peers

        decode_peers = parse_decode_peers(args.decode_peers)
    asyncio.run(
        serve(deployment, args.predictor, args.host, args.rest_port,
              args.grpc_port, uds_path=args.uds_path,
              http_uds_path=args.http_uds_path, gen_role=args.gen_role,
              decode_peers=decode_peers,
              relay_tcp_port=args.relay_tcp_port)
    )


if __name__ == "__main__":
    main()
