"""Server entry point: ``python -m swarmdb_tpu.api.server``.

Builds SwarmDB + the aiohttp app from environment variables using the
reference's env-var catalog (`README.md:78-100`, `api.py:38-74`,
`gunicorn_config.py`): KAFKA_BOOTSTRAP_SERVERS, KAFKA_GROUP_ID,
KAFKA_NUM_PARTITIONS, KAFKA_TOPIC, SAVE_DIR, AUTOSAVE_INTERVAL,
JWT_SECRET_KEY, TOKEN_EXPIRE_MINUTES, RATE_LIMIT_PER_MINUTE, CORS_ORIGINS,
API_HOST, API_PORT. Unlike the reference (one SwarmsDB per gunicorn worker,
defect D7), this runs ONE process owning the broker; scale-out is via the
serving mesh, not API-process replication.

Optional TPU serving: set SERVE_MODEL (e.g. ``llama3-8b``, ``tiny-debug``)
to attach a generation backend; agent->backend routing then drives real
decode on device.

High availability (ISSUE 4): two mutually exclusive env modes —

- ``SWARMDB_HA_NODE_ID`` (+ ``SWARMDB_HA_CLUSTER``): this process IS a
  cluster node. An :class:`~swarmdb_tpu.ha.node.HANode` supervises the
  broker (failure detection, fenced promotion); the runtime writes
  through the node's role facade, and /health, /admin/ha and the
  ``swarmdb_ha_*`` /metrics gauges expose the control plane.
- ``SWARMDB_HA_CLUSTER`` alone: this process is a CLIENT of an external
  HA cluster — SwarmDB binds a ClusterBroker that re-points to the
  current leader on failover (handled in core/runtime.py).
"""

from __future__ import annotations

import logging
import os

from aiohttp import web

from ..core.messages import BrokerConfig
from ..core.runtime import SwarmDB
from .app import ApiConfig, create_app


def build_ha_node():
    """Embedded HA node, when this server process is a cluster member
    (``SWARMDB_HA_NODE_ID`` + ``SWARMDB_HA_CLUSTER`` set). Returns the
    started :class:`~swarmdb_tpu.ha.node.HANode` or None."""
    node_id = os.environ.get("SWARMDB_HA_NODE_ID")
    cluster_path = os.environ.get("SWARMDB_HA_CLUSTER")
    if not node_id:
        return None
    if not cluster_path:
        raise SystemExit(
            "SWARMDB_HA_NODE_ID is set but SWARMDB_HA_CLUSTER is not — an "
            "HA node needs the shared cluster-map path")
    from ..broker.local import LocalBroker
    from ..ha.cluster import FileClusterMap
    from ..ha.node import HANode

    log_dir = os.environ.get("BROKER_LOG_DIR") or "ha_broker_log"
    impl = os.environ.get("BROKER_IMPL", "auto")
    broker = None
    if impl in ("auto", "native"):
        try:
            from ..broker.native import NativeBroker, native_available

            if native_available():
                broker = NativeBroker(log_dir=log_dir)
        except Exception:
            if impl == "native":
                raise
    if broker is None:
        broker = LocalBroker(
            snapshot_path=os.path.join(log_dir, "snapshot.json"))
    listen = os.environ.get("SWARMDB_HA_LISTEN", "0.0.0.0:9444")
    liveness = os.environ.get("SWARMDB_HA_LIVENESS", "0.0.0.0:9445")
    data = os.environ.get("SWARMDB_HA_DATA", "0.0.0.0:9446")
    host, _, port = listen.rpartition(":")
    _, _, lport = liveness.rpartition(":")
    _, _, dport = data.rpartition(":")
    node = HANode(
        node_id, broker, FileClusterMap(cluster_path),
        listen_host=host or "0.0.0.0", replica_port=int(port),
        liveness_port=int(lport),
        data_port=None if dport == "off" else int(dport),
        advertise_host=os.environ.get("SWARMDB_HA_ADVERTISE_HOST"),
        log_dir=log_dir,
        # deployment entry point = cluster mode: partition leadership
        # defaults ON here (SWARMDB_HA_PARTITION_LEADERSHIP overrides)
        cluster_mode=True,
    )
    node.start(role=os.environ.get("SWARMDB_HA_ROLE", "follower"))
    return node


def build_db(ha_node=None) -> SwarmDB:
    cfg = BrokerConfig(
        bootstrap_servers=os.environ.get("KAFKA_BOOTSTRAP_SERVERS", "localhost:9092"),
        group_id=os.environ.get("KAFKA_GROUP_ID", "swarm_agents"),
        num_partitions=int(os.environ.get("KAFKA_NUM_PARTITIONS", "3")),
        log_dir=os.environ.get("BROKER_LOG_DIR") or None,
        implementation=os.environ.get("BROKER_IMPL", "auto"),
    )
    broker = None
    if ha_node is not None:
        # node-level mode: the per-call role facade (acks=all + fencing
        # while leading, read-only mirror as follower). Partition mode
        # (ISSUE 14): a per-partition-routing ClusterBroker whose opener
        # short-circuits THIS node — every produce reaches the owning
        # partition leader instead of fencing on the local facade, which
        # is what lets partition leadership default ON for cluster nodes
        broker = ha_node.client_broker()
    return SwarmDB(
        config=cfg,
        topic_name=os.environ.get("KAFKA_TOPIC", "swarm_messages"),
        save_dir=os.environ.get("SAVE_DIR", "message_history"),
        autosave_interval=float(os.environ.get("AUTOSAVE_INTERVAL", "300")),
        broker=broker,
    )


def _serve_knobs() -> dict:
    """Engine shape knobs — must be IDENTICAL on every host of a pod (the
    worker replays the coordinator's compiled calls shape-for-shape)."""
    return {
        "max_batch": int(os.environ.get("SERVE_MAX_BATCH", "8")),
        "max_seq": int(os.environ.get("SERVE_MAX_SEQ", "1024")),
        "decode_chunk": int(os.environ.get("SERVE_CHUNK", "8")),
        "seed": int(os.environ.get("SERVE_SEED", "0")),
    }


def _build_pod_engine(model_name: str):
    """Sharded engine over the GLOBAL mesh — same construction on every
    host so device state starts identical (parallel/multihost.py)."""
    from ..backend.tokenizer import default_tokenizer
    from ..parallel.serving import build_serving_engine

    k = _serve_knobs()
    engine, sm = build_serving_engine(
        model_name, max_batch=k["max_batch"], max_seq=k["max_seq"],
        seed=k["seed"], decode_chunk=k["decode_chunk"],
    )
    tokenizer = default_tokenizer(sm.cfg.vocab_size,
                                  os.environ.get("SERVE_TOKENIZER") or None)
    return engine, tokenizer


def build_serving(db: SwarmDB, distributed: bool = False, ha_node=None):
    model_name = os.environ.get("SERVE_MODEL")
    if not model_name:
        return None
    try:
        from ..backend.service import ServingService
    except ImportError as exc:
        raise SystemExit(
            f"SERVE_MODEL={model_name!r} requires the serving backend "
            f"(swarmdb_tpu.backend.service): {exc}"
        )
    if distributed:
        engine, tokenizer = _build_pod_engine(model_name)
        engine.enable_multihost()
        serving = ServingService(db, engine, tokenizer)
    else:
        serving = ServingService.from_model_name(db, model_name)
    # conversation locality rides partition leadership (ISSUE 14): lane
    # pins follow partition leaders and re-pin on rebalance events
    serving.bind_partition_leadership(ha_node)
    if db.token_counter is None:
        # explicit wiring (not a constructor side effect): the deployment's
        # single backend tokenizer fills Message.token_count — the counter
        # the reference keeps pluggable but never supplies (` main.py:295`)
        db.token_counter = serving.tokenizer.count
    return serving


def run_worker() -> None:
    """Non-coordinator pod process: join the SPMD decode program.

    Builds the identical sharded engine over the global mesh and replays
    the coordinator's published device calls until it broadcasts stop
    (Engine.worker_loop). No broker, no HTTP — the single-controller /
    SPMD split of SURVEY §7: host 0 owns the request plane, every host
    executes the tensor plane."""
    model_name = os.environ.get("SERVE_MODEL")
    if not model_name:
        raise SystemExit(
            "worker process needs SERVE_MODEL to build the shared engine"
        )
    engine, _tok = _build_pod_engine(model_name)
    logging.getLogger(__name__).info("worker joined decode program")
    engine.worker_loop()


def build_ssl_context():
    """TLS termination (reference: gunicorn keyfile/certfile,
    `/root/reference/gunicorn_config.py:96-126`): set API_SSL_CERT (+
    API_SSL_KEY for a separate key file) to serve HTTPS; absent = HTTP."""
    cert = os.environ.get("API_SSL_CERT")
    if not cert:
        return None
    import ssl

    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(cert, os.environ.get("API_SSL_KEY") or None)
    return ctx


def main() -> None:
    from ..utils.logsink import configure_logging

    configure_logging()  # console + optional rotating/compressed LOG_FILE
    if os.environ.get("SERVE_MODEL"):
        # a serving process compiles big programs: keep them across
        # restarts (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
        from ..utils.xla_cache import enable_compile_cache

        enable_compile_cache()
    from ..parallel.distributed import init_distributed, is_coordinator

    distributed = init_distributed()
    if distributed and not is_coordinator():
        # Multi-host pod: one HTTP ingress (coordinator) owns the broker
        # and API; every process executes the same SPMD decode program
        # over the global mesh. This process joins as a tensor-plane
        # worker (round-2/3 builds refused here; VERDICT #5).
        run_worker()
        return
    ha_node = build_ha_node()
    db = build_db(ha_node=ha_node)
    serving = build_serving(db, distributed=distributed, ha_node=ha_node)
    cfg = ApiConfig.from_env()
    def _recycle() -> None:
        # worker recycling: SIGTERM ourselves; aiohttp drains in-flight
        # requests within shutdown_timeout and the supervisor (compose
        # restart-unless-stopped / k8s) starts a fresh process
        import signal

        os.kill(os.getpid(), signal.SIGTERM)

    if distributed and cfg.max_requests > 0:
        # a recycling coordinator would strand every worker host mid
        # worker_loop and wedge the pod; recycle a pod by rolling ALL its
        # processes from the orchestrator instead. Zero the knob itself so
        # the middleware neither counts nor logs "recycling" misleadingly.
        import dataclasses

        logging.getLogger(__name__).warning(
            "API_MAX_REQUESTS ignored on a multi-host pod coordinator"
        )
        cfg = dataclasses.replace(cfg, max_requests=0)
    app = create_app(db, cfg, serving=serving, on_max_requests=_recycle,
                     ha_node=ha_node)
    if serving is not None:
        serving.start()
    web.run_app(
        app,
        host=cfg.host,
        port=cfg.port,
        ssl_context=build_ssl_context(),
        # bounded graceful drain for in-flight requests/SSE streams on
        # SIGTERM (reference: gunicorn graceful_timeout,
        # `/root/reference/gunicorn_config.py:40-47`)
        shutdown_timeout=float(os.environ.get("API_SHUTDOWN_TIMEOUT", "30")),
    )


if __name__ == "__main__":
    main()
