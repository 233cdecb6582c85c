"""KVStore server-role entry — ≙ python/mxnet/kvstore/kvstore_server.py
(the process main loop driving MXKVStoreRunServer →
KVStoreDistServer, kvstore_dist_server.h:162).

The collective backend has no standalone server processes: updates run
replicated on every worker (or inside the store via set_optimizer —
update_on_kvstore semantics). A launch layout that still starts
DMLC_ROLE=server processes (reference tracker scripts) gets a compatible
no-op loop: the server registers, idles until the job's workers are done,
and exits 0. The optimizer command channel (set_optimizer → serialized
optimizer, kvstore_dist_server.h:232 exec) maps to local deserialize."""
from __future__ import annotations

import os
import pickle

__all__ = ["KVStoreServer", "_init_kvstore_server_module"]


class KVStoreServer:
    """≙ kvstore_server.KVStoreServer — wraps a store, runs the command
    loop."""

    def __init__(self, kvstore):
        self.kvstore = kvstore
        self.init_logging()

    def init_logging(self):
        import logging
        self.logger = logging.getLogger("mxnet_tpu.kvstore.server")

    def controller(self):
        """Command handler ≙ server_controller (kvstore_server.py)."""
        def server_controller(cmd_id, cmd_body):
            if cmd_id == 0:                  # kSetOptimizer
                try:
                    optimizer = pickle.loads(cmd_body)
                except Exception:
                    from .. import optimizer as opt_mod
                    optimizer = opt_mod.create(cmd_body)
                self.kvstore.set_optimizer(optimizer)
            elif cmd_id == 1:                # kStopServer
                self._stop = True
            elif cmd_id == 2:                # kSetProfilerParams
                # ≙ KVStoreServerProfilerCommand (kvstore.h:48; exercised
                # by tests/nightly/test_server_profiling.py): body is
                # "kSetConfig:<json>" | "kState:run|stop" | "kDump"
                from .. import profiler
                body = cmd_body.decode() if isinstance(cmd_body, bytes) \
                    else str(cmd_body)
                kind, _, arg = body.partition(":")
                if kind == "kSetConfig":
                    import json
                    profiler.set_config(**(json.loads(arg) if arg else {}))
                elif kind == "kState":
                    (profiler.start if arg == "run" else profiler.stop)()
                elif kind == "kDump":
                    profiler.dump()
        return server_controller

    def run(self):
        """Server main loop: a REAL parameter server owning this process's
        round-robin key slot (≙ KVStoreDistServer::Run,
        kvstore_dist_server.h:162).  The server id comes from
        DMLC_SERVER_ID (the launcher numbers server roles 0..S-1); the
        address is published through the coordination service, or printed
        for launchers that assemble MXNET_TPU_PS_ADDRS themselves.
        Workers reach it when the layout sets MXNET_TPU_PS_ADDRS or
        MXNET_TPU_PS_STANDALONE=1 (otherwise they self-host)."""
        from .ps import ParameterServer
        sid = int(os.environ.get("DMLC_SERVER_ID", "0"))
        srv = ParameterServer(
            host=os.environ.get("MXNET_TPU_PS_BIND", "0.0.0.0"),
            port=int(os.environ.get("MXNET_TPU_PS_PORT", "0")))
        addr = srv.start(seq=0, sid=sid)
        self.logger.info("kvstore server %d serving at %s", sid, addr)
        print(f"MXNET_TPU_PS_SERVER {sid} {addr}", flush=True)
        srv.serve_forever()


def _init_kvstore_server_module():
    """≙ kvstore_server._init_kvstore_server_module: when DMLC_ROLE=server,
    run the blocking server loop."""
    role = os.environ.get("DMLC_ROLE", "worker").lower()
    if role == "server":
        # Servers never touch the accelerator: a chip belongs to one
        # process, and it is the worker's.  Pin the platform before the
        # server's first optimizer jit initialises a backend.
        import jax
        jax.config.update("jax_platforms", "cpu")
        server = KVStoreServer(None)
        server.run()
        return True
    return False
