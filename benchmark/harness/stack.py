"""The system under test, built the way ``chip_smoke.py`` builds it (PR 22,
ran on the chip): ``build_backend_engine`` / ``build_serving_engine`` ->
``ServingService`` -> ``SwarmDB`` with its default broker. The layout is
data in the configuration file. ``Recorder`` wraps ``Engine.submit`` below
the service, the supervisor and the lane router, so it sees the tokens the
device saw and when."""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict

from . import spec


class Recorder:
    """Per engine request, keyed by the message id the service put in
    ``GenRequest.metadata``: the prompt as the engine got it, the time of
    ``submit``, of the first and the last token, their count, and of
    ``on_done`` with its tokens and reason, and the routing the program
    reports for the request (``GenRequest.routing``: ``[positions,
    L_routed, k]`` int16 where the configuration routes, else ``None``;
    the array is the engine's own, not copied). All times on
    ``time.time()``, the clock of the program's own stage stamps."""

    def __init__(self, engines) -> None:
        self.records: Dict[str, Dict[str, Any]] = {}
        self._lock = threading.Lock()
        for lane, eng in enumerate(engines):
            self._wrap(lane, eng)

    def _wrap(self, lane: int, eng) -> None:
        inner = eng.submit

        def submit(req):
            mid = req.metadata.get("message_id")
            if mid is None or req.metadata.get("_bench_wrapped"):
                return inner(req)       # not a served message, or a retry
            req.metadata["_bench_wrapped"] = True
            rec = {"lane": lane, "prompt": list(req.prompt),
                   "resume_len": req.resume_len,
                   "max_new": req.sampling.max_new_tokens,
                   "submit_t": time.time(), "first_t": None, "last_t": None,
                   "n_tokens": 0, "tokens": None, "reason": None,
                   "done_t": None, "routing": None,
                   "routing_complete": False}
            tok, done = req.on_token, req.on_done

            def on_token(rid, token):
                now = time.time()
                if rec["first_t"] is None:
                    rec["first_t"] = now
                rec["last_t"] = now
                rec["n_tokens"] += 1
                if tok is not None:
                    tok(rid, token)

            def on_done(rid, tokens, reason):
                rec["done_t"] = time.time()
                rec["tokens"], rec["reason"] = list(tokens), reason
                # written by the engine before this call; a dense
                # request leaves None and False
                rec["routing"] = req.routing
                rec["routing_complete"] = req.routing_complete
                if done is not None:
                    done(rid, tokens, reason)

            req.on_token, req.on_done = on_token, on_done
            with self._lock:
                self.records[mid] = rec
            return inner(req)

        eng.submit = submit

    def get(self, message_id: str):
        with self._lock:
            return self.records.get(message_id)


class Stack:
    """db + engine(s) + service of one cell, started and warm."""

    def __init__(self, cfg_file: Dict[str, Any], seed: int, tmp: str) -> None:
        from swarmdb_tpu.backend.service import ServingService
        from swarmdb_tpu.core.runtime import SwarmDB

        self.cfg_file = cfg_file
        self.cfg = spec.model_config(cfg_file)
        self.serving = cfg_file["serving"]
        self.db = SwarmDB(save_dir=os.path.join(tmp, "history"))
        # the program draws keys with PRNGKey(seed): keep it in 31 bits
        self.seed = int(seed) % (2 ** 31 - 1)
        layout = cfg_file.get("layout", {"kind": "single"})
        build = {"single": self._build_single,
                 "lanes": self._build_lanes}.get(layout["kind"])
        if build is None:
            raise spec.SpecError(f"unknown layout {layout!r}")
        self.engine, self.tokenizer, self.lanes = build(layout, tmp)
        self.backend_id = "tpu-0"
        self.service = ServingService(self.db, self.engine, self.tokenizer,
                                      backend_id=self.backend_id)
        self.engine.flight.meta.update({"backend_id": self.backend_id,
                                        "model": self.cfg.name})
        self.recorder = Recorder(self.lanes)
        self.max_batch = sum(e.max_batch for e in self.lanes)
        self.stopped = False

    def _knobs(self, tmp: str) -> Dict[str, Any]:
        s = self.serving
        return dict(max_seq=s["max_seq"], seed=self.seed,
                    decode_chunk=s["decode_chunk"], paged=s["paged"],
                    page_size=s["page_size"],
                    kv_pool_tokens=s.get("kv_pool_tokens"),
                    metrics=self.db.metrics,
                    flight_dir=os.path.join(tmp, "flight"))

    def _build_single(self, layout, tmp):
        from swarmdb_tpu.backend.service import build_backend_engine

        engine, tok = build_backend_engine(
            self.cfg, max_batch=self.serving["max_batch"], **self._knobs(tmp))
        return engine, tok, [engine]

    def _build_lanes(self, layout, tmp):
        """One one-chip replica per chip behind the lane router, as
        ``chip_smoke.py`` ``phase_lanes``; ``max_batch`` is per lane."""
        from swarmdb_tpu.backend.tokenizer import default_tokenizer
        from swarmdb_tpu.parallel.mesh import make_mesh
        from swarmdb_tpu.parallel.serving import build_serving_engine

        n = int(layout["chips"])
        mesh = make_mesh(n, data=n, model=1, expert=1)
        group, _info = build_serving_engine(
            self.cfg, mesh, max_batch=n * self.serving["max_batch"],
            **self._knobs(tmp))
        return group, default_tokenizer(self.cfg.vocab_size), list(group.lanes)

    def start(self) -> float:
        t = time.time()
        self.service.start(warmup=True)
        return time.time() - t

    def compiled_count(self) -> int:
        return sum(e._compiled_count() for e in self.lanes)

    def occupancy(self) -> float:
        active = sum(e.stats()["active_slots"] for e in self.lanes)
        return active / self.max_batch

    def queued(self) -> int:
        return sum(e.stats()["queued"] for e in self.lanes)

    def stop(self) -> None:
        self.stopped = True
        self.service.stop()
        self.db.close()
