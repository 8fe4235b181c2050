"""Open-loop load from one process with three threads: a sender that sends
each message when it is due whether or not earlier ones have finished, a
reader that polls the senders' inboxes for replies, and (traced runs only)
a sampler. Every message is timed from the instant it was due."""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

GEN = {"temperature": 0.0}


class Driver:
    def __init__(self, stack, plan: Dict[str, Any]) -> None:
        self.stack, self.db = stack, stack.db
        self.arrivals: List[Dict[str, Any]] = plan["arrivals"]
        self.assistants = plan["assistants"]
        self.messages: List[Dict[str, Any]] = []
        self._pending: Dict[str, Dict[str, Any]] = {}     # message id -> rec
        self._by_user: Dict[str, int] = {}                # user -> pending
        self._lock = threading.Lock()
        self._sent_all = threading.Event()
        self._stop = threading.Event()
        self.samples: List[Dict[str, float]] = []
        self.t0: Optional[float] = None

    def prepare(self) -> None:
        for a in self.assistants:
            self.db.register_agent(a)
            self.db.assign_llm_backend(a, self.stack.backend_id)
        for u in sorted({a["sender"] for a in self.arrivals}):
            self.db.register_agent(u)

    # ------------------------------------------------------------ threads

    def _send_loop(self) -> None:
        for arr in self.arrivals:
            due = self.t0 + arr["due"]
            while True:
                wait = due - time.time()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05) if wait > 0.002 else 0)
            rec = {"due": due, "phase": arr["phase"], "sender": arr["sender"],
                   "max_new": arr["max_new_tokens"], "sent_t": None,
                   "id": None, "reply_t": None, "replies": 0,
                   "completion_tokens": None, "finish_reason": None,
                   "error": None}
            with self._lock:
                self._by_user[arr["sender"]] = (
                    self._by_user.get(arr["sender"], 0) + 1)
            try:
                mid = self.db.send_message(
                    arr["sender"], arr["receiver"], arr["text"],
                    metadata={"generation": dict(
                        GEN, max_new_tokens=arr["max_new_tokens"])})
                rec["id"], rec["sent_t"] = mid, time.time()
                with self._lock:
                    self._pending[mid] = rec
            except Exception as exc:  # a refused send is a failed message
                rec["error"] = f"{type(exc).__name__}: {exc}"[:200]
                with self._lock:
                    self._by_user[arr["sender"]] -= 1
            self.messages.append(rec)
        self._sent_all.set()

    def _read_loop(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                users = [u for u, n in self._by_user.items() if n > 0]
            for user in users:
                for m in self.db.receive_messages(user, max_messages=10,
                                                  timeout=0.0):
                    now = time.time()
                    meta = m.metadata or {}
                    with self._lock:
                        rec = self._pending.get(meta.get("reply_to"))
                        if rec is None:
                            continue
                        rec["replies"] += 1
                        if rec["reply_t"] is None:
                            rec["reply_t"] = now
                            rec["completion_tokens"] = meta.get(
                                "completion_tokens")
                            rec["finish_reason"] = meta.get("finish_reason")
                            self._by_user[user] -= 1
            time.sleep(0.002)

    def _sample_loop(self, every: float) -> None:
        while not self._stop.is_set():
            self.samples.append({"t": time.time(),
                                 "occupancy": self.stack.occupancy(),
                                 "queued": self.stack.queued()})
            time.sleep(every)

    # ---------------------------------------------------------------- run

    def run(self, seconds: float, drain_s: float, sample_every: float = 0.0,
            at: Optional[Dict[float, Callable[[], None]]] = None) -> None:
        """Send the whole plan; the window starts at the first message
        whose offset is 0. ``at`` maps an offset from the window's start to
        a callable run on the calling thread (the traced run's profiler
        start and stop). Returns when every reply is in or ``drain_s`` has
        passed since the last message was due."""
        lead = -min(a["due"] for a in self.arrivals)
        self.t0 = time.time() + lead + 0.2
        threads = [threading.Thread(target=self._send_loop, daemon=True,
                                    name="bench-send"),
                   threading.Thread(target=self._read_loop, daemon=True,
                                    name="bench-read")]
        if sample_every > 0:
            threads.append(threading.Thread(
                target=self._sample_loop, args=(sample_every,), daemon=True,
                name="bench-sample"))
        for t in threads:
            t.start()
        for off in sorted(at or {}):
            time.sleep(max(0.0, self.t0 + off - time.time()))
            at[off]()
        self._sent_all.wait()
        deadline = time.time() + drain_s
        while time.time() < deadline:
            with self._lock:
                if not any(n > 0 for n in self._by_user.values()):
                    break
            time.sleep(0.02)
        # a doubled reply would come soon after the first: leave it a moment
        time.sleep(0.3)
        self._stop.set()
        for t in threads:
            t.join(timeout=10)

    def joined(self) -> List[Dict[str, Any]]:
        """Client-side records joined to the engine's by message id."""
        out = []
        for m in self.messages:
            row = dict(m)
            eng = self.stack.recorder.get(m["id"]) if m["id"] else None
            for k in ("submit_t", "first_t", "last_t", "n_tokens", "done_t",
                      "reason"):
                row[k] = eng[k] if eng else None
            row["n_tokens"] = row["n_tokens"] or 0
            msg = self.db.get_message(m["id"]) if m["id"] else None
            row["stages"] = dict((msg.metadata or {}).get("stages", {})
                                 ) if msg is not None else {}
            out.append(row)
        return out
