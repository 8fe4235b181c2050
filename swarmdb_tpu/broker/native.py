"""NativeBroker — ctypes binding over the C++ partitioned log engine.

Implements the same ``Broker`` ABC as ``LocalBroker`` on top of
``cpp/libswarmbroker.so`` (built by ``cpp/Makefile``; ``build_native()``
invokes make on demand). This is the in-tree replacement for the
reference's only native dependency, librdkafka + the external
Kafka/Zookeeper containers (SURVEY §2.3; reference ` main.py:12-18`,
`dockerfile-compose.yaml:5-48`): durable partitioned logs, consumer-group
offsets, retention, and blocking consumption — no external brokers.
"""

from __future__ import annotations

import ctypes
import json
import os
import shutil
import struct
import subprocess
import tempfile
import threading
from typing import Dict, List, Optional

import numpy as np

from .base import Broker, BrokerError, Record, TopicMeta, UnknownTopicError

_CPP_DIR = os.path.join(os.path.dirname(__file__), "cpp")
# SWARMDB_BROKER_LIB overrides the library path — used by the TSAN job
# (scripts/tsan_stress.sh) to load the -fsanitize=thread build.
_LIB_PATH = os.environ.get(
    "SWARMDB_BROKER_LIB", os.path.join(_CPP_DIR, "libswarmbroker.so")
)

_REC_HDR = struct.Struct("<qdii")  # offset, ts, key_len, val_len


def build_native() -> bool:
    """Build (or freshen) the shared library from ``broker.cpp``; True if
    it is now present and current.

    Always invokes make when targeting the in-tree library — the Makefile's
    ``broker.cpp`` dependency makes it a no-op when fresh, and it guarantees
    edits to broker.cpp are never shadowed by a stale binary (the .so is
    gitignored, never committed). Without a toolchain the library is NOT
    used, whatever file lies there: False. With one, a build that fails is
    an error, not a reason to load a leftover. A custom SWARMDB_BROKER_LIB
    (e.g. the TSAN build) is loaded as-is.
    """
    if _LIB_PATH != os.path.join(_CPP_DIR, "libswarmbroker.so"):
        return os.path.exists(_LIB_PATH)
    if not (shutil.which("make")
            and shutil.which(os.environ.get("CXX", "g++"))):
        return False
    try:
        subprocess.run(
            ["make", "-s", "libswarmbroker.so"],
            cwd=_CPP_DIR, check=True, capture_output=True, timeout=120,
        )
    except subprocess.CalledProcessError as exc:
        raise BrokerError(
            "building libswarmbroker.so failed:\n"
            + exc.stderr.decode("utf-8", "replace")[-2000:]) from exc
    return os.path.exists(_LIB_PATH)


def native_available(autobuild: bool = True) -> bool:
    if autobuild:
        return build_native()
    return os.path.exists(_LIB_PATH)


_lib = None


def _load() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    if not native_available():
        raise ImportError("libswarmbroker.so not built (run make in broker/cpp)")
    lib = ctypes.CDLL(_LIB_PATH)
    c = ctypes.c_char_p
    lib.swb_open.restype = ctypes.c_void_p
    lib.swb_open.argtypes = [c]
    lib.swb_open2.restype = ctypes.c_void_p
    lib.swb_open2.argtypes = [c, ctypes.c_int]
    lib.swb_durable_offset.restype = ctypes.c_longlong
    lib.swb_durable_offset.argtypes = [ctypes.c_void_p, c, ctypes.c_int]
    lib.swb_wait_durable.restype = ctypes.c_int
    lib.swb_wait_durable.argtypes = [ctypes.c_void_p, c, ctypes.c_int,
                                     ctypes.c_longlong, ctypes.c_double]
    lib.swb_shutdown.argtypes = [ctypes.c_void_p]
    lib.swb_create_topic.restype = ctypes.c_int
    lib.swb_create_topic.argtypes = [ctypes.c_void_p, c, ctypes.c_int,
                                     ctypes.c_longlong]
    lib.swb_list_topics_json.restype = ctypes.POINTER(ctypes.c_char)
    lib.swb_list_topics_json.argtypes = [ctypes.c_void_p]
    lib.swb_free_buf.argtypes = [ctypes.POINTER(ctypes.c_char)]
    lib.swb_create_partitions.restype = ctypes.c_int
    lib.swb_create_partitions.argtypes = [ctypes.c_void_p, c, ctypes.c_int]
    lib.swb_append.restype = ctypes.c_longlong
    lib.swb_append.argtypes = [ctypes.c_void_p, c, ctypes.c_int, c,
                               ctypes.c_int, c, ctypes.c_int, ctypes.c_double]
    lib.swb_fetch.restype = ctypes.c_longlong
    lib.swb_fetch.argtypes = [ctypes.c_void_p, c, ctypes.c_int,
                              ctypes.c_longlong, ctypes.c_int,
                              ctypes.POINTER(ctypes.c_uint8),
                              ctypes.c_longlong,
                              ctypes.POINTER(ctypes.c_int)]
    lib.swb_end_offset.restype = ctypes.c_longlong
    lib.swb_end_offset.argtypes = [ctypes.c_void_p, c, ctypes.c_int]
    lib.swb_begin_offset.restype = ctypes.c_longlong
    lib.swb_begin_offset.argtypes = [ctypes.c_void_p, c, ctypes.c_int]
    lib.swb_wait_for_data.restype = ctypes.c_int
    lib.swb_wait_for_data.argtypes = [ctypes.c_void_p, c, ctypes.c_int,
                                      ctypes.c_longlong, ctypes.c_double]
    lib.swb_commit_offset.argtypes = [ctypes.c_void_p, c, c, ctypes.c_int,
                                      ctypes.c_longlong]
    lib.swb_committed_offset.restype = ctypes.c_longlong
    lib.swb_committed_offset.argtypes = [ctypes.c_void_p, c, c, ctypes.c_int]
    lib.swb_trim_older_than.restype = ctypes.c_longlong
    lib.swb_trim_older_than.argtypes = [ctypes.c_void_p, c, ctypes.c_double]
    lib.swb_flush.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


class NativeBroker(Broker):
    """Durable partitioned-log broker backed by the C++ engine."""

    def __init__(self, log_dir: Optional[str] = None,
                 sync_interval_ms: int = 5) -> None:
        self._lib = _load()
        if log_dir is None:
            self._tmp = tempfile.TemporaryDirectory(prefix="swarmbroker_")
            log_dir = self._tmp.name
        else:
            self._tmp = None
            os.makedirs(log_dir, exist_ok=True)
        self.log_dir = log_dir
        self._h = self._lib.swb_open2(log_dir.encode(), sync_interval_ms)
        if not self._h:
            raise BrokerError(f"swb_open failed for {log_dir}")
        self._fetch_cap = 1 << 18
        self._fetch_bufs = threading.local()  # reused per thread, no memset
        self._closed = False

    # -- admin ---------------------------------------------------------------

    def create_topic(self, name: str, num_partitions: int,
                     retention_ms: int = 7 * 24 * 3600 * 1000) -> bool:
        r = self._lib.swb_create_topic(
            self._h, name.encode(), num_partitions, retention_ms
        )
        if r < 0:
            raise BrokerError(f"create_topic({name}) failed")
        return r == 1

    def list_topics(self) -> Dict[str, TopicMeta]:
        p = self._lib.swb_list_topics_json(self._h)
        try:
            raw = ctypes.cast(p, ctypes.c_char_p).value or b"{}"
        finally:
            self._lib.swb_free_buf(p)
        return {
            name: TopicMeta(name, nparts, ret)
            for name, (nparts, ret) in json.loads(raw.decode()).items()
        }

    def create_partitions(self, name: str, new_total: int) -> None:
        if self._lib.swb_create_partitions(self._h, name.encode(), new_total) < 0:
            raise UnknownTopicError(name)

    # -- data plane ----------------------------------------------------------

    def append(self, topic: str, partition: int, value: bytes,
               key: Optional[bytes] = None,
               timestamp: Optional[float] = None) -> int:
        import time as _t

        off = self._lib.swb_append(
            self._h, topic.encode(), partition,
            key, -1 if key is None else len(key),
            value, len(value),
            timestamp if timestamp is not None else _t.time(),
        )
        if off < 0:
            raise UnknownTopicError(f"{topic}[{partition}]")
        return int(off)

    def _fetch_buf(self) -> "np.ndarray":
        """Per-thread reusable buffer (np.empty: no zero-fill, unlike a
        fresh ctypes array — review finding: ~1 MB memset per message)."""
        buf = getattr(self._fetch_bufs, "buf", None)
        if buf is None or buf.nbytes < self._fetch_cap:
            buf = np.empty(self._fetch_cap, np.uint8)
            self._fetch_bufs.buf = buf
        return buf

    def fetch(self, topic: str, partition: int, offset: int,
              max_records: int = 256) -> List[Record]:
        while True:
            buf = self._fetch_buf()
            count = ctypes.c_int(0)
            n = self._lib.swb_fetch(
                self._h, topic.encode(), partition, offset, max_records,
                buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                buf.nbytes, ctypes.byref(count),
            )
            if n == -1:
                raise UnknownTopicError(f"{topic}[{partition}]")
            if n < -1:  # first record needs -n bytes
                self._fetch_cap = max(self._fetch_cap * 2, int(-n))
                continue
            break
        out: List[Record] = []
        raw = buf[: int(n)].tobytes()
        pos = 0
        for _ in range(count.value):
            off, ts, klen, vlen = _REC_HDR.unpack_from(raw, pos)
            pos += _REC_HDR.size
            key = None
            if klen >= 0:
                key = raw[pos: pos + klen]
                pos += klen
            value = raw[pos: pos + vlen]
            pos += vlen
            out.append(Record(topic, partition, off, key, value, ts))
        return out

    def end_offset(self, topic: str, partition: int) -> int:
        off = self._lib.swb_end_offset(self._h, topic.encode(), partition)
        if off < 0:
            raise UnknownTopicError(f"{topic}[{partition}]")
        return int(off)

    def begin_offset(self, topic: str, partition: int) -> int:
        off = self._lib.swb_begin_offset(self._h, topic.encode(), partition)
        if off < 0:
            raise UnknownTopicError(f"{topic}[{partition}]")
        return int(off)

    def wait_for_data(self, topic: str, partition: int, offset: int,
                      timeout_s: float) -> bool:
        return self._lib.swb_wait_for_data(
            self._h, topic.encode(), partition, offset, timeout_s
        ) == 1

    # -- consumer-group offsets ---------------------------------------------

    def commit_offset(self, group: str, topic: str, partition: int,
                      offset: int) -> None:
        self._lib.swb_commit_offset(
            self._h, group.encode(), topic.encode(), partition, offset
        )

    def committed_offset(self, group: str, topic: str,
                         partition: int) -> Optional[int]:
        off = self._lib.swb_committed_offset(
            self._h, group.encode(), topic.encode(), partition
        )
        return None if off < 0 else int(off)

    # -- retention / durability ---------------------------------------------

    def _check_open(self) -> None:
        if self._closed or self._h is None:
            raise BrokerError("broker is closed")

    def durable_offset(self, topic: str, partition: int) -> int:
        self._check_open()
        off = self._lib.swb_durable_offset(self._h, topic.encode(), partition)
        if off == -2:
            # poisoned by a failed fsync: records can never become durable
            raise BrokerError(
                f"{topic}[{partition}]: partition poisoned by fsync failure"
            )
        if off < 0:
            raise UnknownTopicError(f"{topic}[{partition}]")
        return int(off)

    def wait_durable(self, topic: str, partition: int, offset: int,
                     timeout_s: float) -> bool:
        self._check_open()
        return self._lib.swb_wait_durable(
            self._h, topic.encode(), partition, offset, timeout_s
        ) == 1

    def trim_older_than(self, topic: str, cutoff_ts: float) -> int:
        n = self._lib.swb_trim_older_than(self._h, topic.encode(), cutoff_ts)
        if n < 0:
            raise UnknownTopicError(topic)
        return int(n)

    def flush(self) -> None:
        self._lib.swb_flush(self._h)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._lib.swb_flush(self._h)
        self._lib.swb_shutdown(self._h)
        self._h = None
        if self._tmp is not None:
            self._tmp.cleanup()
