#!/usr/bin/env python
"""Reproduce the swarm100 paged-chunked lowering failure on real TPU."""
import sys

import jax
import numpy as np

from swarmdb_tpu.backend.engine import Engine, GenRequest, PagedKV
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.backend.service import ServingService
from swarmdb_tpu.core.runtime import SwarmDB
from swarmdb_tpu.utils.xla_cache import enable_compile_cache

enable_compile_cache()

db = SwarmDB()
svc = ServingService.from_model_name(
    db, "llama-1b-bench", max_batch=int(sys.argv[1]) if len(sys.argv) > 1 else 8,
    max_seq=256, decode_chunk=16, paged=True,
)
svc.engine.start()
toks, reason = svc.engine.generate_sync(
    list(np.random.default_rng(0).integers(1, 1000, size=45)),
    SamplingParams(max_new_tokens=16, temperature=0.0), timeout=600,
)
print("OK:", len(toks), reason)
svc.engine.stop()
