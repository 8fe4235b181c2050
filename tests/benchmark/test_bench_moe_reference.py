"""The sparse-expert reference (``benchmark/reference/moe_decoder.py``)
against the program's ``mixtral.forward`` on tiny seeded models in float32:
written apart, the two must agree wherever the program drops no token, and
are shown to disagree where it does. And the proof that a second
architecture is data: one whole ``run.py`` of ``tiny-moe.chat``, a cell
that is a configuration file, a reference file and two entries."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec  # noqa: E402

TINY = ROOT / "tests" / "benchmark" / "tiny"


def both(cfg, dims, params, seed):
    """Logits of one sequence of Q_BLOCK tokens at every position: the
    program's forward, and the reference's."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import moe_decoder
    from swarmdb_tpu.models import mixtral

    T = moe_decoder.Q_BLOCK
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (T,), 3,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want, _ = mixtral.forward(
            params, cfg, tokens[None], jnp.arange(T)[None],
            mixtral.init_kv_cache(cfg, 1, T, dtype=jnp.float32))
    got = moe_decoder.logits_at(params, dims, tokens, jnp.arange(T))
    return np.asarray(want[0]), np.asarray(got)


def test_the_tiny_moe_file_holds_the_programs_tiny_moe_widths():
    from swarmdb_tpu.models.configs import TINY_MOE

    cfg_file = json.loads((TINY / "tiny-moe.json").read_text())
    assert spec.model_config(cfg_file) == TINY_MOE


def test_reference_matches_mixtral_forward_where_nothing_is_dropped():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import moe_decoder
    from swarmdb_tpu.models import mixtral

    cfg_file = json.loads((TINY / "tiny-moe.json").read_text())
    cfg = spec.model_config(cfg_file)
    # the reference's dimensions from the file, the program's from the
    # file's program group: 4 experts, top-2 on both sides
    dims = moe_decoder.dims(cfg_file)
    assert (dims["n_experts"], dims["top_k"]) == (4, 2)
    # 4 experts, top-2, the program's capacity factor 2: capacity
    # N * 2 * 2 / 4 = N, and an expert gets a token at most once, so the
    # program drops nothing at these widths whatever N is, and the
    # dropless reference is exact. That is why tiny-moe.chat can be held
    # to it before the program's dispatch is dropless.
    assert mixtral.DEFAULT_CAPACITY_FACTOR * cfg.experts_per_token \
        == cfg.n_experts
    params = mixtral.init_params(cfg, jax.random.PRNGKey(7),
                                 dtype=jnp.float32)
    want, got = both(cfg, dims, params, seed=1)
    # the tolerance of the dense twin (test_bench_reference.py): both
    # sides are float32 at "highest", so what is left is the order of
    # summation (scan and one einsum there, a loop and blocks here),
    # 1e-5 or so on logits of size 1-4; a token routed to another expert
    # would show as 1e-1
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_reference_and_program_disagree_where_the_program_drops():
    """On record for the next ``model_config`` PR: the program's capacity
    drops tokens that every published sparse-expert model computes."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness.check import LOGIT_TOL
    from benchmark.reference import moe_decoder
    from swarmdb_tpu.models import mixtral

    cfg_file = json.loads((TINY / "tiny-moe.json").read_text())
    cfg_file.update(num_local_experts=8,
                    program={"n_experts": 8, "experts_per_token": 2})
    cfg = spec.model_config(cfg_file)
    dims = moe_decoder.dims(cfg_file)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(7),
                                 dtype=jnp.float32)
    # a router that sends every token to expert 1: only expert 0 has a
    # logit, the others tie at 0 and top_k takes the lowest index, so a
    # token chooses {0, 1} or {1, 2}. One sequence of 256 tokens, 8
    # experts, top-2: capacity 256 * 2 * 2 / 8 = 128, so the program
    # computes expert 1 for tokens 0..127 and drops it for 128..255.
    router = params["layers"]["router"]
    params["layers"]["router"] = router.at[:, :, 1:].set(0.0)
    T = moe_decoder.Q_BLOCK
    capacity = int(T * 2 * mixtral.DEFAULT_CAPACITY_FACTOR / 8)
    assert capacity == 128
    want, got = both(cfg, dims, params, seed=1)
    # causal: what happens to later tokens does not reach earlier ones
    np.testing.assert_allclose(got[:capacity], want[:capacity],
                               atol=2e-4, rtol=2e-4)
    gap = np.abs(got[capacity:] - want[capacity:]).max(axis=-1)
    # every dropped token's logits are off, by far more than the
    # benchmark's tolerance on a logit
    assert gap.min() > LOGIT_TOL, gap.min()


def whole_run(tmp_path, seed, code=None):
    """``run.py`` of ``tiny-moe.chat`` in a process of its own; ``code``
    runs there first."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    env.pop("XLA_FLAGS", None)
    run_py = str(ROOT / "benchmark" / "run.py")
    argv = [run_py, "--spec", str(TINY / "spec.json"), "--workload",
            "tiny-moe.chat", "--platform", "cpu", "--seed", str(seed),
            "--seconds", "3", "--trace", "0"]
    start = ([run_py] if code is None else
             ["-c", f"import runpy, sys; sys.argv = {argv!r}\n"
                    f"sys.path.insert(0, {str(ROOT)!r})\n{code}\n"
                    f"runpy.run_path({run_py!r}, run_name='__main__')"])
    proc = subprocess.run([sys.executable, *start, *argv[1:]], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2]), proc.stderr


def test_a_whole_tiny_moe_run_ends_in_the_contract_line(tmp_path):
    out, facts, err = whole_run(tmp_path, 2 ** 31 + 11)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"reply_p90_ms", "ttft_p90_ms",
                                   "tpot_p90_ms", "out_tokens_per_s",
                                   "setup_s"}
    # held to the reference its configuration names, on logits
    assert facts["reference"] == "benchmark/reference/moe_decoder.py"
    assert facts["logit_gaps"] and max(facts["logit_gaps"]) <= facts[
        "logit_tol"]
    # every number compared stands beside its limit at the end of stderr
    last = err.strip().splitlines()[-1]
    assert "compared: logit gaps" in last and "correct True" in last


def test_a_broken_sampler_under_the_engine_is_not_correct(tmp_path):
    """The timed path broken where a token is produced: every decode
    program takes the second-best token. The rest of the run is as it is,
    the replies are whole, and ``correct`` comes out false on the gaps."""
    code = ("import jax.numpy as jnp\n"
            "import swarmdb_tpu.backend.engine as engine\n"
            "engine.sample_tokens = lambda logits, *a, **k: jnp.argsort("
            "logits, axis=-1)[:, -2].astype(jnp.int32)")
    out, facts, err = whole_run(tmp_path, 2 ** 31 + 11, code)
    assert out["correct"] is False and out["failed"] == 0
    assert facts["reply_faults"] == [] and facts["compiles_in_window"] == 0
    assert max(facts["logit_gaps"]) > facts["logit_tol"]
    assert "correct False" in err.strip().splitlines()[-1]
