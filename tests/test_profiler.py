"""swarmprof tests (ISSUE 15): cost-harvest-at-warmup discipline,
per-variant device-time attribution, lane duty cycles, the
dispatch-shape profile (tiny ragged flush waves), flag-off type
identity, the roofline analyzer, and the sentinel MFU/duty SLOs.

One paged engine is built/warmed/served ONCE per module (warmup
compiles are the expensive part; every read-side contract asserts
against that shared run) — the duty-cycle test adds only an unwarmed
idle lane, and the flag-off test a dense two-variant engine.
"""

import json

import jax
import pytest

from swarmdb_tpu.backend.engine import Engine
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.backend.service import build_backend_engine
from swarmdb_tpu.models import llama
from swarmdb_tpu.models.configs import TINY_DEBUG, get_config
from swarmdb_tpu.obs.profiler import (NULL_LANE, KernelProfiler,
                                      LaneProfile, NullLane,
                                      platform_peaks, profiler)

CFG = get_config("tiny-debug")

#: 15 tokens -> largest-fit ragged waves w8 + w4 + w2 + w1 (tiny flush)
PROMPTS = [[1, 5, 9, 2, 7] * 3, [4] * 37, [7]]


def _serve(eng, prompts, n=8):
    eng.start()
    try:
        for p in prompts:
            toks, reason = eng.generate_sync(
                p, SamplingParams(max_new_tokens=n))
            assert reason in ("length", "eos")
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """The shared profiled run: reset registry -> build paged engine ->
    warmup (harvest) -> serve PROMPTS -> capture every surface.

    Pins SWARMDB_RAGGED_MIN_WIDTH=1: the tiny-flush detection and
    exact-packing contracts below deliberately seed width-1 waves,
    which the default floor of 8 folds away (PROFILE.md round 11), and
    the wave planner's ridge to 0: at the CPU row's 5 tokens a wave of 1
    costs a pass over the weights and a tail of 3 is rounded up to 4."""
    mp = pytest.MonkeyPatch()
    mp.setenv("SWARMDB_RAGGED_MIN_WIDTH", "1")
    prof = profiler()
    prof.reset()
    eng = build_backend_engine(CFG, max_batch=4, max_seq=96,
                               paged=True, page_size=16)[0]
    eng._ragged_ridge_tokens = 0.0
    eng._prof.set_label("prof-test-loaded")
    eng.warmup()
    harvest_at_warmup = prof.harvest_calls
    device_s_after_warmup = sum(
        v["device_s"] for v in prof.variants_report())
    _serve(eng, PROMPTS)
    tmp = tmp_path_factory.mktemp("profdump")
    yield {
        "prof": prof,
        "eng": eng,
        "harvest_at_warmup": harvest_at_warmup,
        "device_s_after_warmup": device_s_after_warmup,
        "tmp": tmp,
    }
    prof.reset()
    mp.undo()


# ------------------------------------------------------- harvest discipline


def test_cost_harvest_at_warmup_zero_after(run):
    """The harvest (lower + cost_analysis per variant) runs at warmup
    and NEVER on a serving path: harvest_calls is flat across traffic,
    warmup-time compile stalls are not billed as device time, and the
    harvested facts join the runtime accounting into MFU/roofline."""
    prof = run["prof"]
    assert run["harvest_at_warmup"] > 0, "warmup harvested nothing"
    assert run["device_s_after_warmup"] == 0.0, \
        "warmup compiles were billed as device time"
    assert prof.harvest_calls == run["harvest_at_warmup"], \
        "harvest leaked past warmup"
    rep = prof.report()
    assert rep["enabled"] is True
    ran = [v for v in rep["variants"] if v["invocations"] > 0]
    assert ran, "no runtime attribution recorded"
    assert all(v["device_s"] > 0 for v in ran)
    # at least one executed variant carries the full roofline row
    full = [v for v in ran if v.get("mfu") is not None]
    assert full, f"no harvested variant executed: {rep['variants']}"
    assert full[0]["roofline"] in ("compute-bound", "memory-bound")
    assert full[0]["arithmetic_intensity"] > 0
    assert full[0]["achieved_flops_per_s"] > 0
    assert rep["mfu"] is not None and 0 < rep["mfu"] <= 1


def test_harvest_covers_ragged_variants_with_kernel_meta(run):
    ragged = [v for v in run["prof"].variants_report()
              if v["variant"].startswith("prefill.ragged[")]
    assert ragged, "ragged variants not harvested"
    assert all(v["flops_per_call"] for v in ragged)
    # the ops-dispatcher provenance tag: which kernel these seconds
    # would measure (pallas-ragged on TPU, xla-reference off it)
    assert ragged[0]["meta"]["kernel"] in ("pallas-ragged",
                                           "xla-reference")


# ------------------------------------------------------------- duty cycles


def test_duty_cycle_loaded_vs_idle_lane(run):
    idle = build_backend_engine(CFG, max_batch=4, max_seq=96,
                                paged=True, page_size=16)[0]
    idle._prof.set_label("prof-test-idle")
    lanes = {r["lane"]: r for r in run["prof"].lanes_report()
             if r["lane"].startswith("prof-test-")}
    assert set(lanes) == {"prof-test-loaded", "prof-test-idle"}
    for r in lanes.values():
        assert 0.0 <= r["duty_cycle"] <= 1.0
        assert r["elapsed_s"] >= 0
    assert (lanes["prof-test-loaded"]["duty_cycle"]
            > lanes["prof-test-idle"]["duty_cycle"])
    assert lanes["prof-test-idle"]["busy_s"] == 0.0


# ----------------------------------------------------- dispatch-shape profile


def test_dispatch_profile_and_tiny_flush_detection(run):
    """With no ridge the planner's cover is the binary decomposition, so
    a prompt whose length is odd MUST end in a width-1 flush wave — the
    profile names it tiny and joins the serving variant's accounting."""
    prof = run["prof"]
    rows = {(r["kind"], r["width"]): r for r in prof.dispatch_profile()}
    assert ("ragged", 1) in rows, rows.keys()
    tiny = rows[("ragged", 1)]
    assert tiny["tiny_flush"] is True
    assert tiny["waves"] >= 1 and tiny["packed_tokens"] >= 1
    assert prof.tiny_flush_waves() >= 1
    # exact binary decomposition: ragged waves carry zero padding and
    # pack exactly the prompt tokens served
    ragged = [r for (k, _w), r in rows.items() if k == "ragged"]
    assert sum(r["padding_tokens"] for r in ragged) == 0
    assert (sum(r["packed_tokens"] for r in ragged)
            == sum(len(p) for p in PROMPTS))
    # the per-shape rows join their serving variant's runtime counters
    assert tiny["variants"] == ["prefill.ragged[w1]"]
    assert tiny["variant_invocations"] >= tiny["waves"]
    assert tiny["variant_device_s"] > 0


# ------------------------------------------------------- flag-off identity


def test_profile_flag_off_type_identity(monkeypatch):
    monkeypatch.setenv("SWARMDB_PROFILE", "0")
    reg = KernelProfiler()
    lane = reg.lane()
    assert type(lane) is NullLane
    assert lane is NULL_LANE is reg.lane(), \
        "disabled lanes must be THE shared NullLane singleton"
    assert lane.enabled is False
    # a disabled engine holds the same singleton; serving records
    # nothing and warmup harvests nothing (two-variant dense engine —
    # the cheap compile)
    params = llama.init_params(TINY_DEBUG, jax.random.PRNGKey(0))
    eng = Engine(
        lambda p, t, pos, c: llama.forward(p, TINY_DEBUG, t, pos, c),
        lambda b, s: llama.init_kv_cache(TINY_DEBUG, b, s),
        params, max_batch=2, max_seq=64, prefill_buckets=[16])
    assert eng._prof is NULL_LANE
    before = profiler().harvest_calls
    _serve(eng, [[1, 7, 3]], n=4)
    assert profiler().harvest_calls == before
    lane.dispatch("decode.full", 0, 10)
    lane.wave("ragged", 1, 1, 0)
    assert reg.variants_report() == []
    assert reg.dispatch_profile() == []


def test_profile_flag_on_is_lane_profile(run):
    assert type(run["eng"]._prof) is LaneProfile


# -------------------------------------------------------- derived surfaces


def test_prometheus_and_report_contract(run):
    prof = run["prof"]
    body = "\n".join(prof.prometheus_lines())
    assert "swarmdb_mfu " in body
    assert 'swarmdb_lane_duty_cycle{lane="prof-test-loaded"}' in body
    assert 'swarmdb_kernel_device_seconds_total{variant="' in body
    assert 'swarmdb_kernel_invocations_total{variant="' in body
    rep = prof.report()
    assert rep["kind"] == "swarmdb.profile"
    assert rep["peaks"]["peak_flops"] > 0
    assert rep["harvest_calls"] > 0


def test_chrome_trace_device_tracks(run):
    from swarmdb_tpu.obs import TRACER

    trace = TRACER.to_chrome_trace()
    trace = run["prof"].merge_chrome_trace(trace)
    assert trace["metadata"]["device_tracks"] >= 1
    dev = [e for e in trace["traceEvents"] if e.get("cat") == "device"]
    assert dev, "no device events merged"
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("name") == "thread_name" and e["tid"] >= 900000}
    assert any(n.startswith("device:") for n in names)


def test_dump_analyzer_listing_and_roofline(run):
    from swarmdb_tpu.obs import analyze

    prof, tmp = run["prof"], run["tmp"]
    path = prof.dump_to(str(tmp), "test")
    kind, dump = analyze.load_file(path)
    assert kind == "profile"
    # --roofline: top-3 device-time variants named with numbers
    report = analyze.roofline_report([path], top_n=3)
    top = report["dumps"][0]["top_variants"]
    assert len(top) == 3
    assert top == sorted(top, key=lambda v: -v["device_s"])
    assert all(v["invocations"] > 0 and v["device_s"] > 0 for v in top)
    assert report["dumps"][0]["peaks"]["peak_flops"] > 0
    # profile dumps are listed next to analyzed flight/trace files,
    # like lockcheck/pagecheck dumps
    tracef = tmp / "t_trace.json"
    tracef.write_text(json.dumps({"traceEvents": [
        {"name": "engine.decode_chunk", "ph": "X", "ts": 0.0,
         "dur": 1000.0, "args": {"rid": "r1"}}]}))
    rep = analyze.analyze_files([str(tracef)])
    listed = rep.get("profile_dumps")
    assert listed and listed[0]["path"] == path
    assert listed[0]["top_variant"]
    # and the dump rides flight auto-dumps into the flight dir (the CI
    # failure artifact contract)
    before = set(tmp.glob("profile_*.json"))
    run["eng"].flight.auto_dump("test_reason", str(tmp))
    fresh = set(tmp.glob("profile_*.json")) - before
    assert fresh, "flight auto-dump did not ship a profile dump"


def test_platform_peaks_table_and_overrides(monkeypatch):
    v5e = platform_peaks("tpu", "TPU v5e")
    assert v5e["peak_flops"] == 197e12
    assert v5e["ridge_flops_per_byte"] > 1
    cpu = platform_peaks("cpu")
    assert cpu["peak_flops"] < v5e["peak_flops"]
    # the string a v5e chip reports as its device_kind
    assert platform_peaks("tpu", "TPU v5 lite") == v5e
    # a TPU the table does not know is an error, never the CPU row ...
    with pytest.raises(ValueError, match="weird-chip"):
        platform_peaks("tpu", "weird-chip")
    # ... unless the overrides give both columns
    monkeypatch.setenv("SWARMDB_PEAK_FLOPS", "1e15")
    with pytest.raises(ValueError, match="weird-chip"):
        platform_peaks("tpu", "weird-chip")
    monkeypatch.setenv("SWARMDB_PEAK_BW", "1e12")
    assert platform_peaks("tpu", "weird-chip")["peak_flops"] == 1e15


# ------------------------------------------------------------ sentinel SLOs


def _window(completed=20, mfu=None, duty=None):
    w = {
        "completed": completed, "admission_waves": 4,
        "per_completion_ms": {"queue_wait": 5.0, "prefill": 10.0,
                              "decode": 20.0, "host_sync": 1.0},
        "p95_ttft_s": 0.5, "p95_queue_wait_s": 0.2,
    }
    if mfu is not None:
        w["mfu"] = mfu
    if duty is not None:
        w["min_lane_duty"] = duty
    return w


def test_sentinel_mfu_and_duty_slos():
    from swarmdb_tpu.obs.sentinel import SLOConfig, SLOSentinel

    cfg = SLOConfig(enabled=True, warmup_windows=2, min_completions=8,
                    ttft_p95_s=100.0, queue_p95_s=100.0,
                    cost_growth_x=100.0, retry_rate=100.0,
                    mfu_drop_x=2.0, duty_drop_x=2.0)
    s = SLOSentinel(metrics=None, config=cfg)
    for _ in range(2):
        assert s.ingest(_window(mfu=0.02, duty=0.6)) is None
    assert s.baseline["mfu"] == pytest.approx(0.02)
    assert s.baseline["min_lane_duty"] == pytest.approx(0.6)
    # healthy window: no alert
    assert s.ingest(_window(mfu=0.018, duty=0.55)) is None
    # MFU collapse past baseline/2: breach names the SLO
    alert = s.ingest(_window(mfu=0.005, duty=0.6))
    assert alert is not None
    assert any(b["slo"] == "mfu_drop_x" for b in alert["breaches"])
    # duty collapse alone breaches too
    alert2 = s.ingest(_window(mfu=0.02, duty=0.1))
    assert any(b["slo"] == "duty_drop_x" for b in alert2["breaches"])
    # prometheus surface carries the window numbers
    lines = "\n".join(s.prometheus_lines())
    assert "swarmdb_slo_window_mfu" in lines
    assert "swarmdb_slo_min_lane_duty" in lines


def test_sentinel_profile_window_fold():
    """_profile_window folds profiler deltas into a closing window:
    first close anchors, the second carries mfu/min_lane_duty."""
    from swarmdb_tpu.obs.sentinel import SLOConfig, SLOSentinel

    prof = profiler()
    prof.reset()
    s = SLOSentinel(metrics=None, config=SLOConfig(enabled=True))
    prof.set_platform("cpu", "")
    prof.record_variant("fold.test.variant", 1e6, 2e6)
    lane = prof.lane("fold-test")
    try:
        w1: dict = {}
        s._profile_window(w1)  # anchor
        assert "mfu" not in w1
        lane.dispatch("fold.test.variant", 0, 5_000_000)  # 5 ms busy
        w2: dict = {}
        s._profile_window(w2)
        assert w2["mfu"] > 0
        assert 0.0 <= w2["min_lane_duty"] <= 1.0
    finally:
        prof.reset()
