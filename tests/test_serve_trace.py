"""Flight recorder + SLO observability (serve/trace.py, ISSUE 8).

Fast tier (the tier-1 gate): event-stream completeness under the PR 3
chaos drain (every FinishReason and every fault-injector audit entry has
a matching event), well-formed Perfetto export with correctly nested
per-request spans, histogram percentiles vs numpy, Prometheus exposition
parsing (live endpoint included), bounded-memory regressions (ring,
token-time windows, gauge aggregates, retired-request map), the
taxonomy meta-test (a new FinishReason or fault point cannot silently
skip the recorder), and a kill/restart that leaves a readable
``flight_*.json`` whose trail a restored engine re-carries.  What
tracing costs is the chip's to say (PERF.md), never a host clock here.
"""

import json
import os
import re
import urllib.request
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.runtime.faults import FaultInjector, InjectedKill
from triton_dist_tpu.serve import (
    FinishReason,
    Request,
    SamplingParams,
    ServeEngine,
)
from triton_dist_tpu.serve import trace as trace_mod
from triton_dist_tpu.serve.metrics import (
    TOKEN_TIMES_WINDOW,
    RequestMetrics,
    ServeMetrics,
    format_statline,
    format_stats,
)
from triton_dist_tpu.serve.trace import (
    FlightRecorder,
    LogHistogram,
    start_metrics_server,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=32, max_seq=64,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(3))
    gen = Generator(cfg, mesh, axis="sp", max_seq=64)
    return cfg, params, gen


def _engine(gen, params, **kw):
    kw.setdefault("num_blocks", 40)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_batch", 2)
    kw.setdefault("prefill_chunk", 4)
    return ServeEngine(gen, params, **kw)


# ---------------------------------------------------------------------------
# taxonomy meta-test: new failure paths cannot skip the recorder
# ---------------------------------------------------------------------------


def test_taxonomy_covers_finish_reasons_and_fault_points():
    """Every FinishReason retires through a registered ``retire``
    reason, and every ``.fire("<point>"`` seam in the source tree maps
    to a registered fault event — so adding a retirement reason or an
    injection point without registering it here fails tier-1 instead of
    silently skipping the flight recorder.  The assertions live in the
    analysis rule registry (ISSUE 15: ``finish-reasons-registered`` +
    ``fire-points-registered`` serve this test, scripts/lint_dist.py,
    and the bench-artifact lint stamp in one place)."""
    from triton_dist_tpu.analysis import run_rule

    violations = (run_rule("finish-reasons-registered")
                  + run_rule("fire-points-registered"))
    assert not violations, "\n".join(str(v) for v in violations)
    # the registry's taxonomy invariants themselves (belt and braces:
    # a rule refactor must not drop them)
    assert set(trace_mod.FAULT_POINT_EVENTS.values()) <= \
        trace_mod.EVENT_TYPES
    assert "retire" in trace_mod.EVENT_TYPES


# ---------------------------------------------------------------------------
# histograms: percentiles vs numpy, bounded memory
# ---------------------------------------------------------------------------


def test_log_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(7)
    samples = np.concatenate([
        rng.lognormal(mean=-4.0, sigma=1.2, size=4000),   # ~ms latencies
        rng.uniform(0.5, 2.0, size=1000),                 # a slow tail
    ])
    h = LogHistogram()
    for x in samples:
        h.observe(float(x))
    width = 10.0 ** (1.0 / h.per_decade)   # one bucket's relative width
    for p in (50, 90, 95, 99):
        want = float(np.percentile(samples, p))
        got = h.percentile(p)
        assert got == pytest.approx(want, rel=width - 1.0 + 0.02), p
    assert h.count == len(samples)
    assert h.mean == pytest.approx(float(samples.mean()))
    assert h.max == pytest.approx(float(samples.max()))
    # bounded by construction: observing 10x more samples cannot grow it
    n_buckets = len(h.counts)
    for x in samples:
        for _ in range(3):
            h.observe(float(x))
    assert len(h.counts) == n_buckets


def test_log_histogram_merge_exact_vs_pooled():
    """ISSUE 11: merge() of identical bucket schemes is count-wise
    addition — the merged histogram equals one fed the POOLED samples
    bucket-exactly (counts, count, sum, min, max, and therefore every
    percentile), which is what makes fleet p50/p95/p99 honest."""
    rng = np.random.default_rng(3)
    a_samples = rng.lognormal(-6.0, 1.0, size=1500)   # ~µs: shallow
    b_samples = rng.lognormal(-1.0, 1.5, size=700)    # ~sec: deep
    a, b, pooled = LogHistogram(), LogHistogram(), LogHistogram()
    for x in a_samples:
        a.observe(float(x))
        pooled.observe(float(x))
    for x in b_samples:
        b.observe(float(x))
        pooled.observe(float(x))
    merged = LogHistogram().merge(a).merge(b)
    assert merged.counts == pooled.counts
    assert merged.count == pooled.count
    assert merged.min == pooled.min and merged.max == pooled.max
    assert merged.sum == pytest.approx(pooled.sum)
    for p in (50, 90, 95, 99):
        assert merged.percentile(p) == pooled.percentile(p), p
    # a is untouched by being merged FROM
    assert a.count == len(a_samples)
    # mismatched schemes must refuse, not corrupt
    with pytest.raises(ValueError, match="schemes differ"):
        LogHistogram(per_decade=12).merge(a)


def test_log_histogram_prom_round_trip_and_dense_buckets():
    """The exposition round-trips EXACTLY (from_prom: de-accumulated
    dense buckets + %.17g sum/min/max gauges), and the bucket lines are
    dense — every le from underflow through the deepest reached bucket
    — so cross-replica `sum by (le)` and scrape-and-merge stay monotone
    and complete at different reached depths (the sparse nonzero-only
    output broke exactly that)."""
    from triton_dist_tpu.serve.fleet import parse_prometheus

    rng = np.random.default_rng(4)
    h = LogHistogram()
    for x in rng.lognormal(-4.0, 2.0, size=800):
        h.observe(float(x))
    h.observe(0.0)      # underflow
    h.observe(1e9)      # overflow
    lines = h.prom_lines("x_seconds")
    series = parse_prometheus("\n".join(lines))
    h2 = LogHistogram.from_prom(series, "x_seconds")
    assert h2.counts == h.counts
    assert h2.count == h.count
    assert h2.sum == h.sum                      # %.17g: exact
    assert h2.min == h.min and h2.max == h.max
    for p in (50, 95, 99):
        assert h2.percentile(p) == h.percentile(p)
    # dense: the emitted le set is the FULL prefix of the bucket ladder
    # (no gaps), so every replica's exposition shares its le set
    les = [float(k.split('le="', 1)[1][:-2])
           for k in series if "_bucket{le=" in k and "+Inf" not in k]
    assert len(les) == len(set(les))
    edges = [h.lo] + [h.edge(i) for i in range(len(les) - 1)]
    assert les == sorted(les)
    assert les == pytest.approx(edges, rel=1e-5)   # %.6g labels


def test_log_histogram_edge_cases():
    h = LogHistogram()
    assert h.percentile(50) is None and h.mean is None
    h.observe(0.0)          # fake test clocks produce 0 / negatives
    h.observe(-1.0)
    h.observe(1e9)          # overflow
    assert h.count == 3
    assert h.percentile(1) == -1.0       # underflow reports exact min
    assert h.percentile(99) == 1e9       # overflow reports exact max
    lines = h.prom_lines("x_seconds")
    assert lines[0] == "# TYPE x_seconds histogram"
    assert 'x_seconds_bucket{le="+Inf"} 3' in lines
    with pytest.raises(ValueError):
        LogHistogram(lo=0.0)


# ---------------------------------------------------------------------------
# bounded memory: ring, token-time window, gauges, request map
# ---------------------------------------------------------------------------


def test_flat_memory_footprint_over_a_long_run():
    """The PR 8 regression bar: per-request token times, the per-step
    gauge series, the retired-request map, and the event ring all stay
    bounded no matter how long the engine lives or streams (the old
    lists grew O(steps) and O(tokens) forever)."""
    rm = RequestMetrics(arrival_time=0.0)
    for i in range(10 * TOKEN_TIMES_WINDOW):
        rm.on_token(float(i))
    assert len(rm.token_times) == TOKEN_TIMES_WINDOW
    assert rm.n_tokens == 10 * TOKEN_TIMES_WINDOW
    assert rm.time_at(0) is None                    # forgotten prefix
    assert rm.time_at(rm.n_tokens - 1) == float(rm.n_tokens - 1)
    assert len(rm.inter_token_latencies) == TOKEN_TIMES_WINDOW - 1

    sm = ServeMetrics(requests_retain=8)
    for i in range(5000):
        sm.observe_step(queue_depth=i % 7, running=2,
                        kv_utilization=0.5)
        sm.hist_step.observe(0.001 * (1 + i % 3))
    for i in range(50):
        sm.observe_finish(f"r{i}", RequestMetrics(arrival_time=0.0),
                          FinishReason.LENGTH)
    assert len(sm.requests) == 8
    assert sm.completed == 50                       # counters keep counting
    assert sm.finish_reasons == {"length": 50}
    s = sm.summary()
    assert s["steps"] == 5000 and s["max_queue_depth"] == 6
    # no field may hold a per-step series: everything list/dict-valued on
    # the metrics object stays below a small constant
    for name, val in vars(sm).items():
        if isinstance(val, (list, dict)) and name != "finish_reasons":
            assert len(val) <= 4096, (name, len(val))

    rec = FlightRecorder(capacity=64)
    for i in range(10_000):
        rec.emit("decode_drain", None, tokens=1)
    assert len(rec.events()) == 64
    assert rec.emitted == 10_000 and rec.dropped == 10_000 - 64


def test_recorder_level_gates_and_seed():
    rec = FlightRecorder(capacity=8, level=0)
    rec.emit("submit", "r0")
    assert rec.events() == [] and rec.emitted == 0
    rec.level = 1
    rec.set_step(3)
    rec.emit("submit", "r0", prompt=5)
    assert rec.events()[0][1:4] == (3, "submit", "r0")
    rec2 = FlightRecorder(capacity=8)
    rec2.seed(rec.tail(8))
    assert rec2.events()[0][2] == "submit"
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


# ---------------------------------------------------------------------------
# event-stream completeness under the PR 3 chaos drain
# ---------------------------------------------------------------------------


def test_chaos_drain_event_stream_complete(tiny):
    """The deterministic chaos drain from test_serve_faults, replayed
    against the flight recorder: every retirement (all FinishReason
    classes the drain produces) has a matching ``retire`` event, and
    every fault-injector audit entry has a matching ``fault`` event with
    the same (point, call) coordinates."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(5)
    lens = {"c0": 5, "c1": 5, "c2": 6, "c3": 6, "c4": 5, "c5": 5}
    prompts = {r: rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for r, n in lens.items()}
    inj = (FaultInjector(seed=11)
           .inject("forward", rid="c1", op="paged_decode", error="poison")
           .inject("callback", rid="c2", error="frontend bug")
           .inject("block_alloc", rid="c3", error="alloc fault")
           .inject("clock", at_call=15, skew_s=1000.0))
    eng = _engine(gen, params, max_batch=2, max_queue=3,
                  overload="shed", faults=inj, fault_retries=1,
                  clock=_Clock())

    def req(r, **kw):
        return Request(r, prompts[r],
                       SamplingParams(max_new_tokens=4, **kw),
                       on_token=((lambda rid, t: None)
                                 if r == "c2" else None))

    for r in ("c0", "c1"):
        eng.submit(req(r))
    eng.step()
    for r in ("c2", "c3", "c4", "c5"):
        kw = {"deadline_s": 5.0} if r == "c4" else {}
        eng.submit(req(r, **kw))
    outs = eng.run(max_steps=500)

    evs = eng.trace.events()
    retired = {(e[3], e[4]["reason"]) for e in evs if e[2] == "retire"}
    # every request's retirement — every FinishReason class the drain
    # produced — landed in the ring with its reason
    for rid, out in outs.items():
        assert (rid, out.finish_reason.value) in retired, (rid, retired)
    assert {r for _, r in retired} == {"length", "error", "shed",
                                       "deadline"}
    # every audit entry has a matching fault event at the same seam
    # arrival (the engine mirrors the audit log each step)
    faults = {(e[4]["point"], e[4]["call"]) for e in evs
              if e[2] == "fault" and "call" in e[4]}
    assert inj.fired, "the chaos schedule must have fired"
    for point, call, kind, who, step in inj.fired:
        assert (point, call) in faults, (point, call, faults)
    # submits and admits for every request that entered
    kinds = Counter(e[2] for e in evs)
    assert kinds["submit"] == 6
    assert kinds["admit"] >= 4          # c5 shed, c4 expired waiting
    # quarantines flushed a postmortem? no dump/snapshot dir -> no file,
    # but the flush path must not have crashed the drain (we got here)


# ---------------------------------------------------------------------------
# Perfetto export: well-formed, correctly nested spans
# ---------------------------------------------------------------------------


def test_perfetto_export_spans_nested(tiny, tmp_path):
    cfg, params, gen = tiny
    rng = np.random.default_rng(2)
    # a small pool forces a preemption -> the victim's decode span
    # closes and a second queue/prefill/decode cycle opens
    eng = _engine(gen, params, num_blocks=8, max_batch=2)
    p0 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    p1 = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    eng.submit(Request("a", p0, SamplingParams(max_new_tokens=10)))
    eng.submit(Request("b", p1, SamplingParams(max_new_tokens=10)))
    outs = eng.run(max_steps=500)
    assert all(len(o.token_ids) == 10 for o in outs.values())
    assert eng.metrics.preemptions >= 1
    # queue-time SLO: ONE sample per request — re-admissions after
    # preemption must not re-observe the original first-admit wait
    assert eng.metrics.hist_queue.count == 2

    spans = eng.trace.spans()
    for rid in ("a", "b"):
        names = [n for n, _, _ in spans[rid]]
        assert names[0] == "queue" and "prefill" in names \
            and "decode" in names
        for name, t0, t1 in spans[rid]:
            assert t1 >= t0
        # phases tile the request's lifetime without overlap
        for (_, _, end), (_, start, _) in zip(spans[rid],
                                              spans[rid][1:]):
            assert start == pytest.approx(end)
    victim = next(rid for rid in ("a", "b")
                  if any(n == "queue" for n, _, _ in spans[rid][1:]))
    assert len(spans[victim]) >= 4      # queue/prefill/.../queue again

    path = eng.trace.export_perfetto(str(tmp_path / "eng.trace.json"))
    with open(path) as f:
        doc = json.load(f)              # well-formed JSON
    evs = doc["traceEvents"]
    assert all("ph" in e and "pid" in e for e in evs)
    assert all(e["pid"] == trace_mod.ENGINE_PID for e in evs)
    by_tid = {}
    for e in evs:
        if e["ph"] == "M" and e["name"] == "thread_name":
            by_tid[e["args"]["name"]] = e["tid"]
    for rid in ("a", "b"):
        tid = by_tid[rid]
        req_spans = [e for e in evs if e["ph"] == "X"
                     and e["tid"] == tid and e.get("cat") == "request"]
        assert len(req_spans) == 1
        lo = req_spans[0]["ts"]
        hi = lo + req_spans[0]["dur"]
        phases = [e for e in evs if e["ph"] == "X" and e["tid"] == tid
                  and e.get("cat") == "phase"]
        assert phases
        for ph in phases:               # child spans nest inside parent
            assert ph["ts"] >= lo - 1e-3
            assert ph["ts"] + ph["dur"] <= hi + 1.5  # +1us min-dur pad

    # the gz flavor lands where profiling.merge_rank_traces picks it up
    job = str(tmp_path / "prof")
    out = eng.trace.export_profile(job, rank=0)
    assert out.endswith(os.path.join("rank0", "engine.trace.json.gz"))
    from triton_dist_tpu.runtime.profiling import merge_rank_traces
    merged = merge_rank_traces(job)
    assert merged is not None
    import gzip
    with gzip.open(merged, "rt") as f:
        mdoc = json.load(f)
    # rank re-namespacing kept the engine pid injective
    assert any(e.get("pid") == trace_mod.ENGINE_PID
               for e in mdoc["traceEvents"])


# ---------------------------------------------------------------------------
# Prometheus exposition + live endpoint
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.eE infa]+$')


def _parse_prom(text):
    series = {}
    for ln in text.splitlines():
        if not ln:
            continue
        if ln.startswith("#"):
            assert ln.startswith("# TYPE") or ln.startswith("# HELP"), ln
            continue
        assert _PROM_LINE.match(ln), ln
        name, val = ln.rsplit(" ", 1)
        series[name] = float(val)
    return series


def test_prometheus_exposition_parses(tiny):
    cfg, params, gen = tiny
    rng = np.random.default_rng(4)
    eng = _engine(gen, params)
    for i in range(3):
        eng.submit(Request(f"p{i}",
                           rng.integers(0, cfg.vocab, size=5)
                           .astype(np.int32),
                           SamplingParams(max_new_tokens=4)))
    eng.run()
    text = eng.metrics.to_prometheus()
    series = _parse_prom(text)
    assert series["serve_completed_total"] == 3
    assert series['serve_finished_total{reason="length"}'] == 3
    assert series["serve_decode_tokens_total"] == \
        eng.metrics.decode_tokens
    assert series["serve_trace_events_total"] == eng.trace.emitted
    # histogram contract: cumulative buckets, +Inf == count
    for h in ("serve_ttft_seconds", "serve_itl_seconds",
              "serve_step_time_seconds"):
        buckets = [(k, v) for k, v in series.items()
                   if k.startswith(h + "_bucket")]
        assert buckets, h
        vals = [v for _, v in buckets]
        assert vals == sorted(vals)          # cumulative
        assert series[f'{h}_bucket{{le="+Inf"}}'] == \
            series[f"{h}_count"]
    assert series["serve_ttft_seconds_count"] == 3


def test_live_metrics_endpoint(tiny):
    """The --metrics-port machinery in-process: a Prometheus agent's
    GET during serving returns parseable text that tracks the engine."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(6)
    eng = _engine(gen, params)
    srv = start_metrics_server(eng.metrics, port=0)
    try:
        port = srv.server_address[1]

        def scrape():
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/plain")
                return _parse_prom(r.read().decode())

        s0 = scrape()
        assert s0["serve_completed_total"] == 0
        eng.submit(Request("m0", rng.integers(0, cfg.vocab, size=5)
                           .astype(np.int32),
                           SamplingParams(max_new_tokens=3)))
        eng.step()                      # mid-flight scrape
        mid = scrape()
        assert mid["serve_steps_total"] == 1
        eng.run()
        s1 = scrape()
        assert s1["serve_completed_total"] == 1
        assert s1["serve_decode_tokens_total"] >= 2
    finally:
        srv.shutdown()


def test_stats_formatters_shared(tiny):
    """format_stats/format_statline render summary() for every surface
    (CLI block, periodic line, supervisor postmortem) — the lines the
    CLI tests regex for must come out of the shared formatter."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(8)
    eng = _engine(gen, params)
    eng.submit(Request("f0", rng.integers(0, cfg.vocab, size=5)
                       .astype(np.int32),
                       SamplingParams(max_new_tokens=4)))
    eng.run()
    s = eng.metrics.summary()
    assert {"ttft", "itl", "queue", "step", "snapshot"} <= \
        set(s["latency"])
    assert s["latency"]["ttft"]["p50"] is not None
    assert s["latency"]["ttft"]["p99"] >= s["latency"]["ttft"]["p50"]
    lines = format_stats(s, prefix=True, failures=True, recovery=True)
    text = "\n".join(lines)
    assert "engine metrics: mean ttft" in text
    assert "latency slo: ttft p50/p95/p99" in text
    assert "decode horizon:" in text and "dispatches/token" in text
    assert "prefix cache:" in text and "failure containment:" in text
    assert "crash recovery:" in text
    assert "trace cache (compiles/hits):" in text
    line = format_statline(s)
    assert "ttft p50/p95/p99" in line and "step" in line
    # the cheap periodic/postmortem path renders identically without
    # materializing the per-request map
    assert format_statline(eng.metrics.light_summary()) == line
    # long-lived engines: mean_ttft must come from the all-time
    # histogram, not the pruned requests map
    eng.metrics.requests_retain = 0
    eng.metrics.requests.clear()
    assert eng.metrics.summary()["mean_ttft"] == \
        pytest.approx(s["mean_ttft"])


# ---------------------------------------------------------------------------
# kill/restart: postmortem flush + provenance across restore
# ---------------------------------------------------------------------------


def test_injected_kill_leaves_flight_file_and_restore_carries_trail(
        tiny, tmp_path):
    """An injected kill (the PR 5 harness's stand-in for process death)
    leaves a readable flight_*.json whose last event precedes the crash
    window, and a restored engine re-carries the dead life's trail
    (snapshot tail seeding + a restore event)."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(9)
    d = str(tmp_path / "snap")
    inj = FaultInjector(seed=1)
    eng = _engine(gen, params, snapshot_dir=d, snapshot_every=100,
                  faults=inj)
    prompts = {f"k{i}": rng.integers(0, cfg.vocab, size=5)
               .astype(np.int32) for i in range(2)}
    for rid, p in prompts.items():
        eng.submit(Request(rid, p, SamplingParams(max_new_tokens=6)))
    for _ in range(3):
        eng.step()                      # mid-stream state on disk
    eng.snapshot()
    inj.inject("forward", op="paged_decode", kill=True)
    with pytest.raises(InjectedKill):
        eng.run(max_steps=200)

    files = [n for n in os.listdir(d)
             if n.startswith("flight_") and n.endswith(".json")]
    assert files, os.listdir(d)
    rec = trace_mod.load_flight(trace_mod.latest_flight(d))
    assert rec["reason"].startswith("crash: InjectedKill")
    assert rec["statline"] and "ttft" in rec["statline"]
    evs = rec["events"]
    assert evs, "the ring must have flushed"
    # the last event precedes (or marks) the crash window: nothing in
    # the file postdates the step the kill landed on
    kill_step = inj.fired[-1][4]
    assert all(e[1] <= kill_step for e in evs)
    assert evs[-1][2] == "fault" and evs[-1][4]["point"] == "crash"
    # the kill's own audit entry was mirrored before the flush
    assert any(e[2] == "fault" and e[4].get("kind") == "kill"
               for e in evs)

    # restore: the dead life's trail precedes the new life's events
    eng2 = ServeEngine.restore(d, gen, params)
    evs2 = eng2.trace.events()
    assert any(e[2] == "restore" for e in evs2)
    assert any(e[2] == "submit" and e[3] == "k0" for e in evs2), (
        "snapshot tail must seed the restored ring")
    outs = eng2.run(max_steps=500)
    assert all(len(outs[rid].token_ids) == 6 for rid in prompts)


def test_watchdog_trip_flushes_flight(tiny, tmp_path, monkeypatch):
    """A watchdog trip — the engine-level stall signal — flushes the
    ring under TDT_DUMP_IR (the non-snapshot flight-dir path)."""
    cfg, params, gen = tiny
    d = str(tmp_path / "dump")
    monkeypatch.setenv("TDT_DUMP_IR", d)
    rng = np.random.default_rng(10)
    # op-filtered, no at_call: the stall lands on the FIRST decode
    # dispatch whatever the prefill-arrival count is (an at_call pin
    # would race the chunk count; a compile stall tripping the watchdog
    # first is equally fine — the asserts only need one trip + flush)
    inj = FaultInjector().inject("forward", op="paged_decode",
                                 stall_s=3.0)
    eng = _engine(gen, params, faults=inj, step_timeout_s=0.5)
    eng.submit(Request("w0", rng.integers(0, cfg.vocab, size=5)
                       .astype(np.int32),
                       SamplingParams(max_new_tokens=4)))
    from triton_dist_tpu.runtime.watchdog import WatchdogTimeout
    with pytest.raises(WatchdogTimeout):
        eng.run(max_steps=50)
    path = trace_mod.latest_flight(d)
    assert path is not None
    rec = trace_mod.load_flight(path)
    assert any(e[2] == "fault" and e[4].get("point") == "watchdog"
               for e in rec["events"])


def test_trace_level_zero_records_nothing(tiny):
    cfg, params, gen = tiny
    rng = np.random.default_rng(11)
    eng = _engine(gen, params, trace_level=0)
    eng.submit(Request("z0", rng.integers(0, cfg.vocab, size=5)
                       .astype(np.int32),
                       SamplingParams(max_new_tokens=4)))
    eng.run()
    assert eng.trace.events() == [] and eng.trace.emitted == 0
    assert eng.flight_flush("noop") is None


def test_rotated_journal_preserves_first_token_time(tmp_path):
    """The bounded token-time window None-pads the head of rotation's
    tts/ts lists on long streams; the explicit ``ftt`` carried by the
    done/submit records keeps a restored TTFT honest instead of
    inflating it to the first RETAINED stamp (review regression)."""
    from triton_dist_tpu.serve.recovery import replay_journal

    rm = RequestMetrics(arrival_time=0.0)
    rm.first_token_time = 1.0
    # seeding must never override an explicitly carried first stamp
    rm.seed_token_times([None, None, 500.0, 501.0], total=4)
    assert rm.first_token_time == 1.0
    assert rm.ttft == 1.0 and rm.n_tokens == 4

    path = tmp_path / "journal.jsonl"
    recs = [
        {"t": "done", "rid": "d0", "prompt": [1, 2], "params":
         SamplingParams(max_new_tokens=4).to_dict(), "arrival": 0.0,
         "ftt": 1.0, "toks": [5, 6, 7, 8],
         "tts": [None, None, 500.0, 501.0], "reason": "length",
         "err": None, "fts": 501.0},
        {"t": "submit", "rid": "i0", "prompt": [3], "params":
         SamplingParams(max_new_tokens=4).to_dict(), "ts": 0.0,
         "ftt": 2.0},
        {"t": "tok", "rid": "i0", "i": 0, "tok": 9, "ts": None},
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    j = replay_journal(path)
    assert j["d0"].first_tok == 1.0
    assert j["i0"].first_tok == 2.0
    assert j["d0"].token_list() == [5, 6, 7, 8]


# ---------------------------------------------------------------------------
# per-program wall-time attribution (the ISSUE-14 serve-time tentpole:
# engine step time decomposes by device program)
# ---------------------------------------------------------------------------


def test_program_timing_summary_matches_prometheus(tiny):
    """summary()["programs"] and the ``serve_program_ms{program=}``
    exposition agree: every program's histogram round-trips through
    ``LogHistogram.from_prom`` bucket-exactly, and the horizon rung
    actually served shows up as its own label."""
    from triton_dist_tpu.serve.fleet import parse_prometheus

    cfg, params, gen = tiny
    rng = np.random.default_rng(5)
    eng = _engine(gen, params, horizon=4)
    eng.warmup()
    # warmup's compile stalls must not have polluted the distributions
    assert not any(h.count for h in eng.metrics.program_hists.values())
    for i in range(3):
        eng.submit(Request(f"p{i}", rng.integers(0, cfg.vocab, size=5)
                           .astype(np.int32),
                           SamplingParams(max_new_tokens=5)))
    eng.run()
    progs = eng.metrics.summary()["programs"]
    assert "prefill_chunk" in progs and "fill_pages" in progs
    # the rung the horizon planner actually served is its own label
    assert any(p.startswith("decode_horizon[H=") for p in progs), progs
    for st in progs.values():
        assert st["count"] >= 1 and st["p50"] > 0 and st["p99"] > 0
    g = parse_prometheus(eng.metrics.to_prometheus())
    for name, live in eng.metrics.program_hists.items():
        h = LogHistogram.from_prom(g, "serve_program_ms",
                                   labels=f'program="{name}"')
        assert h.counts == live.counts and h.count == live.count
        assert h.sum == live.sum and h.min == live.min
        assert h.max == live.max
    # the shared formatters carry the breakdown
    line = [ln for ln in format_stats(eng.metrics.summary())
            if ln.startswith("program ms:")]
    assert line and "prefill_chunk" in line[0]
    assert "top program" in format_statline(
        eng.metrics.light_summary())


def test_program_timing_off_at_level_zero(tiny):
    cfg, params, gen = tiny
    rng = np.random.default_rng(6)
    eng = _engine(gen, params, trace_level=0)
    eng.warmup()
    eng.submit(Request("q0", rng.integers(0, cfg.vocab, size=5)
                       .astype(np.int32),
                       SamplingParams(max_new_tokens=4)))
    eng.run()
    assert eng.metrics.program_hists == {}
    assert eng.metrics.summary()["programs"] == {}
    assert "serve_program_ms" not in eng.metrics.to_prometheus()


def test_program_hists_merge_and_scrapes_bucket_exact():
    """ServeMetrics.merge and merge_scrapes both aggregate the
    per-program histograms bucket-exactly against the pooled-sample
    reference — including a program only one replica ever ran."""
    from triton_dist_tpu.serve.fleet import merge_scrapes, parse_prometheus

    a, b, pooled = ServeMetrics(), ServeMetrics(), ServeMetrics()
    for m in (a, b, pooled):
        m.program_timing = True
    sa = [0.3, 1.7, 22.0, 0.9]
    sb = [0.4, 5.0]
    only_b = [2.5, 2.6]
    for v in sa:
        a.observe_program("paged_decode", v)
        pooled.observe_program("paged_decode", v)
    for v in sb:
        b.observe_program("paged_decode", v)
        pooled.observe_program("paged_decode", v)
    for v in only_b:
        b.observe_program("decode_horizon[H=8]", v)
        pooled.observe_program("decode_horizon[H=8]", v)

    scraped = merge_scrapes([a.to_prometheus(), b.to_prometheus()])
    g = parse_prometheus(scraped)
    a.merge(b)   # the in-process path
    for name, ref in pooled.program_hists.items():
        assert a.program_hists[name].counts == ref.counts, name
        h = LogHistogram.from_prom(g, "serve_program_ms",
                                   labels=f'program="{name}"')
        assert h.counts == ref.counts and h.count == ref.count, name
        assert h.sum == ref.sum and h.min == ref.min
        assert h.max == ref.max
    # percentiles of the merged equal percentiles of the pooled
    assert (a.program_hists["paged_decode"].percentile(95)
            == pooled.program_hists["paged_decode"].percentile(95))


def test_program_timer_labels_statics():
    """CountingJit's timed_statics suffix the label with the static
    kwargs' values (the rung-laddered programs' per-rung attribution),
    and MISS calls stay out of the timer — a compile stall is compile
    accounting, never program wall time."""
    from triton_dist_tpu.runtime.jit_cache import CountingJit

    seen = []
    fn = CountingJit(lambda *a, **k: 0, "prog",
                     timer=lambda label, ms: seen.append(label),
                     timed_statics=("H",))
    fn(1, H=8)              # first signature: a miss — not timed
    assert seen == [] and fn.misses == 1
    fn(1, H=8)
    fn(2, H=2)              # miss again (fresh signature)
    fn(2, H=2)
    fn(3)
    fn(3)
    assert seen == ["prog[H=8]", "prog[H=2]", "prog"]


# ---------------------------------------------------------------------------
# step spans (FlightRecorder.span, docs/observability.md "Step spans")
# ---------------------------------------------------------------------------


def _mixed(cfg, n=5, max_new=9, tag="s"):
    """Greedy and seeded-sampled requests of several lengths."""
    rng = np.random.default_rng(11)
    return [Request(
        f"{tag}{i}", rng.integers(1, cfg.vocab, 5 + 3 * i).astype(np.int32),
        SamplingParams(max_new_tokens=max_new) if i % 2 == 0 else
        SamplingParams(max_new_tokens=max_new, temperature=0.8, top_k=16,
                       seed=40 + i))
        for i in range(n)]


def _run(eng, reqs):
    for r in reqs:
        eng.submit(r)
    return {rid: o.token_ids for rid, o in eng.run().items()}


def test_step_phases_sum_to_the_step_and_names_are_closed(tiny):
    """The self times of the phases under ``step`` sum to its total (a
    span's time is its parent's child time, so nothing is counted twice
    and nothing is lost), every name is a STEP_PHASES member, and every
    ``span("...")`` literal in the program is one — and each member has
    a literal, so the set cannot rot."""
    cfg, params, gen = tiny
    eng = _engine(gen, params, horizon=4, pipeline=2)
    _run(eng, _mixed(cfg))
    table = eng.trace.phases
    assert table["step"][0] == eng.metrics.steps
    assert table["submit"][0] == 5
    under_step = sum(s for k, (_, _, s) in table.items() if k != "submit")
    assert under_step == table["step"][1]           # exact, in ns
    for name in table:
        base = name.removesuffix(".compile")
        assert (base in trace_mod.STEP_PHASES
                or base.startswith("dispatch.")), name
    for name in ("admit", "prefill", "prefill.stage", "prefill.wait",
                 "prefill.commit", "decode.plan", "decode.stage",
                 "decode.wait", "decode.commit", "observe",
                 "dispatch.decode_horizon", "dispatch.prefill_chunk",
                 "dispatch.fill_pages", "dispatch.sample_token"):
        assert name in table or name + ".compile" in table, name
    # an engine with a horizon decodes through its links alone (a
    # clamped step is the H = 1 link), sampled rows on the device
    assert not any(k.startswith("dispatch.paged_decode") for k in table)
    # no warm-up here: each program's first call compiled, and is booked
    # apart from the steady-state row of the program
    assert table["dispatch.fill_pages.compile"][0] >= 1
    # the summary and the exposition are the same table
    ph = eng.metrics.summary()["phases"]
    assert ph["step"]["calls"] == eng.metrics.steps
    total = sum(v["self_s"] for k, v in ph.items() if k != "submit")
    assert total == pytest.approx(ph["step"]["total_s"], rel=0.01)
    prom = _parse_prom(eng.metrics.to_prometheus())
    key = 'serve_step_phase_seconds_total{phase="decode.commit"}'
    assert prom[key] == pytest.approx(ph["decode.commit"]["self_s"],
                                      abs=1e-8)
    assert prom['serve_step_phase_calls_total{phase="step"}'] == \
        eng.metrics.steps

    literals = set()
    for path in ([os.path.join(REPO, "triton_dist_tpu", "runtime",
                               "jit_cache.py")]
                 + [os.path.join(REPO, "triton_dist_tpu", "serve", f)
                    for f in os.listdir(os.path.join(
                        REPO, "triton_dist_tpu", "serve"))
                    if f.endswith(".py")]):
        with open(path) as f:
            literals |= {m.rstrip(".") for m in re.findall(
                r'\bspan\(\s*"([a-z][a-z_.]*)"', f.read())}
    assert literals == set(trace_mod.STEP_PHASES)


def test_phases_merge_and_scrape_like_counters(tiny):
    cfg, params, gen = tiny
    a, b = _engine(gen, params), _engine(gen, params)
    _run(a, _mixed(cfg, n=2))
    _run(b, _mixed(cfg, n=3))
    agg = ServeMetrics().merge(a.metrics).merge(b.metrics)
    for k in set(a.trace.phases) | set(b.trace.phases):
        want = [x + y for x, y in zip(a.trace.phases.get(k, [0, 0, 0]),
                                      b.trace.phases.get(k, [0, 0, 0]))]
        assert agg.phases[k] == want, k
    # the aggregate owns its table: the engines' are untouched by it
    assert a.trace.phases["submit"][0] == 2
    from triton_dist_tpu.serve.fleet import merge_scrapes, parse_prometheus
    merged = parse_prometheus(merge_scrapes(
        [a.metrics.to_prometheus(), b.metrics.to_prometheus()]))
    key = 'serve_step_phase_calls_total{phase="step"}'
    assert merged[key] == a.metrics.steps + b.metrics.steps
    key = 'serve_step_phase_seconds_total{phase="admit"}'
    assert merged[key] == pytest.approx(
        agg.phase_stats()["admit"]["self_s"], abs=1e-7)


def test_spans_land_on_the_profilers_host_plane(tiny, tmp_path):
    """Under a running profile every span is an event of the host plane
    (the plane whose clock the device planes share on a chip), children
    inside their parents, and each ``serve.step`` carries the index the
    ring's point events of that step carry."""
    from jax.profiler import ProfileData

    cfg, params, gen = tiny
    eng = _engine(gen, params)
    _run(eng, _mixed(cfg, tag="w"))                 # compile outside
    step0 = eng.metrics.steps
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _run(eng, _mixed(cfg, tag="t"))
    finally:
        jax.profiler.stop_trace()
    pb = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
          for f in fs if f.endswith(".xplane.pb")]
    assert len(pb) == 1
    spans = []
    for plane in ProfileData.from_file(pb[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    assert plane.name == "/host:CPU"
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    names = Counter(n for n, *_ in spans)
    n_steps = eng.metrics.steps - step0
    assert names["serve.step"] == n_steps
    assert names["serve.submit"] == 5
    assert names["serve.dispatch.paged_decode"] > 0
    assert names["serve.decode.commit"] > 0
    steps = sorted((s for s in spans if s[0] == "serve.step"),
                   key=lambda s: s[1])
    assert [s[3]["step"] for s in steps] == \
        list(range(step0, step0 + n_steps))
    for name, lo, hi, stats in spans:
        assert name[len("serve."):].split(".")[0] in \
            trace_mod.STEP_PHASES | {"decode", "prefill"}
        if name in ("serve.step", "serve.submit"):
            continue
        (parent,) = [s for s in steps if s[1] <= lo and hi <= s[2]]
        assert stats["step"] == parent[3]["step"], name
    # ... and the ring's events of a step join to it by the same index
    ring_steps = {e[1] for e in eng.trace.events() if e[2] == "retire"
                  and e[3].startswith("t")}
    assert ring_steps <= {s[3]["step"] for s in steps}


def test_every_program_lowers_under_its_own_name(tiny):
    """Each engine program's HLO module is ``jit_<its name>`` — the name
    its CountingJit, ``_device_call`` and ``serve_program_ms`` use — so
    a device trace tells the programs apart; none is ``jit__unknown``.
    One-chip engine with a horizon (its warm-up traffic never reaches
    ``paged_decode``: a one-step decode is the H = 1 link), without one,
    and with a draft (the mesh engine: tests/test_serve_mesh.py)."""
    from triton_dist_tpu.analysis.jaxpr_audit import lowered_module_names

    cfg, params, _ = tiny
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    draft = Generator(cfg, mesh, axis="sp", max_seq=64)
    for kw in (dict(horizon=4), dict(horizon=1),
               dict(draft=draft, draft_params=params, spec_k=2)):
        # a Generator of its own: a signature is captured when a call
        # compiles, and the shared one's chunk program already has
        eng = _engine(Generator(cfg, mesh, axis="sp", max_seq=64), params,
                      **kw)
        eng.warmup()
        names = lowered_module_names(eng)
        assert {"paged_decode", "prefill_chunk", "fill_pages",
                "load_pages", "cow_copy", "sample_token"} <= set(names)
        if "draft" in kw:
            assert {"spec_round", "draft_prefill", "draft_join",
                    "draft_tail_step"} <= set(names)
        elif kw["horizon"] > 1:
            assert names.pop("paged_decode") == set()
            assert "decode_horizon" in names
        for prog, mods in names.items():
            if prog in ("paged_verify", "draft_step") and not mods:
                continue    # off these engines' paths: never called
            assert mods == {f"jit_{prog}"}, (prog, mods)


def test_span_is_the_shared_noop_at_level_zero(tiny, monkeypatch):
    """``trace_level=0`` turns the spans off with the ring: ``span()``
    hands back the one shared no-op, builds no annotation and keeps no
    table — and the tokens served do not depend on the level."""
    cfg, params, gen = tiny
    on = _engine(gen, params, horizon=4)
    want = _run(on, _mixed(cfg))
    assert on.trace.phases

    def boom(*a, **k):
        raise AssertionError("a TraceAnnotation was built at level 0")

    monkeypatch.setattr(trace_mod, "TraceAnnotation", boom)
    rec = FlightRecorder(level=0)
    assert rec.span("step") is trace_mod.NO_SPAN
    assert rec.span("decode.stage", rows=3) is trace_mod.NO_SPAN
    off = _engine(gen, params, horizon=4, trace_level=0)
    got = _run(off, _mixed(cfg))
    assert got == want                      # bit-identical streams
    assert off.trace.phases == {} and off.trace.emitted == 0
    assert off.metrics.summary()["phases"] == {}
    assert off.metrics.hist_step.count == off.metrics.steps
    # compile accounting does not ride the trace level
    assert off.metrics.compile_misses > 0
