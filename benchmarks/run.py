"""One cell of BENCHMARK.json, once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the serving engine of the cell's configuration through the
constructors ``chip_smoke.py`` uses (weights on the device from ``--seed``),
warms up the programs the cell's traffic can reach, ramps the traffic to a
steady state (all of that is ``setup_s``), measures for ``--seconds`` with
no executable compiled or fetched inside the window (jax's own count),
frees the engine, and compares a sample of what the window served with the
plain float32 reference.  The last line of stdout is the result object.

What the numbers mean (PERF.md §2 has the reasons):

- The client's clock is the ``on_token`` callback.  A COMMIT EVENT is the
  set of deliveries one ``engine.step()`` makes, stamped with its last
  delivery.  The window opens at the first commit event at or after the
  ramp's end and closes at the last one before ``--seconds`` have passed;
  ``out_tok_per_s`` is the tokens of the events after the opening one up to
  and including the closing one, over the time between the two.  No lump
  of a horizon burst is cut.
- Percentiles are over the requests whose last token falls inside the
  window, by nearest rank, and only with ten samples beyond the rank.
- ``--trace 1`` traces a window of at most TRACE_SECONDS and reports the
  per-layer metrics of that window; ``--trace 0`` reports the end-to-end
  metrics with the profiler off.

Off a TPU it exits non-zero and prints no result.  ``--cpu-dryrun`` is the
only other way in: the same code at a toy size with the kernels in the
Pallas interpreter, for rehearsal; its line says so in ``device`` and
carries no metric under a device name.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SECONDS = 15.0    # traced window: traces are large and tracing slows the host
BEYOND = 10             # samples that must lie beyond a reported percentile


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def die(msg: str):
    raise SystemExit(f"[bench] FAIL: {msg}")


class CompileTally:
    """Every executable XLA is asked for in this process, compiled or
    fetched from the persistent cache, counted where jax reports it (the
    idea of ``chip_smoke.CompileTally``, copied: the yardstick may not
    lean on the program for its own gate)."""

    def __init__(self):
        from jax import monitoring

        self.requests, self.seconds, self.cache_hits = 0, 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def named(self):
        """While open, collect jax's line for every executable it compiles
        or fetches: a gate that counts must also name."""
        import jax

        names = []
        handler = logging.Handler()
        handler.emit = lambda rec: names.append(rec.getMessage())
        logger = logging.getLogger("jax._src.dispatch")
        logger.addHandler(handler)
        logger.propagate = False
        jax.config.update("jax_log_compiles", True)
        try:
            yield names
        finally:
            jax.config.update("jax_log_compiles", False)
            logger.propagate = True
            logger.removeHandler(handler)


# ---------------------------------------------------------------------------
# Load generation: one process, one thread, the engine's own step loop
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Rec:
    """One request as its client sees it."""

    rid: str
    client: int
    n_prompt: int
    max_new: int
    sampled: bool
    due: float              # when it was due to be sent
    sent: float = 0.0
    t_first: float = 0.0    # first delivery
    t_last: float = 0.0     # last delivery
    n: int = 0              # tokens delivered
    step_done: int = -1     # index of the engine step that retired it
    out: object = None      # the program's RequestOutput


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    tokens: int             # delivered by this step
    t_last: float           # its last delivery (the commit event's stamp)
    rows: int               # requests decoding when the step began
    ctx_sum: int            # their contexts, summed
    decode_steps: int       # device decode steps the step ran (program's count)
    kv_util: float


class Driver:
    """Feeds one traffic stream to one engine and keeps the clients' view.

    Closed loop: ``clients`` callers, each sends its next request when its
    last one finished.  Open loop: arrivals on the stream's schedule,
    whether or not earlier ones have finished; a request is timed from
    when it was DUE, and how late the generator ran is reported.
    """

    def __init__(self, engine, stream, *, annotate=None, tag: str = "q"):
        self.engine, self.stream, self.tag = engine, stream, tag
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.recs: dict = {}
        self.inflight: dict = {}
        self.done: list = []
        self.steps: list = []
        self.late: list = []
        self._step_tokens, self._step_last = 0, 0.0
        p = stream.params
        self.closed = stream.loop == "closed"
        if self.closed:
            self.n_clients = min(int(p["clients"]), engine.max_batch)
            self.to_start = list(range(self.n_clients))
            self.idle: list = []
            self.first_wave: list = []
            self.phase_first = bool(p["ramp"].get("first_output_phase"))
            self.stagger = int(p["ramp"].get("start_stagger_steps", 0))
        else:
            self._pending = stream.next()
            self.t_next = None

    # -- the client side ----------------------------------------------------

    def _on_token(self, rid, token):
        t = time.perf_counter()
        r = self.recs[rid]
        if r.n == 0:
            r.t_first = t
        r.t_last = t
        r.n += 1
        self._step_tokens += 1
        self._step_last = t

    def _send(self, spec, client: int, due: float, max_new=None):
        from triton_dist_tpu.serve import Request, SamplingParams

        s = self.stream.sampler
        params = SamplingParams(
            max_new_tokens=max_new or spec.max_new,
            temperature=s["temperature"] if spec.sampled else 0.0,
            top_k=s["top_k"] if spec.sampled else None,
            top_p=s["top_p"] if spec.sampled else None,
            seed=spec.sampler_seed)
        rec = Rec(rid=f"{self.tag}{spec.index}", client=client,
                  n_prompt=int(spec.prompt.shape[0]),
                  max_new=params.max_new_tokens, sampled=spec.sampled,
                  due=due, sent=time.perf_counter())
        self.recs[rec.rid] = self.inflight[rec.rid] = rec
        self.late.append(rec.sent - due)
        shed = self.engine.submit(Request(rec.rid, spec.prompt, params,
                                          on_token=self._on_token))
        if shed is not None:
            self._retire(shed)

    def _retire(self, out):
        rec = self.inflight.pop(out.request_id, None)
        if rec is None:
            return
        rec.out, rec.step_done = out, len(self.steps) - 1
        self.done.append(rec)
        if self.closed:
            self.idle.append(rec.client)

    def _feed(self):
        now = time.perf_counter()
        if self.closed:
            n = len(self.to_start) if not self.stagger else 1
            first = [self.to_start.pop(0) for _ in range(min(n, len(self.to_start)))]
            for c in first:
                spec = self.stream.next()
                new = None
                if self.phase_first:
                    # steady state from the start: client c begins
                    # (c + 1/2)/clients of the way through its answer
                    frac = 1.0 - (c + 0.5) / self.n_clients
                    new = max(2, int(round(spec.max_new * frac)))
                self._send(spec, c, now, new)
                self.first_wave.append(self.recs[f"{self.tag}{spec.index}"])
            while self.idle:
                self._send(self.stream.next(), self.idle.pop(0), now)
            return
        if self.t_next is None:
            self.t_next = now
        while self.t_next <= now:
            self._send(self._pending, -1, self.t_next)
            self._pending = self.stream.next()
            self.t_next += self._pending.gap_s

    # -- one turn of the loop -------------------------------------------------

    def pump(self, deadline: float) -> None:
        """Send what is due, then one engine step; when the engine has
        nothing to do (open loop between arrivals) sleep to the next
        arrival or the deadline."""
        with self.annotate("loadgen"):
            self._feed()
        eng = self.engine
        if not eng.has_work():
            wake = min(self.t_next if not self.closed else deadline, deadline)
            time.sleep(max(0.0, min(wake - time.perf_counter(), 0.05)))
            return
        dec = [r for r in self.inflight.values() if r.n]
        d0 = eng.metrics.decode_steps
        self._step_tokens = 0
        t0 = time.perf_counter()
        with self.annotate("engine.step"):
            outs = eng.step()
        t1 = time.perf_counter()
        self.steps.append(StepRec(
            t0, t1, self._step_tokens, self._step_last, len(dec),
            sum(r.n_prompt + r.n for r in dec),
            eng.metrics.decode_steps - d0, eng.bm.utilization))
        for out in outs:
            self._retire(out)

    def ramped(self, t_start: float) -> bool:
        ramp = self.stream.params["ramp"]
        if self.closed:
            return (not self.to_start
                    and all(r.n or r.out for r in self.first_wave)
                    and len(self.done) >= int(ramp["settle_finished"]))
        return time.perf_counter() - t_start >= float(ramp["seconds"])


# ---------------------------------------------------------------------------
# The cell
# ---------------------------------------------------------------------------


def load_cell(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        die(f"--workload {workload!r} is not in BENCHMARK.json "
            f"({sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def in_cell(m):
        return workload in m.get("workloads", [workload])

    return {"cell": cell, "config_file": os.path.join(ROOT, config["file"]),
            "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
            "per_layer": [m for m in bench["per_layer"] if in_cell(m)]}


def device_info(n: int, dryrun: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n if not dryrun else len(devs)}
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs[:n]]
    info["memory_peak_bytes"] = int(max(peaks))
    if dryrun:
        info["cpu_dryrun"] = True
    return info


def tpot_ms(recs: list) -> list:
    """Per request, the mean gap between its deliveries after the first."""
    return [1e3 * (r.t_last - r.t_first) / (r.n - 1) for r in recs if r.n > 1]


def end_to_end(drv: Driver, lo: int, hi: int, t_open: float,
               beyond: int = BEYOND) -> tuple:
    """-> (values by metric name, sample counts, window facts) for the
    commit events of steps[lo:hi]."""
    from benchmarks.readers import nearest_rank

    steps = [s for s in drv.steps[lo:hi] if s.tokens]
    t_close = steps[-1].t_last
    tokens = sum(s.tokens for s in steps)
    recs = [r for r in drv.done if lo <= r.step_done < hi]
    tpot = tpot_ms(recs)
    ttft = [1e3 * (r.t_first - r.due) for r in recs if r.n]
    vals = {
        "out_tok_per_s": tokens / (t_close - t_open),
        "tpot_p50_ms": nearest_rank(tpot, 50, beyond),
        "ttft_p50_ms": nearest_rank(ttft, 50, beyond),
    }
    facts = {"t_close": t_close, "tokens": tokens, "events": len(steps),
             "window_s": t_close - t_open, "finished": len(recs)}
    return vals, {"tpot": len(tpot), "ttft": len(ttft)}, facts, recs


def malformed(rec: Rec, vocab: int) -> str:
    """Why a finished request counts as failed, or ''."""
    from triton_dist_tpu.serve.request import FinishReason

    out = rec.out
    if out.finish_reason is not FinishReason.LENGTH:
        return f"{rec.rid} finished {out.finish_reason.value} ({out.error})"
    toks = np.asarray(out.token_ids)
    if toks.shape[0] != rec.max_new or rec.n != rec.max_new:
        return (f"{rec.rid} emitted {toks.shape[0]} tokens, delivered "
                f"{rec.n}, budget {rec.max_new}")
    if toks.min() < 0 or toks.max() >= vocab:
        return f"{rec.rid} emitted a token outside [0, {vocab})"
    return ""


def check_outputs(config: dict, seed: int, recs: list, n_sample: int,
                  limits: dict, *, int8: bool = False) -> dict:
    """Compare a seeded sample of the greedy requests the window finished,
    the longest among them, with the plain reference: one teacher-forced
    pass over each prompt with its served tokens, then the gap by which
    each served token's reference logit lies below the reference's best.
    ``int8`` reads instead the gap of the token the int8 control puts
    first (the control never decodes)."""
    import importlib

    ref = importlib.import_module(
        f"benchmarks.reference.{config['reference']}")
    greedy = [r for r in recs if not r.sampled and r.n > 1]
    if not greedy:
        return {"numbers": {}, "ok": False, "why": "no greedy request "
                "finished inside the window: nothing to compare"}
    greedy.sort(key=lambda r: (-(r.n_prompt + r.n), r.rid))
    rng = np.random.default_rng([int(seed), 3])
    rest = greedy[1:]
    pick = [greedy[0]] + [rest[i] for i in rng.permutation(len(rest))[
        :max(0, n_sample - 1)]]
    seqs = [np.concatenate([r.out.prompt, np.asarray(r.out.token_ids,
                                                     np.int32)])
            for r in pick]
    n0 = [r.n_prompt for r in pick]
    t0 = time.perf_counter()
    logits = ref.forward_logits(config, seed, seqs, n0)
    chosen = [np.asarray(r.out.token_ids) for r in pick]
    if int8:
        low = ref.forward_logits(config, seed, seqs, n0, int8=True)
        chosen = [lg.argmax(-1) for lg in low]
    gaps = np.concatenate([
        lg.max(-1) - np.take_along_axis(lg, tk[:, None], -1)[:, 0]
        for lg, tk in zip(logits, chosen)])
    numbers = {"gap_max": float(gaps.max()), "gap_mean": float(gaps.mean())}
    return {"numbers": numbers,
            "ok": all(numbers[k] <= limits[k] for k in numbers),
            "requests": [r.rid for r in pick], "tokens": int(gaps.size),
            "agree": float((gaps == 0).mean()),
            "seconds": time.perf_counter() - t0}


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--cpu-dryrun", action="store_true",
                   help="toy size on the CPU, for rehearsal only")
    p.add_argument("--keep-trace", action="store_true",
                   help="leave the traced window's events under "
                        "chiprun_out/ (how fixtures/ was recorded)")
    return p.parse_args(argv)


class Cell:
    """One cell opened in this process: its data files read, the device
    checked, the engine built and warmed.  ``run_cell`` measures it once;
    ``calibrate.py`` measures it for many seeds and reads the control."""

    def __init__(self, workload: str, seed: int, *, dry: bool = False,
                 control: bool = False):
        """``control``: build the program with the lower-precision path
        of its own that the configuration file names (``correct.
        control_engine``) switched on — read by ``calibrate.py`` and the
        tests, never by a benchmark run."""
        spec = load_cell(workload)
        self.spec, self.cell = spec, spec["cell"]
        self.chips = chips = int(self.cell["chips"])
        self.dry, self.seed = dry, int(seed)
        if dry:
            os.environ.setdefault("JAX_PLATFORMS", "cpu")

        # First thing, before any backend exists: where compiled programs
        # go.  JAX_COMPILATION_CACHE_DIR if it is set (nothing is set in
        # code then), else the fixed, git-ignored <checkout>/.jax_cache.
        from triton_dist_tpu.runtime.bootstrap import (
            configure_compile_cache,
            require_tpu,
        )

        self.cache_dir = configure_compile_cache()
        import jax

        from benchmarks import builders, traffic

        if dry:
            if jax.devices()[0].platform == "tpu":
                die("--cpu-dryrun is for a host with no chip")
        else:
            require_tpu("benchmarks/run.py", n_devices=chips)
        config = builders.load_config(spec["config_file"])
        if control:
            config["engine"].update(config["correct"]["control_engine"])
        self.config = builders.toy_config(config) if dry else config
        self.tparams = traffic.load(self.cell["traffic"])
        self.tally = CompileTally()
        self.t_import = time.perf_counter()
        stream = self.stream(self.seed)
        self.ladder = builders.reachable_ladder(
            self.config, [p for p, _, _ in stream.pairs])
        self.engine, _ = builders.build(self.config, self.seed, chips=chips,
                                        ladder=self.ladder, interpret=dry)
        self.t_built = time.perf_counter()
        self.warm = self.engine.warmup()
        self.t_warm = time.perf_counter()
        if self.engine.kernel_gaps and not dry and not control:
            die(f"the engine reports attention off its kernels: "
                f"{self.engine.kernel_gaps}")

    def stream(self, seed: int):
        from benchmarks import traffic

        st = traffic.Traffic(self.tparams, seed,
                             vocab=self.config["vocab_size"],
                             scale=1 / 8 if self.dry else 1.0)
        if self.dry:
            # toy answers: a few tokens each, the interpreter is slow
            st.pairs = [(p, max(2, o // 8), s) for p, o, s in st.pairs]
        return st

    def describe(self, args) -> None:
        import jax

        say(f"cell {self.cell['name']}: config {self.cell['config']}, "
            f"traffic {self.cell['traffic']}, chips {self.chips}, seed "
            f"{self.seed}, seconds {args.seconds}, trace {args.trace}; "
            f"compile cache {self.cache_dir} "
            f"({'from JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'fixed in the checkout'}); "
            f"device {jax.devices()[0].device_kind}"
            + ("; CPU DRY RUN at a toy size: rehearsal, not a measurement"
               if self.dry else ""))
        ms = self.stream(self.seed).multiset()
        say(f"traffic cycle of {len(ms['pairs'])}: prompts "
            f"{sum(p for p, _, _ in ms['pairs'])} tokens, outputs "
            f"{sum(o for _, o, _ in ms['pairs'])}, sampled "
            f"{sum(s for _, _, s in ms['pairs'])}"
            + (f", mean gap {np.mean(ms['gaps']):.4f} s"
               if "gaps" in ms else ""))


NAMES = ("decode_tokens", "dispatches", "decode_steps", "preemptions",
         "steps", "running_sum", "prefill_tokens")


class EngineTap:
    """The two reads of the program's private state, kept in one place
    until it names its programs itself (PERF.md §7, the `tracing` issue):
    its programs are jitted partials and reach the trace as
    ``jit__unknown``, so its one dispatch seam, ``_device_call``, is
    logged in order for ``xplane.relabel``; and its pools are what is
    waited on so that nothing is in flight when the trace starts or
    stops, and the log and the trace hold the same executions."""

    def __init__(self, engine):
        import jax

        self.engine, self.dispatched = engine, []
        jax.block_until_ready(engine._pools)
        seam = engine._device_call

        def logged(op, *a, **kw):
            self.dispatched.append(op)
            return seam(op, *a, **kw)

        engine._device_call = logged

    def close(self) -> list:
        import jax

        jax.block_until_ready(self.engine._pools)
        del self.engine._device_call
        return self.dispatched


def measure(cell: Cell, seed: int, seconds: float, *, traced: bool = False,
            keep_trace: bool = False) -> dict:
    """Ramp the traffic of ``seed`` to its steady state, then one measured
    window.  Returns what the window saw; the engine is left as it is."""
    import jax

    from benchmarks import xplane

    engine = cell.engine
    annotate = jax.profiler.TraceAnnotation if traced else None
    cell.windows = getattr(cell, "windows", 0) + 1
    drv = Driver(engine, cell.stream(seed), annotate=annotate,
                 tag="q" if cell.windows == 1 else f"w{cell.windows}q")
    # -- ramp: part of set-up ---------------------------------------------
    t_ramp = time.perf_counter()
    far = t_ramp + 3600.0
    while not (drv.ramped(t_ramp) and drv.steps and drv.steps[-1].tokens):
        drv.pump(far)
    # -- the window ---------------------------------------------------------
    lo = len(drv.steps)
    t_open = drv.steps[-1].t_last
    deadline = t_open + seconds
    m = engine.metrics
    c0 = {k: getattr(m, k) for k in NAMES}
    xla0 = cell.tally.requests
    trace_dir = os.path.join(ROOT, "chiprun_out", "bench_trace",
                             f"{cell.cell['name']}_{seed}")
    window_span = contextlib.nullcontext()
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        tap = EngineTap(engine)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_span = jax.profiler.TraceAnnotation("bench.window")
    with cell.tally.named() as compiled, window_span:
        while time.perf_counter() <= deadline:
            drv.pump(deadline)
    xla_in_window = cell.tally.requests - xla0
    c1 = {k: getattr(m, k) for k in NAMES}
    red = None
    if traced:
        dispatched = tap.close()
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        t_read = time.perf_counter()
        events = xplane.extract(xplane.newest_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        try:
            named = xplane.relabel(events, dispatched)
        except ValueError as e:
            die(str(e))
        if keep_trace:
            xplane.save_events(events, trace_dir + ".events.json.gz")
        t_reduce = time.perf_counter()
        red = xplane.reduce(events)
        say(f"trace: {len(dispatched)} engine dispatches logged, {named} "
            f"device executions named after them; writing it took "
            f"{t_read - t_stop:.1f} s, reading {t_reduce - t_read:.1f} s, "
            f"reducing {time.perf_counter() - t_reduce:.1f} s")
    if xla_in_window:
        die(f"{xla_in_window} executables were compiled or fetched inside "
            f"the measured window: " + "; ".join(
                x for x in compiled if "XLA compilation" in x))
    # an untraced window closes at the last commit event BEFORE the
    # deadline; a traced one keeps the step that passed it, so that the
    # counters cover exactly what the profiler saw
    hi = len(drv.steps)
    if not traced:
        while hi > lo and not (drv.steps[hi - 1].tokens
                               and drv.steps[hi - 1].t_last <= deadline):
            hi -= 1
    if hi == lo:
        die("no commit event inside the window: nothing was served")
    in_window = drv.steps[lo:hi]
    dsteps = sum(s.decode_steps for s in in_window) or 1
    counters = {f"engine.{k}": c1[k] - c0[k] for k in NAMES}
    counters.update({
        "xla.compiles_in_window": xla_in_window,
        "kv.util_peak": max(s.kv_util for s in in_window),
        "decode.rows_mean": sum(s.rows * s.decode_steps
                                for s in in_window) / dsteps,
        "decode.ctx_sum_mean": sum(s.ctx_sum * s.decode_steps
                                   for s in in_window) / dsteps,
    })
    return {"drv": drv, "lo": lo, "hi": hi, "t_ramp": t_ramp,
            "t_open": t_open, "counters": counters, "trace": red,
            "queue_depth": engine.scheduler.queue_depth}


def per_layer(cell: Cell, w: dict, drv: Driver) -> dict:
    """The cell's per-layer metrics of one measured window, by their
    readers.  One that finds nothing to read is left out — and in a traced
    run on the chip that is an error: the cell declares the metric, so a
    seam or a name it reads has moved."""
    import jax

    from benchmarks import readers

    lo, hi = w["lo"], w["hi"]
    samples = {"step_wall_ms": [1e3 * (s.t1 - s.t0)
                                for s in drv.steps[lo:hi]],
               "tpot_ms": tpot_ms([r for r in drv.done
                                   if lo <= r.step_done < hi])}
    ctx = {"counters": w["counters"], "samples": samples,
           "trace": w["trace"], "config": cell.config,
           "device_kind": jax.devices()[0].device_kind}
    out, missing = {}, []
    for mdef in cell.spec["per_layer"]:
        v = readers.read(mdef["name"], ctx)
        if v is None:
            missing.append(mdef["name"])
            continue
        if mdef["unit"] == "%" and v > 100.0:
            die(f"{mdef['name']} reads {v:.2f}%: the operations or bytes "
                f"are counted too high, or the time leaves out part of "
                f"the work")
        out[mdef["name"]] = {"value": float(v), "unit": mdef["unit"]}
    if missing and w["trace"] is not None:
        die(f"per-layer metrics with nothing to read in this cell: "
            f"{missing} (programs seen: {sorted(w['trace']['module_s'])})")
    return out


def drain(engine) -> None:
    """Abandon what is in flight (calibration, between seeds)."""
    for rid in list(engine.unfinished_rids()):
        engine.abort(rid)
    if engine.bm.num_free != engine.bm.num_allocatable:
        die(f"free list not whole after draining: {engine.bm.num_free} of "
            f"{engine.bm.num_allocatable}")


def say_check(check: dict, limits: dict, n_bad: int) -> None:
    for k, v in check["numbers"].items():
        say(f"check {k} {v:.6g} limit {limits[k]:.6g}")
    say(f"check malformed_requests {n_bad} limit 0")
    if "why" in check:
        say(f"check: {check['why']}")
    else:
        say(f"check compared {check['tokens']} served tokens of requests "
            f"{check['requests']} in {check['seconds']:.1f} s; the served "
            f"token is the reference's first choice at "
            f"{100 * check['agree']:.2f}%")


def run_cell(args, *, engine_hook=None, after_window=None) -> dict:
    """The whole run -> the result object.  ``engine_hook(engine)`` lets a
    test break the timed path underneath; ``after_window(state)`` lets a
    test read the control on the same requests."""
    from benchmarks import xplane

    dry = bool(args.cpu_dryrun)
    cell = Cell(args.workload, args.seed, dry=dry)
    cell.describe(args)
    config, seed, tally = cell.config, cell.seed, cell.tally
    if engine_hook is not None:
        engine_hook(cell.engine)
    traced = bool(args.trace) and not dry
    seconds = min(float(args.seconds), TRACE_SECONDS) if args.trace \
        else float(args.seconds)
    w = measure(cell, seed, seconds, traced=traced,
                keep_trace=args.keep_trace)
    t_end = time.perf_counter()
    drv, lo, hi, t_open = w["drv"], w["lo"], w["hi"], w["t_open"]

    vals, counts, facts, recs = end_to_end(drv, lo, hi, t_open,
                                           0 if dry else BEYOND)
    bad = [x for x in (malformed(r, config["vocab_size"]) for r in recs) if x]
    for x in bad[:5]:
        say(f"failed request: {x}")
    setup_s = t_open - T_PROCESS
    say(f"setup_s {setup_s:.2f} = imports {cell.t_import - T_PROCESS:.2f} + "
        f"weights and engine {cell.t_built - cell.t_import:.2f} + warm-up "
        f"{cell.t_warm - cell.t_built:.2f} ({cell.warm['programs']} "
        f"programs, ladder {list(cell.engine.ladder)}) + ramp "
        f"{t_open - w['t_ramp']:.2f} ({lo} steps, "
        f"{len([r for r in drv.done if r.step_done < lo])} requests "
        f"finished); xla requests {tally.requests}, cache hits "
        f"{tally.cache_hits}, compile seconds {tally.seconds:.1f}")
    late = np.asarray(drv.late[-max(1, len(recs)):])
    ttft = vals["ttft_p50_ms"]
    say(f"window {facts['window_s']:.3f} s between commit events, "
        f"{facts['events']} events, {facts['tokens']} tokens, "
        f"{facts['finished']} requests finished ({len(bad)} failed), "
        f"samples tpot {counts['tpot']} ttft {counts['ttft']} (ttft p50 "
        f"{'-' if ttft is None else format(ttft, '.1f')} ms, not judged); "
        f"generator late p50 {1e3 * np.median(late):.2f} ms max "
        f"{1e3 * late.max():.2f} ms; in flight at close {len(drv.inflight)}, "
        f"waiting {w['queue_depth']}")
    if not (drv.closed or dry) \
            and w["queue_depth"] >= cell.engine.max_batch:
        # an open loop above what the engine sustains: the queue grows all
        # through the run and every number of the cell means something else
        die(f"{w['queue_depth']} requests were waiting when the window "
            f"closed: the cell's fixed rate is above what the engine "
            f"sustains")

    device = device_info(cell.chips, dry)
    # -- free the program's state, then the reference -----------------------
    layer_vals = per_layer(cell, w, drv) if args.trace else {}
    if after_window is not None:
        after_window({"config": config, "seed": seed, "recs": recs})
    drv.engine = cell.engine = None
    gc.collect()
    limits = config["correct"]["limits"]
    check = check_outputs(config, seed, recs,
                          int(cell.tparams.get("check_sample", 4)), limits)
    say_check(check, limits, len(bad))
    correct = bool(check["ok"]) and not bad

    result = {"correct": correct, "attempted": len(recs),
              "failed": len(bad), "metrics": {}, "device": device}
    if args.trace:
        red = w["trace"]
        if red is not None:
            device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
            result["breakdown"] = xplane.breakdown(red)
            say("trace: device seconds by program " + json.dumps(
                {k: round(v, 4) for k, v in red["module_s"].items()}))
        result["metrics"] = layer_vals
    else:
        vals["setup_s"] = setup_s
        for mdef in cell.spec["end_to_end"]:
            v = vals.get(mdef["name"])
            if v is None:
                die(f"{mdef['name']} has no value: too few samples in the "
                    f"window (tpot {counts['tpot']}, ttft {counts['ttft']}; "
                    f"a percentile needs {BEYOND} beyond its rank)")
            result["metrics"][mdef["name"]] = {"value": float(v),
                                               "unit": mdef["unit"]}
    if dry:
        # a CPU time is not a slower device time: no device metric name
        result["metrics"] = {f"dryrun.{k}": v
                             for k, v in result["metrics"].items()}
    say(f"total wall {time.perf_counter() - T_PROCESS:.1f} s "
        f"(window closed at {t_end - T_PROCESS:.1f})")
    return result


def main(argv=None) -> int:
    result = run_cell(parse_args(argv))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
