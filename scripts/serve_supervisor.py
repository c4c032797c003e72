"""Supervisor for crash-resilient serving processes — single child or a
fleet of N engine replicas.

The end-to-end consumer of the engine's snapshot/restore layer
(docs/serving.md "Crash recovery" / "Fleet serving"): run serving
command(s) as child process(es), watch two liveness signals per child,
and restart from the latest snapshot when either says the engine is
gone:

- **process liveness** — the child exited nonzero (OOM-kill, TPU
  preemption, a crash, an injected ``os._exit``);
- **heartbeat staleness** — the child is alive but wedged: the engine
  beats its ``runtime.watchdog.Heartbeat`` file synchronously from the
  step loop, so ``Heartbeat.is_stalled`` going true means steps stopped
  (a hung device dispatch, a deadlocked host thread).  The supervisor
  SIGKILLs the wedged child — in-flight state is already durable in the
  token journal, so killing loses nothing a restart can't replay.

Restarts are PACED by :class:`serve.fleet.RestartBackoff` (exponential
with jitter, capped, and the attempt budget FORGIVEN once a life stays
healthy ``--healthy-reset`` seconds) — a crash-looping child no longer
burns its whole ``--max-restarts`` budget in seconds.  SIGTERM/SIGINT
to the supervisor forward to the child(ren) and reap them, so a killed
supervisor never orphans a running engine; the child is also reaped on
any other supervisor exit.  Each restart surfaces the dead child's
flight-recorder postmortem (``flight_<step>.json``) — files already
reported in a previous life are skipped, not reprinted.

**Fleet mode** (``--fleet N``, ROADMAP #4): N replica children, each
with its own snapshot dir (``<dir>/r<i>``), heartbeat, and health state
(HEALTHY → SUSPECT → DEAD — serve/fleet.py's state machine), restarted
independently under per-replica backoff.  The child command may use the
placeholders ``{dir}``, ``{hb}``, ``{port}``, ``{i}`` — the supervisor
substitutes per replica (``{port}`` counts up from
``--metrics-base-port``), and with a metrics port it scrapes each
replica's Prometheus endpoint for the queue-depth/running pressure
line the router reads (``serve.fleet.parse_prometheus`` — the
subprocess half of the fleet's load signal).

    python scripts/serve_supervisor.py \
        --snapshot-dir /tmp/serve-snap --heartbeat /tmp/serve-snap/hb \
        --hb-interval 2 --max-restarts 3 -- \
        python examples/serve.py --engine --requests 16 \
            --snapshot-dir /tmp/serve-snap --snapshot-every 8 \
            --heartbeat /tmp/serve-snap/hb --hb-interval 2

    JAX_PLATFORMS=cpu python scripts/serve_supervisor.py --fleet 2 \
        --snapshot-dir /tmp/fleet --metrics-base-port 9300 -- \
        python examples/serve.py --engine --requests 16 \
            --snapshot-dir {dir} --heartbeat {hb} --hb-interval 2 \
            --metrics-port {port}

``--fleet N`` with N > 1 is a CPU form (``JAX_PLATFORMS=cpu``) and is
refused otherwise: every child opens every chip of its host and a chip
belongs to one process at a time.  On a TPU host, supervise ONE child
that holds N one-chip engines or one N-chip mesh.

Exercised end-to-end (with children that kill themselves mid-run) by
tests/test_serve_example.py and tests/test_serve_fleet.py.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from triton_dist_tpu.runtime.watchdog import Heartbeat  # noqa: E402
from triton_dist_tpu.serve.fleet import (  # noqa: E402
    ReplicaState,
    RestartBackoff,
    parse_prometheus,
)

#: children the signal handlers / exit reaper must not orphan
_CHILDREN: dict[int, subprocess.Popen] = {}


def parse_args():
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--snapshot-dir", required=True,
                   help="the child's snapshot directory (fleet mode: "
                        "replica i uses <dir>/r<i>)")
    p.add_argument("--heartbeat", default=None,
                   help="heartbeat file the child beats each engine step; "
                        "stale => the child is wedged and gets SIGKILLed "
                        "(fleet mode: derived per replica)")
    p.add_argument("--hb-interval", type=float, default=5.0,
                   help="the child's heartbeat cadence in seconds "
                        "(stall = 3x this with no beat)")
    p.add_argument("--grace-s", type=float, default=30.0,
                   help="seconds after (re)start before stall detection "
                        "arms (model init + warmup beat nothing)")
    p.add_argument("--poll-s", type=float, default=0.5)
    p.add_argument("--max-restarts", type=int, default=3,
                   help="restart budget per child; forgiven after "
                        "--healthy-reset seconds of healthy uptime")
    p.add_argument("--backoff-base", type=float, default=0.5,
                   help="first restart delay in seconds (doubles per "
                        "consecutive crash, jittered)")
    p.add_argument("--backoff-cap", type=float, default=30.0,
                   help="restart delay ceiling in seconds")
    p.add_argument("--healthy-reset", type=float, default=60.0,
                   help="a life that stays up this long resets the "
                        "restart budget (a later crash is a fresh "
                        "incident, not attempt N of a crash loop)")
    p.add_argument("--resume-flag", default="--resume",
                   help="appended to the command on every restart "
                        "('' to disable)")
    p.add_argument("--fleet", type=int, default=None, metavar="N",
                   help="fleet mode: supervise N replica children "
                        "(per-replica snapshot dirs/heartbeats/backoff; "
                        "{dir}/{hb}/{port}/{i} substitute in the child "
                        "command)")
    p.add_argument("--metrics-base-port", type=int, default=None,
                   help="fleet mode: replica i serves Prometheus at "
                        "this port + i ({port} in the child command); "
                        "the supervisor scrapes it for the fleet "
                        "pressure line")
    p.add_argument("--fleet-stats-every", type=float, default=5.0,
                   help="fleet mode: seconds between fleet pressure "
                        "lines (needs --metrics-base-port)")
    p.add_argument("--aggregate-port", type=int, default=None,
                   help="fleet mode: serve a FLEET-LEVEL Prometheus "
                        "aggregate at this port — each GET scrapes "
                        "every replica's /metrics and merges them "
                        "(serve.fleet.merge_scrapes: counters summed, "
                        "SLO histograms bucket-exactly merged); needs "
                        "--metrics-base-port (docs/observability.md "
                        "'Fleet observability')")
    p.add_argument("--fleet-trace-out", default=None, metavar="PATH",
                   help="fleet mode: at exit, assemble the replicas' "
                        "flight_*.json postmortems into ONE replica-"
                        "namespaced Perfetto timeline at PATH "
                        "(serve.fleet.assemble_fleet_trace; open in "
                        "ui.perfetto.dev)")
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="the serving command, after --")
    args = p.parse_args()
    args.cmd = [c for c in args.cmd if c != "--"]
    if not args.cmd:
        p.error("no child command given (pass it after --)")
    if args.fleet is not None and args.fleet < 1:
        p.error(f"--fleet must be >= 1, got {args.fleet}")
    if (args.fleet is not None and args.fleet > 1
            and os.environ.get("JAX_PLATFORMS") != "cpu"):
        # Each child is a JAX process that opens EVERY chip of this host,
        # and a chip belongs to one process at a time: the second replica
        # would hang in backend init.  (This supervisor stays off jax —
        # it must never hold a chip itself — so the children's platform
        # is read from the environment they inherit.)
        p.error(
            f"--fleet {args.fleet}: N > 1 replica processes on one host "
            f"are only supported pinned to the CPU (JAX_PLATFORMS=cpu — "
            f"the test fleet).  On a TPU host a chip belongs to one "
            f"process, so the supported form is ONE process: N one-chip "
            f"engines in it (examples/serve.py --engine --fleet N) or "
            f"one N-chip mesh (--engine --mesh N), supervised as a "
            f"single child (no --fleet).")
    if args.aggregate_port is not None and args.fleet is None:
        p.error("--aggregate-port needs --fleet")
    if (args.aggregate_port is not None
            and args.metrics_base_port is None):
        p.error("--aggregate-port needs --metrics-base-port (the "
                "aggregate is a scrape-and-merge over the replica "
                "endpoints)")
    if args.fleet_trace_out is not None and args.fleet is None:
        p.error("--fleet-trace-out needs --fleet")
    if (args.metrics_base_port is None
            and any("{port}" in c for c in args.cmd)):
        # substituting the literal "None" would hand every child a
        # garbage argument and crash-loop the whole restart budget
        p.error("the child command uses {port} but no "
                "--metrics-base-port was given")
    return args


def _register(proc: subprocess.Popen) -> None:
    _CHILDREN[proc.pid] = proc


def _unregister(proc: subprocess.Popen) -> None:
    _CHILDREN.pop(proc.pid, None)


def reap_children(sig: Optional[int] = None, timeout: float = 10.0) -> None:
    """Forward ``sig`` (if given) to every live child, then reap them
    all — escalating to SIGKILL past ``timeout``.  Called from the
    signal handlers AND the supervisor's exit path, so a dying
    supervisor can never orphan a running engine."""
    for proc in list(_CHILDREN.values()):
        if proc.poll() is None and sig is not None:
            try:
                proc.send_signal(sig)
            except OSError:
                pass
    deadline = time.monotonic() + timeout
    for proc in list(_CHILDREN.values()):
        if proc.poll() is None:
            try:
                proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        _unregister(proc)


def install_signal_forwarding() -> None:
    """SIGTERM/SIGINT to the supervisor forward to the child(ren) and
    reap them before exiting — a killed supervisor used to orphan a
    running engine (and its heartbeat kept beating, so nothing else
    noticed either)."""
    def handler(signum, frame):
        print(f"[supervisor] caught signal {signum}: forwarding to "
              f"{len(_CHILDREN)} child(ren) and exiting", flush=True)
        reap_children(signum)
        sys.exit(128 + signum)

    signal.signal(signal.SIGTERM, handler)
    signal.signal(signal.SIGINT, handler)


def run_once(cmd: list[str], hb: str | None, hb_interval: float,
             grace_s: float, poll_s: float) -> tuple[int, bool]:
    """One child lifetime.  Returns (returncode, was_stalled).

    Stall detection ARMS only ``grace_s`` after launch (model init +
    warmup beat nothing): inside the grace window even a wedged child
    survives, and a child whose first beat lands at the grace edge is
    healthy the moment the detector arms — the arming boundary is
    pinned by tests/test_serve_fleet.py."""
    # Drop a stale heartbeat from the previous life: its age must not
    # trip the stall detector before the new child's first beat.
    if hb is not None and os.path.exists(hb):
        os.unlink(hb)
    proc = subprocess.Popen(cmd)
    _register(proc)
    started = time.monotonic()
    try:
        while True:
            rc = proc.poll()
            if rc is not None:
                return rc, False
            armed = time.monotonic() - started > grace_s
            if (hb is not None and armed
                    and Heartbeat.is_stalled(hb, interval_s=hb_interval)):
                print(f"[supervisor] heartbeat {hb} stale "
                      f"(> {3 * hb_interval:.1f}s): killing wedged child "
                      f"pid {proc.pid}", flush=True)
                proc.send_signal(signal.SIGKILL)
                proc.wait()
                return -signal.SIGKILL, True
            time.sleep(poll_s)
    finally:
        # Only a child that actually exited leaves the registry: an
        # exception escaping the poll loop must keep the live child
        # registered, or the exit-path reap_children would miss it —
        # the exact orphan this registry exists to prevent.
        if proc.poll() is not None:
            _unregister(proc)


def postmortem(snapshot_dir: str,
               seen: Optional[dict] = None) -> Optional[str]:
    """Surface the dead child's flight-recorder trail (the engine
    flushes ``flight_<step>.json`` on fault/kill paths — serve/trace.py;
    the embedded statline comes from the SAME
    ``serve.metrics.format_statline`` the CLI's periodic log uses, so
    the supervisor's view and the engine's can't drift).

    ``seen`` (a mutable ``{path: mtime}`` map the caller keeps across
    restarts) dedups the report: a file already surfaced in a previous
    life is skipped instead of reprinted on every restart — only a NEW
    flush (fresh path, or the same path rewritten) is news.  Returns
    the reported path, or ``None``."""
    import glob
    import json

    files = glob.glob(os.path.join(snapshot_dir, "flight_*.json"))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    mtime = os.path.getmtime(path)
    if seen is not None:
        if seen.get(path) == mtime:
            return None
        seen[path] = mtime
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, json.JSONDecodeError):
        print(f"[supervisor] postmortem {path}: unreadable", flush=True)
        return None
    line = (f"[supervisor] postmortem {path}: "
            f"{len(rec.get('events', []))} events at step "
            f"{rec.get('step')}, reason {rec.get('reason')!r}")
    if rec.get("audit"):
        # a FLEET flight file (FleetController.flight_flush) carries the
        # router decision audit — say so, it answers "why was this
        # request on that replica" post-hoc
        line += f", {len(rec['audit'])} routing decisions"
    if rec.get("statline"):
        line += f" — {rec['statline']}"
    print(line, flush=True)
    return path


def supervise_one(args) -> int:
    """The single-child loop (the original supervisor contract), now
    with paced restarts and deduped postmortems."""
    cmd = list(args.cmd)
    backoff = RestartBackoff(
        base_s=args.backoff_base, cap_s=args.backoff_cap,
        healthy_reset_s=args.healthy_reset,
        max_restarts=args.max_restarts)
    seen: dict = {}
    restarts = 0
    while True:
        label = "starting" if restarts == 0 else f"restart {restarts}"
        print(f"[supervisor] {label}: {' '.join(cmd)}", flush=True)
        backoff.on_start(time.monotonic())
        rc, stalled = run_once(cmd, args.heartbeat, args.hb_interval,
                               args.grace_s, args.poll_s)
        if rc == 0:
            print(f"[supervisor] child completed cleanly after "
                  f"{restarts} restart(s)", flush=True)
            return 0
        why = "stalled" if stalled else f"exited {rc}"
        postmortem(args.snapshot_dir, seen)
        delay = backoff.on_death(time.monotonic())
        restarts += 1
        if delay is None:
            print(f"[supervisor] child {why}; restart budget "
                  f"({args.max_restarts}) exhausted", flush=True)
            return 1
        print(f"[supervisor] child {why}; restarting from the latest "
              f"snapshot under {args.snapshot_dir} in {delay:.2f}s",
              flush=True)
        time.sleep(delay)
        if args.resume_flag and args.resume_flag not in cmd:
            cmd = cmd + [args.resume_flag]


# ---------------------------------------------------------------------------
# Fleet mode: N supervised replica children
# ---------------------------------------------------------------------------


class _Replica:
    """One supervised replica child: its substituted command, health
    state, backoff pacing, and postmortem dedup memory."""

    def __init__(self, i: int, args):
        self.name = f"r{i}"
        self.dir = os.path.join(args.snapshot_dir, self.name)
        os.makedirs(self.dir, exist_ok=True)
        # per-replica heartbeat, always under the replica dir (a shared
        # file across replicas would mask any single wedged child)
        self.hb = os.path.join(self.dir, "hb")
        self.port = (args.metrics_base_port + i
                     if args.metrics_base_port is not None else None)
        subst = {"{dir}": self.dir, "{hb}": self.hb,
                 "{port}": str(self.port), "{i}": str(i)}

        def sub(arg: str) -> str:
            for k, v in subst.items():
                arg = arg.replace(k, v)
            return arg
        self.cmd = [sub(c) for c in args.cmd]
        self.proc: Optional[subprocess.Popen] = None
        self.started = 0.0
        self.state = ReplicaState.DEAD
        self.restart_at: Optional[float] = 0.0  # due immediately
        self.backoff = RestartBackoff(
            base_s=args.backoff_base, cap_s=args.backoff_cap,
            healthy_reset_s=args.healthy_reset,
            max_restarts=args.max_restarts, seed=i)
        self.seen: dict = {}
        self.restarts = 0
        self.done = False     # exited 0
        self.failed = False   # budget exhausted

    def start(self, args, resume: bool) -> None:
        cmd = list(self.cmd)
        if resume and args.resume_flag and args.resume_flag not in cmd:
            cmd = cmd + [args.resume_flag]
        if os.path.exists(self.hb):
            os.unlink(self.hb)
        label = "starting" if self.restarts == 0 else \
            f"restart {self.restarts}"
        print(f"[supervisor] {self.name} {label}: {' '.join(cmd)}",
              flush=True)
        self.proc = subprocess.Popen(cmd)
        _register(self.proc)
        self.backoff.on_start(time.monotonic())
        self.started = time.monotonic()
        self.state = ReplicaState.HEALTHY
        self.restart_at = None

    def scrape_text(self) -> Optional[str]:
        """Raw /metrics text (the aggregate endpoint merges these)."""
        if self.port is None or self.proc is None:
            return None
        import urllib.request
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}/metrics",
                    timeout=2) as r:
                return r.read().decode()
        except Exception:  # noqa: BLE001 — a scrape is best-effort
            return None

    def scrape(self) -> Optional[dict]:
        text = self.scrape_text()
        return parse_prometheus(text) if text is not None else None


class _ScrapeAggregate:
    """``to_prometheus()`` adapter for ``serve.trace.start_metrics_server``:
    each GET scrapes every live replica and merges the texts through
    ``serve.fleet.merge_scrapes`` — the subprocess fleet's one-stop
    Prometheus aggregate (counters summed, SLO histograms merged
    bucket-exactly; docs/observability.md "Fleet observability")."""

    def __init__(self, replicas):
        self.replicas = replicas

    def to_prometheus(self) -> str:
        from concurrent.futures import ThreadPoolExecutor

        from triton_dist_tpu.serve.fleet import merge_scrapes

        # concurrent scrapes: each dead replica costs its 2 s timeout,
        # and paying them SERIALLY would stall this endpoint ~2*N
        # seconds exactly during the incidents it exists to observe —
        # wall time must be the max, not the sum
        with ThreadPoolExecutor(
                max_workers=max(len(self.replicas), 1)) as ex:
            scraped = list(ex.map(lambda r: r.scrape_text(),
                                  self.replicas))
        texts = [t for t in scraped if t is not None]
        out = merge_scrapes(texts)
        # per-replica one-hot health state (the fleet.FLEET_SERIES
        # `fleet_replica_state` series, subprocess edition): alerting
        # on the aggregate scrape sees WHICH breaker is open, not just
        # pressure (docs/observability.md "Fleet observability")
        from triton_dist_tpu.serve.fleet import replica_state_lines

        L = replica_state_lines((rep.name, rep.state)
                                for rep in self.replicas)
        return (f"# HELP fleet_scraped_replicas replicas answering "
                f"this aggregate scrape\n"
                f"# TYPE fleet_scraped_replicas gauge\n"
                f"fleet_scraped_replicas {len(texts)}\n"
                + "\n".join(L) + "\n" + out)


def supervise_fleet(args) -> int:
    """N replica children, each restarted independently under backoff
    with per-replica HEALTHY → SUSPECT → DEAD health (heartbeat age),
    plus a periodic fleet pressure line from the Prometheus scrape —
    the subprocess half of docs/serving.md "Fleet serving"."""
    replicas = [_Replica(i, args) for i in range(args.fleet)]
    # heartbeat stall detection only makes sense when the child command
    # actually BEATS the per-replica file ({hb}): arming it for a child
    # that never writes would read 'missing file' as 'stalled' once the
    # grace passes and SIGKILL every healthy replica in a loop until
    # the whole restart budget burned
    hb_used = any("{hb}" in c for c in args.cmd)
    if not hb_used:
        print("[supervisor] fleet: child command does not use {hb}; "
              "heartbeat stall detection disabled (process liveness "
              "only)", flush=True)
    if args.aggregate_port is not None:
        from triton_dist_tpu.serve.trace import start_metrics_server

        srv = start_metrics_server(_ScrapeAggregate(replicas),
                                   port=args.aggregate_port)
        print(f"[supervisor] fleet aggregate /metrics on port "
              f"{srv.server_address[1]} (scrape-and-merge over "
              f"{args.fleet} replicas)", flush=True)
    last_stats = time.monotonic()
    while True:
        now = time.monotonic()
        for rep in replicas:
            if rep.done or rep.failed:
                continue
            if rep.proc is None:
                if rep.restart_at is not None and now >= rep.restart_at:
                    rep.start(args, resume=rep.restarts > 0)
                continue
            rc = rep.proc.poll()
            if rc is not None:
                _unregister(rep.proc)
                rep.proc = None
                if rc == 0:
                    rep.done = True
                    rep.state = ReplicaState.DEAD
                    print(f"[supervisor] {rep.name} completed cleanly "
                          f"after {rep.restarts} restart(s)", flush=True)
                    continue
                rep.state = ReplicaState.DEAD
                postmortem(rep.dir, rep.seen)
                delay = rep.backoff.on_death(now)
                rep.restarts += 1
                if delay is None:
                    rep.failed = True
                    print(f"[supervisor] {rep.name} exited {rc}; "
                          f"restart budget ({args.max_restarts}) "
                          f"exhausted", flush=True)
                else:
                    rep.restart_at = now + delay
                    print(f"[supervisor] {rep.name} exited {rc}; "
                          f"restarting in {delay:.2f}s", flush=True)
                continue
            # alive: heartbeat-driven health (armed past the grace,
            # and only when the child command beats the file at all)
            armed = hb_used and now - rep.started > args.grace_s
            age = Heartbeat.age_s(rep.hb)
            if armed and Heartbeat.is_stalled(
                    rep.hb, interval_s=args.hb_interval):
                print(f"[supervisor] {rep.name} heartbeat stale: "
                      f"killing wedged child pid {rep.proc.pid}",
                      flush=True)
                rep.proc.send_signal(signal.SIGKILL)
                rep.proc.wait()
                # the exit is handled as a death on the next poll
                continue
            if (armed and age is not None
                    and age > 1.5 * args.hb_interval):
                if rep.state is ReplicaState.HEALTHY:
                    rep.state = ReplicaState.SUSPECT
                    print(f"[supervisor] {rep.name} SUSPECT: heartbeat "
                          f"{age:.1f}s old", flush=True)
            elif rep.state is ReplicaState.SUSPECT:
                rep.state = ReplicaState.HEALTHY
                print(f"[supervisor] {rep.name} recovered", flush=True)
        if all(r.done or r.failed for r in replicas):
            if args.fleet_trace_out is not None:
                from triton_dist_tpu.serve.fleet import \
                    assemble_fleet_trace

                out = assemble_fleet_trace(
                    [(rep.name, rep.dir) for rep in replicas],
                    args.fleet_trace_out)
                print(f"[supervisor] fleet timeline: "
                      f"{out or 'no flight files to assemble'}",
                      flush=True)
            failed = [r.name for r in replicas if r.failed]
            if failed:
                print(f"[supervisor] fleet done; FAILED replicas: "
                      f"{failed}", flush=True)
                return 1
            print(f"[supervisor] fleet completed cleanly "
                  f"({args.fleet} replicas)", flush=True)
            return 0
        if (args.metrics_base_port is not None
                and now - last_stats >= args.fleet_stats_every):
            last_stats = now
            # concurrent scrapes: a serial walk would block THIS loop —
            # the one doing stall detection and restart pacing — for up
            # to 2 s per unreachable replica, exactly mid-incident
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                    max_workers=max(len(replicas), 1)) as ex:
                scrapes = list(ex.map(lambda r: r.scrape(), replicas))
            parts = []
            for rep, g in zip(replicas, scrapes):
                if g is None:
                    parts.append(f"{rep.name}[{rep.state.value}]")
                else:
                    parts.append(
                        f"{rep.name}[{rep.state.value}] "
                        f"q={int(g.get('serve_queue_depth', 0))} "
                        f"run={int(g.get('serve_running', 0))}")
            print(f"[supervisor] fleet: {' | '.join(parts)}", flush=True)
        time.sleep(args.poll_s)


def main() -> int:
    args = parse_args()
    install_signal_forwarding()
    try:
        if args.fleet is not None:
            return supervise_fleet(args)
        return supervise_one(args)
    finally:
        # the supervisor never exits with a live orphan, whatever path
        # got it here (normal return, exception, sys.exit)
        reap_children(signal.SIGTERM)


if __name__ == "__main__":
    sys.exit(main())
