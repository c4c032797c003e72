"""examples/serve.py: the serving CLI (+ scripts/serve_supervisor.py)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "examples", "serve.py")
SUPERVISOR = os.path.join(REPO, "scripts", "serve_supervisor.py")


def _env(devices):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def _run(*extra, devices=8, new_tokens=4, expect_rc=0):
    out = subprocess.run(
        [sys.executable, SCRIPT, "--new-tokens", str(new_tokens), *extra],
        capture_output=True, text=True, env=_env(devices), timeout=600)
    assert out.returncode == expect_rc, (out.returncode,
                                         out.stderr[-2000:])
    return out.stdout


def _too_few_devices(*extra, devices):
    """--mesh N on a runtime with fewer devices exits non-zero and says
    why (a run asked for N chips that served on fewer shows nothing)."""
    out = subprocess.run(
        [sys.executable, SCRIPT, "--new-tokens", "4", *extra],
        capture_output=True, text=True, env=_env(devices), timeout=600)
    assert out.returncode != 0, out.stdout[-2000:]
    assert "--mesh 4 needs 4 devices" in out.stderr, out.stderr[-2000:]
    assert "done" not in out.stdout


def test_serve_llama_sampled_w8a8():
    out = _run("--model", "llama", "--temperature", "0.7", "--top-k", "32",
               "--w8a8")
    assert "decode 4 steps" in out and "done" in out
    assert "w8a8 prompt scoring vs float: cosine 0.99" in out


def test_serve_moe_greedy():
    out = _run("--model", "moe")
    assert "decode 4 steps" in out and "done" in out


def test_serve_speculative_batched():
    """--speculative on a world-1 mesh at batch 3 (the r5 batched q_lens
    verify path end-to-end through the CLI)."""
    out = _run("--batch", "3", "--speculative", "3", devices=1,
               new_tokens=6)
    assert "speculative decode k=3" in out, out


def test_serve_engine_mode():
    """--engine: continuous-batching over the paged KV cache through the
    CLI (staggered traffic, metrics summary)."""
    out = _run("--engine", "--requests", "5", "--stagger", "2",
               "--max-batch", "3", "--page-size", "8", devices=1,
               new_tokens=5)
    assert "engine: 25 tokens / 5 requests" in out, out
    assert "mean ttft" in out and "done" in out


def test_serve_engine_speculative():
    out = _run("--engine", "--requests", "3", "--speculative", "2",
               "--spec-adaptive", "4", devices=1, new_tokens=4)
    assert "engine: 12 tokens / 3 requests" in out, out
    assert "verify)" in out and "done" in out
    # PR 7: the fused-round spec stats line (acceptance, chosen-k
    # histogram, spec tokens/dispatch)
    assert "speculative:" in out and "fused rounds" in out, out
    assert "chosen k" in out, out


def test_serve_engine_mesh():
    """--engine --mesh N: the sharded engine through the CLI (TP
    weights + sharded paged KV under shard_map), plus the loud ERROR
    when the runtime lacks the devices, plus --kv-shard seq."""
    out = _run("--engine", "--mesh", "2", "--requests", "3",
               "--max-batch", "2", "--page-size", "8", devices=2,
               new_tokens=4)
    assert "mesh serving: 2 devices" in out, out
    assert "engine: 12 tokens / 3 requests" in out and "done" in out
    # not enough devices: an error, never a skip that exits 0
    _too_few_devices("--engine", "--mesh", "4", "--requests", "2",
                     devices=1)
    # seq layout end to end
    out = _run("--engine", "--mesh", "2", "--kv-shard", "seq",
               "--requests", "2", "--max-batch", "2", devices=2,
               new_tokens=4)
    assert "kv_shard='seq'" in out and "done" in out, out
    # --mesh without --engine is rejected, not silently ignored
    out = subprocess.run(
        [sys.executable, SCRIPT, "--mesh", "2"], capture_output=True,
        text=True, env=_env(2), timeout=600)
    assert out.returncode != 0
    assert "--mesh is an engine-mode flag" in out.stderr


def test_serve_engine_mesh2d():
    """--engine --mesh 4 --kv-shard heads+seq: the 2D serving mesh
    through the CLI — N factored into tp x sp (4 -> 2x2), TP weights
    over tp, block-sharded paged KV over sp — plus the loud ERROR when
    the runtime lacks the devices."""
    out = _run("--engine", "--mesh", "4", "--kv-shard", "heads+seq",
               "--requests", "3", "--max-batch", "2", "--page-size",
               "8", devices=4, new_tokens=4)
    assert "mesh serving: 4 devices over axes ('tp', 'sp') = 2 x 2" \
        in out, out
    assert "kv_shard='heads+seq'" in out, out
    assert "engine: 12 tokens / 3 requests" in out and "done" in out
    _too_few_devices("--engine", "--mesh", "4", "--kv-shard",
                     "heads+seq", "--requests", "2", devices=2)


def test_serve_engine_spec_adaptive_validated():
    """--spec-adaptive is validated like --sessions: a negative window
    or a use without --speculative is an argparse error, not a silent
    no-op."""
    _run("--engine", "--speculative", "2", "--spec-adaptive", "-1",
         devices=1, expect_rc=2)
    _run("--engine", "--spec-adaptive", "4", devices=1, expect_rc=2)
    _run("--engine", "--speculative", "0", devices=1, expect_rc=2)


def test_serve_engine_chaos():
    """--chaos: seeded fault injection through the engine traffic — the
    run drains, every request retires with a reason, and the failure-
    containment accounting prints."""
    out = _run("--engine", "--chaos", "--requests", "6", "--seed", "3",
               "--page-size", "8", "--max-batch", "2", devices=1,
               new_tokens=4)
    assert "failure containment:" in out, out
    assert "/ 6 requests" in out and "done" in out
    # every request printed a retirement line with a known reason
    import re
    reasons = re.findall(r"req-\d+: prompt \d+ -> \d+ tokens \((\w+)\)",
                         out)
    assert len(reasons) == 6, out
    assert set(reasons) <= {"length", "error", "shed", "deadline"}


def test_serve_engine_mixed_warmup():
    """--mixed --warmup: lengths swept across the bucket ladder compile
    only during warmup; the trace-cache report proves traffic itself was
    compile-free (0 extra compiles beyond warmup's)."""
    out = _run("--engine", "--mixed", "--warmup", "--requests", "6",
               "--prompt-len", "10", "--page-size", "8", devices=1,
               new_tokens=4)
    assert "mixed traffic: ladder" in out, out
    assert "warmup:" in out and "compile-free" in out
    assert "trace cache (compiles/hits):" in out
    # every program the traffic compiled was compiled during warmup
    import re
    warm = int(re.search(r"warmup: (\d+) programs", out).group(1))
    compiles = sum(int(c) for c in
                   re.findall(r"\w+ (\d+)c/\d+h", out))
    assert compiles == warm, out


def test_serve_engine_snapshot_kill_resume(tmp_path):
    """--snapshot-dir + --kill-at-step + --resume: the first run dies
    mid-flight (os._exit — a real process death), the second restores
    from the journal + snapshot and finishes every stream; the token
    total matches a run that never crashed."""
    d = str(tmp_path / "snap")
    base = ("--engine", "--requests", "4", "--stagger", "2",
            "--max-batch", "2", "--page-size", "8",
            "--snapshot-dir", d, "--snapshot-every", "3")
    out = _run(*base, "--kill-at-step", "7", devices=1, new_tokens=6,
               expect_rc=17)
    assert "killing engine process at step 7" in out, out
    assert os.path.exists(os.path.join(d, "journal.jsonl"))

    out = _run(*base, "--kill-at-step", "7", "--resume", devices=1,
               new_tokens=6)          # the kill marker gates a re-kill
    assert "resumed from snapshot:" in out, out
    assert "engine: 24 tokens / 4 requests" in out, out
    assert "crash recovery:" in out and "done" in out
    import re
    reasons = re.findall(r"req-\d+: prompt \d+ -> (\d+) tokens \((\w+)\)",
                         out)
    assert len(reasons) == 4 and all(r == ("6", "length")
                                     for r in reasons), out


def test_serve_supervisor_restarts(tmp_path):
    """scripts/serve_supervisor.py end-to-end: the child serve process
    kills itself mid-run; the supervisor notices the death, restarts it
    with --resume, and the restarted child drains cleanly from the
    snapshot (satellite: the supervisor is the tentpole's consumer)."""
    d = str(tmp_path / "sup")
    hb = os.path.join(d, "hb")
    child = [sys.executable, SCRIPT, "--engine", "--requests", "4",
             "--stagger", "2", "--max-batch", "2", "--page-size", "8",
             "--new-tokens", "6", "--snapshot-dir", d,
             "--snapshot-every", "3", "--heartbeat", hb,
             "--hb-interval", "2", "--kill-at-step", "7"]
    out = subprocess.run(
        [sys.executable, SUPERVISOR, "--snapshot-dir", d,
         "--heartbeat", hb, "--hb-interval", "2", "--grace-s", "120",
         "--max-restarts", "2", "--", *child],
        capture_output=True, text=True, env=_env(1), timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "child exited 17; restarting" in out.stdout, out.stdout
    assert "resumed from snapshot:" in out.stdout, out.stdout
    assert "engine: 24 tokens / 4 requests" in out.stdout, out.stdout
    assert "completed cleanly after 1 restart(s)" in out.stdout, out.stdout


def test_serve_engine_fleet_cli(tmp_path):
    """--fleet N with a mid-run replica kill through the CLI: every
    request retires with its full stream, at least one completes on a
    different replica than it started on (the placement path printed
    per request), and the fleet summary shows the death + migration
    (docs/serving.md "Fleet serving")."""
    out = _run("--engine", "--fleet", "2", "--requests", "6",
               "--stagger", "1", "--max-batch", "2", "--page-size", "8",
               "--fleet-kill-step", "6", "--snapshot-dir",
               str(tmp_path / "fleet"), devices=1, new_tokens=6)
    assert "fleet: 2 replicas" in out, out
    assert "chaos: killing replica r0" in out, out
    assert "fleet: 36 tokens / 6 requests" in out, out
    assert "1 deaths" in out, out
    assert "live-migrated requests:" in out, out
    import re
    reasons = re.findall(r"req-\d+: prompt \d+ -> (\d+) tokens "
                         r"\((\w+)\) via (\S+)", out)
    assert len(reasons) == 6, out
    assert all(r[:2] == ("6", "length") for r in reasons), out
    assert any(">" in r[2] for r in reasons), out
    assert "done" in out


def test_serve_disagg_cli(tmp_path):
    """--disagg P:D through the CLI: every request's printed journey is
    prefill replica -push-> decode replica, the summary counts the
    pushes, and combining --disagg with --engine/--mesh or a malformed
    spec is rejected (docs/serving.md "Disaggregated serving")."""
    out = _run("--disagg", "1:2", "--requests", "4", "--stagger", "2",
               "--max-batch", "2", "--page-size", "8", "--snapshot-dir",
               str(tmp_path / "disagg"), devices=1, new_tokens=5)
    assert "disagg tier: 1 prefill + 2 decode replicas" in out, out
    assert "'r0': 'prefill'" in out and "'r1': 'decode'" in out, out
    assert "disagg: 20 tokens / 4 requests" in out, out
    assert "4 pushes, 0 fallbacks, 0 deaths" in out, out
    import re
    paths = re.findall(r"req-\d+: prompt \d+ -> (\d+) tokens "
                       r"\((\w+)\) via (\S+) -push-> (\S+)", out)
    assert len(paths) == 4, out
    assert all(p[:3] == ("5", "length", "r0") for p in paths), out
    assert all(p[3] in ("r1", "r2") for p in paths), out
    assert "routing audit: route->r0 decode_target->" in out, out
    assert "done" in out
    # --disagg is its own mode, and the spec shape is validated
    for extra in (("--disagg", "1:2", "--engine"),
                  ("--disagg", "1:2", "--mesh", "2"),
                  ("--disagg", "nope")):
        _run(*extra, devices=1, expect_rc=2)


def test_serve_engine_kv_dtype_int8():
    """--kv-dtype int8 (ISSUE 17): the engine serves on quantized
    pools and the end-of-run stats block reports the QUANTIZED pool
    bytes (the capacity the flag exists to buy), and the dispatch-time
    rejection matrix refuses the combinations the engine would reject
    at construction."""
    out = _run("--engine", "--kv-dtype", "int8", "--requests", "3",
               "--page-size", "8", devices=1, new_tokens=5)
    assert "engine: 15 tokens / 3 requests" in out, out
    import re
    m = re.search(r"kv pool: (\d+) bytes for (\d+) token slots "
                  r"\(([\d.]+) B/token, int8\+scales\)", out)
    assert m, out
    # the CLI engine model: n_layers=2, Hkv=2, D=16 -> 2*2*2*(16+4)
    assert float(m.group(3)) == 160.0, out
    assert int(m.group(1)) == 160 * int(m.group(2)), out
    assert "done" in out
    # rejection matrix: bare mode wants --kv-int8; spec needs float KV;
    # serving modes refuse the bare-demo flag
    _run("--kv-dtype", "int8", devices=1, expect_rc=2)
    _run("--engine", "--kv-dtype", "int8", "--speculative", "2",
         devices=1, expect_rc=2)
    _run("--engine", "--kv-int8", devices=1, expect_rc=2)


def test_serve_engine_horizon():
    """--horizon: fused multi-step decode through the CLI — the decode
    stats line proves the dispatch economics (well under one dispatch
    per token), and every request still retires with its full stream."""
    out = _run("--engine", "--horizon", "8", "--pipeline", "2",
               "--requests", "4", "--stagger", "1", "--max-batch", "4",
               "--page-size", "8", devices=1, new_tokens=12)
    assert "horizon 8 (pipeline 2)" in out, out
    assert "engine: 48 tokens / 4 requests" in out, out
    import re
    m = re.search(r"([\d.]+) dispatches/token", out)
    assert m, out
    assert float(m.group(1)) < 0.5, out
    assert "done" in out


def test_serve_engine_shared_prompt():
    """--shared-prompt: every request carries one shared system-prompt
    prefix — the prefix-cache stats line must show hits and skipped
    prefill tokens (docs/serving.md 'Prefix caching')."""
    out = _run("--engine", "--shared-prompt", "--requests", "4",
               "--prompt-len", "24", "--max-batch", "2", "--page-size",
               "8", devices=1, new_tokens=4)
    assert "engine: 16 tokens / 4 requests" in out, out
    import re
    m = re.search(r"prefix cache: (\d+)/(\d+) lookups hit, (\d+) "
                  r"prefill tokens skipped", out)
    assert m, out
    assert int(m.group(1)) >= 1 and int(m.group(3)) > 0, out
    assert "done" in out


def test_serve_engine_sessions():
    """--sessions: multi-turn conversations — turns >= 1 re-admit their
    whole history through the prefix cache (hits on the stats line),
    and every turn's requests retire."""
    out = _run("--engine", "--sessions", "3", "--requests", "2",
               "--prompt-len", "8", "--max-batch", "2", "--page-size",
               "8", devices=1, new_tokens=4)
    import re
    m = re.search(r"prefix cache: (\d+)/(\d+) lookups hit", out)
    assert m and int(m.group(1)) >= 2, out     # turns 2-3 hit history
    # 2 base requests + 2 turns x 2 follow-ups, 4 tokens each
    assert re.search(r"req-0\.t2: prompt \d+ -> 4 tokens", out), out
    assert "done" in out


def test_serve_engine_migrate_in_cli(tmp_path):
    """--migrate-in (the recovery.save_manifest docstring's promise): a
    killed run's journal becomes a JSON manifest, a fresh CLI process
    adopts it at startup, prints per-request placement, and serves the
    carried requests to completion."""
    d1 = str(tmp_path / "src")
    # a run that dies mid-stream leaves its journal behind
    _run("--engine", "--requests", "3", "--stagger", "1", "--max-batch",
         "2", "--page-size", "8", "--snapshot-dir", d1,
         "--kill-at-step", "6", devices=1, new_tokens=8, expect_rc=17)
    from triton_dist_tpu.serve.recovery import (
        manifest_from_journal,
        save_manifest,
    )

    manifest = manifest_from_journal(d1, mark=True)
    assert manifest["requests"], "kill-at-step left nothing in flight"
    path = str(tmp_path / "manifest.json")
    save_manifest(manifest, path)
    out = _run("--engine", "--requests", "0", "--stagger", "1",
               "--max-batch", "2", "--page-size", "8",
               "--migrate-in", path, devices=1, new_tokens=8)
    import re
    for rec in manifest["requests"]:
        # JSON manifests are KV-stripped: every request requeues
        assert f"migrate-in {rec['rid']}: requeued" in out, out
        assert re.search(rf"{rec['rid']}: prompt \d+ -> 8 tokens "
                         rf"\(length\)", out), out
    assert re.search(r"migrate-in: 0 adopted, \d+ requeued, 0 rejected",
                     out), out
    assert "done" in out


def test_serve_engine_serve_port_cli(tmp_path):
    """--serve-port: the network ingest end-to-end through the CLI — a
    request submitted over POST /submit streams back over GET /stream,
    and the child exits on --serve-idle-exit."""
    import json as _json
    import subprocess as _sp
    import time as _time
    import urllib.request

    d = str(tmp_path / "rep")
    os.makedirs(d, exist_ok=True)
    proc = _sp.Popen(
        [sys.executable, SCRIPT, "--engine", "--new-tokens", "6",
         "--serve-port", "0", "--snapshot-dir", d,
         "--serve-idle-exit", "8", "--serve-deadline", "240",
         "--max-batch", "2", "--page-size", "8"],
        env=_env(1), stdout=_sp.PIPE, stderr=_sp.STDOUT, text=True)
    try:
        from triton_dist_tpu.serve.net import PORT_FILE, read_port_file
        port = read_port_file(os.path.join(d, PORT_FILE),
                              deadline_s=180.0)
        url = f"http://127.0.0.1:{port}"

        def post(path, doc):
            req = urllib.request.Request(
                url + path, data=_json.dumps(doc).encode(),
                method="POST",
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                return _json.loads(r.read().decode())

        resp = post("/submit", {"rid": "wire-0",
                                "prompt": [5, 6, 7, 8],
                                "params": {"max_new_tokens": 6}})
        assert resp.get("ok"), resp
        t0 = _time.monotonic()
        while True:
            with urllib.request.urlopen(
                    f"{url}/stream?rid=wire-0&since=0",
                    timeout=30) as r:
                st = _json.loads(r.read().decode())
            if st["done"]:
                break
            assert _time.monotonic() - t0 < 120
            _time.sleep(0.05)
        assert len(st["tokens"]) == 6 and st["reason"] == "length"
        post("/shutdown", {})
        out, _ = proc.communicate(timeout=120)
        assert proc.returncode == 0, out[-2000:]
        assert "net: replica serving at" in out, out
        assert "net: serve loop exited" in out, out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_serve_engine_program_breakdown():
    """Per-program wall-time attribution through the CLI (ISSUE 14):
    the end-of-run stats block renders the shared format_stats
    "program ms:" line naming the horizon rung actually served, and
    the --stats-every periodic line carries the top-program fragment
    from the same light_summary."""
    out = _run("--engine", "--warmup", "--horizon", "8", "--pipeline",
               "2", "--requests", "4", "--stagger", "1", "--max-batch",
               "4", "--page-size", "8", "--stats-every", "2",
               devices=1, new_tokens=12)
    import re
    m = re.search(r"program ms: .*$", out, re.M)
    assert m, out
    # new_tokens=12: 11 post-prefill tokens bucket to the H=8 rung
    # first — the rung the engine actually served must be named
    assert "decode_horizon[H=8]" in m.group(0), m.group(0)
    assert "prefill_chunk" in m.group(0), m.group(0)
    # the periodic statline shares the breakdown (top program by total)
    assert re.search(r"stats: .*\| top program \S+ p50 [\d.]+ ms",
                     out), out
    assert "done" in out
