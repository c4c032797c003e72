"""End-to-end state integrity (docs/serving.md "Durability &
integrity"): CRC-framed journals, snapshot leaf digests, wire manifest
digests, the ``integrity`` corruption fault point, and salvage
recovery.

Fast tier (all of it — this file is the tier-1 gate for ISSUE 20):

- the integrity primitives (canonical-JSON CRC framing, tri-state
  record verification, atomic digested JSON docs) and the
  ``corrupt_bytes`` action vocabulary;
- the ``integrity`` fault point: action validation, ``op``/``at_call``
  filters, the ``fired`` audit;
- journal semantics, PINNED: a torn FINAL line still replays exactly
  as before (CRC-framed and pre-integrity alike), while an interior
  bad line — undecodable, CRC-mismatched, or a token-index gap — is
  LOUD (:class:`JournalCorrupt` with a structured damage report; the
  pre-integrity silent ``continue`` was the ISSUE-20 bug);
- salvage keeps every record that still AUTHENTICATES (suffix records
  behind a rotted line survive — at fleet scale they hold migrated-in
  submits whose prompts exist nowhere else), quarantines the damaged
  original, and rewrites the journal CRC-framed;
- snapshot leaf digests: a bitflipped stored pool leaf refuses to
  restore naming the leaf, ``serve_fsck --salvage`` quarantines the
  step, and the restore falls back to the previous good step with
  bit-exact streams (the snapshot-leaf artifact class, end to end);
  pre-integrity snapshots restore unverified;
- wire manifest digests: KV-blob + request-metadata corruption is
  REJECTED (counted, traced) and the sender's fallback re-routes —
  pre-digest manifests decode unchanged and ``NET_PROTOCOL`` is
  unbumped (back-compat);
- THE corrupt-chaos harness (the ISSUE-20 acceptance bar): the network
  fleet under a bitflipped journal line on disk, a bitflipped
  drain-response blob, a bitflipped migrate_in manifest, plus a
  SIGKILL on the bit-rotted replica — every stream bit-identical to
  the single-engine oracle, exactly-once delivery, zero corrupt state
  adopted;
- the ``serve_fsck`` CLI (subprocess) and the
  ``durable-writes-integrity`` lint rule registration.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.runtime.faults import (
    CORRUPT_ACTIONS,
    FaultInjector,
    corrupt_bytes,
)
from triton_dist_tpu.serve import (
    Request,
    SamplingParams,
    ServeEngine,
    TokenJournal,
    replay_journal,
)
from triton_dist_tpu.serve.fleet import FleetController, RemoteReplica
from triton_dist_tpu.serve.integrity import (
    DOC_CRC,
    REC_CRC,
    atomic_write_json,
    canonical_crc,
    crc32_bytes,
    rec_crc_ok,
    stamp_crc,
    verify_json_doc,
)
from triton_dist_tpu.serve.net import (
    NET_PROTOCOL,
    InProcessReplica,
    ManifestCorrupt,
    corrupt_wire_doc,
    decode_manifest,
    encode_manifest,
)
from triton_dist_tpu.serve.recovery import (
    JOURNAL_NAME,
    KV_SUBDIR,
    META_NAME,
    JournalCorrupt,
    SnapshotCorrupt,
    _corrupt_snapshot_leaf,
    restore_engine,
    salvage_journal,
    scan_journal,
    snapshot_engine,
    verify_snapshot_step,
)
from triton_dist_tpu.serve.request import FinishReason

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FSCK = os.path.join(REPO, "scripts", "serve_fsck.py")


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
# integrity primitives + corrupt actions + the fault point
# ---------------------------------------------------------------------------


def test_crc_primitives_and_doc_framing(tmp_path):
    assert crc32_bytes(b"abc") == crc32_bytes(b"abc")
    assert crc32_bytes(b"abc") != crc32_bytes(b"abd")
    # canonical form is key-order independent; exclude= carves the
    # digest field out of its own coverage
    a = {"x": 1, "y": [2, 3]}
    b = {"y": [2, 3], "x": 1}
    assert canonical_crc(a) == canonical_crc(b)
    assert canonical_crc({"x": 1, "c": 9}, exclude=("c",)) == \
        canonical_crc({"x": 1})
    # record framing: tri-state verification
    rec = stamp_crc({"t": "tok", "rid": "a", "i": 0, "tok": 5})
    assert REC_CRC in rec and rec_crc_ok(rec) is True
    assert rec_crc_ok({"t": "tok", "rid": "a"}) is None  # pre-integrity
    bad = dict(rec)
    bad["tok"] = 6
    assert rec_crc_ok(bad) is False
    # atomic digested docs round-trip through disk
    p = str(tmp_path / "doc.json")
    atomic_write_json(p, {"k": [1, 2], "n": None})
    with open(p, encoding="utf-8") as f:
        doc = json.load(f)
    assert verify_json_doc(doc) is True and DOC_CRC in doc
    doc["k"].append(3)
    assert verify_json_doc(doc) is False
    assert verify_json_doc({"k": 1}) is None


def test_corrupt_bytes_actions():
    data = bytes(range(64))
    flip = corrupt_bytes(data, "bitflip")
    assert len(flip) == len(data)
    assert sum(a != b for a, b in zip(flip, data)) == 1
    assert len(corrupt_bytes(data, "truncate")) == len(data) // 2
    z = corrupt_bytes(data, "zero")
    assert len(z) == len(data) and set(z) == {0}
    assert corrupt_bytes(b"", "bitflip") == b""
    with pytest.raises(ValueError, match="unknown corrupt action"):
        corrupt_bytes(data, "scramble")


def test_integrity_fault_point_filters_and_audit():
    from triton_dist_tpu.serve.trace import FAULT_POINT_EVENTS
    assert "integrity" in FAULT_POINT_EVENTS
    with pytest.raises(ValueError, match="corrupt="):
        FaultInjector().inject("integrity", corrupt="scramble")
    inj = FaultInjector(seed=0)
    # the at_call counter is PER POINT, shared across ops: a filtered
    # arrival still advances it (call counts stay aligned with the
    # traffic, whatever op mix hit the seam)
    inj.inject("integrity", corrupt="bitflip", op="journal", at_call=3)
    assert inj.fire("integrity", op="drain") is None    # call 1, op filter
    assert inj.fire("integrity", op="journal") is None  # call 2 != 3
    assert inj.fire("integrity", op="journal") == "bitflip"
    assert inj.fire("integrity", op="journal") is None  # one-shot
    assert [(p, k) for p, _, k, _, _ in inj.fired] == \
        [("integrity", "bitflip")]
    # max_fires with no at_call: takes its op's FIRST arrival, once —
    # the robust chaos-harness arming pattern
    inj2 = FaultInjector(seed=0)
    inj2.inject("integrity", corrupt="zero", op="migrate_in", max_fires=1)
    assert inj2.fire("integrity", op="drain") is None
    assert inj2.fire("integrity", op="migrate_in") == "zero"
    assert inj2.fire("integrity", op="migrate_in") is None


# ---------------------------------------------------------------------------
# journal framing: torn tail pinned, interior damage loud, salvage
# ---------------------------------------------------------------------------


_JRECS = [
    {"t": "submit", "rid": "a", "prompt": [1, 2],
     "params": {"max_new_tokens": 4}, "ts": 0.0},
    {"t": "tok", "rid": "a", "i": 0, "tok": 10, "ts": 0.1},
    {"t": "tok", "rid": "a", "i": 1, "tok": 11, "ts": 0.2},
    {"t": "submit", "rid": "b", "prompt": [3, 4],
     "params": {"max_new_tokens": 4}, "ts": 0.3},
    {"t": "tok", "rid": "b", "i": 0, "tok": 20, "ts": 0.4},
    {"t": "tok", "rid": "a", "i": 2, "tok": 12, "ts": 0.5},
    {"t": "tok", "rid": "b", "i": 1, "tok": 21, "ts": 0.6},
]


def _write_journal(path, recs, *, framed=True, garbage_at=None,
                   torn=False):
    """Hand-write a journal: optionally CRC-framed, with line
    ``garbage_at`` (0-based) replaced by newline-terminated garbage,
    or the final line torn (no newline)."""
    with open(path, "w", encoding="utf-8") as f:
        for i, r in enumerate(recs):
            line = json.dumps(stamp_crc(dict(r)) if framed else r,
                              separators=(",", ":"))
            if i == garbage_at:
                line = line[:-6] + "\x00XY}]"
            if torn and i == len(recs) - 1:
                f.write(line[:len(line) // 2])
                return
            f.write(line + "\n")


@pytest.mark.parametrize("framed", [True, False])
def test_torn_tail_replays_exactly_as_before(tmp_path, framed):
    """PINNED: the one crash shape — a torn, newline-less final line —
    heals silently, for CRC-framed and pre-integrity journals alike."""
    p = str(tmp_path / "j.jsonl")
    _write_journal(p, _JRECS, framed=framed, torn=True)
    state, damage = scan_journal(p)
    assert damage is None
    assert state["a"].token_list() == [10, 11, 12]
    assert state["b"].token_list() == [20]   # b's last tok was torn
    # and replay_journal (the raising reader) agrees
    assert replay_journal(p)["a"].token_list() == [10, 11, 12]


@pytest.mark.parametrize("framed", [True, False])
def test_interior_corruption_is_loud_not_skipped(tmp_path, framed):
    """THE ISSUE-20 regression: a mid-file bad line used to be silently
    ``continue``d past; now it raises with a structured report —
    whether or not the journal predates CRC framing."""
    p = str(tmp_path / "j.jsonl")
    _write_journal(p, _JRECS, framed=framed, garbage_at=2)
    with pytest.raises(JournalCorrupt) as ei:
        replay_journal(p)
    dmg = ei.value.damage
    assert dmg.bad_lines and dmg.bad_lines[0][0] == 3
    assert dmg.total_lines == len(_JRECS)
    # the salvaged state still applied everything that authenticates:
    # b's records live BEHIND the bad line and survive
    assert ei.value.state["b"].token_list() == [20, 21]
    # a's damaged tok is a gap: truncated + reported, never absorbed
    assert ei.value.state["a"].token_list() == [10]
    assert ("a", 1) in dmg.gaps
    assert "a" in dmg.affected_rids
    assert dmg.last_good_tok["a"] == 0


@pytest.mark.parametrize("lost", [1, 2])
def test_finish_that_counted_a_rotted_token_is_not_adopted(tmp_path, lost):
    """A request FINISHED, then one of its tok lines rots (an interior
    one, or the last — which leaves no index gap to see): the ``fin``
    receipt counted 3 tokens and fewer authenticate, so the salvage
    must hand the request back unfinished (re-queue + recompute from
    the prefix), never a shortened stream marked complete.  ``b``, whose
    count still holds, stays finished."""
    p = str(tmp_path / JOURNAL_NAME)
    recs = _JRECS + [
        {"t": "fin", "rid": "a", "reason": "length", "err": None, "n": 3,
         "ts": 0.7},
        {"t": "fin", "rid": "b", "reason": "length", "err": None, "n": 2,
         "ts": 0.8}]
    a_toks = [i for i, r in enumerate(recs)
              if r["t"] == "tok" and r["rid"] == "a"]
    _write_journal(p, recs, garbage_at=a_toks[lost])
    state, damage = salvage_journal(p)
    assert damage is not None and "a" in damage.affected_rids
    assert state["a"].token_list() == [10, 11][:lost]
    assert state["a"].finish is None
    assert state["b"].finish["n"] == 2 == len(state["b"].token_list())
    # the rewritten journal agrees with itself: no receipt for ``a``
    again, clean = scan_journal(p)
    assert clean is None and again["a"].finish is None


def test_crc_mismatch_on_parseable_line_is_corruption(tmp_path):
    """A record that PARSES but fails its CRC (the silent-rot shape
    JSON alone cannot see) is damage, not state."""
    p = str(tmp_path / "j.jsonl")
    recs = [stamp_crc(dict(r)) for r in _JRECS]
    recs[1]["tok"] = 99                     # rot after stamping
    with open(p, "w", encoding="utf-8") as f:
        for r in recs:
            f.write(json.dumps(r, separators=(",", ":")) + "\n")
    with pytest.raises(JournalCorrupt) as ei:
        replay_journal(p)
    assert ei.value.damage.bad_lines == [(2, "crc mismatch")]
    # the poisoned token was never applied: gap at 0
    assert ei.value.state["a"].token_list() == []


def test_final_line_garbage_with_newline_is_corruption(tmp_path):
    """A newline-TERMINATED garbage final line is not a torn tail — a
    torn write cannot re-close the framing (this is how a ``zero``
    action on the last line stays loud)."""
    p = str(tmp_path / "j.jsonl")
    _write_journal(p, _JRECS, garbage_at=len(_JRECS) - 1)
    with pytest.raises(JournalCorrupt):
        replay_journal(p)


def test_salvage_quarantines_and_rewrites_authenticated(tmp_path):
    p = str(tmp_path / JOURNAL_NAME)
    _write_journal(p, _JRECS, garbage_at=2)
    state, dmg = salvage_journal(p)
    assert dmg is not None and dmg.quarantine
    assert os.path.exists(dmg.quarantine)
    assert dmg.quarantine.startswith(p + ".corrupt-")
    assert state["b"].token_list() == [20, 21]
    # the rewritten journal is clean, CRC-framed, and replay-equal
    with open(p, encoding="utf-8") as f:
        for line in f:
            assert rec_crc_ok(json.loads(line)) is True
    state2 = replay_journal(p)
    assert state2["a"].token_list() == state["a"].token_list()
    assert state2["b"].token_list() == [20, 21]
    # undamaged journals come back untouched (no quarantine)
    p2 = str(tmp_path / "clean.jsonl")
    _write_journal(p2, _JRECS)
    _, dmg2 = salvage_journal(p2)
    assert dmg2 is None


def test_rotted_submit_drops_rid_and_reports(tmp_path):
    """A rid whose submit line rotted has no prompt to recompute from:
    dropped from state entirely (a half request must not reach
    placement), reported with ``last_good_tok == -1``."""
    p = str(tmp_path / "j.jsonl")
    _write_journal(p, _JRECS, garbage_at=3)   # b's submit
    state, dmg = scan_journal(p)
    assert "b" not in state
    assert "b" in dmg.affected_rids
    assert dmg.last_good_tok["b"] == -1
    assert state["a"].token_list() == [10, 11, 12]


def test_token_gap_is_damage_even_pre_integrity(tmp_path):
    """The other silent-loss shape: a vanished interior tok line in a
    journal whose every surviving line verifies (or predates framing).
    ``token_list()``'s quiet truncation is now reported damage."""
    p = str(tmp_path / "j.jsonl")
    recs = [r for r in _JRECS if not (r.get("rid") == "a"
                                      and r.get("i") == 1)]
    for framed in (True, False):
        _write_journal(p, recs, framed=framed)
        with pytest.raises(JournalCorrupt) as ei:
            replay_journal(p)
        assert ei.value.damage.gaps == [("a", 1)]
        assert ei.value.state["a"].token_list() == [10]
        assert ei.value.state["b"].token_list() == [20, 21]


def test_token_journal_appends_are_crc_framed(tmp_path):
    """Every record the production writer appends carries ``"c"``."""
    p = str(tmp_path / "j.jsonl")
    j = TokenJournal(p)
    j.submit(Request("a", np.array([1, 2], np.int32),
                     SamplingParams(max_new_tokens=4),
                     arrival_time=1.0))
    j.token("a", 0, 17, 2.0)
    j.finish("a", "length", None, 1, 3.0)
    j.close()
    with open(p, encoding="utf-8") as f:
        lines = [json.loads(ln) for ln in f]
    assert len(lines) == 3
    assert all(rec_crc_ok(rec) is True for rec in lines)


def test_journal_append_integrity_fault_rots_the_line(tmp_path):
    """The ``op="journal"`` seam damages the STORED line (the next
    reader must detect it) — the writer's in-memory state is unharmed."""
    inj = FaultInjector(seed=0)
    inj.inject("integrity", corrupt="zero", op="journal", at_call=2)
    p = str(tmp_path / "j.jsonl")
    j = TokenJournal(p, faults=inj)
    j.submit(Request("a", np.array([1, 2], np.int32),
                     SamplingParams(max_new_tokens=4),
                     arrival_time=1.0))
    j.token("a", 0, 17, 2.0)   # call 2: zeroed on disk
    j.token("a", 1, 23, 3.0)
    j.close()
    with pytest.raises(JournalCorrupt) as ei:
        replay_journal(p)
    assert ei.value.damage.bad_lines[0][0] == 2
    state, dmg = salvage_journal(p)
    assert state["a"].token_list() == []      # gap at 0 truncates
    assert ("a", 0) in dmg.gaps


# ---------------------------------------------------------------------------
# snapshot leaf digests
# ---------------------------------------------------------------------------


def _mini_reqs(cfg, n=2, new_tokens=6):
    rng = np.random.default_rng(7)
    return [Request(f"g{i}",
                    rng.integers(0, cfg.vocab, size=5).astype(np.int32),
                    SamplingParams(max_new_tokens=new_tokens))
            for i in range(n)]


def _newest_step_dir(directory):
    kvdir = os.path.join(directory, KV_SUBDIR)
    steps = sorted(int(n) for n in os.listdir(kvdir) if n.isdigit())
    return os.path.join(kvdir, str(steps[-1])), steps


def test_snapshot_leaf_rot_refused_then_fsck_fallback(tiny, tmp_path):
    """The snapshot-leaf artifact class end to end: a pool leaf rotted
    AFTER its digest was recorded (the silent class — the stored step
    is internally valid, orbax restores it without complaint) REFUSES
    to restore naming the leaf, ``serve_fsck --salvage`` quarantines
    the damaged step, and restore falls back to the previous good
    step + journal with bit-exact streams."""
    cfg, params, gen = tiny
    ref = {}
    eng = _engine(gen, params)
    for r in _mini_reqs(cfg):
        eng.submit(Request(r.request_id, r.prompt, r.params))
    for o in eng.run().values():
        ref[o.request_id] = list(o.token_ids)

    d = str(tmp_path / "snap")
    inj = FaultInjector(seed=0)
    eng = _engine(gen, params, snapshot_dir=d, faults=inj)
    reqs = _mini_reqs(cfg)
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    snapshot_engine(eng, d)                  # good step
    for _ in range(2):
        eng.step()
    inj.inject("integrity", corrupt="bitflip", op="snapshot",
               max_fires=1)
    snapshot_engine(eng, d)                  # newest step: silent rot
    eng._journal.close()
    assert [k for p, _, k, _, _ in inj.fired
            if p == "integrity"] == ["bitflip"]
    step_dir, steps = _newest_step_dir(d)
    assert len(steps) == 2
    with pytest.raises(SnapshotCorrupt, match="digest mismatch"):
        restore_engine(d, gen, params)
    # the offline verifier sees the same damage...
    findings = verify_snapshot_step(step_dir)
    assert any(not f["ok"] for f in findings)
    # ...and --salvage quarantines the step out of the restore walk
    proc = subprocess.run(
        [sys.executable, FSCK, d, "--salvage"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "CORRUPT" in proc.stdout
    assert not os.path.isdir(step_dir)
    eng2 = restore_engine(d, gen, params)
    while eng2.has_work():
        eng2.step()
    for rid, want in ref.items():
        assert list(eng2._outputs[rid].token_ids) == want, rid
        assert eng2._outputs[rid].finish_reason is FinishReason.LENGTH
    # a clean directory now passes the verifier
    proc = subprocess.run(
        [sys.executable, FSCK, d],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_snapshot_on_disk_rot_is_torn_fallback(tiny, tmp_path):
    """The OTHER stored-rot class: byte damage to the published
    tensorstore files themselves is caught by the store's own framing
    CRC — restore treats the step as torn and falls back to the
    journal, and fsck reports the pool tree unreadable.  (The leaf
    digests exist for the silent class the store can NOT catch — see
    the test above.)"""
    cfg, params, gen = tiny
    d = str(tmp_path / "snap")
    eng = _engine(gen, params, snapshot_dir=d)
    reqs = _mini_reqs(cfg)
    for r in reqs:
        eng.submit(r)
    ref = {o.request_id: list(o.token_ids) for o in eng.run().values()}
    snapshot_engine(eng, d)
    eng._journal.close()
    step_dir, _ = _newest_step_dir(d)
    leaf = _corrupt_snapshot_leaf(step_dir, "bitflip")
    assert leaf is not None and "ocdbt.process" not in leaf
    findings = verify_snapshot_step(step_dir)
    assert any(not f["ok"] and "unreadable" in f["why"]
               for f in findings)
    # journal-only fallback: no adoptable KV step left, so the engine
    # geometry must come from overrides
    eng2 = restore_engine(d, gen, params, num_blocks=40, page_size=4,
                          max_batch=2, prefill_chunk=4)
    while eng2.has_work():
        eng2.step()
    for rid, want in ref.items():
        assert list(eng2._outputs[rid].token_ids) == want, rid


def test_snapshot_meta_and_pre_integrity_paths(tiny, tmp_path):
    """meta.json self-digest refuses a tampered manifest; a
    pre-integrity snapshot (no digests at all) restores unverified."""
    cfg, params, gen = tiny
    d = str(tmp_path / "snap")
    eng = _engine(gen, params, snapshot_dir=d)
    for r in _mini_reqs(cfg):
        eng.submit(r)
    for _ in range(3):
        eng.step()
    snapshot_engine(eng, d)
    eng._journal.close()
    step_dir, _ = _newest_step_dir(d)
    meta_path = os.path.join(step_dir, META_NAME)
    with open(meta_path, encoding="utf-8") as f:
        meta = json.load(f)
    # tamper a covered field, keep the stale self-digest
    tampered = dict(meta)
    tampered["clock"] = (meta.get("clock") or 0.0) + 1e6
    with open(meta_path, "w", encoding="utf-8") as f:
        json.dump(tampered, f)
    with pytest.raises(SnapshotCorrupt, match="self-digest"):
        restore_engine(d, gen, params)
    # strip every digest: the pre-integrity shape restores (unverified)
    from triton_dist_tpu.serve.recovery import META_CRC
    pre = {k: v for k, v in meta.items()
           if k not in ("digests", META_CRC)}
    with open(meta_path, "w", encoding="utf-8") as f:
        json.dump(pre, f)
    findings = verify_snapshot_step(step_dir)
    assert len(findings) == 1 and findings[0]["ok"]
    assert "unverified" in findings[0]["why"]
    eng2 = restore_engine(d, gen, params)
    assert eng2.has_work()


# ---------------------------------------------------------------------------
# wire manifest integrity
# ---------------------------------------------------------------------------


def _wire_manifest():
    rng = np.random.default_rng(0)
    k = rng.standard_normal((1, 2, 4, 8)).astype(np.float32)
    v = rng.standard_normal((1, 2, 4, 8)).astype(np.float32)
    return {"format": 3, "clock": 1.5, "page_size": 4,
            "kv_geom": {"n_layers": 1},
            "requests": [
                {"rid": "a", "prompt": [1, 2], "tokens": [3, 9],
                 "params": {"max_new_tokens": 8},
                 "kv": [(k, v)], "kv_len": 7, "pending": 9},
            ], "finished": []}


def test_wire_digests_roundtrip_and_reject():
    m = _wire_manifest()
    doc = json.loads(json.dumps(encode_manifest(m)))
    enc_rec = doc["requests"][0]
    assert "mdig" in enc_rec                     # request metadata
    assert all("crc" in half for pair in enc_rec["kv"] for half in pair)
    back = decode_manifest(json.loads(json.dumps(doc)))
    np.testing.assert_array_equal(back["requests"][0]["kv"][0][0],
                                  m["requests"][0]["kv"][0][0])
    # each CORRUPT_ACTION on the KV blob is detected
    for act in CORRUPT_ACTIONS:
        with pytest.raises(ManifestCorrupt):
            decode_manifest(corrupt_wire_doc(
                json.loads(json.dumps(doc)), act))
    # metadata rot (a flipped committed token) is detected by mdig
    bad = json.loads(json.dumps(doc))
    bad["requests"][0]["tokens"][-1] ^= 1
    with pytest.raises(ManifestCorrupt):
        decode_manifest(bad)


def test_pre_digest_wire_manifest_tolerated_and_protocol_unbumped():
    """Back-compat both directions: an old sender's digest-less doc
    decodes unchanged, a new sender's doc is plain JSON an old reader
    ignores extra fields of, and NET_PROTOCOL did not bump."""
    assert NET_PROTOCOL == 1
    doc = json.loads(json.dumps(encode_manifest(_wire_manifest())))
    doc["requests"][0].pop("mdig")
    for pair in doc["requests"][0]["kv"]:
        for half in pair:
            half.pop("crc")
    back = decode_manifest(doc)                  # old wire: tolerated
    assert back["requests"][0]["tokens"] == [3, 9]


def test_migrate_in_rejects_corrupt_manifest_counted(tiny, tmp_path):
    """Receiver-side rejection: a corrupted migrate_in manifest is a
    counted 400 (``serve_manifest_corrupt_total``, ``corrupt`` trace
    event), nothing is adopted, and the SAME manifest clean lands —
    corruption became a re-route, never adopted state."""
    cfg, params, gen = tiny
    src = _engine(gen, params, snapshot_dir=str(tmp_path / "src"))
    reqs = _mini_reqs(cfg)
    for r in reqs:
        src.submit(r)
    for _ in range(3):
        src.step()
    manifest = src.drain()
    assert manifest["requests"]

    tgt = _engine(gen, params, snapshot_dir=str(tmp_path / "tgt"))
    rep = InProcessReplica(tgt, step_sleep_s=0.002)
    try:
        inj = FaultInjector(seed=0)
        inj.inject("integrity", corrupt="bitflip", op="migrate_in",
                   at_call=1)
        rr = RemoteReplica("t0", rep.url, kill=rep.kill, retries=2,
                           retry_base_s=0.01, faults=inj)
        assert rr.wait_ready(30)
        res = rr.migrate_in(manifest)
        assert not res["adopted"]
        assert set(res["rejected"]) == {r.request_id for r in reqs}
        assert tgt.metrics.manifest_corrupt == 1
        assert any(ev[2] == "corrupt" for ev in tgt.trace.events())
        # the sender's clean copy re-sends fine (fallback ladder)
        res2 = rr.migrate_in(manifest)
        assert set(res2["adopted"]) == {r.request_id for r in reqs}
        assert "serve_manifest_corrupt_total 1" in \
            tgt.metrics.to_prometheus()
    finally:
        rep.kill()


# ---------------------------------------------------------------------------
# THE corrupt-chaos harness (ISSUE-20 acceptance)
# ---------------------------------------------------------------------------


def test_fleet_corrupt_chaos_zero_loss(tiny, tmp_path):
    """Corruption of the journal-on-disk and both wire directions,
    under load, with a SIGKILL on the bit-rotted replica: every stream
    bit-identical to the single-engine oracle, delivery exactly-once,
    the salvage audited — corruption degraded to re-queue + recompute,
    never adopted rot.  (The snapshot-leaf class runs its own
    end-to-end leg above — restore refusal → fsck quarantine →
    fallback.)"""
    cfg, params, gen = tiny
    rng = np.random.default_rng(11)
    reqs = []
    for i in range(4):
        p = rng.integers(0, cfg.vocab, size=5 + (i % 3)).astype(np.int32)
        reqs.append(Request(f"q{i}", p,
                            SamplingParams(max_new_tokens=12)))
    oracle = {}
    for r in reqs:
        eng = _engine(gen, params)
        eng.submit(Request(r.request_id, r.prompt, r.params))
        oracle[r.request_id] = list(eng.run()[r.request_id].token_ids)

    client_inj = FaultInjector(seed=5)
    # r0's engine carries this injector; the journal-rot spec is armed
    # mid-timeline, after every submit (originals + the drain's
    # re-placements) is journaled — so the rot lands on a tok/fin line
    # (a rotted submit is honest unrecoverable loss: the prompt exists
    # nowhere else)
    journal_inj = FaultInjector(seed=5)
    procs: dict = {}
    # The replicas are PACED BY THE CONTROLLER, not by the wall: one pass
    # of the loop below buys each replica one engine step (a step without
    # a permit idles, still pumping its command queue).  With six xdist
    # workers on the host a pass can take seconds while a free-running
    # tiny engine finishes all four streams in one — then the drain finds
    # nothing to move, the rot lands on whatever line comes next, and the
    # kill finds r0 done instead of mid-decode.
    permits: dict = {}

    def paced(step, permit):
        def run():
            if not permit.acquire(blocking=False):
                time.sleep(0.002)
                return []
            return step()
        return run

    def factory(life_dir):
        name = os.path.basename(os.path.dirname(life_dir))
        eng = _engine(gen, params, snapshot_dir=life_dir,
                      faults=(journal_inj if name == "r0"
                              and life_dir.endswith("life1") else None))
        permits[name] = threading.Semaphore(0)
        eng.step = paced(eng.step, permits[name])
        rep = InProcessReplica(eng, stall_after_s=5.0)
        procs[name] = rep
        rr = RemoteReplica(name, rep.url, kill=rep.kill, retries=2,
                           retry_base_s=0.01, retry_cap_s=0.05,
                           timeout_s=3.0, faults=client_inj)
        return rr.wait_ready(30)

    # Death is decided on a COUNTED clock (+0.05 a reading: suspect after
    # 12 readings without progress, dead after 30), not the wall's: with
    # six xdist workers on the host one poll of a healthy replica can
    # outlast dead_after_s on its own (the client times out at 3 s), and
    # with max_restarts=0 a replica declared dead by mistake takes its
    # streams with it.  A killed replica refuses its connection at once,
    # so its age still walks the SUSPECT -> DEAD ladder in a few seconds.
    ticks = {"t": 0.0}

    def clock():
        ticks["t"] += 0.05
        return ticks["t"]

    fc = FleetController(factory, 2, root=str(tmp_path / "fleet"),
                         clock=clock, suspect_after_s=0.6, dead_after_s=1.5,
                         backoff_base_s=0.05, backoff_cap_s=0.1,
                         max_restarts=0)
    try:
        for r in reqs:
            fc.submit(Request(r.request_id, r.prompt, r.params))
        drained = killed = False
        deadline = time.monotonic() + 120.0
        while fc.has_work():
            assert time.monotonic() < deadline, (
                f"fleet not drained: outputs={sorted(fc.outputs)}")
            for permit in permits.values():
                permit.release()
            fc.step()
            toks = sum(len(s) for s in fc.streams.values())
            if not drained and toks >= 1:
                # both wire directions: the drain RESPONSE (client
                # detects, same-key retry) and the re-placement
                # migrate_in (server rejects, placer walks on) — each
                # spec takes its op's first arrival, once
                client_inj.inject("integrity", corrupt="bitflip",
                                  op="drain", max_fires=1)
                client_inj.inject("integrity", corrupt="bitflip",
                                  op="migrate_in", max_fires=1)
                fc.drain_replica("r1")
                drained = True
                journal_inj.inject("integrity", corrupt="bitflip",
                                   op="journal", max_fires=1)
            elif (drained and not killed and toks >= len(reqs)
                  and journal_inj.fire_count("integrity") >= 1):
                procs["r0"].kill()
                killed = True
        assert killed and fc.deaths >= 1
        # every injected corruption actually fired: the journal spec
        # once, and BOTH wire specs (each is max_fires=1)
        fired = [k for p, _, k, _, _ in journal_inj.fired
                 if p == "integrity"]
        assert "bitflip" in fired, "journal bitflip never fired"
        wire_ops = [k for p, _, k, _, _ in client_inj.fired
                    if p == "integrity"]
        assert wire_ops.count("bitflip") >= 2, \
            f"wire corruption incomplete: {wire_ops}"
        # the crash path salvaged the rotted journal, audited
        assert any(e["kind"] == "journal_corrupt"
                   for e in fc.audit.entries())
        jglob = os.path.join(str(tmp_path / "fleet"), "r0", "life1",
                             JOURNAL_NAME + ".corrupt-*")
        import glob as _glob
        assert _glob.glob(jglob), "damaged journal was not quarantined"
        # bit-identical streams, exactly-once union: zero corrupt
        # state was adopted anywhere
        for r in reqs:
            rid = r.request_id
            assert list(fc.outputs[rid].token_ids) == oracle[rid], rid
            assert fc.streams[rid] == oracle[rid], rid
    finally:
        for rep in procs.values():
            rep.kill()


# ---------------------------------------------------------------------------
# fsck CLI + lint rule + floor registration
# ---------------------------------------------------------------------------


def test_fsck_cli_journal_report_and_salvage(tmp_path):
    d = str(tmp_path / "rep")
    os.makedirs(d)
    p = os.path.join(d, JOURNAL_NAME)
    _write_journal(p, _JRECS)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, FSCK, d, "--json"],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["corrupt"] == 0
    _write_journal(p, _JRECS, garbage_at=2)
    proc = subprocess.run([sys.executable, FSCK, d],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 1
    assert "CORRUPT" in proc.stdout and "line 3" in proc.stdout
    assert os.path.getsize(p) > 0           # report-only: untouched
    with pytest.raises(JournalCorrupt):
        replay_journal(p)
    proc = subprocess.run([sys.executable, FSCK, d, "--salvage"],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 1             # it reports what it fixed
    assert "quarantined" in proc.stdout
    replay_journal(p)                       # now clean
    proc = subprocess.run([sys.executable, FSCK, d],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0
    # not-a-directory is its own exit code
    proc = subprocess.run([sys.executable, FSCK,
                           str(tmp_path / "nope")],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 2


def test_durable_writes_lint_rule_registered_and_waived():
    from triton_dist_tpu.analysis.rules import RULES, run_rules
    assert "durable-writes-integrity" in RULES
    rep = run_rules(["durable-writes-integrity"])
    assert rep["ok"], rep["violations"]
    assert not rep["stale_waivers"], rep["stale_waivers"]
    waived = {w["violation"] for w in rep["waived"]}
    assert any("write_port_file" in w for w in waived)
    assert any("write_trace" in w for w in waived)
