"""Prefix reuse (serve/block_manager.py + engine, docs/serving.md
"Prefix caching"): content-addressed paged KV blocks with copy-on-write
sharing and an LRU-evictable warm cache tier.

Fast tier (tier-1 gate): the content index itself (chain keys,
hash-collision safety with a deliberately degenerate hash, block-id
reuse orphaning, LRU eviction, COW splits), the engine-level oracle —
warm-prefix streams bit-identical to cold streams AND to per-request
``Generator.generate`` (with and without the cache, at horizon 1 and
fused) — multi-turn session hits over generated pages, COW under decode
into a genuinely shared tail block (overlapping restored tables),
eviction-under-pressure × preemption interplay, warm-cache
snapshot/restore with correct refcounts, journal group-commit +
snapshot-barrier rotation (compacted ``done`` records replay
losslessly, chaos restore stays bit-exact), and shared-prompt traffic
counted: warm requests prefill only their own tokens.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import llama
from triton_dist_tpu.models.generate import Generator
from triton_dist_tpu.serve import (
    Request,
    SamplingParams,
    ServeEngine,
    TokenJournal,
    replay_journal,
)
from triton_dist_tpu.serve import block_manager as bm_mod
from triton_dist_tpu.serve.block_manager import BlockExhausted, BlockManager
from triton_dist_tpu.serve.request import FinishReason


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig(vocab=64, dim=16, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=32, max_seq=64,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    params = llama.init_params(cfg, jax.random.key(7))
    gen = Generator(cfg, mesh, axis="sp", max_seq=64)
    return cfg, params, gen


def _engine(gen, params, **kw):
    kw.setdefault("num_blocks", 40)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_batch", 2)
    kw.setdefault("prefill_chunk", 4)
    return ServeEngine(gen, params, **kw)


def _oracle(gen, params, prompt, n_new):
    st = gen.prefill(params, jnp.asarray(np.asarray(prompt)[None]))
    toks, _ = gen.generate(params, st, n_new)
    return [int(t) for t in np.asarray(toks[0])]


def _drain(eng, reqs, max_steps=500):
    for r in reqs:
        eng.submit(r)
    return eng.run(max_steps)


# ---------------------------------------------------------------------------
# fast tier: the content-addressed index (no engine)
# ---------------------------------------------------------------------------


def test_commit_match_share_free_cycle():
    bm = BlockManager(10, 4, prefix_cache=True)
    toks = list(range(12))                       # 3 full pages
    bm.allocate("a", 13)                         # 4 blocks
    for pg in range(3):
        bm.commit_block("a", pg, toks[4 * pg:4 * pg + 4])
    ta = bm.table("a")
    # Longest block-aligned prefix, capped at len-1: a 12-token prompt
    # matches only 2 pages (the last token must prefill for logits).
    assert bm.match_prefix(toks) == ta[:2]
    assert bm.match_prefix(toks + [99]) == ta[:3]
    assert bm.match_prefix([1] + toks[1:]) == []  # diverges in page 0
    # Map the chain into a second request: refcount 2, only the
    # remainder comes off the free list.
    free0 = len(bm._free)
    tb = bm.allocate("b", 13, shared=bm.match_prefix(toks + [99]))
    assert tb[:3] == ta[:3] and all(bm.ref_of(x) == 2 for x in ta[:3])
    assert free0 - len(bm._free) == 1            # one fresh block only
    # Free the committer: committed blocks enter the cache tier (still
    # counted free), the uncommitted tail goes to the free list.
    bm.free("a")
    assert all(bm.ref_of(x) == 1 for x in ta[:3])
    bm.free("b")
    assert bm.num_cached == 3 and bm.num_free == bm.num_allocatable
    # A third life still matches through the cache tier.
    assert bm.match_prefix(toks + [99]) == ta[:3]


def test_match_walks_chain_not_position():
    """A page matches only under its OWN parent chain: identical tokens
    at page 1 under a different page 0 must not alias."""
    bm = BlockManager(12, 2, prefix_cache=True)
    bm.allocate("a", 5)
    bm.commit_block("a", 0, [1, 2])
    bm.commit_block("a", 1, [3, 4])
    bm.allocate("b", 5)
    bm.commit_block("b", 0, [9, 9])
    bm.commit_block("b", 1, [3, 4])              # same tokens, other chain
    ta, tb = bm.table("a"), bm.table("b")
    assert bm.match_prefix([1, 2, 3, 4, 5]) == ta[:2]
    assert bm.match_prefix([9, 9, 3, 4, 5]) == tb[:2]


def test_hash_collision_never_aliases(monkeypatch):
    """The index buckets on _block_hash but matches on the FULL
    (parent, tokens) key: a degenerate constant hash must change
    nothing but lookup cost."""
    monkeypatch.setattr(bm_mod, "_block_hash", lambda p, t: 42)
    bm = BlockManager(12, 2, prefix_cache=True)
    bm.allocate("a", 5)
    bm.commit_block("a", 0, [1, 2])
    bm.commit_block("a", 1, [3, 4])
    bm.allocate("b", 5)
    bm.commit_block("b", 0, [5, 6])
    bm.commit_block("b", 1, [7, 8])
    assert bm.match_prefix([1, 2, 3, 4, 0]) == bm.table("a")[:2]
    assert bm.match_prefix([5, 6, 7, 8, 0]) == bm.table("b")[:2]
    assert bm.match_prefix([1, 2, 7, 8, 0]) == bm.table("a")[:1]


def test_lru_eviction_orphans_descendants():
    """Evicting a cached parent must kill its cached descendants' index
    entries: the parent's block id is about to be reused with different
    contents, and a chain walking through the REUSED id would certify
    KV that was never computed under it."""
    bm = BlockManager(6, 2, prefix_cache=True)                # 5 usable
    bm.allocate("a", 5)                                       # 3 blocks
    bm.commit_block("a", 0, [1, 2])
    bm.commit_block("a", 1, [3, 4])
    ta = bm.table("a")
    bm.free("a")                                  # 2 cached + 1 free
    assert bm.num_cached == 2
    # Demand every remaining block: the LRU root evicts first and takes
    # its cached child with it (the chain is unmatchable either way).
    tb = bm.allocate("b", 9)                      # needs 5 blocks
    assert bm.num_cached == 0 and bm.evictions == 2
    assert set(ta[:2]) <= set(tb)                 # ids reused
    assert bm.match_prefix([1, 2, 3, 4, 0]) == []
    bm.free("b")
    assert bm.num_free == bm.num_allocatable


def test_cow_split_and_guards():
    bm = BlockManager(10, 4, prefix_cache=True)
    bm.allocate("a", 6)
    bm.commit_block("a", 0, [1, 2, 3, 4])
    shared = bm.match_prefix([1, 2, 3, 4, 9, 9])
    bm.allocate("b", 6, shared=shared)
    blk = bm.table("b")[0]
    assert bm.ref_of(blk) == 2
    with pytest.raises(ValueError):
        bm.cow("b", 1)                            # not shared
    old, new = bm.cow("b", 0)
    assert old == blk and new != blk
    assert bm.ref_of(old) == 1 and bm.ref_of(new) == 1
    assert bm.table("b")[0] == new and bm.table("a")[0] == old
    assert bm.cow_copies == 1


def test_admit_cached_and_restore_index():
    bm = BlockManager(10, 2, prefix_cache=True)
    bm.allocate("a", 4)
    ta = bm.table("a")
    bm.restore_index([(ta[0], 0, [1, 2]), (ta[1], ta[0], [3, 4]),
                      (7, 0, [8, 8])])            # 7 is free: skipped
    assert bm.match_prefix([1, 2, 3, 4, 0]) == ta[:2]
    assert bm.admit_cached(7, 0, [8, 8])          # warm-tier admission
    assert not bm.admit_cached(7, 0, [8, 8])      # not free any more
    assert bm.num_cached == 1
    assert bm.match_prefix([8, 8, 0]) == [7]
    # Claiming the cached block through a match pulls it from the tier.
    bm.allocate("c", 3, shared=[7])
    assert bm.num_cached == 0 and bm.ref_of(7) == 1


# ---------------------------------------------------------------------------
# fast tier: engine-level oracle exactness
# ---------------------------------------------------------------------------


def test_warm_prefix_stream_bit_exact_and_faster_path(tiny):
    """THE oracle: a warm-prefix admission must emit the same greedy
    stream as the cold one and as per-request Generator.generate, while
    actually skipping prefill compute (the perf claim, pinned by the
    skipped-token counter and the load_pages program firing)."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=21).astype(np.int32)
    n_new = 6
    want = _oracle(gen, params, prompt, n_new)

    eng = _engine(gen, params)
    outs = _drain(eng, [Request("cold", prompt,
                                SamplingParams(max_new_tokens=n_new))])
    assert outs["cold"].token_ids == want
    assert eng.metrics.prefix_hits == 0

    # Same prompt again: 5 of 6 pages (21 tokens, page 4 -> cap at 20)
    # map read-only; chunked prefill restarts at the chunk floor.
    outs = _drain(eng, [Request("warm", prompt,
                                SamplingParams(max_new_tokens=n_new))])
    assert outs["warm"].token_ids == want
    assert eng.metrics.prefix_hits == 1
    assert eng.metrics.prefix_hit_tokens == 20
    assert eng.metrics.prefix_skipped_tokens == 20
    assert eng._load_fn.misses + eng._load_fn.hits >= 1
    st = eng.metrics.summary()["prefix_cache"]
    assert st["hit_rate"] > 0 and st["cached_blocks"] > 0

    # The cache disabled end-to-end: identical stream, zero hits.
    eng_off = _engine(gen, params, prefix_cache=False)
    outs = _drain(eng_off, [
        Request("a", prompt, SamplingParams(max_new_tokens=n_new)),
        Request("b", prompt, SamplingParams(max_new_tokens=n_new))])
    assert outs["a"].token_ids == want and outs["b"].token_ids == want
    assert eng_off.metrics.prefix_hits == 0
    assert eng_off.bm.num_cached == 0


def test_warm_prefix_sampled_and_divergent_suffix(tiny):
    """Sampled streams keep their per-token PRNG stream across a warm
    admission, and a prompt that shares only PART of the chain matches
    exactly the shared pages."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(1)
    base = rng.integers(0, cfg.vocab, size=16).astype(np.int32)
    sp = SamplingParams(max_new_tokens=5, temperature=0.9, top_k=8,
                        seed=13)
    eng = _engine(gen, params)
    cold = _drain(eng, [Request("c", base, sp)])["c"].token_ids
    warm = _drain(eng, [Request("w", base, sp)])["w"].token_ids
    assert warm == cold
    # Diverge inside page 2: only pages 0-1 (8 tokens) may map.
    fork = base.copy()
    fork[9] = (fork[9] + 1) % cfg.vocab
    _drain(eng, [Request("f", fork, SamplingParams(max_new_tokens=4))])
    f = eng._states["f"]
    assert f.metrics.cached_prefix_tokens == 8


def test_multiturn_session_hits_generated_pages(tiny):
    """Turn 2's prompt embeds turn 1's ANSWER: the pages holding
    generated tokens committed as they filled, so the whole previous
    conversation maps read-only and only the new user chunk prefills."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(2)
    turn1 = rng.integers(0, cfg.vocab, size=13).astype(np.int32)
    n_new = 7
    eng = _engine(gen, params)
    o1 = _drain(eng, [Request("t1", turn1,
                              SamplingParams(max_new_tokens=n_new))])["t1"]
    history = np.concatenate([turn1, np.asarray(o1.token_ids, np.int32)])
    turn2 = np.concatenate(
        [history, rng.integers(0, cfg.vocab, size=6).astype(np.int32)])
    o2 = _drain(eng, [Request("t2", turn2,
                              SamplingParams(max_new_tokens=4))])["t2"]
    # 20 tokens of history -> every full page of it mapped (page 4):
    # the hit reaches past the prompt INTO generated-token pages.
    t2 = eng._states["t2"]
    assert t2.metrics.cached_prefix_tokens >= 16 > len(turn1)
    assert o2.token_ids == _oracle(gen, params, turn2, 4)


def test_warm_prefix_horizon_fused_bit_exact(tiny):
    """Prefix hits compose with the fused decode horizon: warm streams
    at H=4 match cold streams at H=1 and the generate oracle."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, size=17).astype(np.int32)
    n_new = 9
    want = _oracle(gen, params, prompt, n_new)
    eng = _engine(gen, params, horizon=4, pipeline=2)
    eng.warmup()
    sp = SamplingParams(max_new_tokens=n_new)
    assert _drain(eng, [Request("c", prompt, sp)])["c"].token_ids == want
    misses0 = eng.metrics.compile_misses
    outs = _drain(eng, [Request("w", prompt, sp)])
    assert outs["w"].token_ids == want
    assert eng.metrics.prefix_hits == 1
    # warmup covered the load/cow programs: the warm admission and its
    # fused decode compile NOTHING under traffic
    assert eng.metrics.compile_misses == misses0


def test_eviction_under_pressure_with_preemption(tiny):
    """A pool too small for the offered load: preemption and cache
    eviction interleave, and every stream — including preempted ones
    whose recompute re-matches the victim's own cached blocks — stays
    bit-identical to its dedicated oracle."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(4)
    lens = [9, 14, 7, 11, 6]
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in lens]
    n_new = 6
    eng = _engine(gen, params, num_blocks=13, max_batch=3)
    reqs = [Request(f"r{i}", p, SamplingParams(max_new_tokens=n_new))
            for i, p in enumerate(prompts)]
    outs = _drain(eng, reqs, max_steps=800)
    for i, p in enumerate(prompts):
        assert outs[f"r{i}"].token_ids == _oracle(gen, params, p, n_new), i
        assert outs[f"r{i}"].finish_reason is FinishReason.LENGTH
    assert eng.bm.num_free == eng.bm.num_allocatable
    assert eng.metrics.summary()["prefix_cache"]["evictions"] > 0


def test_cow_decode_into_shared_tail_via_restore(tiny, tmp_path):
    """COW under decode-into-a-shared-tail: two restored RUNNING rows
    whose snapshot tables overlap on EVERY block (adopt(shared_ok=))
    both append into the same partially-filled tail page — the first
    writer must copy-on-write split it, and both streams must stay
    bit-identical to the uninterrupted single-request run."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab, size=10).astype(np.int32)
    n_new = 8
    want = _oracle(gen, params, prompt, n_new)

    d = str(tmp_path / "snap")
    eng = _engine(gen, params, snapshot_dir=d)
    eng.submit(Request("r1", prompt, SamplingParams(max_new_tokens=n_new)))
    while eng._states["r1"].kv_len < 13:          # mid-generation,
        eng.step()                                # mid-page (page 4)
    eng.snapshot()

    # Tamper the manifest: clone r1 as r2 on the other slot, SAME block
    # table (a legal state under sharing; the tail block is partial).
    kvdir = os.path.join(d, "kv")
    step = max(int(s) for s in os.listdir(kvdir) if s.isdigit())
    mpath = os.path.join(kvdir, str(step), "meta.json")
    with open(mpath) as f:
        meta = json.load(f)
    r1 = meta["requests"]["r1"]
    r2 = dict(r1, slot=1, seq=r1["seq"] + 1)
    meta["requests"]["r2"] = r2
    meta["tables"]["r2"] = list(meta["tables"]["r1"])
    # state surgery must re-authenticate what it edits (ISSUE 20): the
    # manifest self-digest and each cloned record's CRC frame
    from triton_dist_tpu.serve.integrity import canonical_crc, stamp_crc
    from triton_dist_tpu.serve.recovery import META_CRC
    meta[META_CRC] = canonical_crc(meta, exclude=(META_CRC,))
    with open(mpath, "w") as f:
        json.dump(meta, f)
    # r2 needs journal submit/tok records too (exactly r1's, renamed).
    jpath = os.path.join(d, "journal.jsonl")
    with open(jpath) as f:
        lines = [json.loads(x) for x in f if x.strip()]
    with open(jpath, "a") as f:
        for rec in lines:
            if rec.get("rid") == "r1":
                f.write(json.dumps(stamp_crc(dict(rec, rid="r2")))
                        + "\n")

    eng2 = ServeEngine.restore(d, gen, params)
    tail = eng2.bm.table("r1")[-1]
    assert eng2.bm.ref_of(tail) == 2              # genuinely shared tail
    outs = eng2.run()
    assert outs["r1"].token_ids == want
    assert outs["r2"].token_ids == want
    assert eng2.bm.cow_copies >= 1
    assert eng2.bm.num_free == eng2.bm.num_allocatable


def test_snapshot_restore_carries_warm_cache(tiny, tmp_path):
    """The warm cache survives a restart: restore's adopt path doubles
    as cache admission, so the restarted engine's first warm prompt
    still skips its prefill — bit-identically."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab, size=18).astype(np.int32)
    n_new = 5
    want = _oracle(gen, params, prompt, n_new)
    d = str(tmp_path / "snap")
    eng = _engine(gen, params, snapshot_dir=d)
    _drain(eng, [Request("seed", prompt,
                         SamplingParams(max_new_tokens=n_new))])
    cached = eng.bm.num_cached
    assert cached > 0
    eng.snapshot()

    eng2 = ServeEngine.restore(d, gen, params)
    assert eng2.bm.num_cached == cached
    assert eng2.bm.num_free == eng2.bm.num_allocatable
    outs = _drain(eng2, [Request("warm", prompt,
                                 SamplingParams(max_new_tokens=n_new))])
    assert outs["warm"].token_ids == want
    assert eng2.metrics.prefix_hits == 1
    assert eng2.metrics.prefix_skipped_tokens > 0

    # Geometry-shrunk restore (fewer blocks than the warm tier held):
    # the tier re-admits only what fits; streams stay exact.
    eng3 = ServeEngine.restore(d, gen, params, num_blocks=8)
    outs = _drain(eng3, [Request("w2", prompt,
                                 SamplingParams(max_new_tokens=n_new))])
    assert outs["w2"].token_ids == want


# ---------------------------------------------------------------------------
# fast tier: journal group-commit + rotation
# ---------------------------------------------------------------------------


def test_journal_rewrite_and_done_record_replay(tmp_path):
    p = tmp_path / "j.jsonl"
    j = TokenJournal(p, fsync_interval_s=0.0)     # fsync every append
    j.submit(Request("a", np.array([1, 2], np.int32),
                     SamplingParams(max_new_tokens=2)))
    j.token("a", 0, 5, 1.0)
    j.token("a", 1, 6, 2.0)
    j.finish("a", "length", None, 2, 3.0)
    size0 = j.file_bytes
    assert size0 == os.path.getsize(p)
    j.rewrite([{"t": "done", "rid": "a", "prompt": [1, 2],
                "params": SamplingParams(max_new_tokens=2).to_dict(),
                "arrival": 0.5, "toks": [5, 6], "tts": [1.0, 2.0],
                "reason": "length", "err": None, "fts": 3.0}])
    assert j.file_bytes == os.path.getsize(p) < size0
    rep = replay_journal(p)
    assert rep["a"].token_list() == [5, 6]
    assert rep["a"].finish["reason"] == "length"
    assert rep["a"].finish["n"] == 2
    assert list(rep["a"].prompt) == [1, 2]
    # Appends after the rotation extend the compacted file normally.
    j.token("b", 0, 9, 4.0)
    assert replay_journal(p)["b"].tokens[0][0] == 9
    # A stale .tmp from a crashed rewrite is GC'd on reopen.
    j.close()
    with open(str(p) + ".tmp", "w") as f:
        f.write("garbage")
    TokenJournal(p)
    assert not os.path.exists(str(p) + ".tmp")


def test_rotation_bounds_journal_and_restores_exact(tiny, tmp_path):
    """With rotation on, a long-lived engine's journal stays bounded at
    snapshot barriers, and a kill/restart from the rotated (compacted)
    journal restores every stream bit-identically — including requests
    that finished BEFORE the rotation."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(8)
    prompts = {f"r{i}": rng.integers(0, cfg.vocab, size=5 + i)
               .astype(np.int32) for i in range(4)}
    n_new = 6
    want = {r: _oracle(gen, params, p, n_new)
            for r, p in prompts.items()}

    d = str(tmp_path / "snap")
    eng = _engine(gen, params, snapshot_dir=d, snapshot_every=3,
                  journal_rotate_bytes=200)
    reqs = [Request(r, prompts[r], SamplingParams(max_new_tokens=n_new))
            for r in sorted(prompts)]
    # Submit/serve in two waves so rotation happens with r0/r1 finished
    # and r2/r3 in flight across later barriers.
    _drain(eng, reqs[:2])
    eng.snapshot()                               # barrier -> rotation
    assert eng.metrics.journal_rotations >= 1
    for r in reqs[2:]:
        eng.submit(r)
    for _ in range(4):                           # leave r2/r3 mid-flight
        eng.step()
    eng.snapshot()
    jsize = os.path.getsize(os.path.join(d, "journal.jsonl"))
    # Bounded: compaction keeps one done-line per finished request plus
    # the live tail, nowhere near the raw append stream's growth.
    assert jsize < 4000

    eng2 = ServeEngine.restore(d, gen, params)   # "kill" + restart
    eng2.run()
    for r in sorted(prompts):
        assert eng2._outputs[r].token_ids == want[r], r
        assert eng2._outputs[r].finish_reason is FinishReason.LENGTH
    assert eng2.bm.num_free == eng2.bm.num_allocatable


def test_rotation_retention_bounds_history_and_rewrite_cadence(
        tiny, tmp_path):
    """``journal_retain_done=N`` is what bounds a LONG-lived engine: a
    rotation keeps ``done`` records for only the N newest finished
    requests (pruning the older ones from the journal and the engine's
    request/output maps together), and rotation re-arms only once the
    file at least doubles past the previous rewrite — never a
    full-history rewrite at every barrier once the retained floor sits
    above ``journal_rotate_bytes``."""
    cfg, params, gen = tiny
    rng = np.random.default_rng(11)
    prompts = {f"r{i}": rng.integers(0, cfg.vocab, size=6)
               .astype(np.int32) for i in range(3)}
    d = str(tmp_path / "snap")
    eng = _engine(gen, params, snapshot_dir=d, journal_rotate_bytes=1,
                  journal_retain_done=1)
    sp = SamplingParams(max_new_tokens=3)
    for r in sorted(prompts):                     # finish in order
        _drain(eng, [Request(r, prompts[r], sp)])
    eng.snapshot()                                # barrier -> rotation
    assert eng.metrics.journal_rotations == 1
    # Only the newest finished request survives the rewrite — in the
    # journal AND in the engine's maps (the pruned ones were delivered),
    # including the per-request metrics map (RSS must not grow with
    # every request ever served).
    assert set(eng._outputs) == {"r2"} and set(eng._states) == {"r2"}
    assert "r0" not in eng.metrics.requests
    assert set(replay_journal(os.path.join(d, "journal.jsonl"))) == {"r2"}
    eng2 = ServeEngine.restore(d, gen, params)
    assert eng2.has_request("r2") and not eng2.has_request("r0")
    # Re-arm cadence: the file just rewrote (rotate_bytes=1 stays
    # exceeded forever) — the next barrier must NOT rewrite again until
    # the file doubles past the rewrite floor.
    eng.snapshot()
    assert eng.metrics.journal_rotations == 1


def test_preempt_resets_pending_warm_classification():
    """A warm admission preempted BEFORE its first token must not keep
    its warm label — the recompute admission may land cold (blocks
    evicted meanwhile) and its full-recompute TTFT would pollute the
    warm bucket the <= 0.35x bench gate averages.  A request whose TTFT
    was already recorded keeps the label it was earned under."""
    from triton_dist_tpu.serve.metrics import RequestMetrics
    from triton_dist_tpu.serve.scheduler import FCFSScheduler, ReqState

    bm = BlockManager(10, 4, prefix_cache=True)
    sched = FCFSScheduler(bm, prefill_budget=4, prefill_chunk=4)

    def mk(rid):
        rs = ReqState(req=Request(rid, np.arange(6, dtype=np.int32),
                                  SamplingParams(max_new_tokens=4)),
                      metrics=RequestMetrics(arrival_time=0.0))
        bm.allocate(rid, 7)
        rs.cached_prefix = 4
        rs.metrics.cached_prefix_tokens = 4
        return rs

    a = mk("a")
    sched.preempt(a)
    assert a.metrics.cached_prefix_tokens == 0    # TTFT still pending
    b = mk("b")
    b.metrics.on_token(1.0)                       # TTFT recorded warm
    sched.preempt(b)
    assert b.metrics.cached_prefix_tokens == 4


def test_blocked_head_counts_one_lookup():
    """A head-of-line request blocked on pool pressure re-enters
    admission every engine step; the lookups/lookup_hits gauges must
    count it ONCE per admission attempt or hit_rate becomes a
    queue-depth artifact — and with nothing allocatable at all the
    O(prompt) chain walk is skipped entirely."""
    from triton_dist_tpu.serve.metrics import RequestMetrics
    from triton_dist_tpu.serve.scheduler import FCFSScheduler, ReqState

    def waiter(sched, rid="w"):
        rs = ReqState(req=Request(rid, np.arange(9, dtype=np.int32),
                                  SamplingParams(max_new_tokens=2)),
                      metrics=RequestMetrics(arrival_time=0.0))
        sched.add(rs)
        return rs

    # Total exhaustion: admission breaks before the walk.
    bm = BlockManager(6, 4, prefix_cache=True)
    sched = FCFSScheduler(bm, prefill_budget=4, prefill_chunk=4)
    bm.allocate("hog", 20)                        # all 5 blocks
    assert bm.num_free == 0
    waiter(sched)
    for _ in range(5):
        assert sched.admit([0], 0.0) == []
    assert bm.lookups == 0
    # Partial pressure: the walk runs (a warm prefix could admit where
    # a cold one can't) but counts exactly once across the retries and
    # the eventual admission — and the retries reuse the memoized match
    # (same index generation) instead of re-walking the chain.
    bm2 = BlockManager(6, 4, prefix_cache=True)
    sched2 = FCFSScheduler(bm2, prefill_budget=4, prefill_chunk=4)
    bm2.allocate("hog", 12)                       # 3 of 5 blocks
    rs2 = waiter(sched2)                          # needs 3, only 2 free
    for _ in range(5):
        assert sched2.admit([0], 0.0) == []
    assert bm2.lookups == 1
    assert rs2.match_cache is not None
    assert rs2.match_gen == bm2.index_gen
    bm2.free("hog")
    assert len(sched2.admit([0], 0.0)) == 1
    assert bm2.lookups == 1


def test_group_commit_sweep_fsyncs_idle_tail(tmp_path, monkeypatch):
    """append() only checks the fsync interval when the NEXT record
    arrives — maybe_sync() (driven once per engine step) must fsync a
    dirty tail after the interval even with no further traffic, or the
    burst's last record sits in the page cache indefinitely."""
    clock = [0.0]
    import triton_dist_tpu.serve.recovery as rec_mod
    monkeypatch.setattr(rec_mod.time, "monotonic", lambda: clock[0])
    j = TokenJournal(tmp_path / "j.jsonl", fsync_interval_s=10.0)
    synced = []
    monkeypatch.setattr(rec_mod.os, "fsync",
                        lambda fd: synced.append(clock[0]))
    j.token("a", 0, 5, 0.0)
    assert j._dirty and not synced       # within the interval: deferred
    clock[0] = 5.0
    j.maybe_sync()
    assert j._dirty and not synced       # still within
    clock[0] = 11.0
    j.maybe_sync()
    assert not j._dirty and synced == [11.0]
    j.maybe_sync()                       # clean tail: no second fsync
    assert synced == [11.0]


# ---------------------------------------------------------------------------
# fast tier: shared-prompt traffic pays prefill for its own tokens only
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shared_len,hit,skipped",
                         [(128, 128, 128), (124, 120, 112)],
                         ids=["page_aligned", "not_page_aligned"])
def test_shared_prompt_warm_requests_prefill_only_their_own_tokens(
        tiny, shared_len, hit, skipped):
    """Two cold requests, one seeder that commits the shared prompt's
    pages, then two warm requests = shared prompt + 8 tokens of their
    own.  What a warm time to first token stood for, as counts: the warm
    pair skips the aligned part of the shared prompt, whole, and adds to
    the prefill program's token count only what is left — its own 8
    tokens each when the shared prompt ends on a page and a chunk.  When
    it does not (124 tokens, page 8, chunk 16) admission maps the 120
    tokens that fill pages, prefill starts at the chunk floor of that,
    112, and the rows between recompute.  No clock is read."""
    cfg, params, _ = tiny
    page, own, n_new = 8, 8, 4
    max_seq = 144                                  # 128 + 8 + 4, paged
    gen = Generator(cfg, Mesh(np.array(jax.devices()[:1]), ("sp",)),
                    axis="sp", max_seq=max_seq)
    eng = _engine(gen, params, num_blocks=1 + (max_seq // page) * 3,
                  page_size=page, prefill_chunk=16)
    rng = np.random.default_rng(0)
    sp = SamplingParams(max_new_tokens=n_new)

    def prompt(head):
        return np.concatenate([head, rng.integers(
            0, cfg.vocab, size=own)]).astype(np.int32)

    def prefilled():
        return eng.metrics.summary()["prefill"]["tokens"]

    outs = _drain(eng, [Request(f"cold{i}", prompt(rng.integers(
        0, cfg.vocab, size=shared_len)), sp) for i in range(2)])
    assert prefilled() == 2 * (shared_len + own)
    shared = rng.integers(0, cfg.vocab, size=shared_len)
    outs.update(_drain(eng, [Request("seed0", prompt(shared), sp)]))
    before = prefilled()
    assert before == 3 * (shared_len + own)
    outs.update(_drain(eng, [Request(f"warm{i}", prompt(shared), sp)
                             for i in range(2)]))
    assert all(len(o.token_ids) == n_new for o in outs.values())
    assert prefilled() - before == 2 * (shared_len - skipped + own)
    s = eng.metrics.summary()["prefix_cache"]
    assert s["warm_requests"] == 2 and s["cold_requests"] == 3, s
    assert s["prefix_skipped_tokens"] == 2 * skipped, s
    assert s["hit_rate"] > 0, s
    for i in range(2):
        assert outs[f"warm{i}"].metrics.cached_prefix_tokens == hit
