"""``correct`` can fail: at a toy size on the CPU (kernels in the Pallas
interpreter) the harness's whole run is driven with the chip check skipped
(``--cpu-dryrun``), once sound and once with the timed path broken
underneath, and the int8 control is read on the sound run's own requests.

The limits in the configuration files were set from runs on the chip at
the cells' sizes (PERF.md §2); at this toy size only the ORDER is checked:
sound under the limits, control at least three times the sound reading,
broken path far over the limits."""

import argparse
import types

import numpy as np
import pytest

from benchmarks import run as bench


def args(seed=11):
    return argparse.Namespace(workload="m7b_l16_decode_sat", seed=seed,
                              seconds=12.0, trace=0, cpu_dryrun=True,
                              keep_trace=False)


@pytest.fixture(scope="module")
def sound():
    seen = {}

    def keep(state):
        seen.update(state)

    seen["result"] = bench.run_cell(args(), after_window=keep)
    return seen


def test_sound_run_is_correct(sound):
    r = sound["result"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["cpu_dryrun"] is True
    # a CPU time never goes under a device metric's name
    assert all(k.startswith("dryrun.") for k in r["metrics"])
    assert "dryrun.setup_s" in r["metrics"]


def test_int8_control_reads_worse_than_the_program(sound):
    """The control never decodes: at each position of given prompts and
    tokens it reads the gap of the token int8 puts first.  The toy run
    serves too few tokens to show a rare flip, so the control reads four
    contexts of 384 tokens here; the program's reading is the sound
    run's own."""
    cfg, seed = sound["config"], sound["seed"]
    limits = cfg["correct"]["limits"]
    s = bench.check_outputs(cfg, seed, sound["recs"], 8, limits)
    assert s["ok"], s
    rng = np.random.default_rng(5)
    fake = []
    for i in range(4):
        toks = rng.integers(0, cfg["vocab_size"], size=384).astype(np.int32)
        fake.append(types.SimpleNamespace(
            rid=f"ctl{i}", sampled=False, n=383, n_prompt=1,
            out=types.SimpleNamespace(prompt=toks[:1], token_ids=toks[1:])))
    c = bench.check_outputs(cfg, seed, fake, 4, limits, int8=True)
    assert c["tokens"] == 4 * 383
    assert c["numbers"]["gap_mean"] > 3 * max(s["numbers"]["gap_mean"], 1e-5)
    assert c["numbers"]["gap_max"] > 3 * max(s["numbers"]["gap_max"], 1e-3)


def test_engine_keys_of_the_file_reach_the_engine():
    """The file's ``engine`` group is what the builder constructs: the
    control engine (``correct.control_engine``: int8 pools, the program's
    own lower-precision path) really holds int8 pools, the cell's own does
    not, and a mesh cell is refused by this one-chip builder."""
    from benchmarks import builders

    cfg = builders.toy_config(builders.load_config(
        bench.load_cell("m7b_l16_decode_sat")["config_file"]))
    kw = dict(ladder=[128, 256], interpret=True)
    engine, _ = builders.build(cfg, 5, chips=1, **kw)
    assert engine.kv_quant is False and engine.max_batch == 4
    assert engine.prefix_cache is True and engine.horizon == 8
    cfg["engine"].update(cfg["correct"]["control_engine"])
    engine, _ = builders.build(cfg, 5, chips=1, **kw)
    assert engine.kv_quant is True
    with pytest.raises(ValueError, match="one chip"):
        builders.build(cfg, 5, chips=4, **kw)
    cfg["engine"]["journal"] = False         # a key no constructor takes
    with pytest.raises(TypeError):
        builders.build(cfg, 5, chips=1, **kw)


def test_altered_token_is_not_correct():
    """Every second token of every request is altered where it is produced
    (the engine's commit): the stream stays self-consistent, so only the
    comparison with the reference can see it."""
    n = {"calls": 0}

    def break_commit(engine):
        inner = engine._commit_token
        vocab = engine.cfg.vocab

        def commit(rs, token, now=None):
            n["calls"] += 1
            if len(rs.generated) % 2 == 1:
                token = (int(token) + 1) % vocab
            return inner(rs, token, now)

        engine._commit_token = commit

    r = bench.run_cell(args(seed=12), engine_hook=break_commit)
    assert n["calls"] > 20
    assert r["correct"] is False
