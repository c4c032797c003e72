"""The latent-attention + expert-share cell's benchmark pieces on the CPU:
the counting functions against hand counts at the published widths, the
file's ``engine`` group reaching the constructors, and the whole harness
at a toy size (``--cpu-dryrun``) ending in a ``correct`` line that an
altered token turns false.  (``reason_sat``'s multiset is held by
``test_bench_traffic.py``, which reads every traffic file.)"""

import argparse
import copy

import numpy as np
import pytest

from benchmarks import builders, readers, shapes_mla_moe
from benchmarks import run as bench

CELL = "gc3_ep16_l5_reason_sat"


def config():
    return builders.load_config(bench.load_cell(CELL)["config_file"])


def test_counting_functions_by_hand():
    """GigaChat3.1-702B-A36B's widths, one chip's share: 16 of 256
    experts, 1 dense + 4 expert layers, 16,032 vocabulary rows."""
    cfg = config()
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 320
            + 64 * 192 * 7168)
    assert shapes_mla_moe.attention_params(cfg) == attn == 132_579_328
    expert = 3 * 7168 * 2048
    assert shapes_mla_moe.expert_params(cfg) == expert == 44_040_192
    dense = attn + 3 * 7168 * 18432
    moe = attn + 7168 * 256 + 256 + 17 * expert
    assert shapes_mla_moe.layer_params_held(cfg, False) == dense
    assert shapes_mla_moe.layer_params_held(cfg, True) == moe
    # 64 rows whose contexts sum to 110,000 tokens
    rows, ctx = 64.0, 110_000.0
    a = shapes_mla_moe.mla_paged_attention(cfg, rows=rows, ctx_sum=ctx)
    assert a["bytes"] == (ctx * 5 * 1152 + rows * 64 * 576 * 2 * 5
                          + rows * 64 * 512 * 4 * 5)
    assert a["flops"] == 2 * 64 * (576 + 512) * ctx * 5
    f = shapes_mla_moe.expert_ffn(cfg, rows=rows)
    routed = rows * 8 * 16 / 256                      # 32 rows a layer
    assert routed == 32
    # of 16 held experts, those that one of 512 evenly routed assignments
    # reaches: 16 (1 - (255/256)^512) = 13.84
    hit = 16 * (1 - (255 / 256) ** 512)
    assert shapes_mla_moe.experts_hit(cfg, rows) == pytest.approx(hit)
    assert 13.8 < hit < 13.9
    assert f["bytes"] == pytest.approx(
        2 * (4 * hit * expert + 4 * routed * (7168 + 4096 + 2048 + 7168)))
    assert abs(4 * 16 * expert * 2 / 1e9 - 4 * 1.409) < 0.01   # 4 x 1.41 GB
    assert f["flops"] == 2 * routed * expert * 4
    d = shapes_mla_moe.decode_step(cfg, rows=rows, ctx_sum=ctx)
    weights = dense + 4 * moe + 7168 * 16032
    assert abs(weights * 2 / 1e9 - 8.35) < 0.01                # all held
    read = weights - 4 * (16 - hit) * expert
    assert d["bytes"] == pytest.approx(
        read * 2 + rows * 7168 * 2 + ctx * 5 * 1152 + rows * 5 * 1152
        + rows * 16032 * 4)
    # memory-bound by far: least time is the bytes over 819 GB/s, ~10 ms
    from benchmarks import shapes

    least, bound = shapes.least_seconds(d, shapes.peaks("TPU v5 lite"))
    assert bound == "memory" and 0.0095 < least < 0.0105


def test_roofline_reader_reads_named_calls_and_nothing_without_them():
    ctx = {"counters": {"decode.rows_mean": 64.0,
                        "decode.ctx_sum_mean": 110_000.0,
                        "engine.decode_steps": 100},
           "samples": {}, "config": config(), "device_kind": "TPU v5 lite",
           "trace": {"module_s": {"jit_decode_horizon": 3.0},
                     "module_n": {"jit_decode_horizon": 13},
                     "module_op_s": {
                         "jit_decode_horizon|mla_paged_decode": 0.2,
                         "jit_decode_horizon|moe_gate_up": 0.5,
                         "jit_decode_horizon|moe_down": 0.3,
                         "jit_prefill_chunk|mla_paged_decode": 9.0}}}
    from benchmarks import shapes

    pk = shapes.peaks("TPU v5 lite")
    for name, fn, took in (("mla.paged_attn_roofline", "mla_paged_attention",
                            0.2 / 100),
                           ("moe.expert_ffn_roofline", "expert_ffn",
                            0.8 / 100),
                           ("mla_moe.decode_step_roofline", "decode_step",
                            3.0 / 100)):
        need = shapes_mla_moe.FUNCTIONS[fn](ctx["config"], rows=64.0,
                                            ctx_sum=110_000.0)
        want = 100 * shapes.least_seconds(need, pk)[0] / took
        assert readers.read(name, ctx) == pytest.approx(want)
    # a program without the named calls (the parent commit; the dense
    # cells): nothing to read, and no raise
    bare = copy.deepcopy(ctx)
    bare["trace"]["module_op_s"] = {"jit_decode_horizon|closed_call": 1.0}
    assert readers.read("mla.paged_attn_roofline", bare) is None
    assert readers.read("moe.expert_ffn_roofline", bare) is None
    bare["trace"] = None
    assert readers.read("mla_moe.decode_step_roofline", bare) is None


def test_engine_keys_of_the_file_reach_the_engine():
    from triton_dist_tpu.models.mla_moe import LatentPoolUnsupported

    cfg = builders.toy_config(config())
    eng = cfg["engine"]
    kw = dict(ladder=builders.reachable_ladder(cfg, [100, 200]),
              interpret=True)
    engine, model = builders.build(cfg, 5, chips=1, **kw)
    assert engine.latent and engine.kv_quant is False
    assert (engine.max_batch, engine.page, engine.horizon, engine.pipeline,
            engine.prefix_cache, engine.gen.max_seq,
            engine.scheduler.prefill_chunk, engine.bm.num_blocks) == (
        eng["max_batch"], eng["page_size"], eng["horizon"], eng["pipeline"],
        eng["prefix_cache"], eng["max_seq"], eng["prefill_chunk"],
        eng["num_blocks"])
    # the share, as the file states it
    assert (model.n_experts, model.experts_held, model.expert_offset) == (
        256, 16, 0)
    assert model.latent_width == 576 and model.head_dim == 640
    assert engine._pools[0][0].shape == (eng["num_blocks"], 1, 128, 640)
    with pytest.raises(ValueError, match="one chip"):
        builders.build(cfg, 5, chips=4, **kw)
    bad = copy.deepcopy(cfg)
    bad["engine"]["kv_dtype"] = "int8"
    with pytest.raises(LatentPoolUnsupported):
        builders.build(bad, 5, chips=1, **kw)
    bad = copy.deepcopy(cfg)
    bad["engine"]["journal"] = False         # a key no constructor takes
    with pytest.raises(TypeError):
        builders.build(bad, 5, chips=1, **kw)


@pytest.fixture(scope="module")
def sound():
    """The whole harness once at the toy size (several minutes: every
    kernel of the block runs in the interpreter)."""
    seen = {}
    args = argparse.Namespace(workload=CELL, seed=2 ** 31 + 11, seconds=40.0,
                              trace=0, cpu_dryrun=True, keep_trace=False)
    seen["result"] = bench.run_cell(args, after_window=seen.update)
    return seen


def test_dryrun_ends_in_a_correct_line(sound):
    r = sound["result"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["cpu_dryrun"] is True
    assert set(r["metrics"]) == {"dryrun.out_tok_per_s", "dryrun.setup_s"}


def test_altered_token_is_not_correct(sound):
    """Every second served token of the sound run's own requests moved by
    one id: only the comparison with the reference can see it."""
    cfg, seed = sound["config"], sound["seed"]
    limits = cfg["correct"]["limits"]
    assert bench.check_outputs(cfg, seed, sound["recs"], 4, limits)["ok"]
    altered = []
    for r in sound["recs"]:
        r = copy.copy(r)
        toks = np.asarray(r.out.token_ids).copy()
        toks[1::2] = (toks[1::2] + 1) % cfg["vocab_size"]
        r.out = argparse.Namespace(prompt=r.out.prompt, token_ids=toks)
        altered.append(r)
    assert not bench.check_outputs(cfg, seed, altered, 4, limits)["ok"]
