"""The window + full attention, softmax-routed expert cell's benchmark
pieces on the CPU: the counting functions against hand counts at the
published widths, the traffic file's multiset against the configuration's
limits, the readers on a made-up trace, the file's keys reaching the
constructors (and the builder on a toy file), and the reference through
``check_outputs``' interface.

``--cpu-dryrun`` of this cell is NOT here: the rehearsal's sizes are the
harness's (``builders.TOY_ENGINE``: ``max_seq`` 512; ``run.py``: lengths
/ 8) and this mix's longest prompt is 16,384 / 8 = 2,048 tokens — it
cannot be offered without an edit to one of those files (PERF.md §7)."""

import copy
import importlib
import json

import numpy as np
import pytest

from benchmarks import (
    builders,
    control,
    readers,
    shapes,
    shapes_swa_moe,
    traffic,
)
from benchmarks import run as bench

CELL = "mellum2_l8_mixedctx_sat"


def config():
    return builders.load_config(bench.load_cell(CELL)["config_file"])


def test_counting_functions_by_hand():
    """Mellum2's widths, one pipeline stage: 6 window + 2 full layers, all
    64 experts, the whole vocabulary."""
    cfg = config()
    attn = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 + 2 * 128
    expert = 3 * 2304 * 896
    layer = attn + 2304 * 64 + 64 * expert + 2 * 2304
    assert shapes_swa_moe.attention_params(cfg) == attn == 21_233_920
    assert shapes_swa_moe.expert_params(cfg) == expert == 6_193_152
    assert shapes_swa_moe.layer_params_held(cfg) == layer == 417_747_712
    held = 8 * layer + 2 * 2304 * 98304 + 2304
    assert shapes_swa_moe.params_held(cfg) == held
    assert abs(held * 2 / 1e9 - 7.59) < 0.005                   # 7.59 GB
    assert shapes_swa_moe.kv_bytes_per_token_layer(cfg) == 2048 \
        == cfg["kv_bytes_per_token"]["per_layer"]
    assert cfg["kv_bytes_per_token"]["full_group"] == 2 * 2048
    assert cfg["kv_bytes_per_token"]["window_group"] == 6 * 2048
    # 63 rows whose contexts sum to 410,000 tokens, every one past 1,024
    rows, ctx = 63.0, 410_000.0
    w = shapes_swa_moe.window_attention(cfg, rows=rows, ctx_sum=ctx)
    seen = rows * 1024
    assert w["bytes"] == (seen * 2048 * 6 + rows * 32 * 128 * 2 * 6
                          + rows * 32 * 256 * 4 * 6)
    assert w["flops"] == 4 * seen * 32 * 128 * 6
    f = shapes_swa_moe.full_attention(cfg, rows=rows, ctx_sum=ctx)
    assert f["bytes"] == (ctx * 2048 * 2 + rows * 32 * 128 * 2 * 2
                          + rows * 32 * 256 * 4 * 2)
    # contexts under the window: never more tokens than there are
    assert shapes_swa_moe.window_tokens(cfg, rows=4.0, ctx_sum=1000.0) \
        == 1000.0
    hit = 64 * (1 - (63 / 64) ** (8 * 63))                      # 63.98 of 64
    assert shapes_swa_moe.experts_hit(cfg, rows) == pytest.approx(hit)
    assert 63.9 < hit < 64
    e = shapes_swa_moe.expert_ffn(cfg, rows=rows)
    routed = rows * 8
    assert e["bytes"] == pytest.approx(
        (8 * hit * expert + 8 * routed * (2 * 2304 + 3 * 896)) * 2)
    assert e["flops"] == pytest.approx(2 * routed * expert * 8)
    d = shapes_swa_moe.decode_step(cfg, rows=rows, ctx_sum=ctx)
    read = 8 * layer + 2304 * 98304 + 2304 - 8 * (64 - hit) * expert
    assert d["bytes"] == pytest.approx(
        read * 2 + rows * 2304 * 2 + (seen * 6 + ctx * 2) * 2048
        + rows * 8 * 2048 + rows * 98304 * 4)
    pk = shapes.peaks("TPU v5 lite")
    least, bound = shapes.least_seconds(d, pk)
    # 7.1 GB of weights read + 1.7 GB on full layers + 0.8 GB on window
    assert bound == "memory" and 0.0115 < least < 0.0125
    assert 0.0019 < shapes.least_seconds(f, pk)[0] < 0.0022
    assert 0.0009 < shapes.least_seconds(w, pk)[0] < 0.0011
    assert 0.0075 < shapes.least_seconds(e, pk)[0] < 0.0079


def test_traffic_multiset_fits_the_configuration():
    cfg = config()
    p = traffic.load("mixedctx_sat")
    a = traffic.Traffic(p, 3, vocab=cfg["vocab_size"])
    b = traffic.Traffic(p, 2 ** 31 + 7, vocab=cfg["vocab_size"])
    assert a.multiset() == b.multiset()
    pairs = a.pairs
    eng = cfg["engine"]
    assert len(pairs) == p["cycle"] == p["clients"] == 64 == eng["max_batch"]
    # every decoding row lies at or past the window, from its first step on
    assert min(n for n, _, _ in pairs) >= cfg["sliding_window"] == 1024
    assert max(n + o for n, o, _ in pairs) <= 19456 <= eng["max_seq"]
    assert max(n for n, _, _ in pairs) <= eng["prefill_budget"]
    assert sum(s for _, _, s in pairs) == 21
    mean_p = np.mean([n for n, _, _ in pairs])
    mean_o = np.mean([o for _, o, _ in pairs])
    assert 5500 < mean_p < 5580 and 1285 < mean_o < 1300
    assert 4000 < np.median([n for n, _, _ in pairs]) < 4200
    # the full group holds the live contexts with room: 64 rows at the mean
    # prompt plus half an answer are ~63% of 5,120 blocks of 128 ...
    live = 64 * (mean_p + mean_o / 2)
    assert 0.55 < live / (eng["num_blocks"] * 128) < 0.70
    # ... and ONE table a request over all 8 layers could not be built:
    # the same tokens, 8 layers wide, with the weights pass the chip
    one_table = eng["num_blocks"] * 128 * 8 * 2048
    assert (one_table + shapes_swa_moe.params_held(cfg) * 2) / 2 ** 30 > 16
    spec = a.next()
    assert spec.prompt.max() < cfg["vocab_size"]
    assert builders.reachable_ladder(cfg, [n for n, _, _ in pairs]) == [
        2048, 4096, 8192, 16384]


def test_cell_declares_what_it_reports():
    spec = bench.load_cell(CELL)
    assert [m["name"] for m in spec["end_to_end"]] == ["out_tok_per_s",
                                                       "setup_s"]
    names = {m["name"] for m in spec["per_layer"]}
    assert {"swa.window_attn_roofline", "swa.full_attn_roofline",
            "swa_moe.expert_ffn_roofline", "swa_moe.decode_step_roofline",
            "kv.util_peak_pct", "engine.tpot_p50_ms", "sat.kv.preemptions",
            "sat.sched.rows_mean", "sat.prog.decode_dev_ms",
            "sat.device.idle_share_pct"} <= names
    # no dense or latent roofline counts this family's calls
    assert not names & {"sat.paged_attn_roofline", "paged_attn_roofline",
                        "mla.paged_attn_roofline", "moe.expert_ffn_roofline",
                        "sat.decode_step_roofline"}
    for name in names:
        readers.load(name)                  # every metric has its file
    # the other cells read none of the new metrics
    for other in ("m7b_l16_decode_sat", "gc3_ep16_l5_reason_sat"):
        assert not {m["name"] for m in bench.load_cell(other)["per_layer"]
                    } & {"swa.window_attn_roofline",
                         "swa_moe.decode_step_roofline"}


def test_roofline_reader_reads_named_calls_and_nothing_without_them():
    ctx = {"counters": {"decode.rows_mean": 63.0,
                        "decode.ctx_sum_mean": 410_000.0,
                        "engine.decode_steps": 100},
           "samples": {}, "config": config(), "device_kind": "TPU v5 lite",
           "trace": {"module_s": {"jit_decode_horizon": 1.7},
                     "module_n": {"jit_decode_horizon": 13},
                     "module_op_s": {
                         "jit_decode_horizon|gqa_paged_window": 0.3,
                         "jit_decode_horizon|gqa_paged_full": 0.3,
                         "jit_decode_horizon|moe_gate_up": 0.6,
                         "jit_decode_horizon|moe_down": 0.3,
                         "jit_prefill_chunk|moe_gate_up": 9.0}}}
    pk = shapes.peaks("TPU v5 lite")
    for name, fn, took in (
            ("swa.window_attn_roofline", "window_attention", 0.3 / 100),
            ("swa.full_attn_roofline", "full_attention", 0.3 / 100),
            ("swa_moe.expert_ffn_roofline", "expert_ffn", 0.9 / 100),
            ("swa_moe.decode_step_roofline", "decode_step", 1.7 / 100)):
        need = shapes_swa_moe.FUNCTIONS[fn](ctx["config"], rows=63.0,
                                            ctx_sum=410_000.0)
        want = 100 * shapes.least_seconds(need, pk)[0] / took
        assert readers.read(name, ctx) == pytest.approx(want)
        assert 0 < want < 100
    # a program without the named calls (the dense family's call has no
    # name), or a configuration without layer kinds (the other cells; the
    # parent commit): nothing, and no raise
    bare = copy.deepcopy(ctx)
    bare["trace"]["module_op_s"] = {"jit_decode_horizon|closed_call": 1.0}
    assert readers.read("swa.window_attn_roofline", bare) is None
    assert readers.read("swa.full_attn_roofline", bare) is None
    other = dict(ctx, config=builders.load_config(bench.load_cell(
        "gc3_ep16_l5_reason_sat")["config_file"]))
    assert readers.read("swa_moe.expert_ffn_roofline", other) is None
    assert readers.read("swa_moe.decode_step_roofline", other) is None
    bare["trace"] = None
    assert readers.read("swa_moe.decode_step_roofline", bare) is None


def test_file_keys_reach_the_constructors():
    """The file as the builder reads it, at the published widths and
    without a device array: the model config, the planes and groups, the
    catalog's keys, and an engine key no constructor takes."""
    from benchmarks import builders_swa_moe
    from triton_dist_tpu.models import swa_moe as S

    cfg = config()
    model = builders_swa_moe.model_config(cfg)
    assert (model.n_experts, model.experts_held, model.expert_offset) == (
        64, 64, 0)
    assert model.layer_types.count("window") == 6
    gen = S.SwaMoeGenerator(model, max_seq=cfg["engine"]["max_seq"])
    assert gen.kv_planes == [(4, 128), (4, 128)]
    assert [(g["name"], g["window"], len(g["layers"]))
            for g in gen.kv_groups] == [("full", 0, 2), ("window", 1024, 6)]
    assert (cfg["engine"]["num_blocks"] * 128
            * cfg["kv_bytes_per_token"]["full_group"]) / 1e9 \
        == pytest.approx(2.68, abs=0.01)
    with open(bench.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cfg["name"])
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert entry["source"] == cfg["source"]
    assert cfg["engine"]["prefix_cache"] is False
    for key in ("assumed", "not_run", "deployment", "weights"):
        assert cfg[key]
    import inspect

    from triton_dist_tpu.serve import ServeEngine

    took = set(inspect.signature(ServeEngine.__init__).parameters)
    assert set(cfg["engine"]) - {"max_seq", "kv_dtype"} <= took


def _toy(cfg: dict) -> dict:
    """A toy file of the same keys: 4 layers (S S S F), window 16."""
    rope = copy.deepcopy(cfg["rope_parameters"])
    rope["full_attention"].update(factor=4,
                                  original_max_position_embeddings=32)
    return dict(cfg, hidden_size=128, moe_intermediate_size=128,
                num_hidden_layers=4, layer_types=cfg["layer_types"][:4],
                mlp_layer_types=["sparse"] * 4, num_attention_heads=4,
                num_key_value_heads=2, vocab_size=256, num_experts=8,
                num_experts_per_tok=2, sliding_window=16,
                rope_parameters=rope, torch_dtype="float32",
                engine=dict(cfg["engine"], max_seq=256, page_size=8,
                            prefill_chunk=16, prefill_budget=64,
                            max_batch=2, num_blocks=48))


def test_builder_on_a_toy_file_serves_through_two_groups():
    from benchmarks import builders_swa_moe
    from triton_dist_tpu.serve import Request, SamplingParams
    from triton_dist_tpu.serve.block_manager import KvGroups

    cfg = _toy(config())
    engine, model = builders_swa_moe.build(cfg, 2 ** 31 + 9, chips=1,
                                           ladder=[64])
    assert isinstance(engine.bm, KvGroups) and model.n_layers == 4
    assert engine.group_blocks == [48, 1 + 2 * ((16 + 16 - 2) // 8 + 2)]
    prompt = np.arange(40, dtype=np.int32) % 256
    engine.submit(Request("q0", prompt, SamplingParams(max_new_tokens=24)))
    out = engine.run(500)["q0"]
    assert len(out.token_ids) == 24
    bench.drain(engine)                     # both free lists whole
    with pytest.raises(ValueError):
        builders_swa_moe.build(cfg, 0, chips=4, ladder=[64])


def test_reference_interface_and_int8_control():
    """``check_outputs`` on made-up records at a small size: the reference
    module loads by the file's name, takes sequences and prompt lengths,
    and a request served by the reference's own argmax reads gap 0; its
    int8 control (operands and cached K / V rows) does not."""
    ref = importlib.import_module("benchmarks.reference.swa_moe")
    ref.Q_BLOCK = ref.T_BLOCK = 32
    cfg = _toy(config())
    assert cfg["reference"] == "swa_moe"
    seed, n0, n_new = 2 ** 31 + 3, 40, 12
    rng = np.random.default_rng(0)
    seq = rng.integers(0, 256, n0).astype(np.int32)
    for _ in range(n_new):           # greedy continuation BY the reference
        lg = ref.forward_logits(cfg, seed, [np.append(seq, 0)], [len(seq)])
        seq = np.append(seq, lg[0][-1].argmax()).astype(np.int32)

    class Out:
        prompt, token_ids = seq[:n0], seq[n0:].tolist()

    rec = bench.Rec(rid="q0", client=0, n_prompt=n0, max_new=n_new,
                    sampled=False, due=0.0, n=n_new, out=Out)
    limits = cfg["correct"]["limits"]
    got = bench.check_outputs(cfg, seed, [rec], 3, limits)
    assert got["ok"] and got["tokens"] == n_new
    assert got["numbers"] == {"gap_max": 0.0, "gap_mean": 0.0}
    # the control, as benchmarks/control.py reads it after a cell's window
    ctl = control.read_control({"config": cfg, "seed": seed, "recs": [rec]},
                               3)
    assert ctl["numbers"]["gap_mean"] > 0.0 and not ctl["ok"]
