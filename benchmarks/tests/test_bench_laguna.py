"""The ``laguna`` cell's benchmark pieces on the CPU: the counting functions
against hand counts at the published widths, the traffic file's laws and
multiset against the configuration's limits, the readers on a made-up
trace, the file's keys against the catalog's and ``BENCHMARK.json``, the
builder AS IT STANDS on a toy file, and the reference through
``check_outputs``' interface.

``--cpu-dryrun`` of this cell is NOT here: the rehearsal's sizes are the
harness's (``builders.TOY``: 4 query heads for EVERY layer, 2 layers — no
period of this block, and a head-count list the toy keys do not reach;
``TOY_ENGINE``: ``max_seq`` 512 against a shortest scaled prompt of 128 and
a longest of 1,024) — it cannot be offered without an edit to one of those
files (PERF.md §7)."""

import copy
import importlib
import json

import numpy as np
import pytest

from benchmarks import builders, control, readers, shapes, shapes_laguna, traffic
from benchmarks import run as bench

CELL = "lagS_ep8_l9_agentmix_sat"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config():
    return builders.load_config(bench.load_cell(CELL)["config_file"])


def test_counting_functions_by_hand():
    """Laguna-S-2.1's widths, one chip of the eight that share a layer:
    3 full layers of 48 heads, 6 window layers of 72, a dense lead layer,
    32 of 256 experts + the shared one, an eighth of the vocabulary."""
    cfg = config()
    D, hd = 3072, 128
    full_attn = D * 48 * hd * 2 + 2 * D * 8 * hd + D * 48 + 2 * hd
    win_attn = D * 72 * hd * 2 + 2 * D * 8 * hd + D * 72 + 2 * hd
    assert shapes_laguna.attention_params(cfg, 0) == full_attn
    assert shapes_laguna.attention_params(cfg, 1) == win_attn
    assert shapes_laguna.attention_params(cfg, 4) == full_attn
    expert = 3 * D * 1024
    assert shapes_laguna.expert_params(cfg) == expert == 9_437_184 \
        == shapes_laguna.shared_params(cfg)
    assert shapes_laguna.dense_params(cfg) == 3 * D * 12288 == 113_246_208
    lead = full_attn + 3 * D * 12288 + 2 * D
    assert shapes_laguna.layer_params_held(cfg, 0) == lead
    assert round(lead / 1e6, 1) == 157.4
    win = win_attn + D * 256 + 256 + expert + 32 * expert + 2 * D
    full = full_attn + D * 256 + 256 + expert + 32 * expert + 2 * D
    assert shapes_laguna.layer_params_held(cfg, 1) == win
    assert shapes_laguna.layer_params_held(cfg, 8) == full
    assert (int(win / 1e5), int(full / 1e5)) == (3753, 3564)     # x 100 k
    held = lead + 6 * win + 2 * full + 2 * D * 12544 + D
    assert shapes_laguna.params_held(cfg) == held
    # the matrices alone: what the norms (two a layer, two a head, the
    # final one) and the routers' biases add is a few thousand
    small = 9 * (2 * D + 2 * hd) + D + 8 * 256
    assert held - small == 3_199_401_984 and small == 62_720
    assert abs(held * 2 / 1e9 - 6.40) < 0.005                   # 6.40 GB
    assert shapes_laguna.kv_bytes_per_token_layer(cfg) == 4096 \
        == cfg["kv_bytes_per_token"]["per_layer"]
    assert cfg["kv_bytes_per_token"]["full_group"] == 3 * 4096 == 12288
    assert cfg["kv_bytes_per_token"]["window_group"] == 6 * 4096 == 24576
    # 64 rows whose contexts sum to 250,000 tokens, every one past 512
    rows, ctx = 64.0, 250_000.0
    w = shapes_laguna.window_attention(cfg, rows=rows, ctx_sum=ctx)
    seen = rows * 512
    assert w["bytes"] == (seen * 4096 * 6 + rows * 72 * 128 * 2 * 6
                          + rows * 72 * 256 * 4 * 6)
    assert w["flops"] == 4 * seen * 72 * 128 * 6
    f = shapes_laguna.full_attention(cfg, rows=rows, ctx_sum=ctx)
    assert f["bytes"] == (ctx * 4096 * 3 + rows * 48 * 128 * 2 * 3
                          + rows * 48 * 256 * 4 * 3)
    assert f["flops"] == 4 * ctx * 48 * 128 * 3
    assert shapes_laguna.window_tokens(cfg, rows=4.0, ctx_sum=1000.0) \
        == 1000.0
    hit = 32 * (1 - (255 / 256) ** 640)                         # 29.4 of 32
    assert shapes_laguna.experts_hit(cfg, rows) == pytest.approx(hit)
    assert 29.3 < hit < 29.5
    assert shapes_laguna.routed_rows(cfg, rows) == 80.0         # of 640
    e = shapes_laguna.expert_ffn(cfg, rows=rows)
    assert e["bytes"] == pytest.approx(
        (8 * hit * expert + 8 * 80 * (2 * D + 3 * 1024)) * 2)
    assert e["flops"] == pytest.approx(2 * 80 * expert * 8)
    d = shapes_laguna.decode_step(cfg, rows=rows, ctx_sum=ctx)
    read = (lead + 6 * win + 2 * full + D * 12544 + D
            - 8 * (32 - hit) * expert)
    assert d["bytes"] == pytest.approx(
        read * 2 + rows * D * 2 + (seen * 6 + ctx * 3) * 4096
        + rows * 9 * 4096 + rows * 12544 * 4)
    pk = shapes.peaks("TPU v5 lite")
    least, bound = shapes.least_seconds(d, pk)
    # 5.9 GB of weights read + 3.1 GB on full layers + 0.8 GB on window
    assert bound == "memory" and 0.0115 < least < 0.0125
    assert 0.0036 < shapes.least_seconds(f, pk)[0] < 0.0040
    assert 0.0009 < shapes.least_seconds(w, pk)[0] < 0.0012
    assert 0.0053 < shapes.least_seconds(e, pk)[0] < 0.0056


def test_traffic_laws_and_multiset_fit_the_configuration():
    cfg = config()
    p = traffic.load("agentmix_sat")
    assert (p["loop"], p["clients"], p["cycle"], p["pairing_stride"],
            p["sampled_every"], p["order"], p["check_sample"]) == (
        "closed", 64, 64, 13, 3, "permute", 3)
    assert p["prompt"] == {"law": "log_uniform", "lo": 1024, "hi": 8192}
    assert p["output"] == {"law": "log_uniform", "lo": 256, "hi": 2048}
    assert p["sampler"] == {"temperature": 0.8, "top_k": 64, "top_p": 0.95}
    assert p["ramp"] == traffic.load("mixedctx_sat")["ramp"]
    assert p["ramp"]["settle_finished"] == 16 and "prefix" not in p
    a = traffic.Traffic(p, 3, vocab=cfg["vocab_size"])
    b = traffic.Traffic(p, 2 ** 31 + 7, vocab=cfg["vocab_size"])
    assert a.multiset() == b.multiset()
    pairs = a.pairs
    eng = cfg["engine"]
    assert len(pairs) == 64 == eng["max_batch"]
    # every decoding row lies at least two windows deep from its first step
    assert min(n for n, _, _ in pairs) >= 2 * cfg["sliding_window"] == 1024
    assert max(n + o for n, o, _ in pairs) <= 10240 == eng["max_seq"]
    assert max(n for n, _, _ in pairs) <= eng["prefill_budget"] == 8192
    assert sum(s for _, _, s in pairs) == 21
    mean_p = np.mean([n for n, _, _ in pairs])
    mean_o = np.mean([o for _, o, _ in pairs])
    assert 3430 < mean_p < 3465 and 855 < mean_o < 870
    # the full group holds the live contexts with room: 64 rows at the mean
    # prompt plus half an answer are ~76% of 2,560 blocks of 128
    live = 64 * (mean_p + mean_o / 2)
    assert 0.70 < live / (eng["num_blocks"] * 128) < 0.80
    spec = a.next()
    assert spec.prompt.max() < cfg["vocab_size"] == 12544
    assert builders.reachable_ladder(cfg, [n for n, _, _ in pairs]) == [
        2048, 4096, 8192]


def test_cell_declares_what_it_reports():
    spec = bench.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert [m["name"] for m in spec["end_to_end"]] == ["out_tok_per_s",
                                                       "setup_s"]
    names = {m["name"] for m in spec["per_layer"]}
    own = {"lag.window_attn_roofline", "lag.full_attn_roofline",
           "lag.expert_ffn_roofline", "lag.decode_step_roofline"}
    generic = {"kv.util_peak_pct", "engine.tpot_p50_ms",
               "sat.bootstrap.xla_in_window", "sat.engine.step_wall_p50_ms",
               "sat.engine.tok_per_dispatch", "sat.sched.rows_mean",
               "sat.kv.preemptions", "sat.prog.decode_dev_ms",
               "sat.device.idle_share_pct"}
    assert names == own | generic
    for name in names:
        readers.load(name)                  # every metric has its file
    # the other cells read none of the new metrics
    for other in ("mellum2_l8_mixedctx_sat", "gc3_ep16_l5_reason_sat"):
        assert not {m["name"] for m in bench.load_cell(other)["per_layer"]
                    } & own


def test_roofline_reader_reads_named_calls_and_nothing_without_them():
    ctx = {"counters": {"decode.rows_mean": 64.0,
                        "decode.ctx_sum_mean": 250_000.0,
                        "engine.decode_steps": 100},
           "samples": {}, "config": config(), "device_kind": "TPU v5 lite",
           "trace": {"module_s": {"jit_decode_horizon": 1.7},
                     "module_n": {"jit_decode_horizon": 13},
                     "module_op_s": {
                         "jit_decode_horizon|gqa_paged_window": 0.3,
                         "jit_decode_horizon|gqa_paged_full": 0.5,
                         "jit_decode_horizon|moe_gate_up": 0.5,
                         "jit_decode_horizon|moe_down": 0.3,
                         "jit_prefill_chunk|moe_gate_up": 9.0}}}
    pk = shapes.peaks("TPU v5 lite")
    for name, fn, took in (
            ("lag.window_attn_roofline", "window_attention", 0.3 / 100),
            ("lag.full_attn_roofline", "full_attention", 0.5 / 100),
            ("lag.expert_ffn_roofline", "expert_ffn", 0.8 / 100),
            ("lag.decode_step_roofline", "decode_step", 1.7 / 100)):
        need = shapes_laguna.FUNCTIONS[fn](ctx["config"], rows=64.0,
                                           ctx_sum=250_000.0)
        want = 100 * shapes.least_seconds(need, pk)[0] / took
        assert readers.read(name, ctx) == pytest.approx(want)
        assert 0 < want < 100
    # a program without the named calls, or a configuration that states no
    # heads by layer (the other cells): nothing, and no raise
    bare = copy.deepcopy(ctx)
    bare["trace"]["module_op_s"] = {"jit_decode_horizon|closed_call": 1.0}
    assert readers.read("lag.window_attn_roofline", bare) is None
    assert readers.read("lag.full_attn_roofline", bare) is None
    other = dict(ctx, config=builders.load_config(bench.load_cell(
        "mellum2_l8_mixedctx_sat")["config_file"]))
    assert readers.read("lag.expert_ffn_roofline", other) is None
    assert readers.read("lag.decode_step_roofline", other) is None
    bare["trace"] = None
    assert readers.read("lag.decode_step_roofline", bare) is None


def test_file_keys_against_the_catalog_and_the_constructors():
    """Every number of the catalog entry's ``config`` is in the file under
    the same key unless ``reduced`` names it; no width is among those; the
    file as the builder reads it reaches the constructors."""
    from benchmarks import builders_swa_moe
    from triton_dist_tpu.models import swa_moe as S

    cfg = config()
    with open(bench.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cfg["name"])
    assert entry["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "gating_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size",
        "max_position_embeddings"]
    assert entry["source"] == cfg["source"]
    try:
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-S-2.1")
    except OSError:
        pytest.skip("the catalog is not on this machine")
    assert row["source_url"] == cfg["source"]
    for key, want in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != want
            if isinstance(want, list):          # a cut to the first entries
                assert cfg[key] == want[:9]
        else:
            assert cfg[key] == want, key
    assert not [k for k in cfg["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    model = builders_swa_moe.model_config(cfg)
    assert (model.n_experts, model.experts_held, model.expert_offset) == (
        256, 32, 0)
    assert model.vocab * cfg["share"]["chips_per_layer"] \
        == cfg["share"]["vocab_total"] == 100352
    gen = S.SwaMoeGenerator(model, max_seq=cfg["engine"]["max_seq"])
    assert gen.kv_planes == [(8, 128), (8, 128)]
    assert [(g["name"], g["window"], len(g["layers"]), g["heads"])
            for g in gen.kv_groups] == [("full", 0, 3, 48),
                                        ("window", 512, 6, 72)]
    assert (cfg["engine"]["num_blocks"] * 128
            * cfg["kv_bytes_per_token"]["full_group"]) / 1e9 \
        == pytest.approx(4.03, abs=0.01)
    assert 385 * 128 * cfg["kv_bytes_per_token"]["window_group"] / 1e9 \
        == pytest.approx(1.21, abs=0.01)
    assert cfg["engine"]["prefix_cache"] is False
    for key in ("assumed", "not_run", "deployment", "weights",
                "engine_moved"):
        assert cfg[key]
    assert set(cfg["correct"]["limits"]) == {"gap_max", "gap_mean"}
    import inspect

    from triton_dist_tpu.serve import ServeEngine

    took = set(inspect.signature(ServeEngine.__init__).parameters)
    assert set(cfg["engine"]) - {"max_seq", "kv_dtype"} <= took


def _toy(cfg: dict) -> dict:
    """A toy file of the same keys: 5 layers (F S S S F), window 16, 4 / 6
    query heads over 2 KV heads, 4 of 8 experts held."""
    rope = copy.deepcopy(cfg["rope_parameters"])
    rope["full_attention"].update(factor=4,
                                  original_max_position_embeddings=32)
    return dict(cfg, hidden_size=128, intermediate_size=256,
                moe_intermediate_size=128,
                shared_expert_intermediate_size=128, num_hidden_layers=5,
                layer_types=cfg["layer_types"][:5],
                mlp_layer_types=cfg["mlp_layer_types"][:5],
                gating_types=cfg["gating_types"][:5],
                num_attention_heads=4,
                num_attention_heads_per_layer=[4, 6, 6, 6, 4],
                num_key_value_heads=2, vocab_size=256, num_experts=4,
                share={"experts_total": 8, "expert_offset": 4},
                num_experts_per_tok=3, sliding_window=16,
                rope_parameters=rope, torch_dtype="float32",
                engine=dict(cfg["engine"], max_seq=256, page_size=8,
                            prefill_chunk=16, prefill_budget=64,
                            max_batch=2, num_blocks=48))


def test_the_builder_as_it_stands_serves_a_toy_file_through_two_groups():
    from benchmarks import builders_swa_moe
    from triton_dist_tpu.serve import Request, SamplingParams
    from triton_dist_tpu.serve.block_manager import KvGroups

    cfg = _toy(config())
    assert cfg["builder"] == "benchmarks.builders_swa_moe:build"
    engine, model = builders_swa_moe.build(cfg, 2 ** 31 + 9, chips=1,
                                           ladder=[64])
    assert isinstance(engine.bm, KvGroups) and model.gated
    assert model.heads_by_layer == (4, 6, 6, 6, 4)
    assert engine.group_blocks == [48, 1 + 2 * ((16 + 16 - 2) // 8 + 2)]
    prompt = np.arange(40, dtype=np.int32) % 256
    engine.submit(Request("q0", prompt, SamplingParams(max_new_tokens=24)))
    out = engine.run(500)["q0"]
    assert len(out.token_ids) == 24
    assert engine.metrics.summary()["swa"]["heads"] == {"full": 4,
                                                        "window": 6}
    bench.drain(engine)                     # both free lists whole


def test_reference_interface_and_int8_control():
    """``check_outputs`` on made-up records at a small size: the reference
    module loads by the file's name, takes sequences and prompt lengths,
    and a request served by the reference's own argmax reads gap 0; its
    int8 control (operands and cached K / V rows) does not."""
    ref = importlib.import_module("benchmarks.reference.laguna")
    ref.Q_BLOCK = ref.T_BLOCK = 32
    cfg = _toy(config())
    assert cfg["reference"] == "laguna"
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
    ctl = control.read_control({"config": cfg, "seed": seed, "recs": [rec]},
                               3)
    assert ctl["numbers"]["gap_mean"] > 0.0 and not ctl["ok"]
