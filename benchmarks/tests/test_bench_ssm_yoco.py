"""The decoder-hybrid-decoder cell's benchmark pieces on the CPU: the
counting functions against hand counts at the published widths, the
traffic file's multiset against the configuration's limits, the readers on
a made-up trace, the file's keys reaching the constructors (and the builder
on a toy file), and the reference through ``check_outputs``' interface.

``--cpu-dryrun`` of this cell is NOT here: the rehearsal's sizes are the
harness's (``builders.TOY``: a head of 128 on 4 heads and 2 layers, which
this family's split cannot be cut to) — its rehearsal is
``tests/test_ssm_yoco.py`` and the toy file below."""

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
    shapes_ssm_yoco,
    traffic,
)
from benchmarks import run as bench

CELL = "phi4mf_reason96_sat"
NEW = ("yoco.shared_attn_roofline", "yoco.window_attn_roofline",
       "ssm.scan_roofline", "ssm_yoco.decode_step_roofline")


# the catalogue's ``config`` of this model (model-configs guide,
# architectures.jsonl), key for key
CATALOGUED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


def config():
    return builders.load_config(bench.load_cell(CELL)["config_file"])


def test_counting_functions_by_hand():
    """The published widths, the whole model: 9 state-space, 8 window, 1
    full, 7 gated-memory and 7 cross-attention layers, 200,064 rows."""
    cfg = config()
    assert shapes_ssm_yoco.layer_kinds(32).count("ssm") == 9
    assert shapes_ssm_yoco.mixer_params(cfg, "window") == 19_660_800
    assert shapes_ssm_yoco.mixer_params(cfg, "cross") == 13_107_200
    assert shapes_ssm_yoco.mixer_params(cfg, "ssm") == 41_241_600
    assert shapes_ssm_yoco.mixer_params(cfg, "gmu") == 26_214_400
    total = 3_852_451_840
    assert shapes_ssm_yoco.params_total(cfg) == total
    assert abs(total * 2 / 1e9 - 7.70) < 0.005                   # 7.70 GB
    assert shapes_ssm_yoco.kv_bytes_per_token_layer(cfg) == 5120 \
        == cfg["kv_bytes_per_token"]["per_layer"]
    assert cfg["kv_bytes_per_token"]["window_group"] == 8 * 5120
    assert shapes_ssm_yoco.state_bytes_per_request(cfg) == 3_225_600 \
        == cfg["state_bytes_per_request"]["request"]
    assert cfg["state_bytes_per_request"]["per_layer"] == 358_400
    assert shapes_ssm_yoco.shared_readers(cfg) == 8
    # 96 rows whose contexts sum to 168,768 tokens (mean 1,758)
    rows, ctx = 96.0, 168_768.0
    sh = shapes_ssm_yoco.shared_attention(cfg, rows=rows, ctx_sum=ctx)
    assert sh["bytes"] == (ctx * 5120 * 8 + rows * 40 * 64 * 2 * 8
                           + rows * 40 * 65 * 4 * 8)
    assert sh["flops"] == 4 * ctx * 40 * 64 * 8
    w = shapes_ssm_yoco.window_attention(cfg, rows=rows, ctx_sum=ctx)
    seen = rows * 512
    assert w["bytes"] == (seen * 5120 * 8 + rows * 40 * 64 * 2 * 8
                          + rows * 40 * 65 * 4 * 8)
    # contexts under the window: never more tokens than there are
    assert shapes_ssm_yoco.window_tokens(cfg, rows=4.0, ctx_sum=1000.0) \
        == 1000.0
    sc = shapes_ssm_yoco.ssm_scan(cfg)
    assert sc["flops"] == 6 * 512 * 5120 * 16
    assert sc["bytes"] == (3 * 512 * 5120 * 2 + 2 * 512 * 16 * 2
                           + 2 * 5120 * 16 * 4)
    d = shapes_ssm_yoco.decode_step(cfg, rows=rows, ctx_sum=ctx)
    assert d["bytes"] == (
        total * 2 + rows * 2560 * 2 + (ctx * 8 + seen * 8) * 5120
        + rows * 9 * 5120 + rows * 3_225_600 * 2 + rows * 200064 * 4)
    assert d["flops"] == (2 * rows * total + sh["flops"] + w["flops"]
                          + 6 * rows * 9 * 5120 * 16)
    pk = shapes.peaks("TPU v5 lite")
    least, bound = shapes.least_seconds(d, pk)
    # 7.7 GB of weights + 6.9 GB through the shared cache + 2.0 GB on
    # window layers + 0.6 GB of state
    assert bound == "memory" and 0.0205 < least < 0.0220
    assert 0.0083 < shapes.least_seconds(sh, pk)[0] < 0.0086
    assert 0.0024 < shapes.least_seconds(w, pk)[0] < 0.0026
    assert 19e-6 < shapes.least_seconds(sc, pk)[0] < 21e-6


def test_traffic_multiset_fits_the_configuration():
    cfg = config()
    p = traffic.load("reason96_sat")
    a = traffic.Traffic(p, 3, vocab=cfg["vocab_size"])
    b = traffic.Traffic(p, 2 ** 31 + 7, vocab=cfg["vocab_size"])
    assert a.multiset() == b.multiset()
    pairs = a.pairs
    eng = cfg["engine"]
    assert len(pairs) == p["cycle"] == p["clients"] == 96 == eng["max_batch"]
    assert min(n for n, _, _ in pairs) >= 256
    assert max(n for n, _, _ in pairs) <= 2048 <= eng["prefill_budget"]
    assert min(o for _, o, _ in pairs) >= 512
    assert max(n + o for n, o, _ in pairs) <= 5120 == eng["max_seq"]
    assert sum(s for _, _, s in pairs) == 32            # 1 in 3 sampled
    # reason_sat's laws to the token (the two reasoning cells differ in
    # model and rows only)
    r = traffic.load("reason_sat")
    for key in ("prompt", "output", "sampler", "sampled_every",
                "pairing_stride", "check_sample", "loop", "order"):
        assert p[key] == r[key], key
    mean_p = np.mean([n for n, _, _ in pairs])
    mean_o = np.mean([o for _, o, _ in pairs])
    assert 820 < mean_p < 900 and 1380 < mean_o < 1480
    # the full group holds the live contexts with room
    live = 96 * (mean_p + mean_o / 2)
    assert 0.35 < live / (eng["num_blocks"] * 128) < 0.60
    spec = a.next()
    assert spec.prompt.max() < cfg["vocab_size"]
    assert builders.reachable_ladder(cfg, [n for n, _, _ in pairs]) == [
        512, 1024, 2048]


def test_cell_declares_what_it_reports():
    spec = bench.load_cell(CELL)
    assert [m["name"] for m in spec["end_to_end"]] == ["out_tok_per_s",
                                                       "setup_s"]
    names = {m["name"] for m in spec["per_layer"]}
    assert set(NEW) | {
        "kv.util_peak_pct", "engine.tpot_p50_ms", "sat.kv.preemptions",
        "sat.bootstrap.xla_in_window", "sat.engine.step_wall_p50_ms",
        "sat.engine.tok_per_dispatch", "sat.sched.rows_mean",
        "sat.prog.decode_dev_ms", "sat.device.idle_share_pct"} == names
    for name in names:
        readers.load(name)                  # every metric has its file
    with open(bench.ROOT + "/BENCHMARK.json") as f:
        whole = json.load(f)
    for m in whole["per_layer"]:
        if m["name"] in NEW:
            assert (m["layer"], m["source"], m["moves"], m["workloads"]) == (
                "Kernels", "device_trace", "out_tok_per_s", [CELL])
    # the other cells read none of the new metrics
    for other in ("m7b_l16_decode_sat", "mellum2_l8_mixedctx_sat"):
        assert not {m["name"] for m in bench.load_cell(other)["per_layer"]
                    } & set(NEW)


def test_roofline_readers_read_named_calls_and_nothing_without_them():
    ctx = {"counters": {"decode.rows_mean": 95.0,
                        "decode.ctx_sum_mean": 170_000.0,
                        "engine.decode_steps": 100},
           "samples": {}, "config": config(), "device_kind": "TPU v5 lite",
           "trace": {"module_s": {"jit_decode_horizon": 3.2},
                     "module_n": {"jit_decode_horizon": 13},
                     "op_s": {"ssm_scan": 0.9, "ssm_scan_other": 5.0},
                     "op_n": {"ssm_scan": 900, "ssm_scan_other": 1},
                     "module_op_s": {
                         "jit_decode_horizon|gqa_paged_window": 0.4,
                         "jit_decode_horizon|gqa_paged_full": 0.15,
                         "jit_decode_horizon|gqa_paged_cross": 1.05,
                         "jit_prefill_chunk|ssm_scan": 0.9}}}
    pk = shapes.peaks("TPU v5 lite")
    for name, fn, took in (
            ("yoco.shared_attn_roofline", "shared_attention", 1.2 / 100),
            ("yoco.window_attn_roofline", "window_attention", 0.4 / 100),
            ("ssm_yoco.decode_step_roofline", "decode_step", 3.2 / 100),
            ("ssm.scan_roofline", "ssm_scan", 0.9 / 900)):
        need = shapes_ssm_yoco.FUNCTIONS[fn](ctx["config"], rows=95.0,
                                             ctx_sum=170_000.0)
        want = 100 * shapes.least_seconds(need, pk)[0] / took
        assert readers.read(name, ctx) == pytest.approx(want)
        assert 0 < want < 100
    # a program without the named calls, another family's configuration
    # (the other cells; the parent commit) or no trace: nothing, no raise
    bare = copy.deepcopy(ctx)
    bare["trace"]["module_op_s"] = {"jit_decode_horizon|closed_call": 1.0}
    bare["trace"]["op_s"] = bare["trace"]["op_n"] = {}
    assert readers.read("yoco.shared_attn_roofline", bare) is None
    assert readers.read("yoco.window_attn_roofline", bare) is None
    assert readers.read("ssm.scan_roofline", bare) is None
    other = dict(ctx, config=builders.load_config(bench.load_cell(
        "mellum2_l8_mixedctx_sat")["config_file"]))
    for name in NEW:
        assert readers.read(name, other) is None
    bare["trace"] = None
    for name in NEW:
        assert readers.read(name, bare) is None


def test_file_keys_reach_the_constructors():
    """The file as the builder reads it, at the published widths and
    without a device array: the model config, the planes and groups, the
    catalog's keys, and an engine key no constructor takes."""
    from benchmarks import builders_ssm_yoco
    from triton_dist_tpu.models import ssm_yoco as Y

    cfg = config()
    model = builders_ssm_yoco.model_config(cfg)
    assert (model.d_state, model.d_conv, model.expand, model.dt_rank) == (
        16, 4, 2, 160)
    assert model.n_params() == shapes_ssm_yoco.params_total(cfg)
    assert model.layer_types == shapes_ssm_yoco.layer_kinds(32)
    gen = Y.SsmYocoGenerator(model, max_seq=cfg["engine"]["max_seq"])
    assert gen.kv_planes == [(10, 128), (10, 128)]
    assert [(g["name"], g["window"], len(g["layers"]))
            for g in gen.kv_groups] == [("full", 0, 1), ("window", 512, 8),
                                        ("state", 0, 9)]
    assert model.state_bytes_per_request == 3_225_600
    assert (cfg["engine"]["num_blocks"] * 128
            * cfg["kv_bytes_per_token"]["full_group"]) / 1e9 \
        == pytest.approx(1.68, abs=0.01)
    with open(bench.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cfg["name"])
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "max_position_embeddings"}
    assert entry["source"] == cfg["source"]
    # the catalogued config.json at every width
    for key, val in CATALOGUED.items():
        if key not in entry["reduced"]:
            assert cfg[key] == val, key
    assert cfg["max_position_embeddings"] == cfg["engine"]["max_seq"]
    assert cfg["engine"]["prefix_cache"] is False
    for key in ("assumed", "not_run", "deployment", "weights",
                "engine_derived"):
        assert cfg[key]
    assert "differential" in cfg["not_run"].lower()
    import inspect

    from triton_dist_tpu.serve import ServeEngine

    took = set(inspect.signature(ServeEngine.__init__).parameters)
    assert set(cfg["engine"]) - {"max_seq", "kv_dtype"} <= took


def _toy(cfg: dict) -> dict:
    """A toy file of the same keys: 8 layers (ssm 0 2 4, window 1 3, full
    5, gmu 6, cross 7), 4 heads of 64, window 16."""
    return dict(cfg, hidden_size=256, intermediate_size=256,
                num_hidden_layers=8, num_attention_heads=4,
                num_key_value_heads=2, vocab_size=256, sliding_window=16,
                torch_dtype="float32",
                assumed=dict(cfg["assumed"], dt_rank=16),
                engine=dict(cfg["engine"], max_seq=256, page_size=8,
                            prefill_chunk=16, prefill_budget=64,
                            max_batch=2, num_blocks=48))


def test_builder_on_a_toy_file_serves_through_three_groups():
    from benchmarks import builders_ssm_yoco
    from triton_dist_tpu.serve import Request, SamplingParams
    from triton_dist_tpu.serve.block_manager import KvGroups

    cfg = _toy(config())
    engine, model = builders_ssm_yoco.build(cfg, 2 ** 31 + 9, chips=1,
                                            ladder=[64])
    assert isinstance(engine.bm, KvGroups) and model.n_layers == 8
    assert engine.group_blocks == [48, 1 + 2 * ((16 + 16 - 2) // 8 + 2), 3]
    assert engine.prefill_width == 16           # ONE chunk a call
    prompt = np.arange(40, dtype=np.int32) % 256
    engine.submit(Request("q0", prompt, SamplingParams(max_new_tokens=24)))
    out = engine.run(500)["q0"]
    assert len(out.token_ids) == 24
    bench.drain(engine)                     # all three free lists whole
    with pytest.raises(ValueError):
        builders_ssm_yoco.build(cfg, 0, chips=4, ladder=[64])


def test_reference_interface_and_the_control():
    """``check_outputs`` on made-up records at a small size: the reference
    module loads by the file's name, takes sequences and prompt lengths,
    and a request served by the reference's own argmax reads gap 0; its
    control (int8 operands and cached K / V rows, the state rounded to
    bfloat16 every step) does not."""
    ref = importlib.import_module("benchmarks.reference.ssm_yoco")
    ref.Q_BLOCK = ref.T_BLOCK = 32
    cfg = _toy(config())
    assert cfg["reference"] == "ssm_yoco"
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
    assert ctl["numbers"]["gap_mean"] > 0.0
