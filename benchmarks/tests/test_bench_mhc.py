"""The ``xing4_stage_reason96_sat`` cell's benchmark pieces on the CPU: the
counting functions against hand counts at the published widths, the readers
on a made-up trace, the file's keys against the catalog's and
``BENCHMARK.json``, the builder AS IT STANDS on a toy file, and the
reference through ``check_outputs``' interface with its control.

``--cpu-dryrun`` of this cell is NOT here: the rehearsal's sizes are the
harness's (``builders.TOY``: hidden 512 on 2 layers with the file's 32 heads
and latent ranks kept) and its engine's ``max_seq`` 512 is under
``reason96_sat``'s longest scaled request — ``tests/test_mhc.py`` (tier-1)
is the rehearsal."""

import copy
import importlib
import json

import numpy as np
import pytest

from benchmarks import builders, control, readers, shapes, shapes_mhc
from benchmarks import shapes_mla_moe, traffic
from benchmarks import run as bench

CELL = "xing4_stage_reason96_sat"
CONTROL_CELL = "gc3_ep16_l5_reason_sat"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = {"hc.decode_share_pct", "mhc_moe.decode_step_roofline"}


def config():
    return builders.load_config(bench.load_cell(CELL)["config_file"])


def test_counting_functions_by_hand():
    """Xing4.0-29B-A4B's widths, one pipeline stage: a dense lead layer and
    six expert layers that hold all 64 experts, the whole vocabulary, four
    residual streams."""
    cfg = config()
    D, n, k = 3584, 4, 24
    attn = (D * 768 + 768 * 32 * 192 + D * 576 + 512 * 32 * 256
            + 32 * 128 * D)
    assert shapes_mla_moe.attention_params(cfg) == attn == 28_409_856
    expert = 3 * D * 1024
    assert shapes_mla_moe.expert_params(cfg) == expert == 11_010_048
    maps = n * D * k + k + 3 + n * D            # phi, bias, alpha, gain
    assert shapes_mhc.map_params(cfg) == maps == 358_427
    assert shapes_mhc.n_maps(cfg) == k and shapes_mhc.sublayers(cfg) == 14
    moe = attn + D * 64 + 64 + 65 * expert + 2 * maps
    assert shapes_mhc.layer_params_held(cfg, True) == moe == 745_009_270
    assert round(moe / 1e6, 1) == 745.0                     # ISSUE 49's
    dense = attn + 3 * D * 9216 + 2 * maps
    assert shapes_mhc.layer_params_held(cfg, False) == dense
    assert round(dense / 1e6, 1) == 128.2
    held = dense + 6 * moe + 2 * D * 131072
    assert round(held * 2 / 1e9, 2) == 11.08                # GB at 2 B each
    # a sub-layer's streams at a decode step of 96 rows: X in, the
    # sub-layer's row, X' out
    assert shapes_mhc.stream_bytes(cfg, 96) == 96 * 9 * D * 2 == 6_193_152
    assert 14 * 6_193_152 == 86_704_128                     # 87 MB a step
    # every expert is hit at 96 rows x top-4 over 64: 6 rows an expert
    assert shapes_mla_moe.routed_rows(cfg, 96.0) == 384.0
    assert shapes_mla_moe.experts_hit(cfg, 96.0) == pytest.approx(
        64 * (1 - (63 / 64) ** 384))
    assert 63.8 < shapes_mla_moe.experts_hit(cfg, 96.0) < 64
    rows, ctx = 94.0, 94.0 * 2000
    # a step's need: the family's, and a sub-layer's maps once (float32)
    # with the product with phi; the streams are not counted as HBM bytes
    step = shapes_mhc.decode_step(cfg, rows=rows, ctx_sum=ctx)
    plain = shapes_mla_moe.decode_step(cfg, rows=rows, ctx_sum=ctx)
    assert step["bytes"] == plain["bytes"] + 14 * maps * 4
    assert 14 * maps * 4 == 20_071_912
    assert step["flops"] == plain["flops"] + 14 * rows * 2 * n * D * k
    assert set(step) == {"flops", "bytes"}
    # the mixes' parameters are under 1% of a step's bytes
    assert (step["bytes"] - plain["bytes"]) / step["bytes"] < 0.01
    least, bound = shapes.least_seconds(step, shapes.peaks("TPU v5 lite"))
    assert bound == "memory" and 0.0135 < least < 0.0150
    # the cache as stored and as held
    assert cfg["kv_bytes_per_token"] == 7 * 576 * 2 == 8064
    assert cfg["kv_bytes_per_token_as_stored"] == 7 * 640 * 2 == 8960
    assert 1824 * 128 * 8960 / 1e9 == pytest.approx(2.09, abs=0.005)


def test_traffic_fits_the_configuration():
    cfg = config()
    p = traffic.load("reason96_sat")
    assert (p["loop"], p["clients"]) == ("closed", 96)
    eng = cfg["engine"]
    assert eng["max_batch"] == 96
    a = traffic.Traffic(p, 2 ** 31 + 11, vocab=cfg["vocab_size"])
    pairs = a.pairs
    assert max(n + o for n, o, _ in pairs) <= 5120 <= eng["max_seq"]
    assert a.next().prompt.max() < cfg["vocab_size"] == 131072
    assert builders.reachable_ladder(cfg, [n for n, _, _ in pairs]) == [
        512, 1024, 2048]
    # live contexts: 96 rows at the mean prompt plus half an answer
    live = 96 * (np.mean([n for n, _, _ in pairs])
                 + np.mean([o for _, o, _ in pairs]) / 2)
    assert 0.55 < live / (eng["num_blocks"] * 128) < 0.85


def test_cell_declares_what_it_reports():
    spec = bench.load_cell(CELL)
    assert spec["cell"]["chips"] == 1
    assert spec["cell"]["traffic"] == "reason96_sat"
    assert [m["name"] for m in spec["end_to_end"]] == ["out_tok_per_s",
                                                       "setup_s"]
    names = {m["name"] for m in spec["per_layer"]}
    shared = {"mla.paged_attn_roofline", "moe.expert_ffn_roofline"}
    generic = {"kv.util_peak_pct", "engine.tpot_p50_ms",
               "sat.bootstrap.xla_in_window", "sat.engine.step_wall_p50_ms",
               "sat.engine.tok_per_dispatch", "sat.sched.rows_mean",
               "sat.kv.preemptions", "sat.prog.decode_dev_ms",
               "sat.device.idle_share_pct"}
    assert names == OWN | shared | generic
    for name in names:
        readers.load(name)                  # every metric has its file
    lower = {m["name"] for m in spec["per_layer"] if m["better"] == "lower"}
    assert "hc.decode_share_pct" in lower
    # the control reads none of the new metrics, and keeps its own
    other = {m["name"] for m in bench.load_cell(CONTROL_CELL)["per_layer"]}
    assert not other & OWN and shared <= other


def test_readers_read_named_calls_and_nothing_without_them():
    ctx = {"counters": {"decode.rows_mean": 94.0,
                        "decode.ctx_sum_mean": 188_000.0,
                        "engine.decode_steps": 100},
           "samples": {}, "config": config(), "device_kind": "TPU v5 lite",
           "trace": {"module_s": {"jit_decode_horizon": 1.9,
                                  "jit_prefill_chunk": 0.4},
                     "module_n": {"jit_decode_horizon": 13},
                     "module_op_s": {
                         "jit_decode_horizon|hc_pre": 0.012,
                         "jit_decode_horizon|hc_post": 0.007,
                         "jit_decode_horizon|mla_paged_decode": 0.2,
                         "jit_decode_horizon|moe_gate_up": 0.8,
                         "jit_decode_horizon|moe_down": 0.5,
                         "jit_prefill_chunk|hc_pre": 9.0}}}
    pk = shapes.peaks("TPU v5 lite")
    need = shapes_mhc.decode_step(ctx["config"], rows=94.0,
                                  ctx_sum=188_000.0)
    want = 100 * shapes.least_seconds(need, pk)[0] / (1.9 / 100)
    assert readers.read("mhc_moe.decode_step_roofline",
                        ctx) == pytest.approx(want)
    assert 0 < want < 100
    # the chunk's executions are not the decode programs'
    assert readers.read("hc.decode_share_pct", ctx) == pytest.approx(
        100 * 0.019 / 1.9)
    # the accepted family metrics count this file from its own keys
    assert 0 < readers.read("mla.paged_attn_roofline", ctx) < 100
    assert 0 < readers.read("moe.expert_ffn_roofline", ctx) < 100
    # a program without the named calls (the parent's), or a configuration
    # that states no hc_mult (the other cells): nothing, and no raise
    bare = copy.deepcopy(ctx)
    bare["trace"]["module_op_s"] = {"jit_decode_horizon|fusion": 1.0}
    assert readers.read("hc.decode_share_pct", bare) is None
    other = dict(ctx, config=builders.load_config(bench.load_cell(
        CONTROL_CELL)["config_file"]))
    for name in OWN:
        assert readers.read(name, other) is None
    bare["trace"] = None
    for name in OWN:
        assert readers.read(name, bare) is None


def test_file_keys_against_the_catalog_and_the_constructors():
    """Every number of the catalog entry's ``config`` is in the file under
    the same key unless ``reduced`` names it; no width is among those; the
    file as the builder reads it reaches the constructors."""
    from benchmarks import builders_mla_moe
    from triton_dist_tpu.models import mla_moe as M

    cfg = config()
    with open(bench.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cfg["name"])
    assert entry["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers", "first_k_dense_replace",
        "max_position_embeddings", "num_nextn_predict_layers"]
    assert entry["source"] == cfg["source"]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["max_position_embeddings"],
            cfg["num_nextn_predict_layers"]) == (7, 1, 8192, 0)
    try:
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
    except OSError:
        pytest.skip("the catalog is not on this machine")
    assert row["source_url"] == cfg["source"]
    for key, want in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != want
        else:
            assert cfg[key] == want, key
    assert not [k for k in cfg["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))]
    # not reduced: every expert and the whole vocabulary are held
    assert cfg["share"] == {"chips_per_layer": 1, "experts_total": 64,
                            "expert_offset": 0,
                            "vocab_size_published": 131072,
                            "vocab_shards": 1}
    assert cfg["n_routed_experts"] == 64 and cfg["vocab_size"] == 131072
    assert len(cfg["assumed"]) >= 5
    assert any("Sinkhorn" in a and "columns" in a for a in cfg["assumed"])
    assert any("float32" in a for a in cfg["assumed"])
    for key in ("not_run", "deployment", "weights", "engine_moved"):
        assert cfg[key]
    assert cfg["builder"] == "benchmarks.builders_mla_moe:build"
    assert cfg["reference"] == "mla_mhc_moe_share"
    assert set(cfg["correct"]["limits"]) == {"gap_max", "gap_mean"}
    assert cfg["correct"]["read_on_the_chip"]
    model = builders_mla_moe.model_config(cfg)
    assert (model.n_experts, model.experts_held, model.expert_offset) == (
        64, 64, 0)
    assert (model.hc_mult, model.hc_sinkhorn_iters, model.hc_eps,
            model.hc_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert (model.n_layers, model.first_k_dense, model.n_heads,
            model.top_k, model.vocab) == (7, 1, 32, 4, 131072)
    gen = M.MlaMoeGenerator(model, max_seq=cfg["engine"]["max_seq"])
    assert gen.kv_planes == [(1, 640)]
    assert "streams" in gen.serve_hooks()
    # every program of a layer that holds all its experts gathers
    assert set(gen.moe_combine_forms({"prefill_chunk": 512,
                                      "decode_horizon": 96}).values()) == {
        "gather"}
    import inspect

    from triton_dist_tpu.serve import ServeEngine

    took = set(inspect.signature(ServeEngine.__init__).parameters)
    assert set(cfg["engine"]) - {"max_seq", "kv_dtype"} <= took


def _toy(cfg: dict) -> dict:
    """A toy file of the same keys: one dense + two expert layers that hold
    all 8 experts, four streams of 128."""
    rope = dict(cfg["rope_scaling"], factor=4,
                original_max_position_embeddings=32)
    return dict(cfg, hidden_size=128, intermediate_size=256,
                moe_intermediate_size=128, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=4,
                q_lora_rank=64, kv_lora_rank=128, qk_nope_head_dim=32,
                qk_rope_head_dim=32, v_head_dim=48, vocab_size=256,
                n_routed_experts=8, num_experts_per_tok=2,
                share=dict(cfg["share"], experts_total=8),
                rope_scaling=rope, torch_dtype="float32",
                # the file's limits are the published widths' (read on the
                # chip); a float32 toy is held to tight ones
                correct=dict(cfg["correct"],
                             limits={"gap_max": 0.5, "gap_mean": 0.002}),
                engine=dict(cfg["engine"], max_seq=256, page_size=8,
                            prefill_chunk=16, max_batch=2, num_blocks=48))


def test_the_builder_as_it_stands_serves_a_toy_file_with_streams():
    from benchmarks import builders_mla_moe
    from triton_dist_tpu.serve import Request, SamplingParams

    cfg = _toy(config())
    engine, model = builders_mla_moe.build(cfg, 2 ** 31 + 9, chips=1,
                                           ladder=[64], interpret=True)
    assert model.hc_mult == 4 and model.experts_held == model.n_experts == 8
    prompt = np.arange(40, dtype=np.int32) % 256
    engine.submit(Request("q0", prompt, SamplingParams(max_new_tokens=16)))
    out = engine.run(500)["q0"]
    assert len(out.token_ids) == 16
    hc = engine.metrics.summary()["hc"]
    assert hc["streams"] == 4 and hc["sublayers"] == 6
    assert set(hc["blocking"]) == {"prefill_chunk", "paged_decode",
                                   "decode_horizon"}
    bench.drain(engine)


def test_reference_interface_and_its_control():
    """``check_outputs`` on made-up records at a small size: the reference
    module loads by the file's name, and a request served by the
    reference's own argmax reads gap 0; its control (int8 operands and
    latent rows, the three maps rounded to bfloat16) does not."""
    ref = importlib.import_module("benchmarks.reference.mla_mhc_moe_share")
    base = importlib.import_module("benchmarks.reference.mla_moe_share")
    base.Q_BLOCK = base.T_BLOCK = ref.T_BLOCK = 32
    cfg = _toy(config())
    seed, n0, n_new = 2 ** 31 + 3, 40, 24
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
    # the maps' rounding alone moves the logits: the control is more than
    # the family's
    s = ref.sizes(cfg)
    X = np.asarray(rng.normal(size=(8, 4, 128)), np.float32)
    p = ref.draw_maps(cfg, seed, 1)["hc_mlp"]
    full, low = (ref.stream_maps(X, p, s, low=flag) for flag in (False, True))
    assert any(np.abs(np.asarray(a) - np.asarray(b)).max() > 1e-4
               for a, b in zip(full, low))
