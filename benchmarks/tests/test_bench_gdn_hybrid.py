"""The Gated-DeltaNet hybrid cell's benchmark pieces on the CPU: the
counting functions against hand counts at the published widths, the file's
sizes against the library's constructors, the traffic file's multiset
against the configuration's limits, the readers on a made-up trace, the
file's keys reaching the constructors (and the builder on a toy file), the
reference drawing the builder's weights, and the reference through
``check_outputs``' interface.

``--cpu-dryrun`` of this cell is NOT here: the rehearsal's sizes are the
harness's (``builders.TOY``: 2 layers, which is no period of this family)
— its rehearsal is ``tests/test_gdn_hybrid.py`` and the toy file below."""

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
    shapes_gdn_hybrid,
    traffic,
)
from benchmarks import run as bench

CELL = "olmoh_l8_reason96_sat"
NEW = ("gdn.step_roofline", "gdn.chunk_roofline", "gdn.full_attn_roofline",
       "gdn_hybrid.decode_step_roofline")

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# the catalogue's ``config`` of this model (model-configs guide,
# architectures.jsonl), key for key
CATALOGUED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


def config():
    return builders.load_config(bench.load_cell(CELL)["config_file"])


def test_counting_functions_by_hand():
    """The published widths, one stage: 6 linear and 2 full layers, the
    whole vocabulary, embedding and head apart."""
    cfg = config()
    D, F, V = 3840, 11008, 100352
    lin = (D * (2880 + 2880 + 5760) + 2 * D * 5760 + 2 * D * 30
           + 4 * 11520 + 60 + 192)
    assert shapes_gdn_hybrid.mixer_params(cfg, "linear") == lin == 88_750_332
    assert shapes_gdn_hybrid.mixer_params(cfg, "full") == 4 * D * D + 2 * D
    mlp = 3 * D * F + 2 * D
    assert lin + mlp == 215_570_172                      # a linear layer
    assert 4 * D * D + 2 * D + mlp == 185_809_920        # a full layer
    total = 6 * 215_570_172 + 2 * 185_809_920 + 2 * V * D + D
    assert shapes_gdn_hybrid.params_total(cfg) == total == 2_435_748_072
    assert abs(total * 2 / 1e9 - 4.87) < 0.005                   # 4.87 GB
    assert shapes_gdn_hybrid.kv_bytes_per_token_layer(cfg) == 15360 \
        == cfg["kv_bytes_per_token"]["per_layer"]
    assert cfg["kv_bytes_per_token"]["full_group"] == 2 * 15360 == 30720
    assert shapes_gdn_hybrid.state_bytes_per_layer(cfg) == 2_280_960 \
        == 96 * 5760 * 4 + 3 * 11520 * 2 \
        == cfg["state_bytes_per_request"]["per_layer"]
    assert shapes_gdn_hybrid.state_bytes_per_request(cfg) == 13_685_760 \
        == cfg["state_bytes_per_request"]["request"]
    # 96 rows whose contexts sum to 142,560 tokens (mean 1,485)
    rows, ctx = 96.0, 142_560.0
    at = shapes_gdn_hybrid.full_attention(cfg, rows=rows, ctx_sum=ctx)
    assert at["bytes"] == (ctx * 15360 * 2 + rows * 30 * 128 * 2 * 2
                           + rows * 30 * 129 * 4 * 2)
    assert at["flops"] == 4 * ctx * 30 * 128 * 2
    st = shapes_gdn_hybrid.gdn_step(cfg, rows=rows)
    assert st["bytes"] == rows * (2 * 96 * 5760 + 2 * 30 * 96 + 2 * 30 * 192
                                  + 2 * 30) * 4
    assert st["flops"] == 7 * rows * 30 * 96 * 192
    ch = shapes_gdn_hybrid.gdn_chunk(cfg)
    macs = (2 * 64 * 64 * 96 + 10 * 64 ** 3 + 64 * 64 * (192 + 96)
            + 3 * 64 * 96 * 192 + 64 * 64 * 192)
    assert ch["flops"] == 2 * macs * 30 * 8
    assert ch["bytes"] == (512 * (2 * 2880 + 2 * 5760 + 60)
                           + 2 * 96 * 5760) * 4
    d = shapes_gdn_hybrid.decode_step(cfg, rows=rows, ctx_sum=ctx)
    weights = total - V * D
    assert d["bytes"] == (
        weights * 2 + rows * D * 2 + ctx * 2 * 15360 + rows * 2 * 15360
        + rows * 13_685_760 * 2 + rows * V * 4)
    assert d["flops"] == (2 * rows * weights + at["flops"]
                          + 7 * rows * 6 * 30 * 96 * 192)
    pk = shapes.peaks("TPU v5 lite")
    least, bound = shapes.least_seconds(d, pk)
    # 4.10 GB of weights + 4.38 GB of cache + 2.63 GB of state
    assert bound == "memory" and 0.0133 < least < 0.0140
    assert 0.0053 < shapes.least_seconds(at, pk)[0] < 0.0055
    assert 520e-6 < shapes.least_seconds(st, pk)[0] < 530e-6
    t, what = shapes.least_seconds(ch, pk)
    assert what == "memory" and 45e-6 < t < 50e-6


def test_file_sizes_against_the_constructors():
    """The file's 2,435.7 M parameters, 2,280,960 B a slot a layer and
    30,720 B a token are what the library's constructors build."""
    from benchmarks import builders_gdn_hybrid
    from triton_dist_tpu.models import gdn_hybrid as G

    cfg = config()
    model = builders_gdn_hybrid.model_config(cfg)
    assert model.n_params() == shapes_gdn_hybrid.params_total(cfg) \
        == 2_435_748_072
    assert model.mixer_params("linear") == shapes_gdn_hybrid.mixer_params(
        cfg, "linear")
    assert model.state_bytes_per_layer == 2_280_960
    assert model.state_bytes_per_request == 13_685_760
    gen = G.GdnHybridGenerator(model, max_seq=cfg["engine"]["max_seq"])
    assert gen.kv_planes == [(30, 128), (30, 128)]
    per_token = sum(h * d * 2 for h, d in gen.kv_planes)
    assert per_token == 15360
    assert [(g["name"], g["window"], g["layers"]) for g in gen.kv_groups] == [
        ("full", 0, (3, 7)), ("state", 0, (0, 1, 2, 4, 5, 6))]
    assert per_token * 2 == cfg["kv_bytes_per_token"]["full_group"] == 30720
    eng = cfg["engine"]
    pools = (eng["num_blocks"] * 128 * 30720
             + (eng["max_batch"] + 1) * 13_685_760)
    assert pools / 1e9 == pytest.approx(6.04 + 1.33, abs=0.01)
    # at rest: weights + pools, well over a quarter of the chip
    assert (2 * 2_435_748_072 + pools) / 16e9 > 0.75


def test_traffic_multiset_fits_the_configuration():
    cfg = config()
    p = traffic.load("reason96_sat")
    a = traffic.Traffic(p, 3, vocab=cfg["vocab_size"])
    b = traffic.Traffic(p, 2 ** 31 + 7, vocab=cfg["vocab_size"])
    assert a.multiset() == b.multiset()
    pairs = a.pairs
    eng = cfg["engine"]
    assert len(pairs) == p["clients"] == 96 == eng["max_batch"]
    assert max(n for n, _, _ in pairs) <= 2048 <= eng["prefill_budget"]
    assert max(n + o for n, o, _ in pairs) <= 5120 == eng["max_seq"]
    assert sum(s for _, _, s in pairs) == 32            # 1 in 3 sampled
    mean_p = np.mean([n for n, _, _ in pairs])
    mean_o = np.mean([o for _, o, _ in pairs])
    # the full group holds the live contexts with room
    live = 96 * (mean_p + mean_o / 2)
    assert 0.6 < live / (eng["num_blocks"] * 128) < 0.9
    assert a.next().prompt.max() < cfg["vocab_size"]
    assert builders.reachable_ladder(cfg, [n for n, _, _ in pairs]) == [
        512, 1024, 2048]
    # the same traffic file as the other state cell: they differ in model
    assert bench.load_cell("phi4mf_reason96_sat")["cell"]["traffic"] \
        == bench.load_cell(CELL)["cell"]["traffic"] == "reason96_sat"


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
            assert (m["layer"], m["source"], m["moves"], m["workloads"],
                    m["unit"], m["better"]) == (
                "Kernels", "device_trace", "out_tok_per_s", [CELL], "%",
                "higher")
    assert [m["name"] for m in whole["per_layer"][-4:]] == list(NEW)
    assert whole["workloads"][-1]["name"] == CELL
    assert whole["workloads"][-1]["chips"] == 1
    # the other cells read none of the new metrics
    for other in ("m7b_l16_decode_sat", "phi4mf_reason96_sat"):
        assert not {m["name"] for m in bench.load_cell(other)["per_layer"]
                    } & set(NEW)


def test_roofline_readers_read_named_calls_and_nothing_without_them():
    ctx = {"counters": {"decode.rows_mean": 95.0,
                        "decode.ctx_sum_mean": 140_000.0,
                        "engine.decode_steps": 100},
           "samples": {}, "config": config(), "device_kind": "TPU v5 lite",
           "trace": {"module_s": {"jit_decode_horizon": 2.4},
                     "module_n": {"jit_decode_horizon": 13},
                     "op_s": {"gdn_step": 0.54, "gdn_chunk": 0.06,
                              "gdn_step_other": 5.0},
                     "op_n": {"gdn_step": 600, "gdn_chunk": 120,
                              "gdn_step_other": 1},
                     "module_op_s": {
                         "jit_decode_horizon|gqa_paged_full": 0.75,
                         "jit_decode_horizon|gdn_step": 0.54,
                         "jit_prefill_chunk|gdn_chunk": 0.06}}}
    pk = shapes.peaks("TPU v5 lite")
    for name, fn, took in (
            ("gdn.full_attn_roofline", "full_attention", 0.75 / 100),
            ("gdn_hybrid.decode_step_roofline", "decode_step", 2.4 / 100),
            ("gdn.step_roofline", "gdn_step", 0.54 / 600),
            ("gdn.chunk_roofline", "gdn_chunk", 0.06 / 120)):
        need = shapes_gdn_hybrid.FUNCTIONS[fn](ctx["config"], rows=95.0,
                                               ctx_sum=140_000.0)
        want = 100 * shapes.least_seconds(need, pk)[0] / took
        assert readers.read(name, ctx) == pytest.approx(want)
        assert 0 < want < 100
    # a program without the named calls, another family's configuration
    # (the other cells; the parent commit) or no trace: nothing, no raise
    bare = copy.deepcopy(ctx)
    bare["trace"]["module_op_s"] = {"jit_decode_horizon|closed_call": 1.0}
    bare["trace"]["op_s"] = bare["trace"]["op_n"] = {}
    for name in NEW[:3]:
        assert readers.read(name, bare) is None
    other = dict(ctx, config=builders.load_config(bench.load_cell(
        "phi4mf_reason96_sat")["config_file"]))
    for name in NEW:
        assert readers.read(name, other) is None
    bare["trace"] = None
    for name in NEW:
        assert readers.read(name, bare) is None


def test_file_keys_reach_the_constructors():
    """The file as the builder reads it: the catalog's keys at every
    width, what was cut and why, and an engine key no constructor takes."""
    from benchmarks import builders_gdn_hybrid
    from triton_dist_tpu.models import gdn_hybrid as G

    cfg = config()
    assert set(builders_gdn_hybrid.hf_keys(cfg)) == set(G.HF_KEYS) \
        == set(CATALOGUED) | {"torch_dtype"}
    with open(bench.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cfg["name"])
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "max_position_embeddings"}
    assert entry["source"] == cfg["source"]
    for key, val in CATALOGUED.items():
        if key not in entry["reduced"]:
            assert cfg[key] == val, key
    assert cfg["num_hidden_layers"] == 8 and len(cfg["layer_types"]) == 32
    assert cfg["max_position_embeddings"] == cfg["engine"]["max_seq"]
    assert cfg["engine"]["prefix_cache"] is False
    for key in ("assumed", "not_run", "deployment", "weights",
                "engine_derived", "engine_moved"):
        assert cfg[key]
    assert "rope" in cfg["assumed"]["positional_encoding"].lower()
    assert "four-stage" in cfg["deployment"]
    # a key olmo_hybrid does not have is refused by name, not dropped
    bad = dict(builders_gdn_hybrid.hf_keys(cfg), linear_use_gate=True)
    with pytest.raises(ValueError, match="linear_use_gate"):
        G.GdnHybridConfig.from_hf(bad, max_seq=5120)
    import inspect

    from triton_dist_tpu.serve import ServeEngine

    took = set(inspect.signature(ServeEngine.__init__).parameters)
    assert set(cfg["engine"]) - {"max_seq", "kv_dtype"} <= took


def _toy(cfg: dict) -> dict:
    """A toy file of the same keys: one period (linear 0 1 2, full 3), 2
    full heads of 128, 4 linear heads of 32 x 64."""
    return dict(cfg, hidden_size=256, intermediate_size=256,
                num_hidden_layers=4, num_attention_heads=2,
                num_key_value_heads=2, vocab_size=256,
                linear_num_key_heads=4, linear_num_value_heads=4,
                linear_key_head_dim=32, linear_value_head_dim=64,
                torch_dtype="float32",
                engine=dict(cfg["engine"], max_seq=256, page_size=8,
                            prefill_chunk=16, prefill_budget=64,
                            max_batch=2, num_blocks=48))


def test_builder_on_a_toy_file_serves_through_both_groups():
    from benchmarks import builders_gdn_hybrid
    from triton_dist_tpu.serve import Request, SamplingParams
    from triton_dist_tpu.serve.block_manager import KvGroups

    cfg = _toy(config())
    engine, model = builders_gdn_hybrid.build(cfg, 2 ** 31 + 9, chips=1,
                                              ladder=[64])
    assert isinstance(engine.bm, KvGroups) and model.n_layers == 4
    assert engine.group_blocks == [48, 3]
    assert engine.prefill_width == 16           # ONE chunk a call
    prompt = np.arange(40, dtype=np.int32) % 256
    engine.submit(Request("q0", prompt, SamplingParams(max_new_tokens=24)))
    out = engine.run(500)["q0"]
    assert len(out.token_ids) == 24
    s = engine.metrics.summary()["gdn"]
    assert s["state_bytes_per_request"] == 3 * (3 * 512 * 4 + 32 * 256 * 4)
    assert s["rule_tokens"] == 40 and s["state_slots_peak"] == 1
    bench.drain(engine)                     # both free lists whole
    with pytest.raises(ValueError):
        builders_gdn_hybrid.build(cfg, 0, chips=4, ladder=[64])


def test_reference_and_builder_draw_the_same_weights():
    """The reference draws, from the seed alone, leaf for leaf the weights
    the builder's ``init_params`` draws (a linear layer's q | k | v and a |
    b side by side in the program)."""
    import jax
    import jax.numpy as jnp

    from benchmarks import builders_gdn_hybrid
    from triton_dist_tpu.models import gdn_hybrid as G

    ref = importlib.import_module("benchmarks.reference.gdn_hybrid")
    cfg = _toy(config())
    seed = 2 ** 31 + 11
    model = builders_gdn_hybrid.model_config(cfg)
    params = G.init_params(model, builders_gdn_hybrid.weight_key(seed))
    same = lambda a, b: np.array_equal(np.asarray(a), np.asarray(b))  # noqa: E731
    assert same(params["embed"], ref.draw_embed(cfg, seed, jnp.float32))
    norm_w, head = ref.draw_head(cfg, seed, jnp.float32)
    assert same(params["lm_head"], head)
    assert same(params["final_norm"], norm_w)
    for li, kind in enumerate(model.layer_types):
        w = ref.draw_layer(cfg, seed, li, jnp.float32)
        mine = params["layers"][li]
        for name in ("wgate", "wup", "wdown", "wo"):
            assert same(mine[name], w[name]), (li, name)
        assert same(mine["attn_norm"], w["post_mixer_norm"])
        assert same(mine["mlp_norm"], w["post_mlp_norm"])
        if kind == "full":
            for name in ("wq", "wk", "wv", "q_norm", "k_norm"):
                assert same(mine[name], w[name]), (li, name)
        else:
            assert same(mine["w_qkv"], jnp.concatenate(
                [w["wq"], w["wk"], w["wv"]], axis=1)), li
            assert same(mine["w_ab"], jnp.concatenate(
                [w["w_a"], w["w_b"]], axis=1)), li
            for name in ("w_z", "conv_w", "A_log", "dt_bias", "o_norm"):
                assert same(mine[name], w[name]), (li, name)
            assert float(jnp.exp(w["A_log"]).max()) <= 16.0
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == model.n_params()


def test_reference_interface_and_the_control():
    """``check_outputs`` on made-up records at a small size: the reference
    module loads by the file's name, takes sequences and prompt lengths,
    and a request served by the reference's own argmax reads gap 0; its
    control (int8 operands and cached K / V rows, the matrix state rounded
    to bfloat16 every step) does not."""
    ref = importlib.import_module("benchmarks.reference.gdn_hybrid")
    ref.Q_BLOCK = ref.T_BLOCK = 32
    cfg = _toy(config())
    assert cfg["reference"] == "gdn_hybrid"
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
