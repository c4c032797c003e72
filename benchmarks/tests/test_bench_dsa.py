"""The latent + learned sparse attention cell's benchmark pieces on the CPU:
the counting functions against hand counts at the published widths, the
traffic file's multiset against the configuration's limits, the readers on
a made-up trace, the file's keys reaching the constructors, and the
reference through ``check_outputs``' interface.

``--cpu-dryrun`` of this cell is NOT here: the rehearsal's sizes are the
harness's (``builders.TOY_ENGINE``: ``max_seq`` 512; ``run.py``: lengths
/ 8) and this mix's shortest prompt is 4,096 / 8 = 512 tokens — it cannot
be offered without an edit to one of those files (PERF.md §7)."""

import copy
import importlib
import json

import numpy as np
import pytest

from benchmarks import builders, readers, shapes, shapes_dsa, traffic
from benchmarks import run as bench

CELL = "glm5_ep16_l5_longctx_sat"


def config():
    return builders.load_config(bench.load_cell(CELL)["config_file"])


def test_counting_functions_by_hand():
    """GLM-5's widths, one chip's share: 16 of 256 experts, 1 dense + 4
    expert layers, 19,360 vocabulary rows."""
    cfg = config()
    attn = (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448
            + 64 * 256 * 6144)
    idx = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32 + 2 * 128
    assert shapes_dsa.base.attention_params(cfg) == attn == 165_019_648
    assert shapes_dsa.index_params(cfg) == idx == 9_371_904
    expert = 3 * 6144 * 2048
    dense = attn + idx + 3 * 6144 * 12288
    moe = attn + idx + 6144 * 256 + 256 + 17 * expert
    assert shapes_dsa.layer_params_held(cfg, False) == dense == 400_883_968
    assert shapes_dsa.layer_params_held(cfg, True) == moe == 817_693_184
    held = dense + 4 * moe + 2 * 6144 * 19360 + 6144
    assert shapes_dsa.params_held(cfg) == held
    assert abs(held * 2 / 1e9 - 7.82) < 0.005                   # 7.82 GB
    assert shapes_dsa.cache_bytes_per_token(cfg) == 5 * (576 + 128) * 2 == 7040
    assert cfg["kv_bytes_per_token"] == 7040
    assert cfg["kv_bytes_per_token_as_stored"] == 5 * (640 + 128) * 2 == 7680
    # 32 rows whose contexts sum to 300,000 tokens, every one past 2,048
    rows, ctx = 32.0, 300_000.0
    i = shapes_dsa.index_scores(cfg, rows=rows, ctx_sum=ctx)
    assert i["flops"] == 2 * 32 * 128 * ctx * 5
    assert i["bytes"] == 5 * (ctx * 256 + rows * 32 * (256 + 4) + ctx * 4)
    a = shapes_dsa.sparse_attention(cfg, rows=rows, ctx_sum=ctx)
    sel = rows * 2048
    assert a["bytes"] == (sel * 5 * 1152 + rows * 64 * 576 * 2 * 5
                          + rows * 64 * 512 * 4 * 5)
    assert a["flops"] == 2 * 64 * (576 + 512) * sel * 5
    # contexts under index_topk: never more rows than there are
    assert shapes_dsa.selected_rows(cfg, rows=4.0, ctx_sum=1000.0) == 1000.0
    d = shapes_dsa.decode_step(cfg, rows=rows, ctx_sum=ctx)
    hit = 16 * (1 - (255 / 256) ** 256)                         # 10.1 of 16
    read = dense + 4 * moe + 6144 * 19360 - 4 * (16 - hit) * expert
    assert d["bytes"] == pytest.approx(
        read * 2 + rows * 6144 * 2 + sel * 5 * 1152 + rows * 5 * 1152
        + rows * 19360 * 4 + i["bytes"] + rows * 5 * 256)
    pk = shapes.peaks("TPU v5 lite")
    least, bound = shapes.least_seconds(d, pk)
    assert bound == "memory" and 0.0075 < least < 0.0085
    # the two new calls' least times: ~0.48 and ~0.50 ms a step
    assert 0.00045 < shapes.least_seconds(i, pk)[0] < 0.0005
    assert 0.00048 < shapes.least_seconds(a, pk)[0] < 0.00052


def test_traffic_multiset_fits_the_configuration():
    cfg = config()
    p = traffic.load("longctx_sat")
    a = traffic.Traffic(p, 3, vocab=cfg["vocab_size"])
    b = traffic.Traffic(p, 2 ** 31 + 7, vocab=cfg["vocab_size"])
    assert a.multiset() == b.multiset()
    pairs = a.pairs
    assert len(pairs) == p["cycle"] == p["clients"] == 32 \
        == cfg["engine"]["max_batch"]
    # every decoding row lies past the selection, from its first step on
    assert min(n for n, _, _ in pairs) >= 2 * cfg["index_topk"] == 4096
    assert max(n + o for n, o, _ in pairs) <= cfg["engine"]["max_seq"]
    assert max(n for n, _, _ in pairs) <= cfg["engine"]["prefill_budget"]
    assert sum(s for _, _, s in pairs) == 11
    mean_p = np.mean([n for n, _, _ in pairs])
    mean_o = np.mean([o for _, o, _ in pairs])
    assert 8800 < mean_p < 8900 and 825 < mean_o < 835
    # the pool holds the live contexts with room: 32 rows at the mean
    # prompt plus half an answer, against 3,584 blocks of 128
    live = 32 * (mean_p + mean_o / 2)
    assert live < 0.7 * cfg["engine"]["num_blocks"] * 128
    spec = a.next()
    assert spec.prompt.max() < cfg["vocab_size"]
    # the ladder rungs its prompts reach (warm-up compiles these + the cap)
    assert builders.reachable_ladder(cfg, [n for n, _, _ in pairs]) == [
        8192, 16384]


def test_cell_declares_what_it_reports():
    spec = bench.load_cell(CELL)
    assert [m["name"] for m in spec["end_to_end"]] == ["out_tok_per_s",
                                                       "setup_s"]
    names = {m["name"] for m in spec["per_layer"]}
    assert {"dsa.index_roofline", "dsa.sparse_attn_roofline",
            "dsa_moe.decode_step_roofline", "moe.expert_ffn_roofline",
            "kv.util_peak_pct", "engine.tpot_p50_ms",
            "sat.prog.decode_dev_ms", "sat.device.idle_share_pct"} <= names
    # the dense walk's counts are not this cell's
    assert not names & {"mla.paged_attn_roofline",
                        "mla_moe.decode_step_roofline",
                        "sat.paged_attn_roofline"}
    for name in names:
        readers.load(name)                  # every metric has its file


def test_roofline_reader_reads_named_calls_and_nothing_without_them():
    ctx = {"counters": {"decode.rows_mean": 32.0,
                        "decode.ctx_sum_mean": 300_000.0,
                        "engine.decode_steps": 100},
           "samples": {}, "config": config(), "device_kind": "TPU v5 lite",
           "trace": {"module_s": {"jit_decode_horizon": 1.8},
                     "module_n": {"jit_decode_horizon": 13},
                     "module_op_s": {
                         "jit_decode_horizon|dsa_index_scores": 0.1,
                         "jit_decode_horizon|mla_paged_decode": 0.6,
                         "jit_decode_horizon|moe_gate_up": 0.3,
                         "jit_decode_horizon|moe_down": 0.15,
                         "jit_prefill_chunk|dsa_index_scores": 9.0,
                         "jit_prefill_chunk|mla_paged_decode": 9.0}}}
    pk = shapes.peaks("TPU v5 lite")
    for name, fn, took in (
            ("dsa.index_roofline", "index_scores", 0.1 / 100),
            ("dsa.sparse_attn_roofline", "sparse_attention", 0.6 / 100),
            ("dsa_moe.decode_step_roofline", "decode_step", 1.8 / 100)):
        need = shapes_dsa.FUNCTIONS[fn](ctx["config"], rows=32.0,
                                        ctx_sum=300_000.0)
        want = 100 * shapes.least_seconds(need, pk)[0] / took
        assert readers.read(name, ctx) == pytest.approx(want)
        assert 0 < want < 100
    assert 0 < readers.read("moe.expert_ffn_roofline", ctx) < 100
    # a program without the named calls, or a configuration without an
    # indexer (the parent commit; the other cells): nothing, and no raise
    bare = copy.deepcopy(ctx)
    bare["trace"]["module_op_s"] = {"jit_decode_horizon|closed_call": 1.0}
    assert readers.read("dsa.index_roofline", bare) is None
    assert readers.read("dsa.sparse_attn_roofline", bare) is None
    other = dict(ctx, config=builders.load_config(bench.load_cell(
        "gc3_ep16_l5_reason_sat")["config_file"]))
    assert readers.read("dsa.sparse_attn_roofline", other) is None
    assert readers.read("dsa_moe.decode_step_roofline", other) is None
    bare["trace"] = None
    assert readers.read("dsa_moe.decode_step_roofline", bare) is None


def test_file_keys_reach_the_constructors():
    """The file as the builder reads it, at the published widths and
    without a device array: the model config, the planes, the catalog's
    keys, and an engine key no constructor takes."""
    from benchmarks import builders_mla_moe
    from triton_dist_tpu.models import mla_moe as M

    cfg = config()
    model = builders_mla_moe.model_config(cfg)
    assert (model.n_experts, model.experts_held, model.expert_offset) == (
        256, 16, 0)
    assert (model.n_group, model.topk_group, model.top_k) == (1, 1, 8)
    assert (model.index_n_heads, model.index_head_dim, model.index_topk) == (
        32, 128, 2048)
    assert model.sparse and model.rope_interleave and model.yarn is None
    assert model.rope_theta == 1e6 and model.norm_eps == 1e-5
    assert model.latent_width == 576 and model.head_dim == 640
    assert model.softmax_scale == 256 ** -0.5
    gen = M.MlaMoeGenerator(model, max_seq=cfg["engine"]["max_seq"])
    assert gen.kv_planes == [(1, 640), (1, 128)]
    assert (cfg["engine"]["num_blocks"] * 128
            * cfg["kv_bytes_per_token_as_stored"]) / 1e9 == pytest.approx(
        3.52, abs=0.01)
    with open(bench.ROOT + "/BENCHMARK.json") as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == cfg["name"])
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert entry["source"] == cfg["source"]
    # what the engine group names reaches ServeEngine's signature
    import inspect

    from triton_dist_tpu.serve import ServeEngine

    took = set(inspect.signature(ServeEngine.__init__).parameters)
    assert set(cfg["engine"]) - {"max_seq", "kv_dtype"} <= took


def test_reference_interface_and_int8_control():
    """``check_outputs`` on made-up records at a small size: the reference
    module loads by the file's name, takes sequences and prompt lengths,
    and a request served by the reference's own argmax reads gap 0; its
    int8 control (operands, latent rows AND index keys) does not."""
    ref = importlib.import_module("benchmarks.reference.mla_dsa_moe_share")
    ref.Q_BLOCK = ref.T_BLOCK = 32
    cfg = dict(config(), hidden_size=128, intermediate_size=256,
               moe_intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, q_lora_rank=64, kv_lora_rank=128,
               qk_nope_head_dim=32, qk_rope_head_dim=32, v_head_dim=48,
               vocab_size=256, n_routed_experts=4, num_experts_per_tok=4,
               index_n_heads=16, index_topk=24,
               share={"experts_total": 16, "expert_offset": 4})
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
    ctl = bench.check_outputs(cfg, seed, [rec], 3, limits, int8=True)
    assert ctl["numbers"]["gap_mean"] > 0.0
    # the int8 control quantizes the index keys too: the kept sets move
    probe, probe8 = [], []
    ref.forward_logits(cfg, seed, [seq], [n0], probe=probe)
    ref.forward_logits(cfg, seed, [seq], [n0], int8=True, probe=probe8)
    n = len(seq) - 1
    kept, kept8 = probe[0][0][1][:n, :n], probe8[0][0][1][:n, :n]
    assert (kept.sum(1) == np.minimum(np.arange(n) + 1, 24)).all()
    assert (kept != kept8).any()
