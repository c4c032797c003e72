"""The trace reduction, checked on the CPU: interval arithmetic on
hand-made events whose answers are known, and the recorded chip trace in
``fixtures/`` reduced to the numbers written down when it was recorded."""

import glob
import json
import os

import pytest

from benchmarks import readers, shapes, xplane

FIX = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")

MS = 1_000_000  # ns


def events():
    """Two chips, a 100 ms window.  Chip 0 runs a decode program for
    40 ms (30 ms of it the Mosaic call, 4 ms an all-reduce) and
    a prefill chunk for 20 ms; chip 1 the same, 10 ms later."""
    def chip(t):
        return {
            "modules": [["engine.paged_decode", t, 40 * MS],
                        ["engine.prefill_chunk", t + 50 * MS, 20 * MS]],
            "ops": [["fusion", t, 6 * MS],
                    ["closed_call", t + 6 * MS, 30 * MS],
                    ["all-reduce", t + 36 * MS, 4 * MS],
                    ["closed_call", t + 50 * MS, 5 * MS],
                    ["fusion", t + 55 * MS, 15 * MS]]}
    return {
        "host": [["bench.window", 0, 100 * MS],
                 ["engine.step", 0, 48 * MS],
                 ["dispatch", 41 * MS, 4 * MS],
                 ["loadgen", 48 * MS, 2 * MS],
                 ["engine.step", 50 * MS, 50 * MS]],
        "devices": {"/device:TPU:0": chip(0), "/device:TPU:1": chip(10 * MS)},
    }


def test_busy_idle_and_program_time():
    red = xplane.reduce(events())
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.06)          # 40 + 20 on each
    assert red["idle_share"] == pytest.approx(0.4)
    assert red["module_s"]["engine.paged_decode"] == pytest.approx(0.04)
    assert red["module_n"]["engine.paged_decode"] == 1
    assert red["module_op_s"]["engine.paged_decode|closed_call"] == \
        pytest.approx(0.03)
    assert red["module_op_s"]["engine.prefill_chunk|closed_call"] == \
        pytest.approx(0.005)
    # chip 0 idles 40-50 ms (8 in engine.step, of which 4 under a
    # dispatch, then 2 in loadgen) and 70-100 ms (in engine.step)
    gaps = red["idle_by_span_s"]
    assert gaps["engine.step/dispatch"] == pytest.approx(0.004)
    assert gaps["engine.step/host"] == pytest.approx(0.004 + 0.030)
    assert gaps["loadgen"] == pytest.approx(0.002)
    bd = xplane.breakdown(red)
    assert bd["device_ops"][0][0] == "closed_call"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_window_falls_back_to_device_extent_without_the_span():
    ev = events()
    ev["host"] = []
    red = xplane.reduce(ev)
    assert red["window_s"] == pytest.approx(0.08)        # 0 .. 80 ms


def test_no_device_events_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce({"host": [], "devices": {}})


def test_names_survive_a_recompile():
    assert xplane.op_name("fusion.123") == "fusion"
    assert xplane.op_name("copy-done") == "copy-done"
    assert xplane.op_name("%copy.843.remat2 = bf16[449,8,128,128]{3,1,2,0:"
                          "T(8,128)(2,1)} copy(%p)") == "copy"
    assert xplane.op_name("%while.7 = (s32[]{:T(128)}, bf16[4]) while(") \
        == "while"
    assert xplane.op_name("flash_attention_flops_2147483648_bytes_10485760"
                          "_sol_ms_0") == "flash_attention"
    assert xplane.op_name("") == "_unknown_"


def test_containers_are_busy_time_but_not_in_the_tables():
    ev = events()
    for dev in ev["devices"].values():
        t = dev["modules"][0][1]
        dev["ops"].append(["while", t, 40 * MS])    # holds the decode ops
    red = xplane.reduce(ev)
    assert "while" not in red["op_s"]
    assert red["busy_s"] == pytest.approx(0.06)


def test_relabel_names_unnamed_executions_in_dispatch_order():
    ev = events()
    for dev in ev["devices"].values():
        for m in dev["modules"]:
            m[0] = xplane.UNNAMED
    assert xplane.relabel(ev, ["paged_decode", "prefill_chunk"]) == 4
    red = xplane.reduce(ev)
    assert red["module_s"]["engine.paged_decode"] == pytest.approx(0.04)
    assert red["module_s"]["engine.prefill_chunk"] == pytest.approx(0.02)
    # programs with names of their own need none
    assert xplane.relabel(events(), ["paged_decode"]) == 0
    # a log that does not match the trace is an error, not a quiet miss
    ev2 = events()
    for dev in ev2["devices"].values():
        for m in dev["modules"]:
            m[0] = xplane.UNNAMED
    with pytest.raises(ValueError, match="2 unnamed device executions"):
        xplane.relabel(ev2, ["paged_decode"])
    assert xplane.module_name("jit_paged_decode(1234567)") == \
        "jit_paged_decode"


def test_readers_on_the_reduction():
    red = xplane.reduce(events())
    cfg = json.load(open(os.path.join(
        os.path.dirname(FIX), "configs", "mistral-7b-v0.2-l16.json")))
    ctx = {"trace": red, "config": cfg,
           "device_kind": "TPU v5 lite", "samples": {},
           "counters": {"engine.decode_steps": 2, "decode.rows_mean": 32,
                        "decode.ctx_sum_mean": 32 * 1400}}
    assert readers.read("prog.decode_dev_ms", ctx) == pytest.approx(20.0)
    assert readers.read("prog.prefill_dev_ms", ctx) == pytest.approx(20.0)
    # a split metric is read as the file it names
    assert readers.read("sat.prog.decode_dev_ms", ctx) == pytest.approx(20.0)
    assert readers.read("engine.tpot_p50_ms", ctx) is None
    ctx["samples"]["tpot_ms"] = [3.0, 1.0, 2.0]
    assert readers.read("engine.tpot_p50_ms", ctx) == 2.0
    assert readers.read("device.idle_share_pct", ctx) == pytest.approx(40.0)
    roof = readers.read("decode_step_roofline", ctx)
    need = shapes.decode_step(cfg, rows=32, ctx_sum=32 * 1400)
    least, bound = shapes.least_seconds(need, shapes.peaks("TPU v5 lite"))
    assert bound == "memory" and roof == pytest.approx(100 * least / 0.02)
    ctx["trace"] = None                  # nothing to read: nothing returned
    assert readers.read("prog.decode_dev_ms", ctx) is None
    assert readers.read("device.idle_share_pct", ctx) is None


def test_every_declared_metric_has_a_reader_and_a_cell_that_reports_what_it_moves():
    bench = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(FIX)), "BENCHMARK.json")))
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        spec = readers.load(m["name"])
        assert ":" in spec["reader"] or spec["reader"] in readers.KINDS
        assert set(m["workloads"]) <= set(e2e[m["moves"]]), m["name"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        shapes.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        shapes.peaks("_source")


def test_shape_functions_by_hand():
    cfg = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "head_dim": 128, "num_hidden_layers": 16, "vocab_size": 32000}
    assert shapes.layer_params(cfg) == 218_103_808
    assert shapes.kv_bytes_per_token_layer(cfg) == 4096
    d = shapes.decode_step(cfg, rows=1, ctx_sum=0)
    weights = 16 * 218_103_808 + 4096 * 32000
    assert d["flops"] == 2 * weights
    assert d["bytes"] == 2 * weights + 4096 * 2 + 16 * 4096 + 32000 * 4
    a = shapes.paged_attention(cfg, rows=32, ctx_sum=32 * 1024)
    assert a["bytes"] > 32 * 1024 * 16 * 4096


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(FIX, "*.events.json.gz"))) or [None])
def test_recorded_chip_trace(path):
    if path is None:
        pytest.skip("no recorded trace in fixtures/")
    want = json.load(open(path.replace(".events.json.gz", ".expected.json")))
    red = xplane.reduce(xplane.load_events(path))
    assert red["n_devices"] == want["n_devices"]
    for k in ("window_s", "busy_s", "idle_share"):
        assert red[k] == pytest.approx(want[k], rel=1e-9), k
    for k, v in want["module_s"].items():
        assert red["module_s"][k] == pytest.approx(v, rel=1e-9), k
