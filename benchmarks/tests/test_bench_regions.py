"""The region reduction, checked on the CPU: the innermost scope of an
``op_name`` path; hand-made events with known regions (nested, none, a
container, the dense paged call filed by its name) whose sums equal
``xplane.reduce``'s; a trace without regions, which reads ``None`` and does
not raise; the stale-cache case, which ends with its message; an
``.xplane.pb`` written here byte by byte and read back; and the recorded
chip trace in ``fixtures/`` reduced to the numbers written down when it
was recorded."""

import glob
import json
import os

import pytest

from benchmarks import readers, regions, xplane

FIX = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")
MS = 1_000_000  # ns
P = "jit(decode_horizon)/while/body/closed_call/"


def test_the_innermost_scope_wins_and_a_bare_call_is_filed_by_name():
    assert regions.region_of(P + "rg_ffn/dot_general") == "ffn"
    assert regions.region_of(P + "rg_ffn/rg_moe__route/dot_general") == \
        "moe.route"
    assert regions.region_of(
        "jit(prefill_chunk)/rg_attn/rg_dsa__select/while/body/gt") == \
        "dsa.select"
    assert regions.region_of("jit(paged_decode)/_unknown_/rg_kv_write/"
                             "scatter") == "kv_write"
    assert regions.region_of(P + "mul") == "-"
    assert regions.region_of("") == "-"
    # the dense family's paged call: no scope, its instruction's name
    assert regions.region_of(P + "pallas_call", "closed_call") == "attn"
    assert regions.region_of("jit(paged_decode)/_unknown_/pallas_call",
                             "_unknown_") == "attn"
    # a scope, where there is one, goes before the table
    assert regions.region_of(P + "rg_sample/reduce", "closed_call") == \
        "sample"


def events(regions_too=True):
    """One chip, a 100 ms window.  decode_horizon runs 10-60 ms,
    prefill_chunk 60-90 ms; 90-100 ms lies past an open window's end."""
    rows = [    # program, stem, region, start ms, ms
        ("jit_decode_horizon", "fusion", "proj", 10, 5),
        ("jit_decode_horizon", "closed_call", "attn", 15, 10),
        ("jit_decode_horizon", "fusion", "ffn", 25, 10),
        ("jit_decode_horizon", "moe_gate_up", "moe.experts", 35, 10),
        ("jit_decode_horizon", "copy", "-", 45, 5),
        ("jit_decode_horizon", "fusion", "head", 50, 4),
        ("jit_decode_horizon", "or_select_fusion", "sample", 54, 6),
        ("jit_prefill_chunk", "flash_attention", "attn", 60, 9),
        ("jit_prefill_chunk", "fusion", "mla.expand", 69, 6),
        ("jit_prefill_chunk", "fusion", "ffn", 75, 12),
        ("jit_prefill_chunk", "slice-done", "-", 87, 3),
        ("jit_fill_pages", "fusion", "-", 95, 2),
    ]
    ev = {
        "host": [["bench.window", 0, 100 * MS]],
        "devices": {"/device:TPU:0": {
            "modules": [["jit_decode_horizon", 10 * MS, 50 * MS],
                        ["jit_prefill_chunk", 60 * MS, 30 * MS],
                        ["jit_fill_pages", 95 * MS, 2 * MS]],
            # the old reduction's view of the same operations, and the
            # scan that holds the horizon's (a container: no time of its own)
            "ops": [["while", 10 * MS, 50 * MS]] + [
                [stem, s * MS, d * MS] for _, stem, _, s, d in rows]}},
    }
    if regions_too:
        ev["regions"] = {"/device:TPU:0": [
            [prog, stem, reg, s * MS, d * MS]
            for prog, stem, reg, s, d in rows]}
    return ev


def test_seconds_by_program_and_region_and_the_sums_are_xplanes():
    ev = events()
    red = regions.reduce(ev)
    r = red["region_s"]
    assert r["jit_decode_horizon|attn"] == pytest.approx(0.010)
    assert r["jit_decode_horizon|moe.experts"] == pytest.approx(0.010)
    assert r["jit_decode_horizon|-"] == pytest.approx(0.005)
    assert r["jit_prefill_chunk|mla.expand"] == pytest.approx(0.006)
    assert red["program_s"] == pytest.approx({
        "jit_decode_horizon": 0.050, "jit_prefill_chunk": 0.030,
        "jit_fill_pages": 0.002})
    assert red["attributed_pct"] == pytest.approx({
        "jit_decode_horizon": 90.0, "jit_prefill_chunk": 90.0,
        "jit_fill_pages": 0.0})
    assert red["top"][0] == ["jit_prefill_chunk|ffn|fusion",
                             pytest.approx(0.012)]
    assert len(red["top"]) == 10
    assert red["unattributed"] == [
        ["jit_decode_horizon|copy", pytest.approx(0.005)],
        ["jit_prefill_chunk|slice-done", pytest.approx(0.003)],
        ["jit_fill_pages|fusion", pytest.approx(0.002)]]
    # every program's total is the old reduction's, container left out;
    # fill_pages has no scope to carry and is no fault
    assert regions.check(red, xplane.reduce(ev)) == []
    # an operation is clipped to the window as xplane.reduce clips it
    ev["host"] = [["bench.window", 12 * MS, 80 * MS]]
    red = regions.reduce(ev)
    assert red["region_s"]["jit_decode_horizon|proj"] == pytest.approx(0.003)
    assert red["region_s"]["jit_prefill_chunk|-"] == pytest.approx(0.003)
    assert "jit_fill_pages" not in red["program_s"]
    assert regions.check(red, xplane.reduce(ev)) == []


def test_a_region_row_that_went_missing_fails_the_sum():
    ev = events()
    ev["regions"]["/device:TPU:0"].pop(2)       # the horizon's ffn
    wrong = regions.check(regions.reduce(ev), xplane.reduce(ev))
    assert len(wrong) == 1 and wrong[0].startswith(
        "jit_decode_horizon: the regions sum to 0.040000 s")


def test_region_metrics_by_their_reader():
    ctx = {"trace": regions.reduce(events()), "counters": {}, "samples": {}}
    read = lambda name: readers.read(name, ctx)     # noqa: E731
    assert read("dev.decode.attn_share_pct") == pytest.approx(20.0)
    assert read("dev.decode.ffn_share_pct") == pytest.approx(40.0)
    assert read("dev.decode.head_share_pct") == pytest.approx(8.0)
    assert read("dev.decode.sample_share_pct") == pytest.approx(12.0)
    assert read("dev.decode.unattributed_share_pct") == pytest.approx(10.0)
    assert read("dev.prefill.attn_share_pct") == pytest.approx(50.0)
    assert read("dev.prefill.ffn_share_pct") == pytest.approx(40.0)
    assert read("dev.prefill.head_share_pct") == pytest.approx(0.0)
    assert read("dev.prefill.unattributed_share_pct") == pytest.approx(10.0)
    assert set(regions.METRICS) == {
        os.path.basename(p)[:-len(".json")] for p in glob.glob(os.path.join(
            os.path.dirname(FIX), "layer_metrics", "*.json"))
        if "region_readers" in open(p).read()}
    # a trace in which no decode program ran has no share of one
    ev = events()
    ev["regions"]["/device:TPU:0"] = [
        r for r in ev["regions"]["/device:TPU:0"] if "decode" not in r[0]]
    ctx["trace"] = regions.reduce(ev)
    assert read("dev.decode.ffn_share_pct") is None
    assert read("dev.prefill.ffn_share_pct") == pytest.approx(40.0)


def test_a_trace_without_regions_reads_nothing_and_does_not_raise():
    old = xplane.reduce(events(regions_too=False))
    assert regions.reduce(events(regions_too=False)) == {}     # the parent's
    for trace in ({}, None, old):
        ctx = {"trace": trace, "counters": {}, "samples": {}}
        for name in regions.METRICS:
            assert readers.read(name, ctx) is None
    # the old reduction is untouched by the extra key
    assert xplane.reduce(events()) == old


def test_a_stale_compile_cache_ends_with_its_message():
    ev = events()
    for row in ev["regions"]["/device:TPU:0"]:
        if row[0] == "jit_prefill_chunk":
            row[2] = "-"        # fetched from a cache written before
    wrong = regions.check(regions.reduce(ev), xplane.reduce(ev))
    assert len(wrong) == 1
    assert "['jit_prefill_chunk']" in wrong[0]
    assert "persistent compile cache" in wrong[0]
    assert "jax_compilation_cache_include_metadata_in_key" in wrong[0]
    # no region anywhere (every program from that cache, or the parent's)
    for row in ev["regions"]["/device:TPU:0"]:
        row[2] = "-"
    assert regions.reduce(ev) == {}
    wrong = regions.check({}, xplane.reduce(ev))
    assert any("any program" in w and "clear that cache" in w for w in wrong)


# -- an .xplane.pb written here, read back --------------------------------------


def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _f(field: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _plane(name, ops, modules):
    """ops: [(hlo line, op_name path or None, offset ps, ps)]."""
    meta, events = b"", b""
    for i, (line, path, off, dur) in enumerate(ops + modules, start=1):
        stat = b"" if path is None else _f(5, _f(1, 26) + _f(5, path))
        meta += _f(4, _f(1, i) + _f(2, _f(1, i) + _f(2, line) + stat))
    n = len(ops)
    mk = lambda rows, base: b"".join(       # noqa: E731
        _f(4, _f(1, base + j) + _f(2, off) + _f(3, dur))
        for j, (_, _, off, dur) in enumerate(rows, start=1))
    lines = (_f(3, _f(2, "XLA Modules") + _f(3, 5_000) + mk(modules, n))
             + _f(3, _f(2, "XLA Ops") + _f(3, 5_000) + mk(ops, 0))
             + _f(3, _f(2, "Steps") + _f(3, 5_000) + mk(ops[:1], 0)))
    stats = _f(5, _f(1, 26) + _f(2, _f(1, 26) + _f(2, "tf_op"))) \
        + _f(5, _f(1, 3) + _f(2, _f(1, 3) + _f(2, "flops")))
    return _f(1, _f(1, 7) + _f(2, name) + lines + meta + stats)


def test_device_regions_reads_the_wire_format(tmp_path):
    ops = [
        ("%fusion.3 = bf16[8,128]{1,0} fusion(%p), kind=kLoop",
         P + "rg_ffn/rg_moe__route/dot_general", 1_000_000, 2_000_000),
        ("%while.2 = (s32[]) while(%t), body=%b", P[:-1], 1_000_000,
         9_000_000),
        ("%closed_call.22 = (f32[8]) custom-call(%a)", P + "pallas_call",
         3_000_000, 4_000_000),
        ("%copy.15.remat2 = bf16[4]{0} copy(%x)", None, 7_000_000,
         1_000_000),
        ("%fusion.9 = f32[8]{0} fusion(%q), kind=kLoop", P + "rg_sample/gt",
         20_000_000, 500_000),
    ]
    modules = [("jit_decode_horizon(858533)", None, 0, 10_000_000)]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_plane("/device:TPU:0", ops, modules)
                     + _plane("/host:CPU", ops, modules))
    got = regions.device_regions(str(path))
    assert got == {"/device:TPU:0": [
        ["jit_decode_horizon", "fusion", "moe.route", 6_000, 2_000],
        ["jit_decode_horizon", "closed_call", "attn", 8_000, 4_000],
        ["jit_decode_horizon", "copy", "-", 12_000, 1_000],
        ["-", "fusion", "sample", 25_000, 500]]}     # outside any program


# -- the recorded chip trace ------------------------------------------------------


def test_recorded_chip_trace_reduces_to_its_expected_numbers():
    ev = xplane.load_events(os.path.join(
        FIX, "v5e_chat_regions.events.json.gz"))
    with open(os.path.join(FIX, "v5e_chat_regions.expected.json")) as f:
        want = json.load(f)
    red = regions.reduce(ev)
    old = xplane.reduce(ev)
    assert regions.check(red, old) == []
    assert old["window_s"] == pytest.approx(want["window_s"])
    assert old["busy_s"] == pytest.approx(want["busy_s"])
    assert red["region_s"] == pytest.approx(want["region_s"])
    assert red["program_s"] == pytest.approx(want["program_s"])
    assert red["attributed_pct"] == pytest.approx(want["attributed_pct"])
    assert [k for k, _ in red["top"]] == [k for k, _ in want["top"]]
    ctx = {"trace": red, "counters": {}, "samples": {}}
    for name in regions.METRICS:
        assert readers.read(name, ctx) == pytest.approx(
            want["region_metrics"][name]), name
    # the scan's carry copies and the layout copies carry no region
    assert 0.0 < want["region_metrics"]["dev.decode.unattributed_share_pct"]
    for prog in ("jit_decode_horizon", "jit_prefill_chunk"):
        assert red["attributed_pct"][prog] > 50.0
