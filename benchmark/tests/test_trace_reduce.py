"""The reduction from trace events to numbers, on hand-made events and
on an excerpt recorded on the chip (``data/trace_excerpt.json``)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

MS = 1e6   # nanoseconds


def planes(ops, modules=()):
    return {"/device:TPU:0": {tr.OPS_LINE: list(ops),
                              tr.MODULES_LINE: list(modules)}}


def test_busy_is_the_union_and_idle_the_rest():
    f1 = "%fusion.1 = bf16[32,4096]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[4] %p)"
    ops = [[f1, 0, 10 * MS],
           ["%fusion.2 = f32[8]{0} fusion(f32[8] %q)", 5 * MS, 10 * MS],
           ["%copy.3 = bf16[8]{0} copy(bf16[8] %r)", 30 * MS, 5 * MS],
           [f1, 35 * MS, 5 * MS],
           # a loop's event spans its body: kept out of the table
           ["%while.9 = (s32[]{:T(128)}, bf16[6]{0}) while((s32[]) %t), "
            "condition=%c, body=%b", 0, 40 * MS]]
    s = tr.reduce_planes(planes(ops))
    assert "while.9" not in s["ops"] and s["busy_s"] == pytest.approx(0.040)
    s = tr.reduce_planes(planes(ops[:4]))
    assert s["busy_s"] == pytest.approx(0.025)         # 15 + 10 ms
    assert s["window_s"] == pytest.approx(0.040)       # first start -> last end
    assert s["idle_gaps_s"] == [pytest.approx(0.015)]
    assert s["ops"]["fusion.1"] == [
        2, pytest.approx(0.015), "fusion", "bf16[32,4096]{1,0:T(8,128)(2,1)S(1)}"]
    assert s["ops"]["copy.3"][2] == "copy"
    assert tr.ops_seconds(s, r"^fusion") == (pytest.approx(0.025), 3)
    assert tr.ops_seconds(s, opcode="copy")[1] == 1
    assert tr.breakdown(s)["device_ops"][0][0] == "fusion:fusion.1"
    assert tr.breakdown(s)["idle_gaps"] == [["host", pytest.approx(0.015)]]


def test_programs_are_summed_by_name_without_the_run_id():
    mods = [["jit_step(123)", 0, 20 * MS], ["jit_step(123)", 30 * MS, 22 * MS],
            ["jit_prefill(9)", 60 * MS, 100 * MS]]
    s = tr.reduce_planes(planes([["x", 0, 1]], mods))
    assert s["programs"]["jit_step"]["count"] == 2
    assert s["programs"]["jit_step"]["median_s"] == pytest.approx(0.021)
    assert s["programs"]["jit_prefill"]["total_s"] == pytest.approx(0.1)


def test_four_chips_read_as_one_chips_share():
    one = planes([["%all-reduce.1 = f32[8]{0} all-reduce(f32[8] %g)", 0,
                   10 * MS],
                  ["%fusion.7 = f32[8]{0} fusion(f32[8] %h)", 10 * MS,
                   30 * MS]])
    four = {f"/device:TPU:{i}": one["/device:TPU:0"] for i in range(4)}
    s = tr.reduce_planes(four)
    assert s["chips"] == 4
    assert s["busy_s"] == pytest.approx(0.040)
    assert s["ops"]["all-reduce.1"][:3] == [1, pytest.approx(0.010),
                                            "all-reduce"]


def test_a_kernel_is_a_custom_call_with_a_tuple_result():
    text = ("%closed_call.7 = (bf16[96,4096,128]{2,1,0:T(8,128)(2,1)}, "
            "f32[96,4096,128]{2,1,0:T(8,128)}) custom-call(bf16[96,4096,128]"
            "{2,1,0:T(8,128)(2,1)} %bitcast.1), custom_call_target=\"x\"")
    assert tr.parse_op(text) == (
        "closed_call.7", "custom-call:x",
        "(bf16[96,4096,128]{2,1,0:T(8,128)(2,1)}, "
        "f32[96,4096,128]{2,1,0:T(8,128)})")


def test_an_empty_trace_has_no_busy_time():
    assert tr.reduce_planes({})["busy_s"] == 0.0


def test_recorded_excerpt_of_a_v5e_trace():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_excerpt.json")
    with open(path) as f:
        rec = json.load(f)
    s = tr.reduce_planes(rec["planes"])
    assert s["chips"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    for name, (count, seconds, opcode) in rec["expect"]["ops"].items():
        assert s["ops"][name][:3] == [count, pytest.approx(seconds, rel=1e-9),
                                      opcode]
    assert set(rec["expect"]["programs"]) <= set(s["programs"])
