"""A kernel's share of its roofline, read by the custom call's
instruction name (``layer_metrics/_kernel_roofline.py``): on hand-made
events, and on excerpts recorded on the chip, where it must read what
the readers by RESULT TYPE read that it took the place of (PR 26; kept
here as the reference)."""

import json
import os
import re

import pytest

from benchmark import model_spec
from benchmark import run as bench_run
from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KERNEL = "custom-call:tpu_custom_call"
with open(os.path.join(model_spec.HERE, "peaks.json")) as f:
    PEAKS = json.load(f)["devices"]["TPU v5 lite"]
STATS = [{"kv_blocks_total": 768, "kv_blocks_free": 500 - 40 * i,
          "kv_block_size": 64, "active_slots": 32} for i in (0, 1)]


def _run(config, trace, **cellfile):
    return {"cell": {"chips": 1}, "spec": model_spec.load_config(config),
            "mix": {"seq": 4096}, "cellfile": cellfile, "trace": trace,
            "peaks": PEAKS,
            "raw": {"open": {"stats": STATS[0], "now": 0.0},
                    "close": {"stats": STATS[1], "now": 51.0}}}


def _recorded(name):
    with open(os.path.join(DATA, name)) as f:
        return tr.reduce_planes(json.load(f)["planes"])


# ---- the readers by result type, as they stood before PR 26
def paged_by_result_type(run):
    dep, spec = run["cellfile"]["deployment"], run["spec"]
    shape = (rf"^bf16\[{dep['num_slots']},{spec['num_attention_heads']},"
             rf"{spec['head_dim']}\]")
    seconds, calls = tr.ops_seconds(run["trace"], opcode=KERNEL, result=shape)
    live = []
    for st in STATS:
        blocks = st["kv_blocks_total"] - st["kv_blocks_free"]
        live.append(max(0.0, blocks * st["kv_block_size"]
                        - st["active_slots"] * st["kv_block_size"] / 2))
    need = calls * model_spec.paged_decode_bytes(
        spec, sum(live) / 2, dep["num_slots"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds


def flash_by_result_type(run):
    spec, batch, seq = run["spec"], run["cellfile"]["job"]["batch"], 4096
    shape = (rf"\[{batch * spec['num_attention_heads']},{seq},"
             rf"{spec['head_dim']}\]")
    layout = r"\{[^}]*\}"
    per = model_spec.flash_flops(spec, batch, seq)
    flops = seconds = 0.0
    for kind, result in (("fwd", rf"^\(bf16{shape}{layout}, f32{shape}"),
                         ("bwd_dkv", rf"^\(bf16{shape}{layout}, bf16{shape}"),
                         ("bwd_dq", rf"^bf16{shape}")):
        sec, calls = tr.ops_seconds(run["trace"], opcode=KERNEL,
                                    result=result)
        flops += calls * per[kind]
        seconds += sec
    return 100.0 * flops / run["peaks"]["bf16_flops_per_s"] / seconds


DEPLOYMENT = {"num_slots": 32}
JOB = {"batch": 6}


def test_the_paged_kernel_by_name_reads_what_the_result_type_read():
    run = _run("mistral-7b-l16", _recorded("trace_excerpt.json"),
               deployment=DEPLOYMENT)
    names = [k for k, v in run["trace"]["ops"].items() if v[2] == KERNEL]
    assert names and all(re.match(r"paged_decode_attention\.\d+$", n)
                         for n in names)
    got = bench_run.load_reader("paged_decode_roofline")(run)
    assert got == paged_by_result_type(run)
    assert 5.0 < got < 100.0


def test_the_flash_kernels_by_name_read_what_the_result_types_read():
    run = _run("deepseek-coder-1.3b", _recorded("trace_excerpt_flash.json"),
               job=JOB)
    names = {k.split(".")[0] for k, v in run["trace"]["ops"].items()
             if v[2] == KERNEL}
    assert names == {"flash_attention_fwd", "flash_attention_dq",
                     "flash_attention_dkv"}
    got = bench_run.load_reader("flash_roofline")(run)
    assert got == flash_by_result_type(run)
    assert 20.0 < got < 100.0


def _kernel(name, result, ms):
    return [f"%{name} = {result} custom-call(bf16[8] %x), "
            'custom_call_target="tpu_custom_call"', 0.0, ms * 1e6]


def test_hand_made_events_count_calls_times_the_adapters_bytes():
    planes = {"/device:TPU:0": {tr.OPS_LINE: [
        _kernel("paged_decode_attention.5", "bf16[32,32,128]{2,1,0}", 1.0),
        _kernel("paged_decode_attention.5", "bf16[32,32,128]{2,1,0}", 1.0),
        # another kernel with the same result, and a name that only
        # starts alike: neither is this kernel
        _kernel("ragged_attention.2", "bf16[32,32,128]{2,1,0}", 9.0),
        _kernel("paged_decode_attention_v2.1", "bf16[32,32,128]{2,1,0}", 9.0),
    ]}}
    run = _run("mistral-7b-l16", tr.reduce_planes(planes),
               deployment=DEPLOYMENT)
    live = (268 + 308) / 2 * 64 - 32 * 64 / 2
    need = 2 * model_spec.paged_decode_bytes(run["spec"], live, 32)
    assert bench_run.load_reader("paged_decode_roofline")(run) == (
        pytest.approx(100.0 * need / PEAKS["hbm_bytes_per_s"] / 0.002))


def test_a_cell_without_the_kernel_or_its_sizes_has_nothing_to_read():
    reader = bench_run.load_reader("flash_roofline")
    empty = tr.reduce_planes({"/device:TPU:0": {tr.OPS_LINE: [
        ["%fusion.1 = f32[8]{0} fusion(f32[8] %p)", 0.0, 1e6]]}})
    assert reader(_run("deepseek-coder-1.3b", empty, job=JOB)) is None
    flash = tr.reduce_planes({"/device:TPU:0": {tr.OPS_LINE: [
        _kernel("flash_attention_fwd.16", "(bf16[96,4096,128]{2,1,0}, "
                "f32[96,4096,128]{2,1,0})", 5.0)]}})
    # a serve cell's file has no `job`: the size is not there
    assert reader(_run("deepseek-coder-1.3b", flash,
                       deployment=DEPLOYMENT)) is None
    assert reader(_run("deepseek-coder-1.3b", flash, job=JOB)) > 0
