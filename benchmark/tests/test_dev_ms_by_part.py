"""The reader of a capture by the named part of the block
(``layer_metrics/_dev_ms_by_part.py``), on hand-made events, on a
hand-made ``.xplane.pb`` and against the program's vocabulary."""

import importlib
import json
import os
import sys

import pytest

from benchmark import run as bench_run

FOLDER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "layer_metrics")
sys.path.insert(0, FOLDER)
by_part = importlib.import_module("_dev_ms_by_part")

US = 1_000_000          # the reader's times are picoseconds
STEP, PREFILL, TRAIN = "jit_step(11)", "jit_prefill(22)", "jit_train_step(33)"


def op(start_us, dur_us, name, opcode, path):
    return (start_us * US, dur_us * US,
            f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %x)", path)


def run_of(program, start_us, dur_us):
    return (start_us * US, dur_us * US, program)


# two runs of the decode step and one prefill between them; `fusion.1`
# is an instruction of BOTH programs, under another part in each
RUNS = [run_of(STEP, 0, 100), run_of(PREFILL, 200, 100),
        run_of(STEP, 400, 100)]
OPS = [
    op(0, 10, "fusion.1", "fusion", "jit(step)/attn_proj/dot_general:"),
    # a container spans its body's operations and is skipped
    op(10, 60, "while.2", "while", "jit(step)/while:"),
    op(10, 20, "fusion.3", "fusion",
       "jit(step)/while/body/expert_layer/expert_combine/reduce_sum:"),
    # innermost wins: the kernel inside the expert layer is the kernel
    op(30, 30, "grouped_expert_matmul.4", "custom-call",
       "jit(step)/while/body/expert_layer/grouped_expert_matmul/"
       "pallas_call:"),
    op(60, 5, "all-reduce.5", "all-reduce", "jit(step)/mlp/psum:"),
    op(65, 5, "copy.6", "copy", ""),
    # outside every run
    op(150, 20, "fusion.7", "fusion", "jit(other)/mlp/dot_general:"),
    op(200, 40, "fusion.1", "fusion", "jit(prefill)/attention/dot_general:"),
    op(240, 10, "fusion.8", "fusion", "jit(prefill)/jit(_where)/select_n:"),
    op(400, 10, "fusion.1", "fusion", "jit(step)/attn_proj/dot_general:"),
    op(410, 30, "fusion.3", "fusion",
       "jit(step)/while/body/expert_layer/expert_combine/reduce_sum:"),
]


def ms(rows, program, part):
    row = rows[program]
    return sum(ps for (p, _), ps in row["parts"].items()
               if p == part) / row["runs"] / 1e9


def test_operations_are_filed_by_the_run_they_lie_in_and_their_path():
    rows = by_part.by_part(RUNS, OPS, ".")
    assert set(rows) == {STEP, PREFILL}
    assert rows[STEP]["runs"] == 2 and rows[PREFILL]["runs"] == 1
    # the same instruction name in two programs, told apart by the runs
    assert ms(rows, STEP, "attn_proj") == pytest.approx(0.010)
    assert ms(rows, PREFILL, "attention") == pytest.approx(0.040)
    assert ms(rows, PREFILL, "attn_proj") == 0
    assert ms(rows, STEP, "expert_combine") == pytest.approx(0.025)
    assert ms(rows, STEP, "grouped_expert_matmul") == pytest.approx(0.015)
    assert ms(rows, STEP, "expert_layer") == 0
    assert ms(rows, STEP, "collective") == pytest.approx(0.0025)
    assert ms(rows, STEP, "mlp") == 0
    assert ms(rows, STEP, "unnamed") == pytest.approx(0.0025)
    assert ms(rows, PREFILL, "unnamed") == pytest.approx(0.010)
    assert list(rows[PREFILL]["unnamed"]) == ["fusion.8"]


def test_the_parts_sum_to_the_runs_busy_time_without_the_container():
    rows = by_part.by_part(RUNS, OPS, "^jit_step")
    assert set(rows) == {STEP}
    row = rows[STEP]
    assert sum(row["parts"].values()) == row["busy_ps"] == 110 * US
    assert row["wall_ps"] == 200 * US
    # two operations over one instant: the instant counts once
    twice = [op(0, 10, "a.1", "fusion", "jit(step)/mlp/x:"),
             op(5, 10, "b.2", "fusion", "jit(step)/head/x:")]
    row = by_part.by_part(RUNS, twice, "^jit_step")[STEP]
    assert row["busy_ps"] == 15 * US
    assert row["parts"] == {("mlp", "forward"): 10 * US,
                            ("head", "forward"): 5 * US}


def line(name, opcode, *operand_names, extra=""):
    took = ", ".join(f"bf16[8]{{0}} %{o}" for o in operand_names)
    return f"%{name} = bf16[8]{{0}} {opcode}({took}){extra}"


def test_a_path_less_operation_is_filed_under_its_consumers_part():
    mlp = "jit(step)/mlp/dot_general:"
    instructions = {
        # a weight's prefetch: slices, their waits, the concatenation,
        # none with a path, then the product that reads it
        "slice-start.1": ("slice-start", line("slice-start.1", "slice-start",
                                              "w.1"), ""),
        "slice-done.1": ("slice-done", line("slice-done.1", "slice-done",
                                            "slice-start.1"), ""),
        "custom-call.2": ("custom-call:ConcatBitcast", line(
            "custom-call.2", "custom-call", "slice-done.1",
            extra=', custom_call_target="ConcatBitcast"'), ""),
        "fusion.3": ("fusion", line("fusion.3", "fusion", "custom-call.2",
                                    "x.1", extra=", kind=kOutput, "
                                    "calls=%fused_computation.3"), mlp),
        # named itself: its consumer's name does not matter
        "fusion.4": ("fusion", line("fusion.4", "fusion", "fusion.3"),
                     "jit(step)/head/dot_general:"),
        # a loop is no consumer, and nothing else reads this copy
        "copy-done.5": ("copy-done", line("copy-done.5", "copy-done",
                                          "copy-start.5"), ""),
        "while.6": ("while", line("while.6", "while", "copy-done.5"),
                    "jit(step)/mlp/while:"),
        # named by a path that holds no part, read by a named operation
        "fusion.7": ("fusion", line("fusion.7", "fusion", "x.1"),
                     "jit(step)/add:"),
        "fusion.8": ("fusion", line("fusion.8", "fusion", "fusion.7"),
                     "jit(step)/attn_proj/mul:"),
    }
    filed = by_part.file_program(instructions)
    assert filed["slice-done.1"] == ("mlp", mlp, True)
    assert filed["slice-start.1"] == ("mlp", mlp, True)
    assert filed["custom-call.2"] == ("mlp", mlp, True)
    assert filed["fusion.3"] == ("mlp", mlp, False)
    assert filed["fusion.4"][0] == "head" and not filed["fusion.4"][2]
    assert filed["copy-done.5"] == ("unnamed", "", False)
    assert filed["fusion.7"] == ("attn_proj", "jit(step)/attn_proj/mul:",
                                 True)
    assert by_part.operands(instructions["fusion.3"][1]) == [
        "custom-call.2", "x.1"]
    ops = [(0, 10 * US, instructions["slice-done.1"][1], ""),
           (10 * US, 0, instructions["custom-call.2"][1], ""),
           (10 * US, 30 * US, instructions["fusion.3"][1], mlp)]
    row = by_part.by_part([run_of(STEP, 0, 100)], ops, "^jit_step")[STEP]
    assert row["parts"] == {("mlp", "forward"): 40 * US}
    assert row["by_consumer"] == {"mlp": 10 * US}
    assert "mlp 0.040 ms 100.0% (0.010 by consumer)" in by_part.table(row)


def test_a_train_steps_operations_are_forward_recompute_or_backward():
    body = "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint"
    ops = [
        op(0, 10, "f.1", "fusion",
           "jit(train_step)/jvp()/while/body/closed_call/mlp/dot_general:"),
        op(10, 20, "f.2", "fusion",
           body + "/rematted_computation/mlp/dot_general:"),
        op(30, 30, "f.3", "fusion", body + "/mlp/dot_general:"),
        op(60, 5, "f.4", "fusion",
           "jit(train_step)/transpose(jvp(attention))/transpose:"),
        op(65, 35, "f.5", "fusion", "jit(train_step)/optimizer/mul:"),
    ]
    row = by_part.by_part([run_of(TRAIN, 0, 100)], ops, "^jit_train")[TRAIN]
    assert row["parts"] == {
        ("mlp", "forward"): 10 * US, ("mlp", "recompute"): 20 * US,
        ("mlp", "backward"): 30 * US, ("attention", "backward"): 5 * US,
        ("optimizer", "forward"): 35 * US}
    line = by_part.table(row)
    assert "mlp 0.060 ms 60.0%" in line and "recompute 20.0%" in line
    assert "backward 35.0%" in line


@pytest.mark.parametrize("path, part", [
    ("jit(step)/while/body/closed_call/mlp/dot_general:", "mlp"),
    ("jit(step)/expert_layer/expert_combine/mul:", "expert_combine"),
    ("jit(f)/jvp(attention)/exp:", "attention"),
    ("jit(f)/transpose(jvp(loss))/mul:", "loss"),
    ("jit(step)/mlp_extra/dot_general:", "unnamed"),
    ("", "unnamed")])
def test_the_innermost_name_of_the_vocabulary_is_the_part(path, part):
    assert by_part.part_of(path) == part


def _capture(tmp_path, planes):
    """A hand-made ``.xplane.pb`` from its text form."""
    from jaxlib._profile_data import ProfileData

    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(planes))
    return str(path)


HAND_MADE = '''
planes { name: "/host:CPU" lines { name: "t" events { metadata_id: 1 } } }
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
          events { metadata_id: 2 offset_ps: 0 duration_ps: 60000000
                   stats { metadata_id: 7 uint64_value: 5 } }
          events { metadata_id: 3 offset_ps: 60000000 duration_ps: 30000000 }
          events { metadata_id: 2 offset_ps: 950000000 duration_ps: 5 } }
  event_metadata { key: 1 value { id: 1 name: "jit_step(11)" } }
  event_metadata { key: 2 value {
    id: 2 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop"
    stats { metadata_id: 8 str_value: "loop fusion" }
    stats { metadata_id: 9 str_value: "jit(step)/mlp/dot_general:" } } }
  event_metadata { key: 3 value {
    id: 3 name: "%copy.2 = f32[8]{0} copy(f32[8]{0} %y)" } }
  stat_metadata { key: 7 value { id: 7 name: "device_offset_ps" } }
  stat_metadata { key: 8 value { id: 8 name: "hlo_category" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes { name: "/device:TPU:1" lines { name: "XLA Ops" } }
'''


def test_the_path_is_read_from_the_event_metadatas_tf_op_stat(tmp_path,
                                                              capsys):
    path = _capture(tmp_path, HAND_MADE)
    runs, ops = by_part.read_capture(path)
    assert runs == [(1_000_000, 100_000_000, "jit_step(11)")]
    assert [(s, d, p) for s, d, _, p in ops] == [
        (1_000_000, 60_000_000, "jit(step)/mlp/dot_general:"),
        (61_000_000, 30_000_000, ""), (951_000_000, 5,
                                       "jit(step)/mlp/dot_general:")]
    run = {"trace": {"xplane": path}, "cell": {"name": "none"}}
    assert by_part.read(run, "^jit_step", parts=["mlp"]) == pytest.approx(
        0.060)
    assert by_part.read(run, "^jit_step", parts=["unnamed"],
                        share=True) == pytest.approx(100 / 3)
    said = capsys.readouterr().out
    assert "[dev_ms_by_part] jit_step(11): 1 runs of 0.100 ms" in said
    assert "unnamed copy.2" in said
    # a program with no run, and a part no operation lies under
    assert by_part.read(run, "^jit_prefill", parts=["mlp"]) is None
    assert by_part.read(run, "^jit_step", parts=["head"]) is None


def test_a_run_without_a_capture_reads_none(tmp_path):
    run = {"trace": {"busy_s": 1.0}, "cell": {"name": "no-such-cell"}}
    assert by_part.read(run, "^jit_step", parts=["mlp"]) is None
    host_only = _capture(tmp_path, 'planes { name: "/host:CPU" }')
    assert by_part.read_capture(host_only) == ([], [])


@pytest.mark.parametrize("data", [b"\x0a\x85", b"\x0b\x00"],
                         ids=["cut-in-a-length", "a-wire-type-of-no-xplane"])
def test_a_file_that_is_no_capture_reads_none_and_does_not_raise(
        tmp_path, capsys, data):
    path = tmp_path / "bad.xplane.pb"
    path.write_bytes(data)
    run = {"trace": {"xplane": str(path)}, "cell": {"name": "none"}}
    assert by_part.read(run, "^jit_step", parts=["mlp"]) is None
    assert by_part.read(run, "^jit_step", parts=["unnamed"]) is None
    assert capsys.readouterr().out.count("not read") == 1


def test_the_readers_vocabulary_is_the_programs():
    """The union of ``layer_metrics/parts/*.json`` is the union of every
    tuple of ``profiling`` whose name ends in ``PARTS``: a PR that adds
    a model appends a tuple there and a file here."""
    from ray_tpu.util import profiling

    programs = [name for tup_name in sorted(vars(profiling))
                if tup_name.endswith("PARTS")
                for name in getattr(profiling, tup_name)]
    assert len(programs) == len(set(programs))
    assert set(by_part.PARTS) == set(programs)
    assert len(by_part.PARTS) == len(set(by_part.PARTS))
    assert by_part._KNOWN == frozenset(by_part.PARTS)
    assert not {by_part.UNNAMED, by_part.COLLECTIVE} & set(by_part.PARTS)
    # each tuple of the program has a file of its own
    files = {}
    for entry in os.listdir(by_part.PARTS_DIR):
        with open(os.path.join(by_part.PARTS_DIR, entry)) as f:
            files[entry] = tuple(json.load(f)["parts"])
    assert files["base.json"] == profiling.PARTS
    assert files["state_space.json"] == profiling.SSM_PARTS


def test_a_part_of_a_file_of_its_own_is_filed_under_its_name(tmp_path):
    (tmp_path / "a.json").write_text(json.dumps({"parts": ["mlp", "mix"]}))
    (tmp_path / "b.json").write_text(json.dumps({"parts": ["gate", "mlp"]}))
    (tmp_path / "notes.txt").write_text("no vocabulary")
    assert by_part.load_parts(str(tmp_path)) == ("mlp", "mix", "gate")
    assert by_part.part_of("jit(step)/ssm_update/mul:") == "ssm_update"
    assert by_part.part_of(
        "jit(step)/ssm_update/ssm_decode_update:") == "ssm_update"
    assert by_part.part_of("jit(step)/mix/mul:") == by_part.UNNAMED


ROOT = os.path.dirname(os.path.dirname(FOLDER))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
ALIASES = sorted(name[:-5] for name in os.listdir(FOLDER) if name.endswith(
    ".json") and json.load(open(os.path.join(FOLDER, name)))["reader"]
    == "_dev_ms_by_part")


@pytest.mark.parametrize("name", ALIASES)
def test_every_alias_resolves_to_the_reader_and_is_declared(name, tmp_path):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["source"] == "device_trace" and entry["workloads"]
    with open(os.path.join(FOLDER, name + ".json")) as f:
        args = json.load(f)["args"]
    assert set(args.get("parts", [])) <= set(by_part.PARTS) | {
        by_part.UNNAMED, by_part.COLLECTIVE}
    assert args.get("phase") in (None,) + by_part.PHASES
    # the one end-to-end metric (setup apart) of every cell it lists
    moved = {m["name"] for m in BENCH["end_to_end"] if m["name"] != "setup_s"
             and set(entry["workloads"]) & set(m["workloads"])}
    assert moved == {entry["moves"]}
    assert (entry["moves"] == "train_tokens_per_s") == (
        args["program"] == "^jit_train_step")
    assert entry["unit"] == ("%" if args.get("share") else "ms")
    # the parent's program under this reader: nothing named, no failure
    run = {"trace": {"xplane": _capture(tmp_path, HAND_MADE)},
           "cell": {"name": "none"}}
    value = bench_run.load_reader(name)(run)
    assert value is None or value > 0
