"""What decides ``correct`` beside the logits and gradients: who is
handed the engine, and when a job's loss has fallen (on sequences
recorded on the chip, ``data/train_losses.json``)."""

import json
import os

import numpy as np
import pytest

from benchmark import checks, model_spec
from benchmark import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))


class _Reference:
    @staticmethod
    def rel_err(a, b):
        return float(np.abs(np.asarray(a) - np.asarray(b)).max())


def _adapter(seen, takes_engine):
    def with_engine(params, spec, tokens, deployment, *, prefill,
                    engine=None):
        seen.append(engine)
        return np.zeros((1 + checks.SERVE_DECODE, 4), np.float32)

    def without(params, spec, tokens, deployment, *, prefill):
        seen.append("no engine asked for")
        return np.zeros((1 + checks.SERVE_DECODE, 4), np.float32)

    return type("Adapter", (), {"serve_program_logits": staticmethod(
        with_engine if takes_engine else without)})


@pytest.mark.parametrize("takes_engine", [True, False])
def test_the_engine_goes_to_an_adapter_that_takes_it_and_to_no_other(
        monkeypatch, takes_engine):
    seen, engine = [], object()
    monkeypatch.setattr(model_spec, "reference", lambda spec: _Reference)
    monkeypatch.setattr(model_spec, "adapter",
                        lambda spec: _adapter(seen, takes_engine))
    monkeypatch.setattr(model_spec, "limits", lambda spec: {
        "serve_prefill_logits_rel_err": {"limit": 0.5},
        "serve_decode_logits_rel_err": {"limit": 0.5}})
    monkeypatch.setattr(
        checks, "serve_reference_logits", lambda params, spec, tokens:
        np.zeros((1 + checks.SERVE_DECODE, 4), np.float32))
    spec = {"vocab_size": 16}
    got = checks.serve_check(None, spec, 5, {}, engine=engine)
    assert got["serve_decode_logits_rel_err"] == {"value": 0.0,
                                                  "limit": 0.5}
    assert seen == [engine if takes_engine else "no engine asked for"]
    # control.py and the tests hand none over: the adapter's own choice
    checks.serve_check(None, spec, 5, {})
    assert seen[1] == (None if takes_engine else "no engine asked for")


def test_only_the_adapter_whose_check_fits_no_scratch_copy_takes_one():
    import inspect

    takers = []
    folder = os.path.join(model_spec.HERE, "architectures")
    for entry in sorted(os.listdir(folder)):
        if entry.endswith(".py"):
            mod = model_spec.load_module(os.path.join(folder, entry))
            if "engine" in inspect.signature(
                    mod.serve_program_logits).parameters:
                takers.append(entry)
    assert "nemotron_h.py" in takers and "dense_decoder.py" not in takers


# ----------------------------------------------------- loss_did_not_fall
with open(os.path.join(HERE, "data", "train_losses.json")) as _f:
    RECORDED = json.load(_f)
with open(os.path.join(model_spec.HERE, "limits.json")) as _f:
    LEAST = json.load(_f)["limits"]["loss_fall_min"]


def _train_numbers(losses, cell="train-1chip"):
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    spec = model_spec.load_config(cells[cell]["config"])
    m = {"losses": losses, "steps": len(losses) - 3, "window_s": 51.0,
         "times": {}, "check": {}, "tokens_per_step": 1, "window_open": 0.0,
         "param_device_span": cells[cell]["chips"]}
    return bench_run.train_results(None, cells[cell], spec, {}, m)["numbers"]


@pytest.mark.parametrize("row", RECORDED["rows"], ids=lambda r: "{}-{}{}".format(
    r["cell"], r["seed"], "-" + r["control"] if r["control"] else ""))
def test_a_recorded_job_reads_as_it_did_on_the_chip(row):
    """Sound runs of the program read 0, seed 156545134 (which cost PR
    38 its check under one loss against one) among them; the control,
    whose optimizer does nothing, reads 1."""
    numbers = _train_numbers(row["losses"], row["cell"])
    fall = bench_run.loss_fall(row["losses"])
    assert numbers["loss_fall"] == {"value": fall, "limit": None}
    assert numbers["loss_did_not_fall"] == {
        "value": 1 if row["control"] else 0, "limit": 0}
    if row["control"]:
        assert fall <= LEAST["control_lr0_largest"] + 1e-9
    else:
        assert fall >= LEAST["program_smallest"] - 1e-9


def test_the_job_as_it_stood_diverged_and_reads_so_under_either_comparison():
    was = RECORDED["diverged"]
    assert was["losses"][-1] > was["losses"][0]        # one against one
    assert bench_run.loss_fall(was["losses"]) < 0
    assert _train_numbers(was["losses"], was["cell"])[
        "loss_did_not_fall"]["value"] == 1
    # and had fallen by 0.35 before it rose: a window four steps shorter
    # would have called the same job sound
    assert bench_run.loss_fall(was["losses"][:14]) > 0.3


def test_the_margin_lies_between_the_two_readings_with_room_on_both_sides():
    assert LEAST["control_lr0_largest"] < LEAST["limit"] \
        < LEAST["program_smallest"]
    assert LEAST["limit"] >= 2 * max(LEAST["control_lr0_largest"], 0.0)
    assert LEAST["program_smallest"] >= 2 * LEAST["limit"]


@pytest.mark.parametrize("losses, fell", [
    ([10.9, 10.9, 10.9, 10.9, 10.9, 10.9, 10.9], False),        # flat
    ([10.9, 10.9, 10.9, 10.6, 10.5, 11.2, 11.5, 11.5], False),  # up again
    ([10.9, 10.9, 10.9, 10.6, 10.5, 10.4, 10.4], True),
    ([10.9, 10.4], True), ([10.9], False), ([], False)])
def test_the_means_of_three_steps_at_each_end_are_compared(losses, fell):
    assert (_train_numbers(losses)["loss_did_not_fall"]["value"] == 0) \
        is fell
    assert bench_run.loss_fall([5.0, 4.0, 3.0, 9.0, 2.0, 2.0, 2.0]) \
        == pytest.approx(2.0)
