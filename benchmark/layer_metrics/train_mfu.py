"""Model FLOP/s utilization: operations the forward and backward passes
REQUIRE for a token (no gather, no recomputation;
``model_spec.train_flops_per_token``) times the window's tokens per
second, over chips times peak."""

from benchmark import model_spec


def read(run):
    tps = run["res"]["e2e"].get("train_tokens_per_s")
    if tps is None:
        return None
    need = model_spec.train_flops_per_token(run["spec"], run["mix"]["seq"])
    return (100.0 * need * tps
            / (run["cell"]["chips"] * run["peaks"]["bf16_flops_per_s"]))
