"""The flash-attention kernels (forward, and the two backward kernels)
against the compute roofline: operations of the calls the trace shows
(``model_spec.flash_flops`` for one layer's call; a forward kernel run
again for recomputation is a call like any other) over the peak, over
the kernels' device time. Compute-bound at sequence 4096 (about a
thousand operations a byte)."""

from _lib import ops_seconds

from benchmark import model_spec

# Today the kernels' events are named after the computation they sit in
# (``closed_call.7``, ``rematted_computation.12``, ``checkpoint.24``), not
# after the kernel. They are told apart by what they return, all of shape
# (batch x heads, sequence, head size): forward = (out bf16, lse f32),
# dkv = (dk bf16, dv bf16), dq = dq bf16 alone.
KERNEL = "custom-call:tpu_custom_call"


def _kinds(spec, batch, seq, chips):
    shape = (rf"\[{batch * spec['num_attention_heads'] // chips},{seq},"
             rf"{spec['head_dim']}\]")
    layout = r"\{[^}]*\}"
    return (("fwd", rf"^\(bf16{shape}{layout}, f32{shape}"),
            ("bwd_dkv", rf"^\(bf16{shape}{layout}, bf16{shape}"),
            ("bwd_dq", rf"^bf16{shape}"))


def read(run):
    trace, job = run["trace"], run["cellfile"].get("job")
    if job is None:
        return None
    chips = run["cell"]["chips"]
    per = model_spec.flash_flops(run["spec"], job["batch"],
                                 run["mix"]["seq"])
    flops = seconds = 0.0
    for kind, result in _kinds(run["spec"], job["batch"], run["mix"]["seq"],
                               chips):
        sec, calls = ops_seconds(trace, opcode=KERNEL, result=result)
        flops += calls * per[kind] / chips
        seconds += sec
    if not seconds:
        return None
    return 100.0 * flops / run["peaks"]["bf16_flops_per_s"] / seconds
