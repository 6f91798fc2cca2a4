"""The paged decode-attention kernel against the memory roofline: bytes
it has to move (live keys and values once, queries in, outputs out;
``model_spec.paged_decode_bytes``) over the peak bandwidth, over the
kernel's device time in the trace. Bound by bandwidth at every size
here (under 2 operations a byte). Live tokens are read from the block
allocator's counts at the window's ends (blocks in use, less half a
block for each active slot)."""

from _lib import counters, ops_seconds

from benchmark import model_spec

# Today the kernel's event is named after the computation it sits in
# (``closed_call.9``), not after the kernel; it is the only custom call of
# the decode program that returns (slots, heads, head size) in bf16.


def read(run):
    c = counters(run)
    if c is None:
        return None
    dep, spec = run["cellfile"]["deployment"], run["spec"]
    shape = (rf"^bf16\[{dep['num_slots']},{spec['num_attention_heads']},"
             rf"{spec['head_dim']}\]")
    seconds, calls = ops_seconds(run["trace"], opcode="custom-call:tpu_custom_call",
                                 result=shape)
    if not calls:
        return None
    live = []
    for st in c[:2]:
        blocks = st["kv_blocks_total"] - st["kv_blocks_free"]
        live.append(max(0.0, blocks * st["kv_block_size"]
                        - st["active_slots"] * st["kv_block_size"] / 2))
    need = calls * model_spec.paged_decode_bytes(
        run["spec"], sum(live) / 2, dep["num_slots"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
