"""A kernel's share of its roofline: what ONE call has to do
(``kernel_counts`` of the configuration's adapter: operations where
``bound`` is ``flops``, bytes where it is ``bytes``) times the calls the
trace shows, over the peak, over those calls' device time. A kernel run
again for recomputation is a call like any other.

The kernels are found by the instruction name of their custom call,
which is the ``name=`` the program gives its ``pallas_call``
(``%paged_decode_attention.5``; the compiler numbers the instances).
A metric is one ``.json`` file:

    {"reader": "_kernel_roofline", "args": {
        "kernels": ["flash_attention_fwd", "flash_attention_dkv"],
        "bound": "flops",
        "sizes": {"batch": "cellfile.job.batch", "seq": "mix.seq"}}}

``sizes`` are the keyword arguments of ``kernel_counts``: each a dotted
path into ``run``, or the name of a quantity of ``DERIVED``. A cell that
lacks one has nothing to read. The counts are of the whole call; on a
program sharded over n chips each chip's call does an n-th.
"""

from _lib import live_kv_tokens, ops_seconds

from benchmark import model_spec

KERNEL = "custom-call:tpu_custom_call"
PEAK = {"flops": "bf16_flops_per_s", "bytes": "hbm_bytes_per_s"}
DERIVED = {"live_kv_tokens": live_kv_tokens}


def _size(run, where):
    if where in DERIVED:
        return DERIVED[where](run)
    for key in where.split("."):
        run = run.get(key) if isinstance(run, dict) else None
        if run is None:
            return None
    return run


def read(run, kernels, bound, sizes):
    given = {name: _size(run, where) for name, where in sizes.items()}
    if any(v is None for v in given.values()):
        return None
    chips = run["cell"]["chips"]
    need = seconds = 0.0
    for kernel in kernels:
        sec, calls = ops_seconds(run["trace"], rf"^{kernel}(\.\d+)?$",
                                 opcode=KERNEL)
        if calls:
            need += calls * model_spec.kernel_counts(
                run["spec"], kernel, **given)[bound] / chips
        seconds += sec
    if not seconds:
        return None
    return 100.0 * need / run["peaks"][PEAK[bound]] / seconds
