"""The decode step's share of the chip's memory bandwidth, of a model
whose adapter counts what a WHOLE step has to move
(``decode_step_bytes(spec, live_tokens, slots)``: for a model whose
layers run several times, every pass's read of the weights and every
pool layer's live rows): those bytes times the steps the capture shows,
over the peak, over the decode program's device seconds. The kernels'
own rooflines say how near each call is to its bytes; this says how near
the step is to ALL of its bytes, glue and launches included. None where
the adapter counts no such thing, or the capture holds no decode step."""

from _lib import live_kv_tokens, programs

from benchmark import model_spec


def read(run):
    count = getattr(model_spec.adapter(run["spec"]), "decode_step_bytes",
                    None)
    live = live_kv_tokens(run)
    hit = programs(run["trace"], r"^jit_step")
    seconds = sum(p["total_s"] for p in hit)
    if count is None or live is None or not seconds:
        return None
    need = sum(p["count"] for p in hit) * count(
        run["spec"], live, run["cellfile"]["deployment"]["num_slots"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
