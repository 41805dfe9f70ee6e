"""Paged-attention kernel, in % of its roofline: the least time for the
work its calls in the traced slice needed (q, live K/V rows of the real
lengths, output; bound by HBM bandwidth or bf16 peak) over the kernel's
device time in the trace.  Nothing to read without its kernel; a count
of kernel calls other than one per layer and traced step fails the run."""

from harness.core import BenchError
from harness.flops import least_time, paged_attn_cost
from peaks import peaks_of

#: the kernel's HLO instruction in the compiled decode step is named after
#: its jitted wrapper (``paged_attention_pallas.<n>``, custom call target
#: ``tpu_custom_call``); the step's other custom calls (gather index
#: hints, buffer allocation) and the wrapper's pool transposes
#: (``copy_bitcast_fusion``) carry other names
KERNEL = ("paged_attention_pallas",)


def read(run):
    ts = run.trace_summary
    if ts is None:
        return None
    secs, calls = ts.kernel_seconds(KERNEL)
    if calls == 0 or secs <= 0:
        return None
    cfg = run.cell.config
    peaks = peaks_of(run.devices[0].device_kind)
    layers = cfg["num_hidden_layers"]
    want = layers * len(run.host["traced_steps"])
    if calls != want:
        raise BenchError(f"paged_attn_roofline: {calls} kernel calls in the "
                         f"trace, {want} expected ({layers} layers x "
                         f"{len(run.host['traced_steps'])} traced steps)")
    need = sum(layers * least_time(*paged_attn_cost(cfg, contexts), peaks)
               for _, _, contexts in run.host["traced_steps"])
    return 100 * need / secs
