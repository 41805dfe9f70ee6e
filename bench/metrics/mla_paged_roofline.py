"""Paged latent-attention kernel, in % of its roofline: the least time
for the work its calls in the traced slice needed (q, the live latent
rows at the real lengths counted at kv_lora + rope lanes of 2 bytes
whatever the pool's padding, and the output; bound by bf16 peak or HBM
bandwidth) over the kernel's device time in the trace.  The kernel is
found by its op's own name, never by an operand.  Nothing to read
without the kernel; a count of calls other than one per layer and
traced step fails the run."""

from harness.core import BenchError
from harness.flops import least_time
from harness.mla_moe_flops import mla_kernel_cost
from harness.step_scopes import kernel_seconds
from peaks import peaks_of

#: the ``name`` of the kernel's pallas_call, which the compiler gives the
#: custom call's HLO instruction (``paged_latent_attention_kernel.<n>``)
KERNEL = "paged_latent_attention_kernel"


def read(run):
    ts = run.trace_summary
    if ts is None:
        return None
    secs, calls = kernel_seconds(ts, KERNEL)
    if calls == 0 or secs <= 0:
        return None
    cfg = run.cell.config
    peaks = peaks_of(run.devices[0].device_kind)
    layers = cfg["num_hidden_layers"]
    steps = run.host["traced_steps"]
    if calls != layers * len(steps):
        raise BenchError(f"mla_paged_roofline: {calls} kernel calls in the "
                         f"trace, {layers * len(steps)} expected ({layers} "
                         f"layers x {len(steps)} traced steps)")
    need = sum(layers * least_time(*mla_kernel_cost(cfg, contexts), peaks)
               for _, _, contexts in steps)
    return 100 * need / secs
