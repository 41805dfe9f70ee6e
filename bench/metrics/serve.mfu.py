"""Model FLOPs utilisation of the decode step, in %: the FLOPs of every
token processed in the window (prompt tokens included), counted from
shapes at each token's real context, over window seconds x peak bf16."""

from harness.flops import decode_token_flops
from peaks import peaks_of


def read(run):
    cfg = run.cell.config
    flops = sum(decode_token_flops(cfg, n)
                for _, _, contexts in run.host["steps"] for n in contexts)
    peak = peaks_of(run.devices[0].device_kind)["flops_bf16"]
    return 100 * flops / (run.host["window_s"] * peak * len(run.devices))
