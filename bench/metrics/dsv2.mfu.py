"""Model FLOPs utilisation of DeepSeek-V2's decode, in %: the FLOPs of
every token processed in the window (prompt tokens included), counted
from shapes at each token's real context with the absorbed latent
attention, plus the routed experts' FLOPs of the (token, expert) pairs
the held experts computed (the program's counter
``repro.serve.moe_rows_held``, recorded in traced runs), over window
seconds x peak bf16.  Nothing to read without the counter."""

from harness.mla_moe_flops import decode_token_flops, routed_flops
from peaks import peaks_of

COUNTER = "repro.serve.moe_rows_held"


def read(run):
    rows = (run.host.get("counters") or {}).get(COUNTER)
    if not rows:
        return None
    cfg = run.cell.config
    flops = sum(decode_token_flops(cfg, n)
                for _, _, contexts in run.host["steps"] for n in contexts)
    flops += routed_flops(cfg, rows)
    peak = peaks_of(run.devices[0].device_kind)["flops_bf16"]
    return 100 * flops / (run.host["window_s"] * peak * len(run.devices))
