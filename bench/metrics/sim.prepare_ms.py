"""Host preparation of a simulate_batch job, in ms: the median over the
window's jobs of the program's pack and transfer spans
(timings["pack_s"] + timings["transfer_s"]): numpy packing, huge-page
hash and valid mask, then upload, state init and chunk slicing.  Nothing
to read where the program does not report them."""

from harness.core import quantile


def read(run):
    return quantile([1e3 * (t["pack_s"] + t["transfer_s"])
                     for t in (j["timings"] for j in run.host["jobs"])
                     if "pack_s" in t], 0.5)
