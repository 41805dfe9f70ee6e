"""Device time of the simulator's scan step by part: TLB lookups, page
walk, data-line lookups.

The program wraps each part of a scan step in a ``jax.named_scope``
(``repro.sim.simulator.SCOPES``), which reaches the ``op_name`` metadata
of the compiled chunk runner's instructions.  A device trace's op is an
HLO instruction of the program that ran, its event named by the
instruction's text (``%fusion.514 = s32[...] fusion(...)``), without the
metadata.  So the compiled runner's text, got from the program for the
cell's own job (``simulator.lower_batch``), maps each instruction name to
its part, and the trace's ops inside the runner's ``while`` loop are
summed by part.  A fused instruction carries its root's op_name, so the
whole fusion counts for its root's scope.  An op inside the loop that
the compiled text does not hold means the traced program is not the one
compiled here, and is an error.  A program without the scopes, or
without ``lower_batch``, gives an empty map and nothing to read.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, Optional, Tuple

from harness.core import BenchError

SCOPES = ("tlb", "walk", "data")

_INSTR = re.compile(r"\s*(?:ROOT )?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAP = re.compile(r"^(?:[\w.]+\()+|\)+$")


def instruction_name(event_name: str) -> str:
    """``%fusion.514 = s32[...] ...`` (or a bare ``fusion.514``) ->
    ``fusion.514``."""
    return re.match(r"%?([^\s=]*)", event_name).group(1)


def op_scope(op_name: str) -> Optional[str]:
    """The innermost scope of SCOPES in an op_name such as
    ``jit(run_batch)/while/body/closed_call/vmap(vmap(tlb))/scatter``."""
    parts = [_WRAP.sub("", p) for p in op_name.split("/")]
    found = [p for p in parts if p in SCOPES]
    return found[-1] if found else None


def instruction_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> scope (one of SCOPES, or None) for every
    instruction of a compiled HLO module, those of nested computations
    included."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op_scope(op.group(1)) if op else None
    return out


def _outermost(loops):
    """The disjoint outermost of (start, end) intervals, sorted."""
    out = []
    for s, e in sorted(loops):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def scope_seconds(ts, names: Dict[str, Optional[str]]
                  ) -> Tuple[Dict[str, float], float]:
    """Device seconds per scope of the traced window's ops that lie in
    one of the runner's ``while`` ops (the scan), and the scan steps
    those ops ran: the most runs of one instruction of the loop's body,
    which runs each once a step.  Both averaged over the chips.  Raises
    BenchError where the window runs none of the runner's loops, or an
    op inside one is not an instruction of ``names``."""
    if not names:
        return {}, 0.0
    w0, w1 = ts.window
    total = collections.Counter()
    steps = 0
    for chip_ops in ts.ops:
        ops = [(instruction_name(n), s, e) for n, s, e in chip_ops
               if e > w0 and s < w1]
        loops = _outermost((s, e) for n, s, e in ops
                           if n.startswith("while") and n in names)
        if not loops:
            raise BenchError("the traced window runs no while op of the "
                             "compiled chunk runner")
        starts = [s for s, _ in loops]
        runs = collections.Counter()
        for n, s, e in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or e > loops[i][1]:
                continue
            if n not in names:
                raise BenchError(f"op {n} in the traced runner's loop is no "
                                 "instruction of the compiled runner")
            runs[n] += 1
            if names[n]:
                total[names[n]] += min(e, w1) - max(s, w0)
        steps += max(runs.values())
    n = max(len(ts.ops), 1)
    return {k: v * 1e-9 / n for k, v in total.items()}, steps / n


def runner_scopes(run) -> Dict[str, str]:
    """The map for the cell's own chunk runner (compiled once a run; a
    cache hit after the window's jobs)."""
    if "runner_scopes" not in run.host:
        run.host["runner_scopes"] = _compile_scopes(run)
    return run.host["runner_scopes"]


def _compile_scopes(run) -> Dict[str, str]:
    from repro.sim import simulator
    lower = getattr(simulator, "lower_batch", None)
    if lower is None:
        return {}
    from harness import table2
    cfg, mix = run.cell.config, run.cell.traffic
    m = cfg["machine"]
    mach = run.cell.system().machine_config(m)
    mechs = tuple(x["name"] for x in cfg["mechanisms"])
    traces = table2.generate_suite(mix["workloads"], m["num_cores"],
                                   cfg["accesses_per_core"], run.seed)
    jobs = [simulator.SimJob(mach, tr, mechs) for tr in traces]
    text = lower(jobs, chunk=mix["chunk"],
                 devices=run.cell.chips).compile().as_text()
    return instruction_scopes(text)


def us_per_step(run, scope: str) -> Optional[float]:
    """Device microseconds of ``scope``'s ops per traced scan step; None
    untraced or where nothing in the trace is in the scope."""
    ts = run.trace_summary
    if ts is None:
        return None
    if "scope_seconds" not in run.host:
        run.host["scope_seconds"] = scope_seconds(ts, runner_scopes(run))
    secs, steps = run.host["scope_seconds"]
    if not secs.get(scope):
        return None
    return 1e6 * secs[scope] / steps
