"""Device time of the serving decode step by named scope, and of a kernel
by its own name.

The program puts latent attention and the expert layer of each decode
block under ``jax.named_scope`` (``mla``, ``moe``), which reaches the
``op_name`` metadata of the compiled step's instructions.  A device
trace's op is an HLO instruction named by its text, without the
metadata, so the compiled decode step's own text (kept by the cell's
system in traced runs) maps each instruction name to its scope, and
``harness.scopes.scope_seconds`` sums the traced ops inside the step's
``while`` loop over layers, where the expert layers run.  A program
without the scopes gives a map with none of them, and nothing to read.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from harness import scopes

SCOPES = ("mla", "moe")

_INSTR = re.compile(r"\s*(?:ROOT )?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_scope(op_name: str) -> Optional[str]:
    """The innermost of SCOPES in an op_name such as
    ``jit(decode_step)/jit(main)/while/body/moe/dot_general``."""
    found = [p for p in op_name.split("/") if p in SCOPES]
    return found[-1] if found else None


def instruction_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> scope (one of SCOPES, or None) of a compiled
    HLO module, nested computations included."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op_scope(op.group(1)) if op else None
    return out


def us_per_step(run, scope: str) -> Optional[float]:
    """Device microseconds of ``scope``'s ops inside the decode step's
    layer loop per traced engine step; None untraced or where nothing
    traced is in the scope."""
    ts = run.trace_summary
    text = run.host.get("step_hlo")
    if ts is None or not text or not run.host.get("traced_steps"):
        return None
    names = instruction_scopes(text)
    if scope not in names.values():
        return None
    secs, _ = scopes.scope_seconds(ts, names)
    if not secs.get(scope):
        return None
    return 1e6 * secs[scope] / len(run.host["traced_steps"])


def kernel_seconds(ts, pattern: str) -> Tuple[float, int]:
    """(seconds, calls) of the traced window's ops whose own name (the
    instruction's, before `` = ``) contains ``pattern``, never their
    operands; averaged over chips."""
    w0, w1 = ts.window
    total, calls = 0.0, 0
    for chip_ops in ts.ops:
        for name, s, e in chip_ops:
            if e <= w0 or s >= w1:
                continue
            if pattern in scopes.instruction_name(name):
                total += min(e, w1) - max(s, w0)
                calls += 1
    n = max(len(ts.ops), 1)
    return total * 1e-9 / n, calls // n
