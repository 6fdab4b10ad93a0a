"""What the readers of the program's named scopes share.

The program places each stage of a solve in a ``jax.named_scope``
(``SCOPES``), and the compiler keeps the scope in each instruction's
``op_name`` metadata.  The traced window's device time is kept per
instruction name (``ctx.trace["op_s"]``, ``tracereduce.reduce``); this
module places each name in its scope from the text of the executable the
window ran.  It finds that executable again through the program's
executable cache, with the driver's model and a record of the cell's
layout (``Estimator.lower``, then ``compile``: JAX answers both from its
in-memory caches, so nothing is traced or compiled again).

A scope's device time is that of its **leaf** instructions: ``while``,
``conditional`` and ``call`` run the instructions of their computations,
whose time is their own, so theirs is skipped.  An instruction belongs to
the innermost scope of its ``op_name`` (the one named last).  Times are per
device and per solve of the window (``ctx.driver.solves``).

A program without the scopes, as an older commit is, gives ``None``: the
harness then leaves the metric out.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

SCOPES = ("smoother.elements", "smoother.scan", "smoother.fill",
          "smoother.recover", "nonlinear.linearize", "estimator.cost")
CONTAINERS = frozenset({"while", "conditional", "call"})
UNSCOPED = "unscoped"
OTHER = "other"

_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?(%[^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_tables: Dict[int, Dict[str, Tuple[str, Optional[str]]]] = {}


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` in an ``op_name`` path, or ``None``."""
    best, at = None, -1
    for scope in SCOPES:
        i = op_name.rfind(scope)
        if i > at:
            best, at = scope, i
    return best


def instructions(hlo_text: str) -> Dict[str, Tuple[str, Optional[str]]]:
    """``{"%name": (opcode, scope)}`` of every instruction of an HLO
    module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        rest = line[m.end():]
        if rest.startswith("("):        # a tuple shape: skip to its end
            depth = 0
            for i, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            rest = rest[i + 1:]
        else:
            rest = rest.partition(" ")[2]
        opcode = rest.strip().partition("(")[0]
        op_name = _OP_NAME.search(rest)
        out[m.group(1)] = (opcode,
                           scope_of(op_name.group(1)) if op_name else None)
    return out


def executable_text(driver) -> str:
    """The optimised HLO text of the executable that ``driver`` ran."""
    from repro.core import Estimator, Problem

    _, method, options = driver.cfgmod.build(driver.cfg)
    est = Estimator(driver.model, method=method, options=options)
    problem = Problem.single(driver.model, driver.ts32, driver.ys32[0])
    return est.lower(problem).compile().as_text()


def _table(ctx) -> Dict[str, Tuple[str, Optional[str]]]:
    key = id(ctx.driver)
    if key not in _tables:
        _tables[key] = instructions(executable_text(ctx.driver))
    return _tables[key]


def split_ms(ctx) -> Optional[Dict[str, float]]:
    """Device milliseconds per solve of the window's leaf instructions by
    scope, with those of the executable in no scope (``UNSCOPED``) and
    those not in it (``OTHER``: other programs' ops); ``None`` where the
    trace or the window's solves are missing."""
    t = ctx.trace
    solves = getattr(ctx.driver, "solves", 0)
    if not t or not solves or not t["devices"]:
        return None
    table = _table(ctx)
    out = dict.fromkeys(SCOPES + (UNSCOPED, OTHER), 0.0)
    for name, s in t["op_s"].items():
        opcode, scope = table.get(name, (None, OTHER))
        if opcode in CONTAINERS:
            continue
        out[scope or UNSCOPED] += 1e3 * s / t["devices"] / solves
    return out


def scope_ms(ctx, *scopes: str) -> Optional[float]:
    """Device milliseconds per solve of the leaf instructions in
    ``scopes``; ``None`` where the executable holds none of them."""
    split = split_ms(ctx)
    if split is None or not {scope for _, scope in _table(ctx).values()
                             }.intersection(scopes):
        return None
    return sum(split[s] for s in scopes)
