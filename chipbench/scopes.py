"""The layer part of the hybrid LM that each device op of the segment
program belongs to.

Inside local training and eval the program names each part of its layer
stack with a ``jax.named_scope`` (``mamba``, ``attention``, ``mlp``,
``lm_head``) and the streamed Eq. 18 sum ``accumulate``, so an
instruction's ``op_name`` metadata holds the part below its stage:
``.../local_train/.../transpose(jvp(mamba))/...``.  ``scope_map`` reads the
live segment executables' HLO as ``chipbench/stages.py`` does (its
parsing, imported) and gives ``{instruction name: part}``: the part of an
instruction is the first ``op_name`` component that names one (a
transformation's wrapper, such as ``jvp(...)``, read through), and an
instruction whose ``op_name`` names none takes the part of the
control-flow instruction that calls its computation.
"""
from __future__ import annotations

import re
import sys
import time

from chipbench import stages
from chipbench.trace import op_name

PARTS = ("mamba", "attention", "mlp", "lm_head", "accumulate")
KEY = "scope_map"
_WRAPPED = re.compile(r"^(?:[\w]+\()*([\w]+)\)*$")


def part_of(op_path: str):
    """The first component of an ``op_name`` path that names a part."""
    for comp in op_path.split("/"):
        m = _WRAPPED.match(comp)
        if m and m.group(1) in PARTS:
            return m.group(1)
    return None


def module_parts(text: str) -> dict:
    """{instruction name: part or None} of one HLO module, an instruction
    without a part in its own ``op_name`` taking its callers' (where they
    agree), as ``stages.module_stages`` does for stages."""
    own, comp_of, callers = {}, {}, {}
    comp = None
    for line in text.splitlines():
        h = stages._HEADER.match(line)
        if h:
            comp = h.group(1)
            continue
        m = stages._INSTR.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        p = stages._PATH.search(line)
        own[name] = part_of(p.group(1)) if p else None
        comp_of[name] = comp
        for one, many in stages._CALLS.findall(line):
            for c in ([one] if one else many.split(",")):
                callers.setdefault(c.strip().lstrip("%"), []).append(name)
    memo: dict = {}

    def part(name):
        if own[name] is not None:
            return own[name]
        comp = comp_of[name]
        if comp not in memo:
            memo[comp] = None
            up = {part(c) for c in callers.get(comp, ())}
            memo[comp] = up.pop() if len(up) == 1 else None
        return memo[comp]

    return {name: part(name) for name in own}


def scope_map(ctx) -> dict:
    """The run's map, built on the first call and kept in ``ctx``."""
    if KEY not in ctx:
        t0 = time.perf_counter()
        placed, disputed = {}, set()
        for text in stages.segment_hlo():
            for name, pt in module_parts(text).items():
                if name in placed and placed[name] != pt:
                    disputed.add(name)
                placed.setdefault(name, pt)
        ctx[KEY] = {k: v for k, v in placed.items()
                    if v is not None and k not in disputed}
        print(f"scope map: {len(ctx[KEY])} instructions in layer parts in "
              f"{time.perf_counter() - t0:.4f} s", file=sys.stderr,
              flush=True)
    return ctx[KEY]


def ms_per_round(ctx, part: str):
    """Device time of the traced stretch's ops in ``part``, in ms per
    round; ``None`` when there is no stretch or no op of the program is
    in any part (a program that names none)."""
    if not ctx["ops"] or not ctx["segments"]:
        return None
    smap = scope_map(ctx)
    if not smap:
        return None
    ns = sum(d for name, _, d in ctx["ops"]
             if smap.get(op_name(name)) == part)
    return ns * 1e-6 / (ctx["segments"] * ctx["seg"])
