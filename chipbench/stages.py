"""The stage of the round step that each device op of the segment program
belongs to.

The program wraps each stage of its round step in a ``jax.named_scope``
(``ROUND_STAGES`` in ``src/repro/fed/scan_engine.py``), so every
instruction of the compiled segment program records its stage in the
``op_name`` of its metadata: ``jit(run_segment)/.../while/body/<stage>/...``.
``stage_map`` reads the HLO of the segment executables the process still
holds (the window's program is live when the readers run) and gives
``{instruction name: stage}``: the stage of an instruction is the first
``op_name`` component that names a stage, and an instruction whose
``op_name`` names none takes the stage of the control-flow instruction
that calls its computation.  An instruction with no stage either way (the
scan's own loop control, the copies of its carry), or one that two live
segment executables place in different stages, is unstaged and left out
of the map.  The instruction names are those of the device trace's op
line (``chipbench.trace.op_name``).

The map is built once per run, when the first reader asks, which is after
the window, and kept in the readers' ``ctx``.  A program that names no
stage gives an empty map, and the readers then report nothing.
"""
from __future__ import annotations

import re
import sys
import time

from chipbench.trace import SEGMENT_MODULE, op_name

STAGES = ("availability", "sampler", "local_train", "fault", "aggregate",
          "graph_refresh", "eval", "fairness", "telemetry")
KEY = "stage_map"               # where the map is kept in ctx
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?(\S+) .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?(\S+) = ")
_PATH = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:body|condition|true_computation|false_computation"
                    r"|to_apply|calls)=%?([^\s,}]+)"
                    r"|branch_computations=\{([^}]*)\}")


def stage_of(op_path: str):
    """The first component of an ``op_name`` path that names a stage."""
    for part in op_path.split("/"):
        if part in STAGES:
            return part
    return None


def segment_hlo() -> list:
    """The HLO text of each live segment executable, large constants
    elided (the segment program bakes the clients' data)."""
    import jax
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions()
    opts.print_large_constants = False
    opts.print_backend_config = False
    texts = []
    for exe in jax.devices()[0].client.live_executables():
        try:
            mods = exe.hlo_modules()
        except jax.errors.JaxRuntimeError:
            continue
        texts.extend(m.to_string(opts) for m in mods
                     if SEGMENT_MODULE in m.name)
    return texts


def module_stages(text: str) -> dict:
    """{instruction name: stage or None} of one HLO module.  An
    instruction whose ``op_name`` names no stage (a copy the compiler
    inserted inside a training loop, a constant branch of the eval
    ``cond``) takes the stage of the instruction that calls its
    computation (the ``while``, ``conditional``, ``call`` or ``fusion``),
    where every such caller agrees on one."""
    own, comp_of, callers = {}, {}, {}
    comp = None
    for line in text.splitlines():
        h = _HEADER.match(line)
        if h:
            comp = h.group(1)
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        p = _PATH.search(line)
        own[name] = stage_of(p.group(1)) if p else None
        comp_of[name] = comp
        for one, many in _CALLS.findall(line):
            for c in ([one] if one else many.split(",")):
                callers.setdefault(c.strip().lstrip("%"), []).append(name)
    memo: dict = {}

    def stage(name):
        if own[name] is not None:
            return own[name]
        comp = comp_of[name]
        if comp not in memo:
            memo[comp] = None               # no caller: the entry
            up = {stage(c) for c in callers.get(comp, ())}
            memo[comp] = up.pop() if len(up) == 1 else None
        return memo[comp]

    return {name: stage(name) for name in own}


def build(texts) -> dict:
    """{instruction name: stage} over the HLO texts; unstaged and
    disputed instructions left out."""
    placed, disputed = {}, set()
    for text in texts:
        for name, st in module_stages(text).items():
            if name in placed and placed[name] != st:
                disputed.add(name)
            placed.setdefault(name, st)
    return {k: v for k, v in placed.items()
            if v is not None and k not in disputed}


def stage_map(ctx) -> dict:
    """The run's map, built on the first call and kept in ``ctx``."""
    if KEY not in ctx:
        t0 = time.perf_counter()
        texts = segment_hlo()
        ctx[KEY] = build(texts)
        print(f"stage map: {len(ctx[KEY])} staged instructions from "
              f"{len(texts)} segment executables in "
              f"{time.perf_counter() - t0:.4f} s", file=sys.stderr,
              flush=True)
    return ctx[KEY]


def ms_per_round(ctx, stage):
    """Device time of the traced stretch's ops in ``stage`` (``None``: the
    unstaged ops), in ms per round.  ``None`` when there is no stretch, no
    segment executable or no stage in the program."""
    if not ctx["ops"] or not ctx["segments"]:
        return None
    smap = stage_map(ctx)
    if not smap:
        return None
    ns = sum(d for name, _, d in ctx["ops"]
             if smap.get(op_name(name)) == stage)
    return ns * 1e-6 / (ctx["segments"] * ctx["seg"])
