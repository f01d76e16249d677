"""Name scopes of the device ops in a reduced trace.

:func:`chipbench.trace.load` keeps, per chip, each executed op by its HLO
instruction name (``fusion.3``) and each executed program by its module
name (``jit__solve_all_classes(<id>)``). An op's name scope
(``jax.named_scope``) is the ``op_name`` metadata of that instruction in
the program's optimized HLO, which the process that ran the traced window
still holds (``Client.live_executables()``). Op names such as ``fusion.3``
repeat across programs, so each op is looked up in the program whose run
holds it.

Scope seconds are the union of the intervals of the ops whose scope path
has a given component (``tree_predict``), clipped to the window and
averaged over chips, so that a scoped container op (``while``) and the ops
of its body count once.
"""
from __future__ import annotations

import bisect
import functools
import re
from typing import Dict, List, Optional, Tuple

from chipbench.trace import Trace, clip, union

# an instruction of HLO text, and the op_name of its metadata
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = ')
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
# a transform's wrapper round a scope in a name stack: vmap(sample.noise)
_WRAPPED = re.compile(r"^[\w.]*\((.*)\)$")

# module name -> the scope maps of the live programs of that name
Programs = Dict[str, List[Dict[str, str]]]


def hlo_scopes(text: str) -> Dict[str, str]:
    """Instruction name -> its ``op_name`` metadata ("" where it has none),
    for every instruction of an HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            s = _OP_NAME.search(line, m.end())
            out[m.group(1)] = s.group(1) if s else ""
    return out


def live_programs(devices) -> Programs:
    """The scope maps of the programs compiled for ``devices`` that are
    still alive, by module name."""
    out: Programs = {}
    for client in {d.client for d in devices}:
        for exe in client.live_executables():
            for mod in exe.hlo_modules():
                out.setdefault(mod.name, []).append(
                    hlo_scopes(mod.to_string()))
    return out


def module_name(event_name: str) -> str:
    """``jit_f(1234)`` -> ``jit_f``: a trace module event's program name."""
    if event_name.endswith(")") and "(" in event_name:
        return event_name[:event_name.rindex("(")]
    return event_name


@functools.lru_cache(maxsize=4096)
def scope_components(path: str) -> frozenset:
    """The components of a scope path, split at ``/`` (and at ``;``, which
    joins the paths of merged ops), each also without the wrappers that
    transforms put round a scope: ``jit(f)/vmap(sample.noise)/add`` ->
    {``jit(f)``, ``f``, ``vmap(sample.noise)``, ``sample.noise``, ``add``}."""
    out = set()
    for part in re.split(r"[/;]", path):
        while part and part not in out:
            out.add(part)
            m = _WRAPPED.match(part)
            part = m.group(1) if m else ""
    return frozenset(out)


def _scope(maps: List[Dict[str, str]], op: str) -> Optional[str]:
    """The op's scope in the programs of one name; None where none of them
    has the op or they disagree on its scope."""
    paths = {m[op] for m in maps if op in m}
    return paths.pop() if len(paths) == 1 else None


def op_scopes(trace: Trace, programs: Programs
              ) -> List[List[Tuple[Optional[str], int, int]]]:
    """Per chip, the (scope path, start, end) of each of its ops: the path
    from the live program whose run on that chip holds the op's start;
    None where no live program of that name knows the op."""
    out = []
    for dev in trace.devices:
        runs = sorted(dev.modules, key=lambda m: m[1])
        starts = [s for _, s, _ in runs]
        ops = []
        for name, a, b in dev.ops:
            i = bisect.bisect_right(starts, a) - 1
            path = None
            if i >= 0 and a <= runs[i][2]:
                maps = programs.get(module_name(runs[i][0]), [])
                path = _scope(maps, name)
            ops.append((path, a, b))
        out.append(ops)
    return out


def scope_s(trace: Trace, component: str, programs: Programs) -> float:
    """Mean over chips of the seconds inside the window in which an op
    runs whose scope path has ``component`` as a whole component
    (:func:`scope_components`): the union of their intervals."""
    if not trace.devices:
        return 0.0
    total = 0
    for ops in op_scopes(trace, programs):
        hits = [(a, b) for path, a, b in ops
                if path and component in scope_components(path)]
        total += sum(b - a for a, b in union(clip(hits, *trace.window)))
    return total * 1e-9 / len(trace.devices)
