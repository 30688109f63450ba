"""Device time per layer of the served episode, by the scopes the program
names (``jax.named_scope``), and the program's own host spans.

Scopes.  The extract of a trace keeps each ``XLA Ops`` event's HLO text,
which names the instruction but not its scope.  The compiled program holds
the scope: every instruction of the optimized HLO carries
``metadata={op_name="jit(counted)/.../<scope>/..."}``, the same metadata
the profiler reads, and the executable that ran is still loaded when the
readers run (``live_scope_maps``).  An operation counts toward the first
scope of ``SCOPES`` in its path.  An instruction without a scope of its own
(XLA drops the metadata of the loops it builds when it expands a batched
gather or scatter) takes the scope of the instruction whose loop, branch
or call holds it.  What no scope covers is ``unscoped`` (the scan's own
glue).  An operation whose instruction the map lacks, or gives another
result type, is ``unmatched``; a map that leaves more than
``1 - MATCHED_SHARE`` of the episode's op time unmatched is not the
executable that ran, and the readers read nothing.

Spans.  ``repro.common.obs`` keeps the last durations of each host span
the serving loop opens (``serve.*``, ``ckpt.*``); the traced windows are
the last ones served before the readers run.

A program from before its scopes and spans (no ``repro.common.obs``) gives
the readers nothing to read: they return None, and the harness leaves the
metric out.  In a program that has them, a reader that finds nothing fails
the run (``Unread``): a dropped scope or span, or an episode whose
operations the map no longer matches, is a fault of the yardstick.
"""
from __future__ import annotations

import re
import sys
from typing import Dict, List, Optional, Tuple

from chipbench.trace import CONTAINERS, MODULES_LINE, OPS_LINE, Summary

SCOPES = ("scene", "roidet", "control", "keep", "compact", "encode",
          "detect")
# the served episode's executable (as ``episode_device_ms_per_slot``)
EPISODE_MODULE = "jit_counted"
MATCHED_SHARE = 0.95

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")
_CALLED = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%([\w.\-]+)|branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


class Unread(RuntimeError):
    """A program with scopes and spans, whose trace or record lacks one."""


def program_names_layers() -> bool:
    """Whether the program names its device scopes and opens its host spans:
    both came with ``repro.common.obs``."""
    try:
        from repro.common import obs  # noqa: F401
    except ImportError:
        return False
    return True


def first_scope(op_name: str) -> Optional[str]:
    return next((c for c in op_name.split("/") if c in SCOPES), None)


def result_type(instruction: str) -> str:
    """The first word of an instruction's text after its name: its result
    type (the start of it, for a tuple)."""
    return instruction.split(" = ", 1)[-1].split(" ", 1)[0]


def scope_map(hlo_text: str) -> Dict[str, Tuple[Optional[str], str]]:
    """Instruction name -> (its scope or None, its result type), from an
    HLO module's text."""
    own: Dict[str, Optional[str]] = {}
    types: Dict[str, str] = {}
    computation_of: Dict[str, str] = {}
    caller: Dict[str, str] = {}           # computation -> calling instruction
    computation = ""
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(1)
            continue
        name = m.group(1)
        types[name] = result_type(line)
        computation_of[name] = computation
        meta = _OP_NAME.search(line)
        own[name] = first_scope(meta.group(1)) if meta else None
        for one, many in _CALLED.findall(line):
            for callee in ([one] if one else
                           [c.strip().lstrip("%") for c in many.split(",")]):
                caller.setdefault(callee, name)

    resolved: Dict[str, Optional[str]] = {}

    def resolve(name: str) -> Optional[str]:
        chain = []
        while name not in resolved and len(chain) < 256:
            chain.append(name)
            if own.get(name) is not None:
                break
            parent = caller.get(computation_of.get(name, ""))
            if parent is None or parent in chain:
                break
            name = parent
        scope = resolved.get(name, own.get(name))
        for n in chain:
            resolved[n] = scope
        return scope

    return {name: (resolve(name), types[name]) for name in own}


def live_scope_maps() -> List[Dict[str, Tuple[Optional[str], str]]]:
    """The scope map of every loaded episode executable that names a scope
    (normally one: the bucket the windows are served with)."""
    import jax

    maps = []
    for ex in jax.devices()[0].client.live_executables():
        for module in ex.hlo_modules():
            if module.name != EPISODE_MODULE:
                continue
            m = scope_map(module.to_string())
            if any(s is not None for s, _ in m.values()):
                maps.append(m)
    return maps


def episode_ops(trace: Summary) -> List[Tuple[str, str, int]]:
    """(instruction name, result type, duration ns) of every operation that
    ran inside the episode program in the traced window, containers left
    out."""
    w0, w1 = trace.window
    out = []
    for lines in trace.devices.values():
        spans = [(s, s + d) for n, s, d in lines.get(MODULES_LINE, [])
                 if n.startswith(EPISODE_MODULE + "(") and w0 <= s < w1]
        spans.sort()
        i = 0
        for name, s, d in sorted(lines.get(OPS_LINE, []),
                                 key=lambda e: e[1]):
            while i < len(spans) and spans[i][1] <= s:
                i += 1
            if i == len(spans):
                break
            if s < spans[i][0]:
                continue
            head = name.split(" = ", 1)[0]
            if head.startswith(CONTAINERS):
                continue
            out.append((head.lstrip("%"), result_type(name), d))
    return out


def layer_seconds(trace: Summary, maps: List[Dict[str, Tuple]]
                  ) -> Dict[str, float]:
    """Device seconds of the episode's operations per scope, summed over the
    devices, with ``unscoped`` and ``unmatched``.  Raises ``Unread`` when no
    map names a scope, the trace holds no episode, or no map is the one
    that ran."""
    ops = episode_ops(trace)
    if not maps:
        raise Unread("no loaded episode executable names a scope")
    if not ops:
        raise Unread("the traced windows hold no episode operation")

    def split(smap):
        out = dict.fromkeys(SCOPES + ("unscoped", "unmatched"), 0)
        for name, rtype, d in ops:
            scope, mtype = smap.get(name, (None, None))
            out["unmatched" if mtype != rtype else
                scope or "unscoped"] += d
        return out

    # the map of the executable that ran: the one that knows the most time
    out = min((split(m) for m in maps), key=lambda o: o["unmatched"])
    total = sum(out.values())
    if out["unmatched"] > (1 - MATCHED_SHARE) * total:
        raise Unread(f"{out['unmatched'] / total:.1%} of the episode's op "
                     "time matches no instruction of the loaded executable")
    return {k: v * 1e-9 for k, v in out.items()}


_memo: Dict[int, Dict[str, float]] = {}


def read_scope(ctx, scope: str) -> Optional[float]:
    """Microseconds of device time in ``scope`` per slot served in the
    traced windows; None when the program predates its scopes."""
    if not program_names_layers():
        return None
    key = id(ctx.trace)
    if key not in _memo:
        _memo.clear()
        secs = layer_seconds(ctx.trace, live_scope_maps())
        _memo[key] = secs
        total = sum(secs.values())
        print("# episode op time by scope (s): "
              + ", ".join(f"{k} {v!r}" for k, v in secs.items())
              + f"; total {total!r}", file=sys.stderr)
    if ctx.slots <= 0:
        raise Unread("no slot was served in the traced windows")
    return 1e6 * _memo[key][scope] / ctx.chips / ctx.slots


def read_span_ms(ctx, name: str) -> Optional[float]:
    """Mean milliseconds of span ``name`` over the traced windows (one span
    per window, the last ones the program recorded); None when the program
    predates its spans."""
    if not program_names_layers():
        return None
    from repro.common import obs

    spans = obs.recent(name)
    if len(spans) < ctx.windows:
        raise Unread(f"the program recorded {len(spans)} {name} spans, "
                     f"fewer than the {ctx.windows} traced windows")
    last = spans[-ctx.windows:]
    return 1e3 * sum(last) / len(last)
