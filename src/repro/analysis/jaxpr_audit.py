"""Jaxpr invariant auditor — trace, walk, assert; execute nothing.

Checks (see the package docstring for the full catalog):

* no-host-callback: no ``*_callback`` / ``debug_*`` / infeed / outfeed /
  host-memory ``device_put`` primitive anywhere in a timed program's
  ClosedJaxpr (recursing into scan/while/cond/pjit sub-jaxprs);
* no-device-constant: no device array captured as a constant in a timed
  program — lowering fetches its value to the host, a device-to-host
  transfer inside the guarded first call (enforced on a TPU, silent on
  the CPU backend);
* donation: ``lowered.args_info`` marks exactly the claimed donated
  leaves (slot-step: frames/gt_boxes/gt_valid; everything else: none);
* two-harvest: every episode jaxpr emits exactly TWO slot-stacked
  outputs — the "exactly 2 harvest fetches per run" contract;
* fleet-size-independent PRNG: ``slot_camera_keys`` lowers to the same
  primitive multiset at different camera counts;
* matrix-count: episode registry == methods x buckets.

CLI::

    PYTHONPATH=src python -m repro.analysis.jaxpr_audit

prints one PASS/FAIL line per check and exits non-zero on any failure.
Pure tracing — no compile, no fake devices, no episode execution.
"""
from __future__ import annotations

import sys
from collections import Counter
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from repro.analysis.programs import METHODS, Program, get_programs

# primitive-name fragments that must never appear in a timed scope: host
# callbacks (pure/io/debug), debug prints, host infeed/outfeed channels
FORBIDDEN_FRAGMENTS: Tuple[str, ...] = (
    "callback", "debug", "infeed", "outfeed")


def _sub_jaxprs(params: Dict[str, Any]) -> Iterable[Any]:
    """Yield every jaxpr hiding in an eqn's params (scan/while/cond bodies,
    pjit calls, custom_* rules), tolerating both closed and open forms."""
    for v in params.values():
        items = v if isinstance(v, (list, tuple)) else (v,)
        for item in items:
            if hasattr(item, "jaxpr") and hasattr(item, "consts"):
                yield item.jaxpr          # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item                # raw Jaxpr


def collect_primitives(jaxpr) -> Counter:
    """Primitive-name multiset of a (Closed)Jaxpr, sub-jaxprs included."""
    counts: Counter = Counter()
    stack = [jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr]
    while stack:
        jx = stack.pop()
        for eqn in jx.eqns:
            counts[eqn.primitive.name] += 1
            stack.extend(_sub_jaxprs(eqn.params))
    return counts


def _is_host_device_put(name: str, params: Dict[str, Any]) -> bool:
    """A ``device_put`` moving data to host memory (pinned_host etc.) —
    any memory-kind mention of "host" in its placement params."""
    if name != "device_put":
        return False
    return "host" in repr(params.get("devices", params)).lower()


def forbidden_primitives(jaxpr) -> List[str]:
    """Names of forbidden primitives present (with multiplicity)."""
    bad: List[str] = []
    stack = [jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr]
    while stack:
        jx = stack.pop()
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if (any(f in name for f in FORBIDDEN_FRAGMENTS)
                    or _is_host_device_put(name, eqn.params)):
                bad.append(name)
            stack.extend(_sub_jaxprs(eqn.params))
    return bad


def device_constants(jaxpr) -> List[str]:
    """Shape/dtype of every device array captured as a constant by a
    (Closed)Jaxpr or any of its closed sub-jaxprs."""
    import jax

    found: List[str] = []
    stack = [jaxpr]
    while stack:
        jx = stack.pop()
        found.extend(f"{c.dtype}{list(c.shape)}" for c in
                     getattr(jx, "consts", ()) if isinstance(c, jax.Array))
        for eqn in getattr(jx, "jaxpr", jx).eqns:
            for v in eqn.params.values():
                items = v if isinstance(v, (list, tuple)) else (v,)
                stack.extend(i for i in items if hasattr(i, "eqns")
                             or hasattr(i, "consts"))
    return found


def trace_program(prog: Program):
    """ClosedJaxpr of an audited program over its abstract args."""
    import jax
    return jax.make_jaxpr(prog.fn)(*prog.abs_args)


def donated_indices(prog: Program) -> Tuple[int, ...]:
    """Flattened donated-arg indices the LOWERING records (``args_info``)
    — donation intent as jit actually staged it, which holds even on
    backends where XLA declines the buffer reuse (CPU's "donated buffers
    were not usable")."""
    import warnings

    import jax
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message=".*donated buffers were not usable.*")
        lowered = prog.fn.lower(*prog.abs_args)
    flat = jax.tree.leaves(
        lowered.args_info, is_leaf=lambda x: hasattr(x, "donated"))
    return tuple(i for i, a in enumerate(flat) if a.donated)


def stacked_outputs(prog: Program, jaxpr) -> List[Tuple[int, ...]]:
    """Shapes of episode outputs stacked along the scanned slot axis —
    each is one harvest fetch at episode end."""
    bucket = int(prog.name.rsplit("b", 1)[-1])
    return [tuple(av.shape) for av in jaxpr.out_avals
            if av.ndim >= 1 and av.shape[0] == bucket]


def prng_fold_multiset(num_cams: int) -> Counter:
    """Primitive multiset of the per-(slot, camera) codec-key fold at a
    given fleet size."""
    import jax
    import jax.numpy as jnp
    from repro.core import fleet as fleet_mod
    jx = jax.make_jaxpr(fleet_mod.slot_camera_keys)(
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((num_cams,), jnp.int32))
    return collect_primitives(jx)


def audit(programs: Optional[Sequence[Program]] = None,
          verbose: bool = False) -> List[str]:
    """Run every check; returns failure strings (empty == all invariants
    hold).  Traces each program once — nothing compiles, nothing runs."""
    from repro.core.fleet import EPISODE_BUCKETS

    failures: List[str] = []
    programs = get_programs() if programs is None else tuple(programs)

    def ok(line: str) -> None:
        if verbose:
            print(f"PASS  {line}")

    episodes = [p for p in programs if p.kind == "episode"]
    want = len(METHODS) * len(EPISODE_BUCKETS)
    if len(episodes) != want:
        failures.append(
            f"matrix-count: {len(episodes)} episode executables registered, "
            f"expected methods x buckets = {want}")
    else:
        ok(f"matrix-count: {want} episode executables "
           f"({len(METHODS)} methods x {len(EPISODE_BUCKETS)} buckets)")

    for prog in programs:
        jx = trace_program(prog)
        if prog.timed:
            bad = forbidden_primitives(jx)
            if bad:
                failures.append(
                    f"no-host-callback[{prog.name}]: forbidden primitives "
                    f"in timed scope: {sorted(set(bad))}")
            else:
                ok(f"no-host-callback[{prog.name}]")
            consts = device_constants(jx)
            if consts:
                failures.append(
                    f"no-device-constant[{prog.name}]: device arrays "
                    f"captured as constants: {consts}")
            else:
                ok(f"no-device-constant[{prog.name}]")
        got = donated_indices(prog)
        if got != prog.donated:
            failures.append(
                f"donation[{prog.name}]: lowered args_info donates leaves "
                f"{got}, claimed {prog.donated}")
        else:
            ok(f"donation[{prog.name}] leaves={got or '()'}")
        if prog.kind == "episode":
            stacked = stacked_outputs(prog, jx)
            if len(stacked) != 2:
                failures.append(
                    f"two-harvest[{prog.name}]: {len(stacked)} slot-stacked "
                    f"outputs {stacked}, the harvest contract pins exactly 2 "
                    "(log pack + control pack)")
            else:
                ok(f"two-harvest[{prog.name}] {stacked}")

    base = prng_fold_multiset(5)
    grown = prng_fold_multiset(9)
    if base != grown:
        failures.append(
            "prng-fold: slot_camera_keys primitive multiset depends on the "
            f"fleet size: C=5 {dict(base)} vs C=9 {dict(grown)}")
    elif not any("fold_in" in p for p in base):
        failures.append(
            "prng-fold: slot_camera_keys no longer lowers to a fold_in — "
            f"got {dict(base)}")
    else:
        ok(f"prng-fold: fleet-size-independent ({dict(base)})")
    return failures


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quiet", action="store_true",
                    help="failures only (default prints each PASS)")
    args = ap.parse_args(argv)
    failures = audit(verbose=not args.quiet)
    for f in failures:
        print(f"FAIL  {f}")
    if failures:
        print(f"jaxpr audit: {len(failures)} invariant(s) violated")
        return 1
    print("jaxpr audit: all invariants hold (nothing was executed)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
