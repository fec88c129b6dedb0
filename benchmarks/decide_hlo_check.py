"""What the TPU compiler makes of the flow serve steps, without a chip.

Compiles ``jit_decide_b1024_mixed`` and ``jit_decide_b4096_uniform`` at
``mesh-100k``'s geometry (100k flows, window 10 x 100 ms) for a described
v5e (``jax.experimental.topologies``: libtpu compiles with no chip, about
6 s a program) and reports the window-sized instructions of each entry
computation and of the branches of its ``cond``s (the occupy window is
read and written only there). The TPU's scatter takes a flat operand and
its gather a tiling of its own: a scatter into the tiled ``[F, B, E]``
window makes the compiler copy the whole window flat (a ``while`` of
``dynamic-update-slice``), scatter, and copy it back, and a gather of one
channel makes it copy the window into another tiling first, every dispatch
(PERF.md section 5). The
step therefore scatters into one bucket's slab (the current one, or the
target of a booking ahead) and gathers whole rows (``stats/window.py``).
This is the check that the copies stay away; the CPU backend never shows
them. ``tests/test_step_layout.py`` runs it.

Usage: ``python benchmarks/decide_hlo_check.py [--json] [--flows N]
[--dump DIR]``. Exits 0 when clean, 1 when a program holds one of
:func:`violations`, 3 when libtpu cannot describe the topology here.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO not in _sys.path:
    _sys.path.insert(0, _REPO)

import argparse
import json
import re

PROGRAMS = ((1024, False), (4096, True))
# "%name = <type> opcode(operands" of one HLO instruction: the opcode is the
# first word after a blank that a parenthesis follows (a type holds
# parentheses, as in T(1024), but none after a blank)
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
_ARRAY = re.compile(r"\w+\[([\d,]*)\](\{[^}]*\})?")


def _arrays(type_text: str):
    """``[(cells, layout text)]`` of the arrays in an HLO type."""
    out = []
    for dims, layout in _ARRAY.findall(type_text):
        cells = 1
        for d in filter(None, dims.split(",")):
            cells *= int(d)
        out.append((cells, layout))
    return out


def _tiling(layout: str) -> str:
    """A layout without its memory space: ``{0:T(1024)S(1)}`` ->
    ``{0:T(1024)}``. A copy between memory spaces keeps it; a conversion
    (tiled 3-D to flat) changes it."""
    return re.sub(r"S\(\d+\)", "", layout)


_COMPUTATION = re.compile(
    r"^(ENTRY )?%?([\w.\-]+) \([^\n]*\) -> [^\n]*\{\n(.*?)\n\}", re.S | re.M)
_CALLED = re.compile(
    r"(?:branch_computations=\{([^}]*)\}|(?:true|false)_computation=(%?[\w.\-]+)"
    r"|calls=(%?[\w.\-]+))")


def _callees(rest: str):
    """The computations an instruction calls: a ``conditional``'s branches,
    a fusion's ``calls=``."""
    for group in _CALLED.findall(rest):
        for name in ",".join(group).split(","):
            if name.strip():
                yield name.strip().lstrip("%")


def _instructions(body: str):
    """``[(name, type text, opcode, rest of the line)]`` of a computation."""
    return [m.groups() for m in map(_INSTR.match, body.splitlines()) if m]


def _scope(line: str) -> str:
    """The step's own scope of an instruction (``threshold``, ``commit``,
    ...): the first part of its ``op_name`` after the jitted name."""
    m = re.search(r'op_name="[^"/]*/([^"/]+)', line)
    return m.group(1) if m else "?"


def _window_findings(instrs, computations, window_cells: int):
    """What a list of instructions does to arrays of ``window_cells`` cells
    or more: ``(layout copies, memory moves, scatters, passes)``. A layout
    copy is a ``copy`` / ``copy-start``, or a fusion, whose window-sized
    result tiles differently from its window-sized operand; a memory move
    is such a copy that keeps the tiling (the compiler's prefetch and
    write-back); a scatter has a window-sized operand; a pass is a fusion
    that writes a window-sized result in its operand's tiling and is not an
    in-place ``dynamic-update-slice`` (a reset by multiplying the window).
    """
    types = {name: type_text for name, type_text, _op, _rest in instrs}
    copies, moves, scatters, passes = [], [], [], []
    for name, type_text, op, rest in instrs:
        arrays = _arrays(type_text)
        if not arrays or max(a[0] for a in arrays) < window_cells:
            continue
        operand = re.match(r"%?([\w.\-]*)", rest).group(1)
        said = f"{name} = {type_text[:80]} {op}({operand})"
        if op == "scatter":
            scatters.append(said)
            continue
        if op not in ("copy", "copy-start", "fusion"):
            continue
        out = max(arrays)
        sources = [max(_arrays(types[o])) for o in re.findall(
            r"%([\w.\-]+)", rest.split(")", 1)[0])
            if _arrays(types.get(o, ""))]
        sources = [src for src in sources if src[0] >= window_cells]
        if not sources:
            continue
        same = any(_tiling(src[1]) == _tiling(out[1]) for src in sources)
        if not same:
            copies.append(said)
        elif op != "fusion":
            moves.append(said)
        else:
            roots = [re.search(r"ROOT [^\n]* ([\w\-]+)\(", computations.get(c, ""))
                     for c in _callees(rest)]
            if not all(r and r.group(1) == "dynamic-update-slice"
                       for r in roots):
                passes.append(said)
    return copies, moves, scatters, passes


def entry_report(hlo_text: str, window_cells: int) -> dict:
    """The entry computation's ``while`` count and its copies of
    ``window_cells`` cells or more (the smallest window, the flow window's
    plane of one channel) that change a layout: a ``copy`` or ``copy-start``
    whose source and destination tile differently. One that only changes
    the memory space (the compiler's prefetch and its write-back) is listed
    apart. The branches of the entry's ``conditional``s (and of theirs, and
    the fusions they call) are walked too, each finding under the scope of
    the step its ``cond`` belongs to: ``branch_layout_copies``,
    ``branch_window_scatters`` (a scatter whose operand is window-sized)
    and ``branch_window_passes`` (a window-sized elementwise rewrite)."""
    computations, entry = {}, None
    for is_entry, name, body in _COMPUTATION.findall(hlo_text):
        computations[name] = body
        if is_entry:
            entry = body
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")
    instrs = _instructions(entry)
    copies, moves, _scatters, _passes = _window_findings(
        instrs, computations, window_cells)
    found = {"branch_layout_copies": [], "branch_window_scatters": [],
             "branch_window_passes": []}
    seen = set()

    def walk(name: str, scope: str) -> None:
        if name in seen or name not in computations:
            return
        seen.add(name)
        inner = _instructions(computations[name])
        c, _m, s, p = _window_findings(inner, computations, window_cells)
        for key, got in zip(found, (c, s, p)):
            found[key] += [f"{scope}: {x}" for x in got]
        for _n, _t, _op, rest in inner:
            for callee in _callees(rest):
                walk(callee, scope)

    for _name, _type, op, rest in instrs:
        if op == "conditional":
            for callee in _callees(rest):
                walk(callee, _scope(rest))
    return {"entry_while": sum(op == "while" for _n, _t, op, _r in instrs),
            "window_layout_copies": copies, "window_memory_moves": moves,
            **found}


# Layout copies of a window a cond's branches may hold, by the step's scope.
# `threshold` fetches the occupy window's rows once for `matured` and
# `waiting`: the stored [F, 2B, 1] window lies T(1,128), and every gather the
# v5e compiler has (whole rows, 2B column gathers, a transposed or reshaped
# view) retiles what it reads; whole rows do it in one copy, 41 us at 100k
# flows, and were the cheapest by 4x (PERF.md section 6, PR 32).
ALLOWED_BRANCH_COPIES = {"threshold": 1}


def violations(report: dict) -> list:
    """What of one program's :func:`entry_report` a serve step must not
    hold: a ``while`` or a layout copy of a window in the entry
    computation; in a cond's branch a scatter into a whole window, a
    rewrite of one, or more layout copies than
    :data:`ALLOWED_BRANCH_COPIES`. Each starts with ``<scope>: ``."""
    out = [f"entry: {c}" for c in report["window_layout_copies"]]
    if report["entry_while"]:
        out.append(f"entry: {report['entry_while']} while")
    out += report["branch_window_scatters"] + report["branch_window_passes"]
    by_scope = {}
    for c in report["branch_layout_copies"]:
        by_scope.setdefault(c.split(":")[0], []).append(c)
    for scope, copies in by_scope.items():
        if len(copies) > ALLOWED_BRANCH_COPIES.get(scope, 0):
            out += copies
    return out


def describe_v5e():
    """The described (not attached) v5e 2x2 the programs are compiled for.
    Loads libtpu into this process, which keeps it until it exits."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu")


def compile_report(topo, flows: int = 100_000, dump: str = None) -> dict:
    """``{program name: entry_report + sizes}`` for :data:`PROGRAMS` at
    ``flows`` rule slots, compiled for chip 0 of ``topo``. The step picks its
    TPU forms (the namespace matvec, ``ops/scan_mm``) from
    ``jax.default_backend()``, which here is the CPU: the caller makes that
    say "tpu" for the length of the call (``main`` below; a test by
    ``monkeypatch``)."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from sentinel_tpu.engine import EngineConfig, build_rule_table
    from sentinel_tpu.engine.decide import PACKED_LINES, decide_donating
    from sentinel_tpu.engine.state import make_state

    on = SingleDeviceSharding(topo.devices[0])

    def described(x):
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=on)

    out = {}
    for bucket, uniform in PROGRAMS:
        cfg = EngineConfig(max_flows=flows, max_namespaces=64,
                           batch_size=bucket)
        table, _ = build_rule_table(cfg, [])
        state = jax.eval_shape(lambda: make_state(cfg))
        step = decide_donating(cfg, grouped=True, uniform=uniform)
        packed = np.zeros((PACKED_LINES, bucket), np.int32)
        compiled = step.lower(
            *jax.tree.map(described, (state, table, packed))).compile()
        text = compiled.as_text()
        name = f"jit_decide_b{bucket}_{'uniform' if uniform else 'mixed'}"
        if dump:
            _os.makedirs(dump, exist_ok=True)
            with open(_os.path.join(dump, name + ".hlo.txt"), "w") as f:
                f.write(text)
        memory = compiled.memory_analysis()
        # one channel's plane of the flow window, half the occupy window
        window_cells = flows * cfg.n_buckets
        out[name] = dict(
            entry_report(text, window_cells), window_cells=window_cells,
            argument_bytes=int(memory.argument_size_in_bytes),
            temp_bytes=int(memory.temp_size_in_bytes))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="print the report as one JSON line")
    ap.add_argument("--flows", type=int, default=100_000)
    ap.add_argument("--dump", help="write each program's HLO text here")
    args = ap.parse_args()
    import jax

    try:
        topo = describe_v5e()
    except Exception as e:  # no libtpu, or one another process holds
        print(f"no v5e topology: {type(e).__name__}: {e}", file=_sys.stderr)
        raise SystemExit(3)
    jax.default_backend = lambda: "tpu"
    report = compile_report(topo, args.flows, args.dump)
    if args.json:
        print(json.dumps(report))
    else:
        for name, r in report.items():
            print(f"{name}: {r['entry_while']} while, layout copies "
                  f"{r['window_layout_copies'] or 'none'}, memory moves "
                  f"{r['window_memory_moves'] or 'none'}; in cond branches: "
                  f"layout copies {r['branch_layout_copies'] or 'none'}, "
                  f"window scatters {r['branch_window_scatters'] or 'none'}, "
                  f"window passes {r['branch_window_passes'] or 'none'}; "
                  f"arguments {r['argument_bytes'] / 1e6:.1f} MB, temp "
                  f"{r['temp_bytes'] / 1e6:.1f} MB")
            for v in violations(r):
                print(f"  NOT CLEAN {v}")
    raise SystemExit(1 if any(map(violations, report.values())) else 0)


if __name__ == "__main__":
    main()
