"""What the TPU compiler makes of the flow serve steps, without a chip.

Compiles ``jit_decide_b1024_mixed`` and ``jit_decide_b4096_uniform`` at
``mesh-100k``'s geometry (100k flows, window 10 x 100 ms) for a described
v5e (``jax.experimental.topologies``: libtpu compiles with no chip, about
6 s a program) and reports the window-sized instructions of each entry
computation. The TPU's scatter takes a flat operand and its gather a tiling
of its own: a scatter into the tiled ``[F, B, E]`` window makes the compiler
copy the whole window flat (a ``while`` of ``dynamic-update-slice``),
scatter, and copy it back, and a gather of one channel makes it copy the
window into another tiling first, every dispatch (PERF.md section 5). The
step therefore scatters into the current bucket's slab and gathers whole
rows (``stats/window.py``). This is the check that the copies stay away;
the CPU backend never shows them. ``tests/test_step_layout.py`` runs it.

Usage: ``python benchmarks/decide_hlo_check.py [--json] [--flows N]
[--dump DIR]``. Exits 0 when clean, 1 when a program holds a ``while`` or a
layout copy of a window, 3 when libtpu cannot describe the topology here.
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


def entry_report(hlo_text: str, window_cells: int) -> dict:
    """The entry computation's ``while`` count and its copies of
    ``window_cells`` cells or more (the smallest window, occupy's) that
    change a layout: a ``copy`` or ``copy-start`` whose source and
    destination tile differently. One that only changes the memory space
    (the compiler's prefetch and its write-back) is listed apart."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)\n\}", hlo_text, re.S | re.M)
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")
    types, whiles, copies, moves = {}, 0, [], []
    for line in entry.group(1).splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, type_text, op, rest = m.groups()
        types[name] = type_text
        if op == "while":
            whiles += 1
        if op not in ("copy", "copy-start"):
            continue
        arrays = _arrays(type_text)
        if not arrays or arrays[0][0] < window_cells:
            continue
        operand = re.match(r"%?([\w.\-]+)", rest).group(1)
        src = _arrays(types.get(operand, ""))
        same = bool(src) and _tiling(src[0][1]) == _tiling(arrays[0][1])
        (moves if same else copies).append(
            f"{name} = {type_text[:80]} {op}({operand})")
    return {"entry_while": whiles, "window_layout_copies": copies,
            "window_memory_moves": moves}


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
        window_cells = flows * cfg.n_buckets  # the occupy window's
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
                  f"{r['window_memory_moves'] or 'none'}, arguments "
                  f"{r['argument_bytes'] / 1e6:.1f} MB, temp "
                  f"{r['temp_bytes'] / 1e6:.1f} MB")
    bad = any(r["entry_while"] or r["window_layout_copies"]
              for r in report.values())
    raise SystemExit(1 if bad else 0)


if __name__ == "__main__":
    main()
