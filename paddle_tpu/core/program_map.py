"""Which scope every instruction of a compiled serve program belongs to.

The device timeline names an operation by its instruction (`fusion.123`)
and nothing else: the profiler's event carries no `jit(...)` path. The
compiler does: an optimized instruction keeps the `op_name` of the
operation it was made from, and `jax.named_scope` is part of that path
(`jit(decode_step)/layer3/attn/jit(_paged_call_once)/pallas_call`). So the
map from instruction to scope is read off the executable, once a program,
and laid over the timeline by whoever reads a trace
(benchmark/lib/scope_reduce.py; benchmark/README_scopes.md).

- `SCOPES` is the vocabulary of the served decoders, in ONE place: the
  nets wrap their work in these words (`text/models/*.py`,
  `nn/layer/{transformer,experts}.py`, `inference/serving.py`), innermost
  word wins, and the readers import the tuple.
- `shapes(args)` / `note(label, jitted, shapes)` remember a compiled
  program under a label. `ServeLoop._call_traced` calls them on a
  program's FIRST call only: the shapes before it (the arenas are
  donated), `note` after it, where `jitted.lower(*shapes).compile()` finds
  the executable the call just made and builds nothing. The `Compiled` is
  kept: not the net, not the parameters.
- `scopes(label)` parses `as_text()` on first demand: `{"module": the HLO
  module's name, "ops": {instruction name: op_name path}}` over every
  computation of the module (a `while` body's instructions are events of
  the timeline too). An instruction without metadata (the compiler's own
  `slice-done`, `copy-done`: the weight prefetches) is not in it.
- `scope_of(path)`: the innermost vocabulary word of a path, else None.
- `dump(dir)` writes `program_map.json`, every noted program's map, so that
  a trace taken here can be read in another process;
  `profiler.xplane_trace(dir)` calls it on exit.

No flag and no environment variable: a scope is a name at trace time and
costs nothing at run time; a program that is never asked for its map pays
one cache lookup at its first call. A program served from a compile cache
that another checkout filled carries THAT compile's metadata: the map then
shows the scopes of whoever compiled it.
"""
from __future__ import annotations

import json
import os
import re

__all__ = ["SCOPES", "shapes", "note", "labels", "parse", "scopes",
           "scope_of", "dump", "reset"]

SCOPES = ("embed", "attn", "linear_attn", "window_attn", "ffn", "router",
          "experts", "head", "sample")
FILE_NAME = "program_map.json"

_WORDS = frozenset(SCOPES)
_compiled = {}          # label -> jax.stages.Compiled
_maps = {}              # label -> parse(as_text()), made on first demand
_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([^\s=]+) = .*\bmetadata=\{[^}]*?op_name="([^"]*)"')


def shapes(args):
    """`args` with every array replaced by its shape, dtype and (where it
    is committed to one) sharding: what `jitted.lower` needs to find the
    program a call with `args` makes. Taken BEFORE the call: a donated
    array has no shape to ask for afterwards."""
    import jax

    def struct(x):
        if not isinstance(x, jax.Array):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None,
            weak_type=x.weak_type)
    return jax.tree.map(struct, args)


def note(label, jitted, arg_shapes):
    """Remember under `label` the program `jitted` runs for `arg_shapes`
    (`shapes(args)` of a call that has just returned: the executable is
    looked up, not built). A label noted again names the newer program."""
    _compiled[label] = jitted.lower(*arg_shapes).compile()
    _maps.pop(label, None)


def labels():
    return sorted(_compiled)


def parse(hlo_text):
    """{"module": name, "ops": {instruction name: op_name path}} of an
    optimized HLO module's text, over all of its computations."""
    module, ops = None, {}
    for line in hlo_text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        if "op_name=" in line:
            m = _INSTRUCTION.match(line)
            if m:
                ops[m.group(1)] = m.group(2)
    return {"module": module, "ops": ops}


def scopes(label):
    """The map of the program noted under `label` (parsed once, then
    remembered); None for a label nobody noted."""
    if label not in _maps:
        if label not in _compiled:
            return None
        _maps[label] = parse(_compiled[label].as_text())
    return _maps[label]


def scope_of(path):
    """The innermost vocabulary word of an op_name path
    ("jit(f)/layer0/attn/jit(g)/dot_general" -> "attn"); None when the
    path holds none."""
    for part in reversed((path or "").split("/")):
        if part in _WORDS:
            return part
    return None


def dump(directory):
    """Write every noted program's map to `<directory>/program_map.json`
    ({"scopes": the vocabulary, "programs": {label: map}}) and return the
    path; None, and no file, when nothing was noted."""
    if not _compiled:
        return None
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, FILE_NAME)
    with open(path, "w") as f:
        json.dump({"scopes": list(SCOPES),
                   "programs": {k: scopes(k) for k in labels()}}, f)
    return path


def reset():
    """Forget every program (tests)."""
    _compiled.clear()
    _maps.clear()
