"""Repo-level framework lint (reference tools/check_op_desc.py +
tools/check_api_compatible.py discipline, folded into one gate).

Two families of checks, both pure-Python and fast enough for tier-1:

1. Registry <-> surface cross-check: every `@defop`-registered op must be
   visible in the committed API.spec (an op added without regenerating
   the spec is invisible to API review), no spec entry may be MISSING
   (dead surface), and each op's (signature, version) pair must match the
   committed OP_VERSIONS.json snapshot — changing an op's signature
   WITHOUT bumping `@defop(version=...)` is version drift: saved
   .pdmodel artifacts would replay the op under new semantics with no
   load-time warning (framework/program_serde.py op-version check).

2. Tracer-concretization hazard scan: AST-walk every `@defop` body for
   patterns that crash or silently specialize under jit/eval_shape
   tracing — `if`/`while` on a tensor argument, `float()`/`int()`/
   `bool()` of a tensor argument, and `.item()` anywhere. Tensor
   arguments are approximated as positional parameters without defaults
   (attrs carry defaults by convention). Deliberate host-side ops mark
   the line with `# lint: concretization-ok`.

Usage:
  python tools/framework_lint.py            # check; exit 1 on violations
  python tools/framework_lint.py --update   # rewrite OP_VERSIONS.json
"""
from __future__ import annotations

import ast
import inspect
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SPEC_PATH = os.path.join(REPO, "API.spec")
VERSIONS_PATH = os.path.join(REPO, "OP_VERSIONS.json")
OPS_DIR = os.path.join(REPO, "paddle_tpu", "ops")

PRAGMA = "lint: concretization-ok"

def _defop_modules():
    """Every paddle_tpu module that registers ops — found by source scan,
    so the lint's registry view does not depend on import order."""
    pkg_root = os.path.join(REPO, "paddle_tpu")
    mods = []
    for root, _dirs, files in os.walk(pkg_root):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            with open(path) as f:
                if "defop" not in f.read():
                    continue
            rel = os.path.relpath(path, REPO)[:-3].replace(os.sep, ".")
            if rel.endswith(".__init__"):
                rel = rel[: -len(".__init__")]
            mods.append(rel)
    return sorted(mods)


def _registry():
    # import the complete op-defining surface first: op registration is
    # an import side effect, and the lint must see the SAME registry no
    # matter what the test process imported beforehand
    import importlib
    for mod in _defop_modules():
        try:
            importlib.import_module(mod)
        except Exception:
            pass  # optional deps (pallas on TPU etc.) may be absent
    from paddle_tpu.ops import OP_REGISTRY
    return OP_REGISTRY


def _sig(fn):
    try:
        return str(inspect.signature(fn))
    except (TypeError, ValueError):
        return "(...)"


def _is_static_registration(fn):
    """True for ops the version-snapshot discipline binds: defined at
    module level of a repo module (registered by importing the library).
    Runtime registrations — user custom ops (`register_custom_op`) and
    kernels minted inside functions/classes (e.g. moe_layer) — are
    process-local and cannot be snapshot-pinned."""
    raw = getattr(fn, "raw", fn)
    try:
        path = inspect.getsourcefile(raw)
        lines, _ = inspect.getsourcelines(raw)
    except (TypeError, OSError):
        return False
    if not path or not os.path.abspath(path).startswith(
            os.path.join(REPO, "paddle_tpu") + os.sep):
        return False
    first = next((ln for ln in lines if ln.strip()), "")
    return not first.startswith((" ", "\t"))  # column-0 def/decorator


# ---------------------------------------------------------------------------
# check 1: registry vs API.spec vs OP_VERSIONS.json
# ---------------------------------------------------------------------------

def spec_leaf_names(spec_path=SPEC_PATH):
    """Leaf names with at least one committed `def`/`class` entry."""
    names = set()
    missing = []
    with open(spec_path) as f:
        for line in f:
            line = line.rstrip("\n")
            if not line:
                continue
            head = line.split(" ", 1)[0]
            leaf = head.rsplit(".", 1)[-1]
            if " MISSING" in line:
                missing.append(head)
            else:
                names.add(leaf)
    return names, missing


def _public_surface_leaves():
    """Leaf names of the LIVE public surface (the same sweep
    gen_api_spec commits to API.spec). Ops outside it are internal
    kernels (serde-registered dispatch heads etc.) and owe the spec
    nothing — but a publicly exported op missing from the committed spec
    is an unreviewed surface change."""
    import gen_api_spec
    names = set()
    for line in gen_api_spec.collect().splitlines():
        head = line.split(" ", 1)[0]
        names.add(head.rsplit(".", 1)[-1])
    return names


def check_registry_spec(spec_path=SPEC_PATH, versions_path=VERSIONS_PATH):
    """Returns a list of violation strings (empty = clean)."""
    reg = _registry()
    problems = []
    leaves, spec_missing = spec_leaf_names(spec_path)
    for head in spec_missing:
        problems.append(f"API.spec entry '{head}' is MISSING — dead "
                        "surface; regenerate with tools/gen_api_spec.py")
    public = _public_surface_leaves()
    for name in sorted(reg):
        if name in public and name not in leaves:
            problems.append(
                f"op '{name}' is in OP_REGISTRY but absent from API.spec "
                "— regenerate the spec (tools/gen_api_spec.py --update) "
                "or export the op")
    try:
        with open(versions_path) as f:
            snapshot = json.load(f)
    except FileNotFoundError:
        return problems + [
            f"{os.path.basename(versions_path)} not found — generate it "
            "with `python tools/framework_lint.py --update`"]
    for name, fn in sorted(reg.items()):
        if not _is_static_registration(fn):
            continue
        live_v = int(getattr(fn, "op_version", 1))
        live_sig = _sig(fn)
        snap = snapshot.get(name)
        if snap is None:
            problems.append(
                f"op '{name}' has no OP_VERSIONS.json entry — run "
                "`python tools/framework_lint.py --update`")
            continue
        if live_v < int(snap["version"]):
            problems.append(
                f"op '{name}' version regressed: snapshot v{snap['version']}"
                f" but @defop declares v{live_v}")
        elif live_v > int(snap["version"]):
            # a stale snapshot would disarm the drift check for every
            # future signature change to this op
            problems.append(
                f"op '{name}' was bumped to v{live_v} but OP_VERSIONS.json "
                f"still records v{snap['version']} — run "
                "`python tools/framework_lint.py --update` to re-pin it")
        elif live_sig != snap["sig"]:
            problems.append(
                f"op '{name}' signature drifted ({snap['sig']} -> "
                f"{live_sig}) without a version bump — bump "
                f"@defop(version={live_v + 1}) so program_serde flags old "
                "artifacts, then --update the snapshot")
    for name in sorted(set(snapshot) - set(reg)):
        problems.append(
            f"OP_VERSIONS.json lists op '{name}' which is no longer "
            "registered — removed ops break saved artifacts; run --update "
            "if the removal is deliberate")
    return problems


def update_versions(versions_path=VERSIONS_PATH):
    reg = _registry()
    snap = {name: {"version": int(getattr(fn, "op_version", 1)),
                   "sig": _sig(fn)}
            for name, fn in sorted(reg.items())
            if _is_static_registration(fn)}
    with open(versions_path, "w") as f:
        json.dump(snap, f, indent=0, sort_keys=True)
        f.write("\n")
    return len(snap)


# ---------------------------------------------------------------------------
# check 2: tracer-concretization hazards in @defop bodies
# ---------------------------------------------------------------------------

def _is_defop_decorator(dec):
    if isinstance(dec, ast.Name) and dec.id == "defop":
        return True
    if isinstance(dec, ast.Call):
        return _is_defop_decorator(dec.func)
    if isinstance(dec, ast.Attribute) and dec.attr == "defop":
        return True
    return False


_ARRAY_ROOTS = {"jnp", "jax", "lax"}


def _call_root(func):
    while isinstance(func, ast.Attribute):
        func = func.value
    return func.id if isinstance(func, ast.Name) else None


def _tensor_params(fdef: ast.FunctionDef):
    """Parameters that flow into jnp/jax/lax as the FIRST positional
    bare-name argument of a call — the dataflow approximation of 'this
    is the traced array', robust against int-like attrs (`axis`,
    `num_classes`) that a signature-position heuristic misclassifies."""
    params = {a.arg for a in fdef.args.posonlyargs + fdef.args.args}
    tensors = set()
    for node in ast.walk(fdef):
        if isinstance(node, ast.Call) and node.args \
                and _call_root(node.func) in _ARRAY_ROOTS \
                and isinstance(node.args[0], ast.Name) \
                and node.args[0].id in params:
            tensors.add(node.args[0].id)
    return tensors


_STATIC_CALLS = {"isinstance", "len", "getattr", "hasattr", "type"}


def _value_names(node, out=None):
    """Names used in VALUE position: excludes attribute access
    (`x.dtype`, `x.shape[i]` — static metadata), `is`/`is not`
    comparisons, and isinstance/len/… introspection calls, all of which
    are legitimate at trace time."""
    if out is None:
        out = set()
    if isinstance(node, ast.Name):
        out.add(node.id)
        return out
    if isinstance(node, ast.Attribute):
        return out  # x.anything — metadata/method access, not the value
    if isinstance(node, ast.Call):
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in _STATIC_CALLS:
            return out
        for a in node.args:
            _value_names(a, out)
        for k in node.keywords:
            _value_names(k.value, out)
        return out
    if isinstance(node, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
        return out  # `x is None` — identity test, never concretizes
    for child in ast.iter_child_nodes(node):
        _value_names(child, out)
    return out


class _HazardVisitor(ast.NodeVisitor):
    def __init__(self, path, src_lines, fdef):
        self.path = path
        self.lines = src_lines
        self.fdef = fdef
        self.tensors = _tensor_params(fdef)
        self.hits = []

    def _pragma(self, node):
        line = self.lines[node.lineno - 1] if node.lineno - 1 < len(
            self.lines) else ""
        return PRAGMA in line

    def _hit(self, node, what):
        if not self._pragma(node):
            self.hits.append(
                f"{os.path.relpath(self.path, REPO)}:{node.lineno} "
                f"[{self.fdef.name}] {what}")

    def visit_If(self, node):
        bad = _value_names(node.test) & self.tensors
        if bad:
            self._hit(node, "`if` on traced tensor argument "
                            f"({', '.join(sorted(bad))}) — the branch is "
                            "baked at trace time; use jnp.where/lax.cond")
        self.generic_visit(node)

    def visit_While(self, node):
        bad = _value_names(node.test) & self.tensors
        if bad:
            self._hit(node, "`while` on traced tensor argument "
                            f"({', '.join(sorted(bad))}) — use "
                            "lax.while_loop")
        self.generic_visit(node)

    def visit_Call(self, node):
        if isinstance(node.func, ast.Name) \
                and node.func.id in ("float", "int", "bool") and node.args:
            bad = _value_names(node.args[0]) & self.tensors
            if bad:
                self._hit(node, f"`{node.func.id}()` concretizes traced "
                                f"tensor argument ({', '.join(sorted(bad))})")
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item":
            self._hit(node, "`.item()` concretizes a traced value")
        self.generic_visit(node)


def check_concretization(ops_dir=OPS_DIR):
    """AST-scan @defop bodies; returns a list of violation strings."""
    hits = []
    for root, _dirs, files in os.walk(ops_dir):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(root, fname)
            with open(path) as f:
                src = f.read()
            try:
                tree = ast.parse(src)
            except SyntaxError as e:
                hits.append(f"{path}: unparseable ({e})")
                continue
            src_lines = src.splitlines()
            for node in ast.walk(tree):
                if isinstance(node, ast.FunctionDef) and any(
                        _is_defop_decorator(d) for d in node.decorator_list):
                    v = _HazardVisitor(path, src_lines, node)
                    for stmt in node.body:
                        v.visit(stmt)
                    hits.extend(v.hits)
    return hits


# ---------------------------------------------------------------------------
# check 3: sibling lint tools (each exposes self_check() -> [violations])
# ---------------------------------------------------------------------------

# Cross-check registry: domain lints that ride along with the framework
# gate. Each module lives in tools/, exposes `self_check()` returning a
# list of violation strings, and `main(argv)` for standalone use.
TOOL_CROSS_CHECKS = ["spmd_lint", "spmd_plan", "hlo_evidence",
                     "obs_report", "ps_load_test", "elastic_drill",
                     "serve_load_test", "pp_schedule_report",
                     "online_drill", "cluster_obs_drill", "capacity_plan"]


def check_tool_registry(tools_dir=None):
    """Every tools/*.py that defines a top-level self_check() must be
    listed in TOOL_CROSS_CHECKS — an unregistered self_check is a lint
    nobody runs, which is how cross-checks silently rot."""
    import ast
    problems = []
    tools_dir = tools_dir or os.path.dirname(os.path.abspath(__file__))
    for fname in sorted(os.listdir(tools_dir)):
        if not fname.endswith(".py"):
            continue
        mod_name = fname[:-3]
        if mod_name == "framework_lint":
            continue          # the registry itself, not a registrant
        try:
            with open(os.path.join(tools_dir, fname)) as f:
                tree = ast.parse(f.read(), filename=fname)
        except SyntaxError as e:
            problems.append(f"tool registry: tools/{fname} does not "
                            f"parse: {e}")
            continue
        has_self_check = any(
            isinstance(node, ast.FunctionDef) and node.name == "self_check"
            for node in tree.body)
        if has_self_check and mod_name not in TOOL_CROSS_CHECKS:
            problems.append(
                f"tool registry: tools/{fname} defines self_check() but "
                "is not listed in framework_lint.TOOL_CROSS_CHECKS — "
                "register it so the gate actually runs it")
    return problems


def check_registered_tools():
    problems = []
    tools_dir = os.path.dirname(os.path.abspath(__file__))
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    for mod_name in TOOL_CROSS_CHECKS:
        try:
            import importlib
            mod = importlib.import_module(mod_name)
        except Exception as e:
            problems.append(f"cross-check tool '{mod_name}' failed to "
                            f"import: {e!r}")
            continue
        if not callable(getattr(mod, "self_check", None)):
            problems.append(f"cross-check tool '{mod_name}' has no "
                            "self_check()")
            continue
        problems.extend(mod.self_check())
    return problems


# ---------------------------------------------------------------------------
# check 4: perf floors over the committed HLO evidence
# ---------------------------------------------------------------------------

EVIDENCE_PATH = os.path.join(REPO, "HLO_EVIDENCE.json")

# Floors over the committed HLO_EVIDENCE.json: analytic ratios (XLA
# counts and kernel grid arithmetic) and one CPU timing band — counts,
# not chip measurements (ROADMAP D2). A regenerated evidence file that
# regresses below a floor FAILS the build instead of silently rewriting
# the record. (label, path-into-the-json, floor)
PERF_FLOORS = [
    ("decode-attention FLOPs reduction",
     ("graphs", "gpt_decode_step", "attention_per_step",
      "flops_reduction_x"), 2.0),
    ("decode-attention bytes reduction",
     ("graphs", "gpt_decode_step", "attention_per_step",
      "bytes_reduction_x"), 2.0),
    ("serve_decode KV-bytes reduction",
     ("graphs", "serve_decode", "kv_bytes_per_step",
      "bytes_reduction_x_at_typical_fill"), 2.0),
    ("scan-fused dispatch reduction",
     ("graphs", "pipeline_scan_megastep", "dispatch_model",
      "dispatch_reduction_x"), 2.0),
    ("hierarchical dp sync inter-pod wire-bytes reduction",
     ("graphs", "hierarchical_sync", "wire_model",
      "inter_pod_reduction_x"), 2.0),
    # capacity model held inside its declared error bands when last
    # validated against the hub (tools/capacity_plan.py --validate);
    # headroom < 1.0 means a metric escaped its band
    ("capacity model validated within band",
     ("graphs", "capacity_validation", "band_headroom_x"), 1.0),
]


def check_perf_floors(evidence_path=EVIDENCE_PATH, floors=None):
    """Returns a list of violation strings (empty = clean)."""
    problems = []
    try:
        with open(evidence_path) as f:
            evidence = json.load(f)
    except FileNotFoundError:
        return [f"{os.path.basename(evidence_path)} not found — the "
                "committed HLO evidence is the perf record of truth; "
                "regenerate with `python tools/hlo_evidence.py`"]
    except json.JSONDecodeError as e:
        return [f"{os.path.basename(evidence_path)} is not valid JSON "
                f"({e}) — regenerate with `python tools/hlo_evidence.py`"]
    missing = object()  # distinct from a legitimately-null JSON leaf
    for label, path, floor in (PERF_FLOORS if floors is None else floors):
        node = evidence
        for key in path:
            if not isinstance(node, dict) or key not in node:
                problems.append(
                    f"perf floor '{label}': {'/'.join(path)} missing from "
                    f"{os.path.basename(evidence_path)} — the evidence "
                    "record lost a headline metric; regenerate with "
                    "`python tools/hlo_evidence.py` (a restructure needs "
                    "a matching PERF_FLOORS update)")
                node = missing
                break
            node = node[key]
        if node is missing:
            continue
        try:
            value = float(node)
        except (TypeError, ValueError):
            problems.append(
                f"perf floor '{label}': {'/'.join(path)} is "
                f"non-numeric ({node!r})")
            continue
        if value < floor:
            problems.append(
                f"perf floor '{label}': {value}x regressed below the "
                f"{floor}x floor — an evidence regeneration may not "
                "silently rewrite the perf record; fix the kernel path "
                "or justify a floor change in the PR")
    return problems


# ---------------------------------------------------------------------------
# check 5: doc flag tables may not drift from core/flags.py
# ---------------------------------------------------------------------------

DOCS_DIR = os.path.join(REPO, "docs")

# a markdown flag-table row: first cell is a backticked PADDLE_*/FLAGS_*
# name (the convention every docs/*.md flag table follows)
_DOC_FLAG_ROW = re.compile(r"^\| *`((?:PADDLE_|FLAGS_)[A-Za-z0-9_]+)`")


def check_doc_flags(docs_dir=DOCS_DIR):
    """Every flag a docs/*.md table documents must still exist in
    core/flags.py — a renamed or deleted flag whose doc row survives is
    operator-facing misinformation (the doc tells someone to set an env
    var nothing reads). Returns a list of violation strings."""
    problems = []
    try:
        from paddle_tpu.core import flags as _flags
    except Exception as e:  # pragma: no cover
        return [f"doc-flag check: paddle_tpu import failed: {e!r}"]
    for fname in sorted(os.listdir(docs_dir)):
        if not fname.endswith(".md"):
            continue
        path = os.path.join(docs_dir, fname)
        with open(path) as f:
            for lineno, line in enumerate(f, 1):
                m = _DOC_FLAG_ROW.match(line)
                if m and m.group(1) not in _flags._DEFS:
                    problems.append(
                        f"docs/{fname}:{lineno} documents flag "
                        f"{m.group(1)} which is not defined in "
                        "core/flags.py — update the doc table or "
                        "restore the flag")
    return problems


_DOC_COMMAND = re.compile(r"\bpython3? +([\w./-]+\.py)\b")


def check_doc_commands(root=REPO):
    """Every `python[3] <path>.py` that README.md, BASELINE.md or docs/*.md
    quotes must name a file of the tree (the driver's checkout holds what
    git tracks): no doc sends its reader to a retired entry point."""
    names = ["README.md", "BASELINE.md"] + sorted(
        f"docs/{f}" for f in os.listdir(os.path.join(root, "docs"))
        if f.endswith(".md"))
    problems = []
    for name in names:
        with open(os.path.join(root, name)) as f:
            for lineno, line in enumerate(f, 1):
                for path in _DOC_COMMAND.findall(line):
                    if not os.path.isfile(os.path.join(root, path)):
                        problems.append(
                            f"{name}:{lineno} tells the reader to run "
                            f"`{path}`, which the repository does not have")
    return problems


# ---------------------------------------------------------------------------
# check 6: the traffic lab must stay deterministic
# ---------------------------------------------------------------------------

TRAFFIC_DIR = os.path.join(REPO, "paddle_tpu", "traffic")

# suppression pragma for a deliberate, reviewed exception
_DETERMINISM_PRAGMA = "lint: traffic-determinism-ok"


def _attr_chain(node):
    """Dotted name of an attribute access ('np.random.RandomState'),
    or None for anything fancier than Name.attr.attr..."""
    import ast
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def check_traffic_determinism(traffic_dir=None):
    """Replayability is paddle_tpu/traffic/'s contract: every draw comes
    from a named, seeded stream. This AST lint forbids the ambient
    entropy sources that silently break byte-identical replay:

      - `time.time()` / `time.time_ns()` (wall clock in generated data;
        `time.perf_counter`/`time.sleep` pacing is fine)
      - any call through the stdlib `random` module (global PRNG)
      - `numpy.random` module-level draws (`np.random.rand(...)` uses
        global state) and UNSEEDED constructors (`np.random.RandomState()`
        / `np.random.default_rng()` with no arguments)

    A deliberate exception carries the `# lint: traffic-determinism-ok`
    pragma on the offending line."""
    import ast
    problems = []
    traffic_dir = traffic_dir or TRAFFIC_DIR
    if not os.path.isdir(traffic_dir):
        return [f"traffic determinism: {traffic_dir} missing"]
    seeded_ctors = {"RandomState", "default_rng", "Generator",
                    "SeedSequence"}
    for fname in sorted(os.listdir(traffic_dir)):
        if not fname.endswith(".py"):
            continue
        path = os.path.join(traffic_dir, fname)
        with open(path) as f:
            src = f.read()
        lines = src.splitlines()
        try:
            tree = ast.parse(src, filename=fname)
        except SyntaxError as e:
            problems.append(
                f"traffic determinism: {fname} does not parse: {e}")
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if chain is None:
                continue
            bad = None
            if chain in ("time.time", "time.time_ns"):
                bad = f"{chain}() (wall clock)"
            elif chain.startswith("random."):
                bad = f"{chain}() (global stdlib PRNG)"
            if bad is None:
                head, _, tail = chain.rpartition(".")
                if head in ("np.random", "numpy.random"):
                    if tail in seeded_ctors:
                        if not node.args and not node.keywords:
                            bad = (f"{chain}() without a seed "
                                   "(nondeterministic entropy)")
                    else:
                        bad = f"{chain}() (global numpy PRNG state)"
            if bad is None:
                continue
            line = lines[node.lineno - 1] if node.lineno <= len(lines) \
                else ""
            if _DETERMINISM_PRAGMA in line:
                continue
            problems.append(
                f"traffic determinism: paddle_tpu/traffic/{fname}:"
                f"{node.lineno} calls {bad} — every draw must come from "
                "a named seeded stream (workload.Stream); add "
                f"`# {_DETERMINISM_PRAGMA}` only for a reviewed "
                "exception")
    return problems


# ---------------------------------------------------------------------------

def run_lint(spec_path=SPEC_PATH, versions_path=VERSIONS_PATH,
             ops_dir=OPS_DIR):
    problems = check_registry_spec(spec_path, versions_path)
    problems += check_concretization(ops_dir)
    problems += check_perf_floors()
    problems += check_tool_registry()
    problems += check_registered_tools()
    problems += check_doc_flags()
    problems += check_doc_commands()
    problems += check_traffic_determinism()
    return problems


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--update" in argv:
        n = update_versions()
        print(f"wrote {VERSIONS_PATH} ({n} ops)")
        return 0
    problems = run_lint()
    if problems:
        print(f"framework_lint: {len(problems)} violation(s)")
        for p in problems:
            print(f"  {p}")
        return 1
    print("framework_lint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
