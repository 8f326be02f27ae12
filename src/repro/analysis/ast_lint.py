"""Pass 2 — AST lint of repo conventions over ``src/`` (ESSR2xx).

Where the jaxpr audit checks what the compiler actually sees, this pass
checks what reviewers keep having to say in words:

  ESSR201  no new free-function inference entry points outside ``repro.api``
           (the ROADMAP convention: modes/backends plug into
           `ExecutionPlan`/`SREngine`). Detected as a module-level public
           function taking both ``params`` and ``frame``/``frames``.
  ESSR202  no ``numpy`` (``np.``) host ops inside traced bodies in ``core/``
           and ``kernels/`` — a np call under trace either crashes or, via
           ``__array__``, silently materializes the tracer on the host.
  ESSR203  no ``time`` module calls inside traced bodies there — wall-clock
           reads bake a compile-time constant and measure nothing.
  ESSR204  no ``.block_until_ready()`` / ``jax.device_get`` inside traced
           bodies there — a host sync inside the graph's staging path
           serializes the stream the async dispatch exists to overlap.
  ESSR205  no mutable or unhashable fields on frozen dataclasses (plans,
           configs, quant packs ride through jit as static arguments; one
           list-typed field makes the whole plan unhashable and every
           frame a cache miss). Frozen-with-``eq=False`` classes hash by
           identity and are exempt (that is `PatchGeometry`'s contract).
  ESSR206  no free-function STREAM-serving entry points outside ``repro.api``
           — multi/single-stream serving is an `SREngine` mode
           (``stream``/``serve_streams``); the multiplexer must not
           reintroduce the retired FrameServer shape. Detected as a
           module-level public function taking a stream bundle
           (``streams``/``frame_streams``/``stream_iters``/``iterables``)
           next to ``params`` or an ``engine``.
  ESSR207  no broad exception swallowing in ``runtime/`` / ``api/`` — a
           bare ``except``, ``except Exception`` or ``except BaseException``
           there must re-raise or record what it caught (a call whose name
           mentions record/warn/retire/quarantine/degrade/note_/fail);
           a silent handler in the serving path hides exactly the faults
           the resilience ledger (`runtime.guard`) exists to surface.

A "traced body" is resolved statically, at function granularity: a function
is traced when it is jit/pallas/shard_map-decorated, or its name is passed
into a ``jit`` / ``pallas_call`` / ``shard_map`` / ``vmap`` / ``scan`` /
``cond`` / ``while_loop`` / ``custom_jvp``-style call anywhere in the same
module (including through ``functools.partial``). Indirectly-traced helpers
are out of static reach — the jaxpr pass covers what actually lands in the
graph.

Suppression: a ``# essr: allow[ESSR201]`` comment on the flagged line or
the line directly above it waives that code at that site (multiple codes
comma-separate). Use it to grandfather documented legacy surfaces, never to
mute a new hazard.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from repro.analysis.report import Violation

#: Call names that put their function-valued arguments on the traced path.
TRACER_CALLS = frozenset({
    "jit", "pallas_call", "shard_map", "vmap", "pmap", "scan", "while_loop",
    "cond", "switch", "remat", "checkpoint", "custom_jvp", "custom_vjp",
    "grad", "value_and_grad", "make_jaxpr", "eval_shape", "named_call",
    "phase_jit",
})

#: Annotation tokens that sink a frozen dataclass's hashability (ESSR205).
_MUTABLE_ANN = re.compile(
    r"\b(list|dict|set|List|Dict|Set|DefaultDict|Deque|deque|bytearray|"
    r"ndarray|Array|MutableMapping|MutableSequence)\b")

_ALLOW = re.compile(r"essr:\s*allow\[([A-Za-z0-9_,\s]+)\]")

#: Directory scope (repo-relative prefixes) for the traced-body rules.
TRACED_BODY_SCOPE = ("src/repro/core/", "src/repro/kernels/")

#: The one package allowed to define free-function inference entry points.
ENTRY_POINT_EXEMPT = ("src/repro/api/",)

#: Directory scope for the swallowed-exception rule (ESSR207): the serving
#: runtime and the facade, where every fault must land on the guard ledger.
RESILIENCE_SCOPE = ("src/repro/runtime/", "src/repro/api/")

#: Call-name tokens that count as recording/handling a caught exception.
_RECOVERY_CALL = re.compile(
    r"(record|warn|retire|quarantine|degrad|note_|fail)", re.IGNORECASE)


def _suppressions(source: str) -> Dict[int, Set[str]]:
    """line number -> rule codes waived on that line (1-based)."""
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _ALLOW.search(line)
        if m:
            out[i] = {c.strip() for c in m.group(1).split(",") if c.strip()}
    return out


def _is_suppressed(code: str, line: int,
                   suppressions: Dict[int, Set[str]]) -> bool:
    """A marker covers its own line and the line below (so long ``def``
    headers take the marker on the preceding line)."""
    return (code in suppressions.get(line, ()) or
            code in suppressions.get(line - 1, ()))


def _name_tokens(node: ast.AST) -> Set[str]:
    """Every bare-name and attribute-name token in an expression subtree."""
    out: Set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            out.add(sub.id)
        elif isinstance(sub, ast.Attribute):
            out.add(sub.attr)
    return out


def _collect_traced_names(tree: ast.Module) -> Set[str]:
    """Names of functions this module puts on a traced path (see module
    docstring for the resolution rules)."""
    defs = {n.name for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    traced: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _name_tokens(dec) & TRACER_CALLS:
                    traced.add(node.name)
        elif isinstance(node, ast.Call):
            if _name_tokens(node.func) & TRACER_CALLS:
                for arg in list(node.args) + [kw.value for kw in
                                              node.keywords]:
                    traced.update(_name_tokens(arg) & defs)
    return traced


def _iter_traced_bodies(tree: ast.Module
                        ) -> Iterable[Tuple[str, ast.AST]]:
    traced = _collect_traced_names(tree)
    for node in ast.walk(tree):
        if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in traced):
            yield node.name, node


def _lint_traced_body(name: str, fn: ast.AST, relpath: str
                      ) -> Iterable[Violation]:
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute):
            base = node.value
            if isinstance(base, ast.Name) and base.id in ("np", "numpy"):
                yield Violation(
                    "ESSR202", f"{relpath}:{node.lineno}",
                    f"numpy op 'np.{node.attr}' inside traced body "
                    f"'{name}'")
            elif isinstance(base, ast.Name) and base.id == "time":
                yield Violation(
                    "ESSR203", f"{relpath}:{node.lineno}",
                    f"wall-clock call 'time.{node.attr}' inside traced "
                    f"body '{name}'")
            elif node.attr == "block_until_ready":
                yield Violation(
                    "ESSR204", f"{relpath}:{node.lineno}",
                    f"host sync '.block_until_ready()' inside traced body "
                    f"'{name}'")
            elif (node.attr == "device_get"
                  and isinstance(base, ast.Name) and base.id == "jax"):
                yield Violation(
                    "ESSR204", f"{relpath}:{node.lineno}",
                    f"host transfer 'jax.device_get' inside traced body "
                    f"'{name}'")


def _lint_entry_points(tree: ast.Module, relpath: str
                       ) -> Iterable[Violation]:
    for node in tree.body:                      # module level only
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name.startswith("_"):
            continue
        args = {a.arg for a in (node.args.posonlyargs + node.args.args
                                + node.args.kwonlyargs)}
        if "params" in args and ({"frame", "frames"} & args):
            yield Violation(
                "ESSR201", f"{relpath}:{node.lineno}",
                f"free-function inference entry point '{node.name}"
                f"(params, frame...)' outside repro.api — new modes plug "
                f"into ExecutionPlan/SREngine")
        stream_args = {"streams", "frame_streams", "stream_iters",
                       "iterables"} & args
        if stream_args and ({"params", "engine"} & args):
            yield Violation(
                "ESSR206", f"{relpath}:{node.lineno}",
                f"free-function stream-serving entry point '{node.name}"
                f"(..., {sorted(stream_args)[0]})' outside repro.api — "
                f"stream serving is an SREngine mode "
                f"(stream()/serve_streams())")


def _lint_swallowed_exceptions(tree: ast.Module, relpath: str
                               ) -> Iterable[Violation]:
    """ESSR207 — a broad except handler in the serving path must either
    re-raise or make a call that records the fault. Narrow handlers
    (``except StopIteration``, ``except OSError``) are out of scope: the
    rule targets catch-alls that can swallow injected faults whole."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        broad = (node.type is None or
                 bool(_name_tokens(node.type)
                      & {"Exception", "BaseException"}))
        if not broad:
            continue
        recovered = False
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Raise):
                    recovered = True
                elif (isinstance(sub, ast.Call)
                      and any(_RECOVERY_CALL.search(n)
                              for n in _name_tokens(sub.func))):
                    recovered = True
            if recovered:
                break
        if not recovered:
            caught = (ast.unparse(node.type) if node.type is not None
                      else "<bare>")
            yield Violation(
                "ESSR207", f"{relpath}:{node.lineno}",
                f"broad 'except {caught}' swallows the fault without "
                f"re-raising or recording it — serving-path handlers must "
                f"put what they caught on the resilience ledger "
                f"(guard.record / warnings.warn / ...)")


def _dataclass_flags(node: ast.ClassDef) -> Optional[Dict[str, bool]]:
    """None when not a dataclass; else {'frozen': ..., 'identity_eq': ...}."""
    for dec in node.decorator_list:
        tokens = _name_tokens(dec)
        if "dataclass" not in tokens:
            continue
        frozen = identity_eq = False
        if isinstance(dec, ast.Call):
            for kw in dec.keywords:
                if isinstance(kw.value, ast.Constant):
                    if kw.arg == "frozen":
                        frozen = bool(kw.value.value)
                    elif kw.arg == "eq":
                        identity_eq = not kw.value.value
        return {"frozen": frozen, "identity_eq": identity_eq}
    return None


def _lint_frozen_fields(tree: ast.Module, relpath: str
                        ) -> Iterable[Violation]:
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        flags = _dataclass_flags(node)
        if not flags or not flags["frozen"] or flags["identity_eq"]:
            continue
        for stmt in node.body:
            if not isinstance(stmt, ast.AnnAssign):
                continue
            ann = ast.unparse(stmt.annotation)
            m = _MUTABLE_ANN.search(ann)
            if m:
                yield Violation(
                    "ESSR205", f"{relpath}:{stmt.lineno}",
                    f"frozen dataclass '{node.name}' field "
                    f"'{ast.unparse(stmt.target)}: {ann}' is "
                    f"mutable/unhashable ('{m.group(1)}'); it rides "
                    f"through jit as a static argument")
            elif stmt.value is not None and isinstance(
                    stmt.value, (ast.List, ast.Dict, ast.Set)):
                yield Violation(
                    "ESSR205", f"{relpath}:{stmt.lineno}",
                    f"frozen dataclass '{node.name}' field "
                    f"'{ast.unparse(stmt.target)}' has a mutable literal "
                    f"default")


def lint_source(source: str, relpath: str) -> List[Violation]:
    """Lint one module's source. ``relpath`` is the repo-relative path used
    for rule scoping and violation sites (tests pass synthetic ones)."""
    tree = ast.parse(source)
    suppressions = _suppressions(source)
    found: List[Violation] = []
    if not relpath.startswith(ENTRY_POINT_EXEMPT):
        found.extend(_lint_entry_points(tree, relpath))
    if relpath.startswith(TRACED_BODY_SCOPE):
        for name, fn in _iter_traced_bodies(tree):
            found.extend(_lint_traced_body(name, fn, relpath))
    if relpath.startswith(RESILIENCE_SCOPE):
        found.extend(_lint_swallowed_exceptions(tree, relpath))
    found.extend(_lint_frozen_fields(tree, relpath))
    return [v for v in found
            if not _is_suppressed(v.code, int(v.site.rsplit(":", 1)[1]),
                                  suppressions)]


def lint_file(path: str, repo_root: str) -> List[Violation]:
    relpath = os.path.relpath(os.path.abspath(path),
                              os.path.abspath(repo_root))
    with open(path) as f:
        return lint_source(f.read(), relpath.replace(os.sep, "/"))


def default_src_root() -> str:
    """The repo root this installed tree lives in (…/src/repro/analysis/
    ast_lint.py -> repo root three levels up from the package)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(os.path.dirname(here)))


def run_ast_lint(repo_root: Optional[str] = None) -> List[Violation]:
    """The whole pass: every ``.py`` under ``src/``."""
    root = repo_root if repo_root is not None else default_src_root()
    out: List[Violation] = []
    src = os.path.join(root, "src")
    for dirpath, _dirnames, filenames in os.walk(src):
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.extend(lint_file(os.path.join(dirpath, fn), root))
    return out
