"""JAX trace-discipline lints: the jit boundary as a checkable contract.

The pjit/TPU scaling work (PAPERS.md, arxiv 2204.06514) shows step-time
regressions on the training plane are dominated not by kernels but by
boundary mistakes: an accidental host sync serializing the dispatch
pipeline, a retrace storm from a Python-value branch inside a jitted
function, a donated buffer read after the callee already aliased it, and
benchmarks that read the wall clock before the device finished. Four
rules, each mechanizing one of those:

- JAX001 **host sync on a jit output in a hot path**: a value produced by
  a jitted callable consumed on the host (``.item()``, ``float(...)``,
  ``np.asarray``/``np.array``) inside ``parallel/`` or
  ``embedding/hbm_cache/`` without a sentinel-style guard in the function
  (tokens: sentinel / isfinite / isnan / nonfinite / block_until_ready —
  the deliberate-sync idioms the health plane already uses). Each such
  sync drains the dispatch queue; per-step it serializes host and device.
- JAX002 **retrace hazard**: a jitted function branching (``if``/
  ``while``/``for _ in range(...)``) on a parameter not marked static via
  ``static_argnums``/``static_argnames``. Branching on a traced value
  either raises at trace time or — when callers pass Python scalars —
  silently retraces per distinct value. ``x is None`` / ``x is not None``
  and shape/dtype attribute probes (``x.shape``, ``x.ndim``, ``x.dtype``)
  are static under trace and exempt.
- JAX003 **donated-buffer reuse**: an argument passed in a donated
  position (``donate_argnums``) of a jitted callable and then read again
  before being rebound. XLA may alias the donated buffer into the output;
  the read observes garbage — or silently stale data on backends that
  copy. The loop idiom ``state, loss = step(state, batch)`` rebinds and
  is clean.
- JAX004 **un-synced benchmark timing** (the benchmark's timed path,
  ``perf/harness.py`` + ``perf/entries/``): a ``t0 = time.perf_counter()``
  … ``x - t0`` window that calls a device-producing function (jitted, or a
  package function touching jax/jnp — resolved through imports,
  whole-program like CONC005) with no ``block_until_ready`` inside the
  window. The window then measures dispatch, not execution.
  Host-orchestrated loops (ctx methods that sync internally) stay silent —
  only resolvable device-producing callees count.

Suppress with ``# persia-lint: disable=JAX00n`` on the reported line.
Pure stdlib; jax itself is never imported.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from persia_tpu.analysis.common import Finding, REPO_ROOT, read_text, rel

# JAX001 hot-path scope
_SYNC_SCOPE_DIRS = (
    os.path.join("persia_tpu", "parallel"),
    os.path.join("persia_tpu", "embedding", "hbm_cache"),
)
# JAX004 bench scope: the benchmark's timed path (the study scripts beside
# it, e.g. perf/limits_one.py, time themselves on purpose)
_BENCH_SCOPE_FILES = (os.path.join("perf", "harness.py"),)
_BENCH_SCOPE_DIRS = (os.path.join("perf", "entries"),)

_GUARD_TOKENS = ("sentinel", "isfinite", "isnan", "nonfinite", "block_until_ready")
_CLOCK_FUNCS = ("perf_counter", "monotonic", "time")


@dataclass
class _JitInfo:
    jitted: bool = False
    donate: Tuple[int, ...] = ()
    static_nums: Tuple[int, ...] = ()
    static_names: Tuple[str, ...] = ()
    device: bool = False  # produces device values (jitted or touches jax/jnp)
    def_node: Optional[ast.AST] = None


def _int_tuple(node: Optional[ast.expr]) -> Tuple[int, ...]:
    out: List[int] = []
    for sub in ast.walk(node) if node is not None else ():
        if isinstance(sub, ast.Constant) and isinstance(sub.value, int):
            out.append(sub.value)
    return tuple(out)


def _str_tuple(node: Optional[ast.expr]) -> Tuple[str, ...]:
    out: List[str] = []
    for sub in ast.walk(node) if node is not None else ():
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            out.append(sub.value)
    return tuple(out)


def _is_jit_ref(node: ast.expr) -> bool:
    """``jax.jit`` / bare ``jit`` / ``pjit``."""
    if isinstance(node, ast.Attribute):
        return node.attr in ("jit", "pjit")
    return isinstance(node, ast.Name) and node.id in ("jit", "pjit")


def _jit_call_opts(call: ast.Call) -> Optional[_JitInfo]:
    """Options when ``call`` is ``jax.jit(...)`` / ``partial(jax.jit, ...)``,
    else None."""
    f = call.func
    if _is_jit_ref(f):
        info = _JitInfo(jitted=True, device=True)
    elif (
        (isinstance(f, ast.Attribute) and f.attr == "partial")
        or (isinstance(f, ast.Name) and f.id.lstrip("_") == "partial")
    ) and call.args and _is_jit_ref(call.args[0]):
        info = _JitInfo(jitted=True, device=True)
    else:
        return None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            info.donate = _int_tuple(kw.value)
        elif kw.arg == "static_argnums":
            info.static_nums = _int_tuple(kw.value)
        elif kw.arg == "static_argnames":
            info.static_names = _str_tuple(kw.value)
    return info


def _decorated_jit(node) -> Optional[_JitInfo]:
    for dec in node.decorator_list:
        if _is_jit_ref(dec):
            return _JitInfo(jitted=True, device=True, def_node=node)
        if isinstance(dec, ast.Call):
            info = _jit_call_opts(dec)
            if info is not None:
                info.def_node = node
                return info
    return None


def _root_name(node: ast.expr) -> str:
    """Leftmost Name of an Attribute/Subscript chain: m["loss"].x -> m."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else ""


def _target_names(tgt: ast.expr) -> List[str]:
    out: List[str] = []
    for sub in ast.walk(tgt):
        if isinstance(sub, ast.Name):
            out.append(sub.id)
    return out


def _uses_jax(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in ("jax", "jnp"):
            return True
    return False


def _own_nodes(fn) -> List[ast.AST]:
    """All nodes of ``fn``'s body except nested function/class scopes."""
    out: List[ast.AST] = []
    stack: List[ast.AST] = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            continue
        out.append(node)
        stack.extend(ast.iter_child_nodes(node))
    return out


# ---------------------------------------------------------------- module scan


class _Module:
    """One file's jit surface: imports, jitted/device-producing defs,
    jitted assignments (``step = jax.jit(f, ...)``, incl. self-attrs)."""

    def __init__(self, text: str, path: str):
        self.path = path
        p = path[:-3] if path.endswith(".py") else path
        parts = [x for x in p.split(os.sep) if x]
        if parts and parts[-1] == "__init__":
            parts = parts[:-1]
        self.dotted = ".".join(parts)
        self.tree = ast.parse(text, filename=path)
        self.imports: Dict[str, str] = {}
        self.defs: Dict[str, _JitInfo] = {}  # module-level def name -> info
        self.assigned: Dict[str, _JitInfo] = {}  # name or attr-source -> info
        self._scan()

    def _scan(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.imports[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:  # relative: resolve against this package
                    pkg = self.dotted.split(".")
                    pkg = pkg[: len(pkg) - node.level]
                    base = ".".join(pkg + ([node.module] if node.module else []))
                if not base:
                    continue
                for a in node.names:
                    if a.name != "*":
                        self.imports[a.asname or a.name] = f"{base}.{a.name}"
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info = _decorated_jit(node)
                if info is None:
                    info = _JitInfo(device=_uses_jax(node), def_node=node)
                self.defs.setdefault(node.name, info)
            elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                info = _jit_call_opts(node.value)
                if info is None:
                    continue
                # wrapped local def: jax.jit(step, ...) — attach the def so
                # JAX002 can check its params against the static sets
                if node.value.args and isinstance(node.value.args[0], ast.Name):
                    wrapped = node.value.args[0].id
                    if wrapped in self.defs:
                        info.def_node = self.defs[wrapped].def_node
                for tgt in node.targets:
                    try:
                        self.assigned[ast.unparse(tgt)] = info
                    except Exception:  # pragma: no cover — synthetic nodes
                        pass

    def jit_info_for_call(self, call: ast.Call, registry: Dict[str, _JitInfo]) -> Optional[_JitInfo]:
        """Resolve a call's target to its jit info: local assignment
        (``step(...)`` / ``self._kstep_jit(...)``), module-level def,
        from-imported name via the package registry."""
        f = call.func
        try:
            src = ast.unparse(f)
        except Exception:  # pragma: no cover
            src = ""
        if src in self.assigned:
            return self.assigned[src]
        if isinstance(f, ast.Name):
            if f.id in self.defs:
                return self.defs[f.id]
            tgt = self.imports.get(f.id)
            if tgt and tgt in registry:
                return registry[tgt]
        return None


def _registry_from(paths: Sequence[str], root: str) -> Dict[str, _JitInfo]:
    """dotted.module.func -> jit info for every module-level def in the
    package (the JAX004/JAX003 whole-program half: an imported callee's
    jit/donation/device facts travel to the caller's module)."""
    registry: Dict[str, _JitInfo] = {}
    for p in paths:
        abspath = p if os.path.isabs(p) else os.path.join(root, p)
        rp = rel(abspath)
        dotted = rp[:-3].replace(os.sep, ".") if rp.endswith(".py") else rp
        try:
            mod = _Module(read_text(abspath), rp)
        except SyntaxError:
            continue
        for name, info in mod.defs.items():
            registry[f"{dotted}.{name}"] = info
        for name, info in mod.assigned.items():
            if "." not in name:  # module-level simple names only
                registry[f"{dotted}.{name}"] = info
    return registry


# -------------------------------------------------------------------- JAX001


def _jax001(mod: _Module, registry: Dict[str, _JitInfo], findings: List[Finding]) -> None:
    for fn in ast.walk(mod.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        try:
            fn_src = ast.unparse(fn)
        except Exception:  # pragma: no cover
            fn_src = ""
        if any(tok in fn_src for tok in _GUARD_TOKENS):
            continue  # the function syncs deliberately, guard-style
        nodes = _own_nodes(fn)
        tracked: Set[str] = set()
        for node in nodes:
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                info = mod.jit_info_for_call(node.value, registry)
                if info is not None and info.jitted:
                    for tgt in node.targets:
                        tracked.update(_target_names(tgt))
        if not tracked:
            continue
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            hit = ""
            if isinstance(f, ast.Name) and f.id == "float" and node.args:
                if _root_name(node.args[0]) in tracked:
                    hit = f"float({ast.unparse(node.args[0])})"
            elif isinstance(f, ast.Attribute) and f.attr == "item":
                if _root_name(f.value) in tracked:
                    hit = f"{ast.unparse(f.value)}.item()"
            elif (
                isinstance(f, ast.Attribute)
                and f.attr in ("asarray", "array")
                and isinstance(f.value, ast.Name)
                and f.value.id in ("np", "numpy")
                and node.args
            ):
                if _root_name(node.args[0]) in tracked:
                    hit = f"np.{f.attr}({ast.unparse(node.args[0])})"
            if hit:
                findings.append(Finding(
                    "JAX001", mod.path, node.lineno,
                    f"host sync {hit} on a jit output in a hot path — "
                    "drains the dispatch queue every step; batch the read "
                    "behind a sentinel/guard or move it off the step path",
                ))


# -------------------------------------------------------------------- JAX002


def _static_params(fn, info: _JitInfo) -> Set[str]:
    params = [a.arg for a in fn.args.args]
    static = {params[i] for i in info.static_nums if i < len(params)}
    static.update(n for n in info.static_names if n in params)
    return static


def _bare_names(expr: ast.expr) -> Set[str]:
    """Names whose VALUE the expression branches on. Exempt as static
    under trace: ``x is None`` / ``x is not None``; ``key in x``
    membership (dict/pytree KEY structure, not data); and any name under
    an Attribute/Subscript (``state.batch_stats`` truthiness probes pytree
    structure, ``x.shape``/``x.ndim`` are static metadata)."""
    out: Set[str] = set()
    skip: Set[int] = set()
    for node in ast.walk(expr):
        if isinstance(node, (ast.Attribute, ast.Subscript)):
            for sub in ast.walk(node.value):
                skip.add(id(sub))
        elif isinstance(node, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
        ):
            for sub in ast.walk(node):
                skip.add(id(sub))
        elif isinstance(node, ast.Compare) and all(
            isinstance(op, (ast.In, ast.NotIn)) for op in node.ops
        ):
            for comp in node.comparators:  # container side only
                for sub in ast.walk(comp):
                    skip.add(id(sub))
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and id(node) not in skip:
            out.add(node.id)
    return out


def _jax002(mod: _Module, findings: List[Finding]) -> None:
    checked: Set[int] = set()
    for info in list(mod.defs.values()) + list(mod.assigned.values()):
        fn = info.def_node
        if not info.jitted or fn is None or id(fn) in checked:
            continue
        checked.add(id(fn))
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        traced = {a.arg for a in fn.args.args} - _static_params(fn, info) - {"self"}
        if not traced:
            continue
        for node in _own_nodes(fn):
            bad: Set[str] = set()
            where = ""
            if isinstance(node, (ast.If, ast.While)):
                bad = _bare_names(node.test) & traced
                where = "branches on"
            elif (
                isinstance(node, ast.For)
                and isinstance(node.iter, ast.Call)
                and isinstance(node.iter.func, ast.Name)
                and node.iter.func.id == "range"
            ):
                bad = set()
                for arg in node.iter.args:
                    bad |= _bare_names(arg) & traced
                where = "sizes a range() loop with"
            if bad:
                findings.append(Finding(
                    "JAX002", mod.path, node.lineno,
                    f"jitted function {fn.name!r} {where} traced "
                    f"argument(s) {', '.join(sorted(bad))} — raises at "
                    "trace time for arrays, retraces per distinct value "
                    "for Python scalars; mark static via static_argnums/"
                    "static_argnames or branch with jnp.where",
                ))


# -------------------------------------------------------------------- JAX003


def _jax003(mod: _Module, registry: Dict[str, _JitInfo], findings: List[Finding]) -> None:
    for fn in ast.walk(mod.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if _decorated_jit(fn) is not None:
            # inside a jit trace the callee inlines — its donate_argnums
            # are ignored, so "reuse" there is not a donation hazard
            continue
        nodes = _own_nodes(fn)
        for node in nodes:
            if not (isinstance(node, ast.Assign) or isinstance(node, ast.Expr)):
                continue
            value = node.value
            if not isinstance(value, ast.Call):
                continue
            info = mod.jit_info_for_call(value, registry)
            if info is None or not info.donate:
                continue
            rebound: Set[str] = set()
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    rebound.update(_target_names(tgt))
            for i in info.donate:
                if i >= len(value.args) or not isinstance(value.args[i], ast.Name):
                    continue
                donated = value.args[i].id
                if donated in rebound:
                    continue  # state, loss = step(state, ...) — clean
                reuse = _first_read_after(
                    nodes, donated, getattr(node, "end_lineno", node.lineno)
                )
                if reuse is not None:
                    findings.append(Finding(
                        "JAX003", mod.path, reuse,
                        f"donated buffer {donated!r} read after being "
                        f"passed in donate_argnums position {i} at line "
                        f"{value.lineno} — XLA may alias it into the "
                        "output; rebind the result or copy before the call",
                    ))


def _first_read_after(nodes: Sequence[ast.AST], name: str, call_line: int) -> Optional[int]:
    """Line of the first Load of ``name`` after ``call_line``, unless a
    rebind (Store) intervenes."""
    events: List[Tuple[int, str]] = []
    for node in nodes:
        if isinstance(node, ast.Name) and node.id == name:
            kind = "store" if isinstance(node.ctx, (ast.Store, ast.Del)) else "load"
            events.append((node.lineno, kind))
    for line, kind in sorted(events):
        if line <= call_line:
            continue
        if kind == "store":
            return None
        return line
    return None


# -------------------------------------------------------------------- JAX004


def _jax004(mod: _Module, registry: Dict[str, _JitInfo], findings: List[Finding]) -> None:
    for fn in ast.walk(mod.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        nodes = _own_nodes(fn)
        # clock-var assignments and elapsed reads, in line order
        assigns: List[Tuple[int, str]] = []
        elapsed: List[Tuple[int, str]] = []
        for node in nodes:
            if (
                isinstance(node, ast.Assign)
                and _is_clock(node.value)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
            ):
                assigns.append((node.lineno, node.targets[0].id))
            elif (
                isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Sub)
                and isinstance(node.right, ast.Name)
            ):
                elapsed.append((node.lineno, node.right.id))
        if not elapsed:
            continue
        # block_until_ready, plus d2h conversions — np.asarray/.item()
        # force completion, and roundtrip benches time them on purpose
        syncs = [
            n.lineno for n in nodes
            if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and (
                n.func.attr in ("block_until_ready", "item")
                or (
                    n.func.attr in ("asarray", "array")
                    and isinstance(n.func.value, ast.Name)
                    and n.func.value.id in ("np", "numpy")
                )
            )
        ]
        for end_line, var in elapsed:
            starts = [ln for ln, v in assigns if v == var and ln < end_line]
            if not starts:
                continue
            start_line = max(starts)
            window_calls = [
                n for n in nodes
                if isinstance(n, ast.Call) and start_line < n.lineno <= end_line
            ]
            device_call = None
            for call in window_calls:
                info = mod.jit_info_for_call(call, registry)
                if info is not None and info.device:
                    device_call = call
                    break
            if device_call is None:
                continue
            if any(start_line <= ln <= end_line for ln in syncs):
                continue
            try:
                callee = ast.unparse(device_call.func)
            except Exception:  # pragma: no cover
                callee = "<call>"
            findings.append(Finding(
                "JAX004", mod.path, end_line,
                f"timer window (t0 at line {start_line}) calls "
                f"device-producing {callee}() but reads the clock with no "
                "block_until_ready in the window — this measures dispatch, "
                "not execution",
            ))


def _is_clock(node: ast.expr) -> bool:
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr in _CLOCK_FUNCS
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id in ("time", "_time")
    )


# --------------------------------------------------------------------- scope


def _in_sync_scope(path: str) -> bool:
    p = rel(path) if os.path.isabs(path) else path
    return any(p.startswith(d + os.sep) for d in _SYNC_SCOPE_DIRS)


def _in_bench_scope(path: str) -> bool:
    p = rel(path) if os.path.isabs(path) else path
    return p in _BENCH_SCOPE_FILES or any(
        p.startswith(d + os.sep) for d in _BENCH_SCOPE_DIRS
    )


# ----------------------------------------------------------------------- API


def check_source(
    text: str, path: str,
    sync_scope: Optional[bool] = None,
    bench_scope: Optional[bool] = None,
    registry: Optional[Dict[str, _JitInfo]] = None,
) -> List[Finding]:
    """Lint one module. Scope flags default from the path (fixtures pass
    explicit True); ``registry`` carries cross-module jit facts."""
    registry = registry or {}
    findings: List[Finding] = []
    mod = _Module(text, path)
    if sync_scope if sync_scope is not None else _in_sync_scope(path):
        _jax001(mod, registry, findings)
    _jax002(mod, findings)
    _jax003(mod, registry, findings)
    if bench_scope if bench_scope is not None else _in_bench_scope(path):
        _jax004(mod, registry, findings)
    # dedupe by site (a tuple target tracked twice reports once)
    seen: Set[Tuple[str, int, str]] = set()
    out: List[Finding] = []
    for f in findings:
        k = (f.rule, f.line, f.message)
        if k not in seen:
            seen.add(k)
            out.append(f)
    return out


def check(root: str = REPO_ROOT, files: Optional[Sequence[str]] = None) -> List[Finding]:
    from persia_tpu.analysis.common import python_files

    pkg = python_files(root)
    # bench scope rides along the package scan
    extra = [
        os.path.join(root, p) for p in _BENCH_SCOPE_FILES
        if os.path.exists(os.path.join(root, p))
    ]
    bench_dirs = [os.path.join(root, d) for d in _BENCH_SCOPE_DIRS]
    for d in bench_dirs:
        if os.path.isdir(d):
            extra.extend(
                os.path.join(d, f) for f in sorted(os.listdir(d))
                if f.endswith(".py")
            )
    paths = list(files) if files is not None else pkg + extra
    registry = _registry_from(pkg, root)
    findings: List[Finding] = []
    for p in paths:
        abspath = p if os.path.isabs(p) else os.path.join(root, p)
        if (os.sep + "analysis" + os.sep) in abspath:
            continue  # the lint does not lint itself
        try:
            findings.extend(
                check_source(read_text(abspath), rel(abspath), registry=registry)
            )
        except SyntaxError:
            continue
    return findings
